"""Federated AdaLD driver — the paper's experiment as a CLI.

  PYTHONPATH=src python -m repro.launch.fed_train --method adald --rounds 10

Reduced-scale GPT-2-family models on the synthetic Banking77-statistics
dataset (DESIGN §1); writes a JSON history consumable by benchmarks/fig2/3.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
from repro.data import make_banking77_like
from repro.fed import FedConfig, run_federated
from repro.fed.rounds import METHODS


def family_configs(spec: str, seq_len: int):
    """Resolve ``--families`` into per-family model configs aligned to the
    shared exchange contracts (one vocab, one LoRA rank — paper §II): each
    comma-separated arch id's smoke config is re-based onto the reduced
    experiment's vocab/LoRA; SSM families get a chunk size dividing the
    experiment sequence length."""
    fams = []
    for arch in spec.split(","):
        arch = arch.strip()
        if not arch:
            continue
        smoke = get_smoke_config(arch)
        over = dict(
            name=f"fam-{arch}",
            vocab_size=REDUCED_CLIENT.vocab_size,
            lora=REDUCED_CLIENT.lora,
            max_seq_len=max(seq_len, 32),
        )
        if smoke.ssm is not None:
            chunk = next(c for c in (8, 4, 2, 1) if seq_len % c == 0)
            over["ssm"] = dataclasses.replace(smoke.ssm, chunk_size=chunk)
        fams.append(smoke.with_overrides(**over))
    if not fams:
        raise SystemExit(f"--families {spec!r} names no architectures")
    return fams


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", choices=list(METHODS), default="adald")
    ap.add_argument("--engine",
                    choices=["sequential", "batched", "fused", "fused_e2e"],
                    default="batched",
                    help="round executor (batched = vmapped per-phase cohort "
                         "steps; fused = one jitted CLIENT-phase body; "
                         "fused_e2e = one jitted call for the WHOLE round — "
                         "sparse-wire aggregation + server distill + "
                         "broadcast folded in)")
    ap.add_argument("--shard-clients", action="store_true",
                    help="fused/fused_e2e engines: place the client axis "
                         "over jax devices via shard_map (for fused_e2e the "
                         "placement lives inside the whole-round executable; "
                         "the server phase stays replicated)")
    ap.add_argument("--scan-rounds", action="store_true",
                    help="fused_e2e only: run ALL rounds as one compiled "
                         "lax.scan dispatch with the per-round eval tapped "
                         "inside the scan")
    ap.add_argument("--families", default=None,
                    help="comma-separated arch ids from repro.configs (e.g. "
                         "'gpt2-paper,mamba2-130m'): heterogeneous fleet — "
                         "clients cycle these families round-robin, served "
                         "by the family-bucketed engines; smoke configs are "
                         "re-based onto the shared vocab/LoRA exchange "
                         "contract.  Default: homogeneous REDUCED_CLIENT")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--fleet-size", type=int, default=None,
                    help="alias for --clients aimed at fleet-scale runs "
                         "(takes precedence when both are given); pair with "
                         "--fleet-store host so device memory stays "
                         "O(cohort) regardless of this number")
    ap.add_argument("--fleet-store", choices=["device", "host"],
                    default="device",
                    help="fleet-state residency (repro.fed.store): 'device' "
                         "keeps the whole fleet stacked on the accelerator; "
                         "'host' keeps it in host memory and streams only "
                         "each round's cohort to the device, prefetching "
                         "round r+1's cohort under round r's compute")
    ap.add_argument("--per-round", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--lam", type=float, default=0.03)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--quantize-wire", action="store_true",
                    help="int8-quantize the sparse uplink wire (one fp32 "
                         "scale per (client, sample) row): entries are "
                         "priced at 8 bits, so the same Shannon budget "
                         "affords a larger adaptive k at fixed SNR")
    ap.add_argument("--compute-dtype", choices=["float32", "bfloat16"],
                    default="float32",
                    help="fused engines: round-body compute dtype; fp32 "
                         "master LoRA/optimizer state is kept either way")
    ap.add_argument("--scenario", default=None,
                    help="channel-dynamics preset from repro.core.scenario "
                         "(iid | gauss_markov | jakes | gilbert_elliott | "
                         "mobility): time-correlated fading / bursty outage "
                         "/ mobility trajectories.  Default: the i.i.d. "
                         "per-round channel")
    ap.add_argument("--faults", default=None,
                    help="fault-injection preset from repro.core.faults "
                         "(none | corruption | crashes | bursty | lossy): "
                         "payload corruption with HARQ retransmission, "
                         "client crashes mid-round, Gilbert-Elliott fault "
                         "bursts.  Default: no faults")
    ap.add_argument("--ckpt-dir", default=None,
                    help="write an atomic round-granular checkpoint after "
                         "every completed round (crash-safe: a kill mid-"
                         "save never corrupts the latest step)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest valid checkpoint in "
                         "--ckpt-dir; the resumed run is bit-identical to "
                         "an uninterrupted one (same k, bytes, accuracies)")
    ap.add_argument("--public-batch", type=int, default=128)
    ap.add_argument("--out", default="experiments/fed")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.resume and args.ckpt_dir is None:
        ap.error("--resume requires --ckpt-dir")

    seq_len = 24
    ds = make_banking77_like(vocab_size=REDUCED_CLIENT.vocab_size, seq_len=seq_len, seed=args.seed)
    client_cfg = (
        family_configs(args.families, seq_len) if args.families else REDUCED_CLIENT
    )
    fed = FedConfig(
        method=args.method,
        engine=args.engine,
        fleet_store=args.fleet_store,
        num_clients=(
            args.fleet_size if args.fleet_size is not None else args.clients
        ),
        clients_per_round=args.per_round,
        rounds=args.rounds,
        public_size=512,
        public_batch=args.public_batch,
        eval_size=512,
        non_iid=not args.iid,
        seed=args.seed,
        lam=args.lam,
        use_kernels=args.use_kernels,
        quantize_wire=args.quantize_wire,
        compute_dtype=args.compute_dtype,
        shard_clients=args.shard_clients,
        scan_rounds=args.scan_rounds,
        scenario=args.scenario,
        faults=args.faults,
    )
    run = run_federated(
        client_cfg, REDUCED_SERVER, ds, fed, verbose=True,
        ckpt_dir=args.ckpt_dir, resume=args.resume,
    )

    os.makedirs(args.out, exist_ok=True)
    rec = {
        "method": args.method,
        "families": args.families,
        "family_client_acc": run.family_client_acc,
        "scenario": args.scenario,
        # scenario scan runs only: the in-scan channel tap (-inf SNR in
        # outage is not valid JSON; clamp to a sentinel)
        "snr_db": None if run.snr_db is None else [
            [x if math.isfinite(x) else -1e9 for x in row] for row in run.snr_db
        ],
        "outage": run.outage,
        "faults": args.faults,
        "num_quarantined": run.num_quarantined,
        "num_crashed": run.num_crashed,
        "retrans_bytes": run.retrans_bytes,
        "fed": {k: v for k, v in dataclasses.asdict(fed).items() if not isinstance(v, dict)},
        "server_acc": run.server_acc,
        "client_acc": run.client_acc,
        "mean_k": run.mean_k,
        # null, not bare NaN (engines without the in-program tap report NaN;
        # bare NaN is not RFC-8259 JSON)
        "distill_loss": [
            None if math.isnan(x) else x for x in run.distill_loss
        ],
        "uplink_mb_per_round": [r.uplink_bytes / 1e6 for r in run.ledger.rounds],
        "downlink_mb_per_round": [r.downlink_bytes / 1e6 for r in run.ledger.rounds],
        "summary": run.summary(),
    }
    path = os.path.join(args.out, f"{args.method}_seed{args.seed}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[fed] {args.method}: final server acc "
          f"{run.server_acc[-1]:.3f}, total {run.ledger.total_mb:.2f} MB -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
