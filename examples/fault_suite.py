"""Fault suite: federated learning under injected faults (PR 8).

Runs every fault preset from ``repro.core.faults`` (no faults, i.i.d.
payload corruption with HARQ retransmission, client crashes mid-upload,
Gilbert-Elliott fault bursts, the lossy kitchen sink) through the batched
engine and records per-preset curves: server accuracy, per-round uplink
bytes (retransmitted copies included), quarantine/crash counts and
retransmission bytes.  It prints one line per preset and, with ``--out``,
writes the curves as JSON.  The ``none`` preset doubles as the bit-identity
witness: the suite re-runs with ``faults=None`` and reports whether the two
are indistinguishable.  (The per-round realisations of the corruption,
crashes and lossy presets are pinned by ``tests/test_faults.py``'s golden
trajectory.)

Run:  PYTHONPATH=src python examples/fault_suite.py [--out curves.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.configs.base import LoRAConfig  # noqa: E402
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER  # noqa: E402
from repro.core import FAULTS, ChannelConfig  # noqa: E402
from repro.data import make_banking77_like  # noqa: E402
from repro.fed import FedConfig, run_federated  # noqa: E402

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
CLIENT = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
# Constrained uplink so the adaptive k varies and HARQ retries actually
# price against a finite Shannon budget.
CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.1)
ROUNDS = 10


def _fed(rounds: int, faults) -> FedConfig:
    return FedConfig(
        method="adald", engine="batched", num_clients=6, clients_per_round=3,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        pretrain_steps=0, local_steps=2, distill_steps=1, seed=0,
        channel=CHAN, faults=faults,
    )


def run_preset(ds, rounds: int, faults):
    run = run_federated(CLIENT, SERVER, ds, _fed(rounds, faults))
    uplink = [r.uplink_bytes for r in run.ledger.rounds]
    out = {
        "server_acc": [float(a) for a in run.server_acc],
        "uplink_bytes": [float(b) for b in uplink],
        "cum_uplink_mb": [float(b) / 1e6 for b in np.cumsum(uplink)],
        "mean_k": [float(k) for k in run.mean_k],
        "final_acc": float(run.server_acc[-1]),
        "total_uplink_mb": float(sum(uplink)) / 1e6,
    }
    if run.num_quarantined is not None:
        out["num_quarantined"] = [int(n) for n in run.num_quarantined]
        out["num_crashed"] = [int(n) for n in run.num_crashed]
        out["retrans_bytes"] = [float(b) for b in run.retrans_bytes]
    return run, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, help="write the curves to this JSON file")
    args = ap.parse_args(argv)

    ds = make_banking77_like(vocab_size=CLIENT.vocab_size, seq_len=12,
                            total=500, seed=0)

    record = {"rounds": ROUNDS, "presets": {}}
    print(f"{'preset':>12} {'uplink MB':>10} {'quar':>5} {'crash':>6} "
          f"{'retrans MB':>11} {'final acc':>10}")
    runs = {}
    for name in FAULTS:
        run, out = run_preset(ds, ROUNDS, name)
        runs[name] = run
        record["presets"][name] = out
        quar = sum(out.get("num_quarantined", [0]))
        crash = sum(out.get("num_crashed", [0]))
        retrans = sum(out.get("retrans_bytes", [0.0])) / 1e6
        print(f"{name:>12} {out['total_uplink_mb']:10.3f} {quar:5d} "
              f"{crash:6d} {retrans:11.4f} {out['final_acc']:10.3f}")

    # The disabled-machinery guarantee with teeth: the `none` preset must be
    # bit-identical to a run with NO faults configured at all.
    baseline, base_out = run_preset(ds, ROUNDS, None)
    none = runs["none"]
    record["no_fault_bit_identical"] = bool(
        none.per_client_k == baseline.per_client_k
        and record["presets"]["none"]["uplink_bytes"] == base_out["uplink_bytes"]
        and none.server_acc == baseline.server_acc
    )
    print(f"\nnone preset vs faults=None bit-identical: "
          f"{record['no_fault_bit_identical']}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
