"""Scenario suite: accuracy-vs-communication curves under time-correlated
channels (grown from the old channel_sweep example).

Runs every channel-dynamics preset from ``repro.core.scenario`` (i.i.d.,
Gauss-Markov AR(1) fading, Jakes/Doppler fading, Gilbert-Elliott bursty
outage, mobility trajectories) through the one-dispatch ``fused_e2e``
multi-round scan and records fig2/fig3-style curves per scenario: server
accuracy against cumulative uplink MB, the per-round adaptive k, and the
in-scan outage tap.  It prints one line per scenario and, with ``--out``,
writes the curves as JSON.  (The per-round k and payload bytes of the
correlated presets are pinned by ``tests/test_scenario.py``'s golden
trajectory.)

Run:  PYTHONPATH=src python examples/scenario_suite.py [--out curves.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER  # noqa: E402
from repro.core import SCENARIOS, ChannelConfig  # noqa: E402
from repro.data import make_banking77_like  # noqa: E402
from repro.fed import FedConfig, run_federated  # noqa: E402

CLIENT = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=32,
)
SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32,
)
# Constrained uplink so the adaptive k actually moves with the fading, plus
# a nonzero memoryless dropout so the i.i.d. presets exercise outage too.
CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.1)
ROUNDS = 10


def _fed(rounds: int, scenario) -> FedConfig:
    return FedConfig(
        method="adald", engine="fused_e2e", num_clients=6, clients_per_round=3,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        pretrain_steps=0, local_steps=2, distill_steps=1, seed=0,
        channel=CHAN, scenario=scenario, scan_rounds=True,
    )


def run_scenario(ds, rounds: int, scenario):
    run = run_federated(CLIENT, SERVER, ds, _fed(rounds, scenario))
    uplink = [r.uplink_bytes for r in run.ledger.rounds]
    out = {
        "server_acc": [float(a) for a in run.server_acc],
        "cum_uplink_mb": [float(b) / 1e6 for b in np.cumsum(uplink)],
        "uplink_bytes": [int(b) for b in uplink],
        "mean_k": [float(k) for k in run.mean_k],
        "final_acc": float(run.server_acc[-1]),
        "best_acc": float(max(run.server_acc)),
        "total_uplink_mb": float(sum(uplink)) / 1e6,
    }
    if run.outage is not None:
        flat = [o for row in run.outage for o in row]
        out["outage_rate"] = float(np.mean(flat)) if flat else 0.0
    return run, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, help="write the curves to this JSON file")
    args = ap.parse_args(argv)

    ds = make_banking77_like(vocab_size=CLIENT.vocab_size, seq_len=12,
                            total=500, seed=0)

    record = {"rounds": ROUNDS, "scenarios": {}}
    print(f"{'scenario':>16} {'mean k':>8} {'uplink MB':>10} {'outage':>7} "
          f"{'best acc':>9}")
    runs = {}
    for name in SCENARIOS:
        run, out = run_scenario(ds, ROUNDS, name)
        runs[name] = run
        record["scenarios"][name] = out
        print(f"{name:>16} {np.mean(out['mean_k']):8.0f} "
              f"{out['total_uplink_mb']:10.3f} {out['outage_rate']:7.2f} "
              f"{out['best_acc']:9.3f}")

    # The rho=0 guarantee with teeth: the `iid` preset must be bit-identical
    # to a run with NO scenario at all (the legacy per-round i.i.d. path).
    legacy, legacy_out = run_scenario(ds, ROUNDS, None)
    iid = runs["iid"]
    record["iid_bit_identical"] = bool(
        iid.per_client_k == legacy.per_client_k
        and record["scenarios"]["iid"]["uplink_bytes"] == legacy_out["uplink_bytes"]
        and np.allclose(iid.server_acc, legacy.server_acc, atol=1e-6)
    )
    print(f"\niid preset vs legacy i.i.d. path bit-identical: "
          f"{record['iid_bit_identical']}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
