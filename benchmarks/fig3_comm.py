"""Paper Fig. 3: total communication cost (MB) to reach accuracy thresholds,
IID setting (the paper uses IID here 'due to the large variance under
Non-IID').

Reproduced claim: AdaLD reaches each threshold with the least uplink MB;
All-logits is 1-2 orders of magnitude more expensive.  Thresholds are
scaled to the reduced models' accuracy range.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER  # noqa: E402
from repro.fed import FedConfig, run_federated  # noqa: E402
from repro.fed.rounds import METHODS  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "fig3.json")
THRESHOLDS = (0.05, 0.08, 0.12)  # reduced-scale counterparts of 0.70/0.75/0.79


def run(rounds: int = 10, quick: bool = False):
    if quick:
        rounds = 2
    client = REDUCED_CLIENT.with_overrides(num_layers=2, d_model=128, num_heads=4, d_ff=512)
    server = REDUCED_SERVER.with_overrides(
        num_layers=3, d_model=192, num_heads=4, num_kv_heads=4, d_ff=768
    )
    results: dict[str, dict] = {}
    for method in METHODS:
        from repro.data import make_fed_benchmark_dataset

        ds = make_fed_benchmark_dataset(client.vocab_size, seed=0)
        fed = FedConfig(
            method=method, num_clients=6, clients_per_round=3, rounds=rounds,
            public_size=256, public_batch=96, eval_size=256, local_steps=10,
            distill_steps=1, server_distill_steps=25, lr=2e-3, seed=0,
            non_iid=False,  # paper: IID for Fig. 3
        )
        r = run_federated(client, server, ds, fed)
        results[method] = {
            "mb_to_reach": {str(t): r.ledger.mb_to_reach(t) for t in THRESHOLDS},
            "uplink_mb_total": r.ledger.uplink_mb,
            "total_mb": r.ledger.total_mb,
            "mean_k": sum(r.mean_k) / len(r.mean_k),
            "best_acc": max(r.server_acc),
        }
        print(f"[fig3] {method:10s} uplink={r.ledger.uplink_mb:8.3f}MB "
              f"mean_k={results[method]['mean_k']:7.1f} "
              f"mb_to_reach={results[method]['mb_to_reach']}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    return results


def bench(quick: bool = True):
    t0 = time.time()
    results = run(quick=quick)
    us = (time.time() - t0) * 1e6
    adald = results["adald"]["uplink_mb_total"]
    full = results["all_logits"]["uplink_mb_total"]
    return [("fig3_comm", us, f"adald_vs_all_logits_uplink={adald:.3f}MB/{full:.3f}MB")]


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(rounds=int(sys.argv[1]) if len(sys.argv) > 1 else 10)
