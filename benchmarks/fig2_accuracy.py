"""Paper Fig. 2: accuracy vs training rounds for the four methods
(AdaLD / Adaptive / ZeroPad / All-logits), Non-IID Dirichlet γ=0.5.

Reduced scale (DESIGN §1): GPT-2-family reduced models on the synthetic
Banking77-statistics dataset.  The reproduced claim is the ORDERING
AdaLD ≥ Adaptive > All-logits > ZeroPad, not the absolute 0.85.
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

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "fig2.json")


def run(rounds: int = 8, seeds=(0,), quick: bool = False):
    if quick:
        rounds, seeds = 2, (0,)
    client = REDUCED_CLIENT.with_overrides(num_layers=2, d_model=128, num_heads=4, d_ff=512)
    server = REDUCED_SERVER.with_overrides(
        num_layers=3, d_model=192, num_heads=4, num_kv_heads=4, d_ff=768
    )
    results: dict[str, dict] = {}
    for method in METHODS:
        accs, t0 = [], time.time()
        for seed in seeds:
            from repro.data import make_fed_benchmark_dataset

            ds = make_fed_benchmark_dataset(client.vocab_size, seed=seed)
            fed = FedConfig(
                method=method, num_clients=6, clients_per_round=3, rounds=rounds,
                public_size=256, public_batch=96, eval_size=256, local_steps=10,
                distill_steps=1, server_distill_steps=25, lr=2e-3, seed=seed,
            )
            run_ = run_federated(client, server, ds, fed)
            accs.append(run_.server_acc)
        mean_acc = [sum(col) / len(col) for col in zip(*accs)]
        results[method] = {
            "server_acc": mean_acc,
            "final": mean_acc[-1],
            "best": max(mean_acc),
            "wall_s": time.time() - t0,
        }
        print(f"[fig2] {method:10s} best={max(mean_acc):.3f} "
              f"trajectory={['%.3f' % a for a in mean_acc]}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    return results


def bench(quick: bool = True):
    """run.py hook: name,us_per_call,derived rows."""
    t0 = time.time()
    results = run(quick=quick)
    us = (time.time() - t0) * 1e6
    best = max(results, key=lambda m: results[m]["best"])
    return [("fig2_accuracy", us, f"best_method={best}:{results[best]['best']:.3f}")]


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(rounds=int(sys.argv[1]) if len(sys.argv) > 1 else 8)
