"""Ablation: aggregation scheme quality vs sparsity (paper §III-A argument).

Measures, WITHOUT training, the quality of the aggregated soft label as a
teaching signal: NLL of the true underlying class under σ(K_g/T), where the
"true" signal is shared across heterogeneous (biased) clients.  The paper's
claim is that zero-padding degrades sharply as k shrinks (it divides by N
including non-transmitting clients, washing out client-specific confident
dims), while adaptive aggregation degrades gracefully.

Measured result (k = top-k per client, lower NLL = better teacher):
adaptive ≈ zeropad at k=vocab, but at k≤16 adaptive < zeropad by >1 nat —
the bandwidth-constrained regime the paper targets.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.aggregation import aggregate  # noqa: E402
from repro.core.topk import densify, topk_sparsify  # noqa: E402


def run(vocab=2048, clients=10, samples=64, ks=(2048, 256, 64, 16, 4), temp=2.0):
    key = jax.random.PRNGKey(0)
    # heterogeneous clients: shared signal + per-client bias (Non-IID proxy)
    signal = jax.random.normal(key, (samples, vocab)) * 2
    true_cls = jnp.argmax(signal, -1)
    stacks = []
    for c in range(clients):
        bias = jax.random.normal(jax.random.fold_in(key, c + 1), (1, vocab)) * 1.5
        noise = 0.5 * jax.random.normal(jax.random.fold_in(key, 100 + c), (samples, vocab))
        stacks.append(signal + bias + noise)
    full = jnp.stack(stacks)  # (N, S, V)

    out = {}
    for k in ks:
        sparse = densify(topk_sparsify(full, k))
        row = {}
        for mode in ("adaptive", "zeropad", "mean_nonzero"):
            agg = aggregate(sparse, mode)
            logp = jax.nn.log_softmax(agg / temp, -1)
            row[mode] = float(-jnp.take_along_axis(logp, true_cls[:, None], -1).mean())
        out[k] = row
    return out


def bench(quick: bool = True):
    t0 = time.time()
    res = run(ks=(256, 16) if quick else (2048, 256, 64, 16, 4))
    us = (time.time() - t0) * 1e6
    k = min(res)
    adv = res[k]["zeropad"] - res[k]["adaptive"]
    return [("agg_ablation", us, f"adaptive_beats_zeropad_by={adv:.2f}nats@k={k}")]


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for k, row in run().items():
        print(f"k={k:5d}  " + "  ".join(f"{m}={v:.4f}" for m, v in row.items()))
