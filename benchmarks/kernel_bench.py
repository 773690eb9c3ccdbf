"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs jnp reference.

On CPU the interesting number is the REFERENCE path (the jnp oracle is what
a TPU would fall back to without the kernel); interpret-mode timings measure
the Python-executed kernel body and are NOT TPU performance — the roofline
for kernels comes from BlockSpec arithmetic, printed as 'derived'.
"""

from __future__ import annotations

import sys
import time
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.topk_select import rows_block_for  # noqa: E402


def _time(fn, *args, reps=5) -> float:
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6  # us


def bench(quick: bool = True):
    rows = []
    vocab = 50_288 if quick else 202_048
    n_rows = 32

    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (n_rows, vocab))

    # top-k: jnp oracle timing + kernel VMEM-tiling arithmetic
    topk_ref = jax.jit(lambda x: ref.topk_mask_ref(x, 128))
    us = _time(topk_ref, logits)
    rb = rows_block_for(vocab)
    hbm_passes = 2  # read + write, single pass by construction
    derived = f"rows_blk={rb};hbm_bytes={hbm_passes * n_rows * vocab * 4}"
    rows.append(("topk_ref_jnp", us, derived))

    t = jax.random.normal(key, (n_rows, vocab))
    s = jax.random.normal(jax.random.fold_in(key, 1), (n_rows, vocab))
    kl_ref = jax.jit(lambda a, b: jnp.mean(ref.distill_kl_ref(a, b, 2.0)))
    us = _time(kl_ref, t, s)
    # fused kernel: 1 read of each operand vs ~3 for the naive path
    rows.append(("distill_kl_ref_jnp", us, f"fused_hbm_reads=2x{n_rows * vocab * 4}B_vs_6x"))

    stack = jax.random.normal(key, (10, n_rows, vocab))
    stack = jnp.where(jax.random.uniform(jax.random.fold_in(key, 2), stack.shape) < 0.1, stack, 0.0)
    agg_ref = jax.jit(ref.sparse_agg_ref)
    us = _time(agg_ref, stack)
    rows.append(("sparse_agg_ref_jnp", us, f"stack_bytes={stack.size * 4}"))

    q = jax.random.normal(key, (4, 1024, 128))
    kk = jax.random.normal(jax.random.fold_in(key, 3), (4, 1024, 128))
    v = jax.random.normal(jax.random.fold_in(key, 4), (4, 1024, 128))
    fa_ref = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
    us = _time(fa_ref, q, kk, v)
    rows.append(("flash_attn_ref_jnp", us, "blocks=128x128;vmem_per_step~200KB"))

    if not quick:
        # interpret-mode correctness timing (kernel body in Python)
        from repro.kernels import ops

        us = _time(lambda x: ops.topk_mask(x, 128), logits, reps=1)
        rows.append(("topk_pallas_interpret", us, "correctness-mode, not TPU perf"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in bench(quick=False):
        print(f"{name},{us:.0f},{derived}")
