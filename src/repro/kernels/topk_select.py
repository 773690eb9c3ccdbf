"""Pallas TPU kernel: per-row Top-k threshold selection by vectorized bisection.

The paper's uplink hot-spot is selecting the top-k of a 50k-256k-wide logit
vector per public sample (§III-A).  GPU implementations use radix select
(warp ballots, shared-memory histograms) — no TPU analogue.  The TPU-native
adaptation (DESIGN §2): the row fits VMEM, so we run a **vectorized binary
search on the threshold value**: ~`ITERS` rounds of

    cnt(θ) = Σ_v 1[x_v >= θ]        (one VPU pass over the row tile)

maintaining the invariant cnt(lo) >= k > cnt(hi), then emit
``x * 1[x >= lo]``.  30 iterations narrow [min,max] by 2^30 — below fp32
resolution for logit-scale inputs — so the threshold converges to the k-th
value and the kept count is exactly k for distinct entries (ties are all
kept, see ref).

Block layout: grid over row blocks; each step owns (ROWS_BLK, V) in VMEM —
V up to 256k fp32 = 1 MB/row, ROWS_BLK sized to keep in+out under ~8 MB.
The vocab axis is NOT tiled: bisection needs whole-row counts each
iteration, and a row always fits; this trades grid parallelism for zero
cross-tile reduction traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["topk_mask_pallas", "topk_mask_dynamic_pallas", "rows_block_for"]

# Single source of truth shared with the pure-jnp topk_mask_dynamic: the two
# bisections must converge identically (exact-parity contract).
from repro.core.topk import BISECTION_ITERS as ITERS  # noqa: E402


def rows_block_for(vocab: int, dtype=jnp.float32) -> int:
    """Rows per block so in+out tiles stay within ~8 MB of VMEM."""
    bytes_per_row = 2 * vocab * jnp.dtype(dtype).itemsize  # in + out
    budget = 8 * 1024 * 1024
    return max(1, min(8, budget // max(1, bytes_per_row)))


def _topk_kernel(x_ref, out_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)  # (R_b, V)
    lo = jnp.min(x, axis=-1)  # cnt(lo) = V >= k
    hi = jnp.max(x, axis=-1) + 1.0  # cnt(hi) = 0 < k (strictly above max)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((x >= mid[:, None]).astype(jnp.int32), axis=-1)
        take = cnt >= k  # mid keeps enough -> move lo up
        new_lo = jnp.where(take, mid, lo)
        new_hi = jnp.where(take, hi, mid)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, ITERS, body, (lo, hi))
    keep = x >= lo[:, None]
    out_ref[...] = jnp.where(keep, x_ref[...], jnp.zeros_like(x_ref[...]))


def _topk_dynamic_kernel(x_ref, k_ref, out_ref):
    """Per-row budget variant: k arrives as DATA (int32 per row), so one
    compiled program serves every round of adaptive budgets — the fused
    round engine's requirement (a static k would recompile per round)."""
    x = x_ref[...].astype(jnp.float32)  # (R_b, V)
    k = k_ref[...]  # (R_b, 1) int32, pre-clamped to [0, V]
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True) + 1.0

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((x >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        take = cnt >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    # For k == 0 the loop drives lo toward max+1 -> nothing kept, which is
    # exactly the dropped-straggler contract; the explicit k > 0 guard below
    # makes it robust to the last-ulp of the bisection regardless.
    lo, hi = jax.lax.fori_loop(0, ITERS, body, (lo, hi))
    keep = (x >= lo) & (k > 0)
    out_ref[...] = jnp.where(keep, x_ref[...], jnp.zeros_like(x_ref[...]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_mask_dynamic_pallas(
    logits: jax.Array, ks: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Dense top-k mask of (rows, vocab) with a per-row int32 budget ``ks``
    (threshold semantics; ``ks[i] == 0`` zeroes row i entirely)."""
    assert logits.ndim == 2 and ks.ndim == 1, "fold batch dims before calling"
    rows, vocab = logits.shape
    # budgets ride as a (rows, 1) column: Mosaic takes a rank-1 block only
    # when it spans the whole array or a multiple of 128 lanes
    ks = jnp.clip(ks.astype(jnp.int32), 0, vocab)[:, None]
    rb = rows_block_for(vocab, logits.dtype)
    pad = (-rows) % rb
    x = jnp.pad(logits, ((0, pad), (0, 0))) if pad else logits
    kp = jnp.pad(ks, ((0, pad), (0, 0))) if pad else ks
    grid = (x.shape[0] // rb,)

    out = pl.pallas_call(
        _topk_dynamic_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, vocab), lambda r: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((rb, vocab), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, logits.dtype),
        interpret=interpret,
    )(x, kp)
    return out[:rows] if pad else out


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_mask_pallas(logits: jax.Array, k: int, *, interpret: bool = False) -> jax.Array:
    """Dense top-k mask of a (rows, vocab) array (threshold semantics)."""
    assert logits.ndim == 2, "fold batch dims before calling"
    rows, vocab = logits.shape
    rb = rows_block_for(vocab, logits.dtype)
    # pad rows to a multiple of the block
    pad = (-rows) % rb
    x = jnp.pad(logits, ((0, pad), (0, 0))) if pad else logits
    grid = (x.shape[0] // rb,)

    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=int(min(k, vocab))),
        grid=grid,
        in_specs=[pl.BlockSpec((rb, vocab), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((rb, vocab), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, logits.dtype),
        interpret=interpret,
    )(x)
    return out[:rows] if pad else out
