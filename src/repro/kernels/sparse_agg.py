"""Pallas TPU kernel: fused adaptive logit aggregation (paper eqs. 6-7).

The jnp reference materialises three (N, rows, V) temporaries (|K|, weights,
weighted stack) — four HBM passes over N x rows x V.  This kernel reads each
input tile once and emits the aggregated tile directly:

    out = ( Σ_n |x_n| · x_n ) / ( Σ_n |x_n| + ε )

Grid: (row_blocks, vocab_tiles); each step owns an (N, R_b, V_b) input block
(the client axis N is small — the paper selects 10 clients/round — so it
rides whole in VMEM) and the (R_b, V_b) output tile.  Pure VPU elementwise +
client-axis reduction: the canonical memory-bound fusion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.aggregation import dequant_channels

__all__ = [
    "sparse_agg_pallas",
    "scatter_wire_sums_pallas",
    "scatter_wire_sums_dequant_pallas",
]

ROWS_BLK = 8
VOCAB_BLK = 2048
EPS = 1e-12


def _agg_kernel(stack_ref, out_ref):
    x = stack_ref[...].astype(jnp.float32)  # (N, R_b, V_b)
    s = jnp.abs(x)
    num = jnp.sum(s * x, axis=0)
    den = jnp.sum(s, axis=0)
    out_ref[...] = (num / (den + EPS)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_agg_pallas(stack: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(N, rows, vocab) densified sparse logits -> (rows, vocab) fp32."""
    assert stack.ndim == 3
    n, rows, vocab = stack.shape
    rb = min(ROWS_BLK, rows)
    vb = min(VOCAB_BLK, vocab)
    rpad = (-rows) % rb
    vpad = (-vocab) % vb
    x = jnp.pad(stack, ((0, 0), (0, rpad), (0, vpad))) if (rpad or vpad) else stack
    r_all, v_all = x.shape[1:]
    grid = (r_all // rb, v_all // vb)

    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, rb, vb), lambda r, j: (0, r, j))],
        out_specs=pl.BlockSpec((rb, vb), lambda r, j: (r, j)),
        out_shape=jax.ShapeDtypeStruct((r_all, v_all), jnp.float32),
        interpret=interpret,
    )(x)
    return out[:rows, :vocab]


# ---------------------------------------------------------------------------
# Scatter-accumulate straight from the sparse wire format.
#
# The kernel above still READS a densified (N, rows, V) stack — O(N·rows·V)
# HBM traffic that exists only because the uplink was scattered back to
# dense.  The wire-format kernel reads the (value, index) entries themselves
# (the actual on-air payload) and writes the two (rows, V) sums once:
# O(N·rows·k) reads + O(rows·V) writes.
#
# Mosaic has no lowering for an in-kernel scatter, so the scatter is a
# one-hot matmul on the MXU.  Each vocab id v splits into (v >> 7, v & 127);
# for one row and a chunk of E entries,
#
#     num[hi, lo] += Σ_e  a_e·[hi_e == hi] · [lo_e == lo]
#                 =  (A @ Lᵀ)[hi, lo],  A (H, E), L (128, E)
#
# so the output row lives as an (H, 128) tile (H = V/128 rounded up to 8),
# the VPU builds the two one-hot operands in O(E·(H + 128)) instead of the
# O(E·V) of a flat one-hot, and the MXU does the reduction.  Grid: (row
# blocks, entry chunks); the entry axis is innermost so the (R_b, H, 128)
# output tiles stay resident while every client's entries of those rows
# stream through.  The kernel is mode-agnostic: callers pre-compute the two
# per-entry contribution channels (adaptive: s·v and s; zeropad/mean_nonzero:
# v and mask), so ONE kernel serves all three aggregation modes.
# ---------------------------------------------------------------------------

SCATTER_ENTRY_BLK = 512  # wire entries per grid step (lane-aligned)
_LANES = 128


def _scatter_wire_kernel(a_ref, b_ref, idx_ref, num_ref, den_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    rb, e = a_ref.shape
    h = num_ref.shape[1]
    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (h, e), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, e), 0)
    nt = (((1,), (1,)), ((), ()))  # contract the entry axis of both operands

    def row(i, carry):
        a = a_ref[pl.ds(i, 1), :].astype(jnp.float32)  # (1, E)
        b = b_ref[pl.ds(i, 1), :].astype(jnp.float32)
        idx = idx_ref[pl.ds(i, 1), :]
        hit_hi = hi_ids == (idx >> 7)  # (H, E)
        onehot_lo = (lo_ids == (idx & (_LANES - 1))).astype(jnp.float32)

        def tile(w):
            return jax.lax.dot_general(
                jnp.where(hit_hi, w, 0.0), onehot_lo, nt,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )

        num_ref[i] = num_ref[i] + tile(a)
        den_ref[i] = den_ref[i] + tile(b)
        return carry

    jax.lax.fori_loop(0, rb, row, 0)


@functools.partial(jax.jit, static_argnames=("vocab", "interpret"))
def scatter_wire_sums_pallas(
    a: jax.Array,
    b: jax.Array,
    indices: jax.Array,
    vocab: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Two-channel wire scatter: ``a, b, indices (N, rows, k)`` ->
    ``(num, den)`` each ``(rows, vocab)`` fp32, where
    ``num[r, idx[n,r,j]] += a[n,r,j]`` (b into den).  Masked-out entries
    must carry zero contributions (their index may be any valid id)."""
    assert a.ndim == 3 and a.shape == b.shape == indices.shape
    n, rows, k = a.shape
    # every client's entries of one row become one row of the kernel input
    flat = lambda x: jnp.swapaxes(x, 0, 1).reshape(rows, n * k)
    ents = n * k
    eb = ents if ents <= SCATTER_ENTRY_BLK else SCATTER_ENTRY_BLK
    rb = rows if rows <= ROWS_BLK else ROWS_BLK
    pad = ((0, (-rows) % rb), (0, (-ents) % eb))  # zero contributions at id 0
    a, b = jnp.pad(flat(a), pad), jnp.pad(flat(b), pad)
    indices = jnp.pad(flat(indices.astype(jnp.int32)), pad)
    r_all, e_all = a.shape
    h = -(-vocab // _LANES)
    h += (-h) % 8

    wire_spec = pl.BlockSpec((rb, eb), lambda r, j: (r, j))
    out_spec = pl.BlockSpec((rb, h, _LANES), lambda r, j: (r, 0, 0))
    num, den = pl.pallas_call(
        _scatter_wire_kernel,
        grid=(r_all // rb, e_all // eb),
        in_specs=[wire_spec, wire_spec, wire_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((r_all, h, _LANES), jnp.float32)] * 2,
        interpret=interpret,
    )(a, b, indices)
    unflat = lambda x: x.reshape(r_all, h * _LANES)[:rows, :vocab]
    return unflat(num), unflat(den)


@functools.partial(jax.jit, static_argnames=("vocab", "mode", "interpret"))
def scatter_wire_sums_dequant_pallas(
    q_values: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    indices: jax.Array,
    vocab: int,
    mode: str = "adaptive",
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Dequantize-fused two-channel wire scatter.

    ``q_values (N, rows, k) int8``, ``scale (N, rows) f32``,
    ``mask (N, rows, k) int8 in {0, 1}``, ``indices (N, rows, k) int32`` ->
    ``(num, den)`` each ``(rows, vocab)`` fp32 for the given aggregation
    ``mode`` (static).  Masked entries contribute exactly 0 to both
    channels regardless of their index.  The channels
    (:func:`repro.core.aggregation.dequant_channels`) are O(N·rows·k)
    elementwise work that XLA fuses into the kernel's input relayout; the
    scatter itself is :func:`scatter_wire_sums_pallas`."""
    assert q_values.ndim == 3 and q_values.shape == mask.shape == indices.shape
    assert scale.shape == q_values.shape[:2]
    a, b = dequant_channels(q_values, scale, mask, mode)
    return scatter_wire_sums_pallas(a, b, indices, vocab, interpret=interpret)
