"""Pallas TPU kernel: fused temperature-softmax KL divergence over vocab tiles.

The distillation loss (paper eq. 9) over a large vocab is memory-bound: the
naive form reads the (rows, V) teacher and student tensors ~3x (logsumexp,
softmax, reduction) and materialises two (rows, V) intermediates.  This
kernel streams both operands tile-by-tile ONCE, carrying online-rescaled
accumulators (flash-attention-style):

    m_t, Z_t : running max / scaled partition of teacher logits t̃ = t/T
    m_s, Z_s : same for student
    U        : Σ exp(t̃ - m_t) · (t̃ - s̃)

and finishes with  KL = U/Z_t - (m_t + log Z_t) + (m_s + log Z_s).

Grid: (row_blocks, vocab_tiles) — vocab innermost so the scratch
accumulators (VMEM-resident (R_b, 1) columns) persist across the
sequential tile sweep; the per-row KL is emitted at the last tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["distill_kl_pallas"]

ROWS_BLK = 8
VOCAB_BLK = 2048


def _kl_kernel(t_ref, s_ref, out_ref, mt, zt, u, ms, zs, *, inv_temp: float, n_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mt[...] = jnp.full_like(mt[...], -jnp.inf)
        zt[...] = jnp.zeros_like(zt[...])
        u[...] = jnp.zeros_like(u[...])
        ms[...] = jnp.full_like(ms[...], -jnp.inf)
        zs[...] = jnp.zeros_like(zs[...])

    t = t_ref[...].astype(jnp.float32) * inv_temp  # (R, Vb)
    s = s_ref[...].astype(jnp.float32) * inv_temp

    # --- teacher online logsumexp + weighted (t - s) accumulator ---
    mt_old = mt[...]
    mt_new = jnp.maximum(mt_old, jnp.max(t, axis=-1, keepdims=True))
    scale_t = jnp.exp(mt_old - mt_new)
    w = jnp.exp(t - mt_new)
    zt[...] = zt[...] * scale_t + jnp.sum(w, axis=-1, keepdims=True)
    u[...] = u[...] * scale_t + jnp.sum(w * (t - s), axis=-1, keepdims=True)
    mt[...] = mt_new

    # --- student online logsumexp ---
    ms_old = ms[...]
    ms_new = jnp.maximum(ms_old, jnp.max(s, axis=-1, keepdims=True))
    zs[...] = zs[...] * jnp.exp(ms_old - ms_new) + jnp.sum(
        jnp.exp(s - ms_new), axis=-1, keepdims=True
    )
    ms[...] = ms_new

    @pl.when(j == n_tiles - 1)
    def _finish():
        lse_t = mt[...] + jnp.log(zt[...])
        lse_s = ms[...] + jnp.log(zs[...])
        out_ref[...] = u[...] / zt[...] - lse_t + lse_s


@functools.partial(jax.jit, static_argnames=("temperature", "interpret"))
def distill_kl_pallas(
    teacher: jax.Array,
    student: jax.Array,
    temperature: float = 2.0,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Per-row KL(σ(t/T) || σ(s/T)) for (rows, vocab) inputs -> (rows,) fp32."""
    assert teacher.shape == student.shape and teacher.ndim == 2
    rows, vocab = teacher.shape
    rb = min(ROWS_BLK, rows)
    vb = min(VOCAB_BLK, vocab)
    rpad = (-rows) % rb
    vpad = (-vocab) % vb
    if rpad or vpad:
        # pad vocab with -inf-like values that contribute nothing
        t = jnp.pad(teacher, ((0, rpad), (0, vpad)), constant_values=-1e30)
        s = jnp.pad(student, ((0, rpad), (0, vpad)), constant_values=-1e30)
    else:
        t, s = teacher, student
    r_all, v_all = t.shape
    n_tiles = v_all // vb
    grid = (r_all // rb, n_tiles)

    out = pl.pallas_call(
        functools.partial(_kl_kernel, inv_temp=1.0 / temperature, n_tiles=n_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, vb), lambda r, j: (r, j)),
            pl.BlockSpec((rb, vb), lambda r, j: (r, j)),
        ],
        # per-row results and accumulators are (rb, 1) columns: Mosaic
        # refuses a rank-1 block that is neither the whole array nor a
        # multiple of 128 lanes
        out_specs=pl.BlockSpec((rb, 1), lambda r, j: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((r_all, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32) for _ in range(5)],
        interpret=interpret,
    )(t, s)
    return out[:rows, 0]
