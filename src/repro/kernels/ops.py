"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels execute in ``interpret=True`` mode — the
kernel body runs in Python per grid step against the same BlockSpec tiling,
validating the TPU program's logic; on a TPU backend they compile to Mosaic.
Batch-dim folding/unfolding lives here so callers pass natural shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.distill_kl import distill_kl_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sparse_agg import (
    scatter_wire_sums_dequant_pallas,
    scatter_wire_sums_pallas,
    sparse_agg_pallas,
)
from repro.kernels.topk_select import topk_mask_dynamic_pallas, topk_mask_pallas

__all__ = [
    "topk_mask",
    "topk_mask_dynamic",
    "distill_kl",
    "sparse_aggregate",
    "scatter_wire_sums",
    "scatter_wire_sums_dequant",
    "flash_attention",
    "interpret_mode",
]


def interpret_mode() -> bool:
    return jax.default_backend() == "cpu"


def _fold(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape((-1, x.shape[-1])), lead


def topk_mask(logits: jax.Array, k: int) -> jax.Array:
    """Dense top-k sparsification of (..., vocab) logits (paper eq. 4)."""
    flat, lead = _fold(logits)
    out = topk_mask_pallas(flat, k, interpret=interpret_mode())
    return out.reshape(lead + (logits.shape[-1],))


def topk_mask_dynamic(logits: jax.Array, ks: jax.Array) -> jax.Array:
    """Per-row-budget top-k mask: ``logits (..., vocab)`` with int32 budgets
    ``ks`` matching the leading shape — the fused round engine's uplink
    sparsifier (k is data, so one compiled program serves every round)."""
    flat, lead = _fold(logits)
    out = topk_mask_dynamic_pallas(
        flat, ks.reshape((-1,)), interpret=interpret_mode()
    )
    return out.reshape(lead + (logits.shape[-1],))


def distill_kl(teacher: jax.Array, student: jax.Array, temperature: float = 2.0) -> jax.Array:
    """Mean KL(σ(t/T)||σ(s/T)) with Hinton T² scaling — matches
    ``repro.core.distill.kl_divergence`` on (..., vocab) inputs."""
    t_flat, _ = _fold(teacher)
    s_flat, _ = _fold(student)
    per_row = distill_kl_pallas(t_flat, s_flat, float(temperature), interpret=interpret_mode())
    return jnp.mean(per_row) * (temperature**2)


def sparse_aggregate(stack: jax.Array) -> jax.Array:
    """Adaptive aggregation of (N, ..., vocab) -> (..., vocab) (eqs. 6-7)."""
    n = stack.shape[0]
    vocab = stack.shape[-1]
    flat = stack.reshape((n, -1, vocab))
    out = sparse_agg_pallas(flat, interpret=interpret_mode())
    return out.reshape(stack.shape[1:]).astype(stack.dtype)


def scatter_wire_sums(
    a: jax.Array, b: jax.Array, indices: jax.Array, vocab: int
) -> tuple[jax.Array, jax.Array]:
    """Two-channel scatter-accumulate from the sparse uplink wire format:
    ``a, b, indices (N, ..., k)`` -> ``(num, den)`` each ``(..., vocab)`` —
    the O(N·B·k) aggregation primitive (no dense (N, B, V) stack is ever
    formed; see :func:`repro.core.aggregation.aggregate_wire`)."""
    n, k = a.shape[0], a.shape[-1]
    lead = a.shape[1:-1]
    fold = lambda x: x.reshape((n, -1, k))
    num, den = scatter_wire_sums_pallas(
        fold(a), fold(b), fold(indices), vocab, interpret=interpret_mode()
    )
    return (
        num.reshape(lead + (vocab,)).astype(a.dtype),
        den.reshape(lead + (vocab,)).astype(b.dtype),
    )


def scatter_wire_sums_dequant(
    q_values: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    indices: jax.Array,
    vocab: int,
    mode: str = "adaptive",
) -> tuple[jax.Array, jax.Array]:
    """Dequantize-fused scatter-accumulate from the int8 quantized wire:
    ``q_values/mask/indices (N, ..., k)`` + per-row ``scale (N, ...)`` ->
    ``(num, den)`` each ``(..., vocab)`` fp32 for the given aggregation
    mode.  Nothing of size O(N·B·V) is ever formed: the contribution
    channels are O(N·B·k) elementwise work ahead of the scatter kernel."""
    n, k = q_values.shape[0], q_values.shape[-1]
    lead = q_values.shape[1:-1]
    fold = lambda x: x.reshape((n, -1, k))
    num, den = scatter_wire_sums_dequant_pallas(
        fold(q_values),
        scale.reshape((n, -1)),
        fold(mask.astype(jnp.int8)),
        fold(indices),
        vocab,
        mode,
        interpret=interpret_mode(),
    )
    return num.reshape(lead + (vocab,)), den.reshape(lead + (vocab,))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention, (B, H, S, D) or (B, S, D)."""
    if q.ndim == 4:
        b, h, s, d = q.shape
        fold = lambda x: x.reshape((b * h, s, d))
        out = flash_attention_pallas(fold(q), fold(k), fold(v), interpret=interpret_mode())
        return out.reshape((b, h, s, d))
    return flash_attention_pallas(q, k, v, interpret=interpret_mode())
