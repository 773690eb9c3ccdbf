"""Server-side logit aggregation schemes (paper §III-A, eqs. 6-7).

Given N clients' sparse logit uploads, the paper's *adaptive* aggregation
weights each client's contribution per dimension by its confidence share:

    s_{n,c}   = |K̃_{n,c}(x)|                     (confidence score)
    S[c]      = Σ_n s_{n,c}
    w_{n,c}   = s_{n,c} / S[c]                    (eq. 6)
    K_{g,c}   = Σ_n w_{n,c} * K̃_{n,c}(x)         (eq. 7)

Only clients that actually transmitted dimension c contribute, so the
zero-padding bias of naive averaging disappears.  Baselines implemented for
the paper's comparison: ``zeropad`` (mean over all N including zeros — the
paper's "ZeroPad"), and ``mean_nonzero`` (mean over transmitting clients
only; an ablation between ZeroPad and Adaptive).

Two input representations:

* **dense** ``(N, ..., vocab)`` stacks (zeros off-support) — the reference
  oracle the sequential/batched engines feed.  Every dense mode accepts an
  optional explicit ``mask`` (same shape, True = transmitted): without it,
  "transmitted" is inferred from the ``!= 0`` sentinel, which silently
  treats a transmitted logit that is exactly 0.0 as untransmitted (it then
  drops out of the ``mean_nonzero`` denominator).  The sparse wire path
  always carries the explicit mask.
* **sparse wire** :class:`repro.core.topk.SparseWire` ``(values, indices,
  mask)`` of width ``k_cap`` — what the fused end-to-end round consumes.
  :func:`aggregate_wire` scatter-accumulates straight from the wire into
  ONE ``(..., vocab)`` output, so the aggregation working set is
  O(N·B·k_cap) instead of the dense stack's O(N·B·V); the Pallas
  scatter-accumulate kernel (:mod:`repro.kernels.sparse_agg`) is the
  ``use_kernel=True`` route.

Shapes: ``stack`` is ``(N, ..., vocab)`` — leading client axis, then any
batch shape, vocab last.  All functions are jit/pjit friendly.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.topk import QuantizedWire, SparseWire

__all__ = [
    "aggregate_adaptive",
    "aggregate_zeropad",
    "aggregate_mean_nonzero",
    "aggregate",
    "aggregate_sparse",
    "aggregate_wire",
    "scatter_wire_sums",
    "scatter_wire_sums_dequant",
    "dequant_channels",
    "max_intermediate_elems",
]

_EPS = 1e-12


def _support(stack: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Transmit mask as the stack's dtype: explicit when given, else the
    legacy ``!= 0`` sentinel (which cannot see transmitted true zeros)."""
    if mask is None:
        return (stack != 0).astype(stack.dtype)
    return mask.astype(stack.dtype)


def aggregate_adaptive(
    stack: jax.Array, *, mask: jax.Array | None = None, eps: float = _EPS
) -> jax.Array:
    """Paper eqs. 6-7: dimension-wise confidence-weighted aggregation.

    Dimensions no client transmitted stay exactly 0.  The confidence score
    of a transmitted 0.0 is 0, so the explicit ``mask`` does not change the
    value here — it is threaded for API uniformity (and so masked-out
    garbage can never leak in).
    """
    m = _support(stack, mask)
    s = jnp.abs(stack) * m  # (N, ..., V) confidence scores
    total = jnp.sum(s, axis=0)  # (..., V) S[c]
    w = s / (total[None] + eps)  # (N, ..., V) w_{n,c}
    return jnp.sum(w * stack, axis=0)


def aggregate_zeropad(stack: jax.Array, *, mask: jax.Array | None = None) -> jax.Array:
    """Paper's 'ZeroPad' baseline: plain mean including zero padding."""
    if mask is not None:
        stack = stack * mask.astype(stack.dtype)
    return jnp.mean(stack, axis=0)


def aggregate_mean_nonzero(
    stack: jax.Array, *, mask: jax.Array | None = None, eps: float = _EPS
) -> jax.Array:
    """Mean over transmitting clients only (uniform, support-aware).

    With the explicit ``mask``, a transmitted logit that is exactly 0.0
    counts toward the denominator (it was on the air); the legacy sentinel
    fallback silently dropped it.
    """
    m = _support(stack, mask)
    count = jnp.sum(m, axis=0)
    return jnp.sum(stack * m, axis=0) / (count + eps)


AggregationMode = Literal["adaptive", "zeropad", "mean_nonzero"]


def aggregate(
    stack: jax.Array,
    mode: AggregationMode = "adaptive",
    *,
    mask: jax.Array | None = None,
    use_kernel: bool = False,
) -> jax.Array:
    """Dispatch on aggregation mode; ``use_kernel`` routes the adaptive path
    through the fused Pallas kernel.  ``mask`` is the optional explicit
    (N, ..., vocab) transmit mask (see module docstring)."""
    if mode == "adaptive":
        if use_kernel:
            from repro.kernels import ops as kops

            x = stack if mask is None else stack * mask.astype(stack.dtype)
            return kops.sparse_aggregate(x)
        return aggregate_adaptive(stack, mask=mask)
    if mode == "zeropad":
        return aggregate_zeropad(stack, mask=mask)
    if mode == "mean_nonzero":
        return aggregate_mean_nonzero(stack, mask=mask)
    raise ValueError(f"unknown aggregation mode: {mode!r}")


def scatter_wire_sums(
    a: jax.Array, b: jax.Array, indices: jax.Array, vocab: int
) -> tuple[jax.Array, jax.Array]:
    """Scatter-accumulate two channels of per-entry contributions
    ``a, b: (N, ..., k)`` at ``indices`` into ``(..., vocab)`` sums.

    The one primitive every aggregation mode reduces to — a single XLA
    scatter-add over the O(N·B·k) wire entries; nothing of size
    O(N·B·vocab) is ever materialised.  Masked-out entries must already be
    zeroed (adding 0 at a valid index is a no-op).
    """
    n, k = a.shape[0], a.shape[-1]
    batch_shape = a.shape[1:-1]
    af = a.reshape((n, -1, k))
    bf = b.reshape((n, -1, k))
    idf = indices.reshape((n, -1, k))
    rows = af.shape[1]
    row_ix = jnp.broadcast_to(
        jnp.arange(rows, dtype=jnp.int32)[None, :, None], idf.shape
    )
    num = jnp.zeros((rows, vocab), a.dtype).at[row_ix, idf].add(af)
    den = jnp.zeros((rows, vocab), b.dtype).at[row_ix, idf].add(bf)
    return (
        num.reshape(batch_shape + (vocab,)),
        den.reshape(batch_shape + (vocab,)),
    )


def scatter_wire_sums_dequant(
    q_values: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    indices: jax.Array,
    vocab: int,
    mode: AggregationMode = "adaptive",
) -> tuple[jax.Array, jax.Array]:
    """Dequantize-fused variant of :func:`scatter_wire_sums` for the int8
    :class:`~repro.core.topk.QuantizedWire`: reconstruct each entry's float
    value (``q * scale`` per row) and scatter the mode's two contribution
    channels in one pass, without ever materialising a separate float wire
    on the caller's side.

    The dequantized values live only as an O(N·B·k_cap) intermediate — the
    same order as the wire itself — so the dense-stack-free O(N·B·k_cap)
    contract of the sparse aggregation path is preserved (trace-asserted by
    the bench and tests/test_engine.py).
    """
    a, b = dequant_channels(q_values, scale, mask, mode)
    return scatter_wire_sums(a, b, indices, vocab)


def dequant_channels(
    q_values: jax.Array,
    scale: jax.Array,
    mask: jax.Array,
    mode: AggregationMode = "adaptive",
) -> tuple[jax.Array, jax.Array]:
    """The two per-entry contribution channels of an int8 wire for the
    aggregation ``mode`` (adaptive: ``|v|·v`` and ``|v|``; zeropad /
    mean_nonzero: ``v`` and the transmit mask), with ``v = q · scale``
    per row and exactly 0 where masked — the input of both the jnp and the
    Pallas wire scatter."""
    m = mask.astype(jnp.float32)
    v = q_values.astype(jnp.float32) * scale[..., None].astype(jnp.float32) * m
    if mode == "adaptive":
        return jnp.abs(v) * v, jnp.abs(v)
    if mode in ("zeropad", "mean_nonzero"):
        return v, m
    raise ValueError(f"unknown aggregation mode: {mode!r}")


def aggregate_wire(
    wire: SparseWire | QuantizedWire,
    mode: AggregationMode = "adaptive",
    *,
    num_transmitters: jax.Array | None = None,
    eps: float = _EPS,
    use_kernel: bool = False,
) -> jax.Array:
    """Aggregate straight from the sparse wire format (values, indices,
    mask) — O(N·B·k_cap) work and memory, one (..., vocab) densification at
    the very end (the output itself).

    Float-tolerance-consistent with the dense reference fed
    ``wire_densify(wire)`` + ``mask=wire_support(wire)`` in all three modes,
    including k == 0 clients (all-False mask rows contribute nothing) and
    true-zero transmitted logits.  ``num_transmitters`` (zeropad's
    denominator: clients with k > 0) may be passed as traced data when the
    caller already knows it; derived from the mask otherwise.  The dense
    oracle's stack holds ONLY transmitting clients, so its ``mean(axis=0)``
    divides by the same count.

    A :class:`~repro.core.topk.QuantizedWire` routes through the
    dequantize-fused scatter (:func:`scatter_wire_sums_dequant` /
    :func:`repro.kernels.ops.scatter_wire_sums_dequant`), which reconstructs
    the float values in the same O(N·B·k_cap) pass.

    ``use_kernel=True`` routes the scatter-accumulate through the Pallas
    kernels (:mod:`repro.kernels.sparse_agg`).
    """
    if mode not in ("adaptive", "zeropad", "mean_nonzero"):
        raise ValueError(f"unknown aggregation mode: {mode!r}")
    if isinstance(wire, QuantizedWire):
        if use_kernel:
            from repro.kernels import ops as kops

            num, den = kops.scatter_wire_sums_dequant(
                wire.values, wire.scale, wire.mask, wire.indices, wire.vocab, mode
            )
        else:
            num, den = scatter_wire_sums_dequant(
                wire.values, wire.scale, wire.mask, wire.indices, wire.vocab, mode
            )
    else:
        m = wire.mask.astype(wire.values.dtype)
        v = wire.values * m  # belt-and-braces: sparsify_wire already zeroed
        if mode == "adaptive":
            s = jnp.abs(v)  # confidence; 0 for masked entries
            a, b = s * v, s
        else:
            a, b = v, m

        if use_kernel:
            from repro.kernels import ops as kops

            num, den = kops.scatter_wire_sums(a, b, wire.indices, wire.vocab)
        else:
            num, den = scatter_wire_sums(a, b, wire.indices, wire.vocab)

    if mode == "zeropad":
        if num_transmitters is None:
            client_axes = tuple(range(1, wire.mask.ndim))
            num_transmitters = jnp.sum(
                jnp.any(wire.mask, axis=client_axes).astype(jnp.int32)
            )
        denom = jnp.maximum(num_transmitters, 1).astype(num.dtype)
        return num / denom
    return num / (den + eps)


def max_intermediate_elems(jaxpr) -> int:
    """Largest element count of any equation output anywhere in a jaxpr —
    sub-jaxprs (pjit / scan / cond bodies) included.

    This is the inspection behind the sparse path's memory contract:
    ``tests/test_engine.py`` asserts that
    ``max_intermediate_elems(jax.make_jaxpr(aggregate_wire-ish)(...))``
    stays below the dense ``(N, B, V)`` stack's element count.  Accepts a
    ``ClosedJaxpr`` or a raw ``Jaxpr``.
    """
    from jax import core as jax_core

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    worst = 0
    for eqn in inner.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            n = 1
            for s in shape:
                n *= int(s)
            worst = max(worst, n)
        for sub in jax_core.jaxprs_in_params(eqn.params):
            worst = max(worst, max_intermediate_elems(sub))
    return worst


def aggregate_sparse(
    values: jax.Array,
    indices: jax.Array,
    vocab: int,
    mode: AggregationMode = "adaptive",
    *,
    eps: float = _EPS,
) -> jax.Array:
    """Aggregate directly from sparse (value, index) payloads without first
    densifying each client — the per-row ``fori_loop`` reference formulation
    (every entry assumed transmitted; see :func:`aggregate_wire` for the
    masked wire-format fast path the round engine uses).

    values/indices: ``(N, ..., k)``.
    """
    n_clients = values.shape[0]
    batch_shape = values.shape[1:-1]
    k = values.shape[-1]

    flat_vals = values.reshape((n_clients, -1, k))
    flat_idx = indices.reshape((n_clients, -1, k))
    rows = flat_vals.shape[1]

    def per_row(vals_nk, idx_nk):
        # vals_nk, idx_nk: (N, k) for one (sample) row.
        sum_sv = jnp.zeros((vocab,), dtype=vals_nk.dtype)  # Σ s*K = Σ |K|*K
        sum_s = jnp.zeros((vocab,), dtype=vals_nk.dtype)  # Σ |K|
        sum_k = jnp.zeros((vocab,), dtype=vals_nk.dtype)  # Σ K (for baselines)
        cnt = jnp.zeros((vocab,), dtype=vals_nk.dtype)

        def body(n, carry):
            sum_sv, sum_s, sum_k, cnt = carry
            v = vals_nk[n]
            i = idx_nk[n]
            s = jnp.abs(v)
            sum_sv = sum_sv.at[i].add(s * v)
            sum_s = sum_s.at[i].add(s)
            sum_k = sum_k.at[i].add(v)
            cnt = cnt.at[i].add(jnp.ones_like(v))
            return sum_sv, sum_s, sum_k, cnt

        sum_sv, sum_s, sum_k, cnt = jax.lax.fori_loop(
            0, n_clients, body, (sum_sv, sum_s, sum_k, cnt)
        )
        if mode == "adaptive":
            return sum_sv / (sum_s + eps)
        if mode == "zeropad":
            return sum_k / float(n_clients)
        return sum_k / (cnt + eps)

    out = jax.vmap(per_row, in_axes=(1, 1))(flat_vals, flat_idx)  # (rows, vocab)
    del rows  # rows == prod(batch_shape); reshape below restores it
    return out.reshape(batch_shape + (vocab,))
