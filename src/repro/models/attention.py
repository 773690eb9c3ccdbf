"""Grouped-query attention with KV cache, sliding window, LoRA hooks.

Sharding-relevant layout decisions (see DESIGN §4):
  * activations carry explicit head axes: q (B,S,Kv,G,Dh), k/v (B,T,Kv,Dh) —
    the Kv/G axes are what tensor parallelism shards;
  * the decode KV cache is laid out (B, Kv, Dh, C) with C, the cache
    length, minor: the TPU tiles it (Dh, C) without padding, and both
    decode contractions read it there in place (scores over Dh, output
    over C).  Decode never writes a layer's cache inside the layer loop:
    each layer returns its new K/V row, and ``write_kv_rows`` writes every
    layer's row into the layer-stacked cache with one single-slot update
    per tensor.  At decode shapes C is sharded along the **sequence** axis
    over the ``model`` mesh axis (flash-decoding on TPU): every device
    attends its slice, XLA turns the seq-contraction + softmax into partial
    reductions + ``psum``;
  * sliding-window mode stores a ring buffer of C = window entries with an
    absolute-position side array, so a 524k-token stream needs a 4k cache.

RoPE is applied at *write* time for keys (rotation by absolute position),
so cached keys never need re-rotation (relative property preserved).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_apply, dense_init

__all__ = [
    "KVCache", "KVRow", "attn_init", "attn_apply", "init_kv_cache", "write_kv_rows",
    "cross_attn_apply",
]

_NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, Kv, Dh, C) — RoPE already applied
    v: jax.Array  # (B, Kv, Dh, C)
    pos: jax.Array  # (C,) absolute position of each slot, -1 = empty
    length: jax.Array  # () int32 — tokens seen so far (absolute)


class KVRow(NamedTuple):
    """One decode step's new K/V entry of one layer."""

    k: jax.Array  # (B, Kv, Dh) — RoPE already applied
    v: jax.Array  # (B, Kv, Dh)


def attn_init(rng: jax.Array, cfg: ModelConfig, *, cross: bool = False) -> dict:
    hd = cfg.head_dim
    keys = jax.random.split(rng, 4)
    return {
        "wq": dense_init(keys[0], cfg.d_model, cfg.num_heads * hd, use_bias=cfg.use_bias, dtype=cfg.param_dtype),
        "wk": dense_init(keys[1], cfg.d_model, cfg.num_kv_heads * hd, use_bias=cfg.use_bias, dtype=cfg.param_dtype),
        "wv": dense_init(keys[2], cfg.d_model, cfg.num_kv_heads * hd, use_bias=cfg.use_bias, dtype=cfg.param_dtype),
        "wo": dense_init(keys[3], cfg.num_heads * hd, cfg.d_model, use_bias=cfg.use_bias, dtype=cfg.param_dtype),
    }


def init_kv_cache(
    cfg: ModelConfig, batch: int, cache_len: int, *, dtype: str | None = None
) -> KVCache:
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    hd = cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, cfg.num_kv_heads, hd, cache_len), dt),
        v=jnp.zeros((batch, cfg.num_kv_heads, hd, cache_len), dt),
        pos=jnp.full((cache_len,), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


def _lora_delta(lora_p: dict, x: jax.Array, *, alpha: float, rank: int, compute_dtype: str):
    """x @ A @ B scaled by alpha/r.  Returns (delta, h) with h = x @ A.

    Two adapter layouts (multi-tenant serving, repro.serve):
      * shared  — A (d, r), B (r, o): one adapter for the whole batch;
      * batched — A (B, d, r), B (B, r, o): row b of the batch applies
        adapter b.  Row b's contraction is the SAME einsum over the same
        operands as the shared case, so stacked multi-tenant decode is
        bit-identical to running each request alone with its adapter.
    """
    cd = jnp.dtype(compute_dtype)
    a, b = lora_p["A"].astype(cd), lora_p["B"].astype(cd)
    if a.ndim == 3:  # per-request adapters, leading axis = batch
        h = jnp.einsum("b...i,bir->b...r", x.astype(cd), a)
        delta = jnp.einsum("b...r,bro->b...o", h, b) * (alpha / rank)
    else:
        h = jnp.einsum("...i,ir->...r", x.astype(cd), a)
        delta = jnp.einsum("...r,ro->...o", h, b) * (alpha / rank)
    return delta, h


def _project(
    params: dict,
    x: jax.Array,
    name: str,
    cfg: ModelConfig,
    lora: dict | None,
) -> tuple[jax.Array, jax.Array | None]:
    y = dense_apply(params[f"w{name}"], x, compute_dtype=cfg.compute_dtype)
    h = None
    if lora is not None and name in lora:
        delta, h = _lora_delta(
            lora[name], x, alpha=cfg.lora.alpha, rank=cfg.lora.rank, compute_dtype=cfg.compute_dtype
        )
        y = y + delta
    return y, h


def _repeat_kv(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """(B,T,Kv,Dh) -> (B,T,Hq,Dh): single head axis so tensor parallelism
    shards scores/probs by head (§Perf iteration 1 — the (Kv,G) split axis
    defeated XLA's sharding propagation and replicated the score tensors)."""
    g = cfg.q_per_kv
    if g == 1:
        return x
    return jnp.repeat(x, g, axis=2)


def _gqa_scores(q: jax.Array, k: jax.Array, cfg: ModelConfig) -> jax.Array:
    """q (B,S,Hq,Dh), k (B,T,Kv,Dh) -> scores (B,Hq,S,T), head-sharded."""
    from repro import sharding as _sh

    dh = q.shape[-1]
    k_rep = _repeat_kv(k, cfg)
    scale = dh**-0.5
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), k_rep.astype(jnp.float32)
    ) * scale
    return _sh.constrain(scores, "batch", "heads", None, None)


def _gqa_output(probs: jax.Array, v: jax.Array, cfg: ModelConfig) -> jax.Array:
    """probs (B,Hq,S,T), v (B,T,Kv,Dh) -> (B,S,Hq*Dh)."""
    v_rep = _repeat_kv(v, cfg)
    out = jnp.einsum("bhst,bthd->bshd", probs, v_rep.astype(jnp.float32))
    b, s, h, dh = out.shape
    return out.reshape(b, s, h * dh)


# q-chunk length for the memory-efficient full-sequence path.  4k-512k
# sequences never materialise (S, T) scores — peak attention memory is
# (B, heads, Q_CHUNK, T) per in-flight chunk, which XLA's scan keeps to one.
Q_CHUNK = 512

# REPRO_UNROLL=1: replace the chunk scan with a python loop so HLO cost
# analysis sees every chunk (XLA counts while-loop bodies ONCE — the dry-run
# cost mode needs fully-materialised op counts; see launch/dryrun.py).
import os as _os

_UNROLL = _os.environ.get("REPRO_UNROLL", "0") == "1"


def _dense_attention(q, k, v, cfg, positions, window, causal) -> jax.Array:
    """Reference O(S·T)-memory attention for short sequences."""
    scores = _gqa_scores(q, k, cfg)  # (B,H,S,T)
    if causal:
        cmask = positions[..., :, None] >= positions[..., None, :]
        if window is not None:
            cmask &= positions[..., :, None] - positions[..., None, :] < window
        mask = cmask if cmask.ndim == 3 else cmask[None]
        scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_output(probs, v, cfg)


def _chunked_attention(q, k, v, cfg, positions, window, causal) -> jax.Array:
    """Scan over query chunks: memory O(Q_CHUNK · T), exact softmax per row.

    The jnp twin of kernels/flash_attention.py (which is the TPU-compiled
    version for inference prefill); this one is used inside the
    differentiable train path so the backward pass composes with
    ``jax.checkpoint`` over the layer scan.
    """
    b, s, hq, dh = q.shape
    nc = s // Q_CHUNK
    assert s % Q_CHUNK == 0, f"seq {s} not divisible by q-chunk {Q_CHUNK}"
    pos1d = positions if positions.ndim == 1 else positions[0]

    q_chunks = q.reshape(b, nc, Q_CHUNK, hq, dh).transpose(1, 0, 2, 3, 4)
    pos_chunks = pos1d.reshape(nc, Q_CHUNK)

    def one_chunk(args):
        qc, qpos = args  # (B, Cq, Hq, Dh), (Cq,)
        scores = _gqa_scores(qc, k, cfg)  # (B,H,Cq,T)
        if causal:
            m = qpos[:, None] >= pos1d[None, :]  # (Cq, T)
            if window is not None:
                m &= qpos[:, None] - pos1d[None, :] < window
            scores = jnp.where(m[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return _gqa_output(probs, v, cfg)  # (B, Cq, Hq*Dh)

    if _UNROLL:
        out = jnp.stack([one_chunk((q_chunks[i], pos_chunks[i])) for i in range(nc)])
    else:
        out = jax.lax.map(one_chunk, (q_chunks, pos_chunks))  # (nc, B, Cq, H*D)
    return out.transpose(1, 0, 2, 3).reshape(b, s, hq * dh)


def attn_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    window: int | None = None,
    cache: KVCache | None = None,
    lora: dict | None = None,
    causal: bool = True,
) -> tuple[jax.Array, KVRow | None, jax.Array | None]:
    """Self-attention.  Returns (output, new K/V row or None, lora_h).

    Full-sequence mode (cache is None): causal (+optional window) mask over
    the input sequence — used by train and prefill steps.

    Decode mode (cache: one layer's KVCache): x is (B, 1, D).  The query
    attends over the cache as it stands, with the new K/V in place of ring
    slot ``length % C``, whose old entry it is about to overwrite.  The
    cache is not written here: the new K/V comes back as a ``KVRow``, and
    ``write_kv_rows`` writes every layer's row at once.
    """
    q_flat, h_q = _project(params, x, "q", cfg, lora)
    k_flat, _ = _project(params, x, "k", cfg, lora)
    v_flat, h_v = _project(params, x, "v", cfg, lora)

    b, s, _ = x.shape
    hd = cfg.head_dim
    q = q_flat.reshape(b, s, cfg.num_heads, hd)
    k = k_flat.reshape(b, s, cfg.num_kv_heads, hd)
    v = v_flat.reshape(b, s, cfg.num_kv_heads, hd)
    if cache is None:  # full-seq: anchor head sharding (decode keeps the
        # seq-sharded-cache layout instead — q replicated over model)
        from repro import sharding as _sh

        q = _sh.constrain(q, "batch", None, "heads", None)
        k = _sh.constrain(k, "batch", None, "kv", None)
        v = _sh.constrain(v, "batch", None, "kv", None)

    if cfg.positional == "rope":
        q = apply_rope(q, positions, theta=cfg.rope_theta)
        k = apply_rope(k, positions, theta=cfg.rope_theta)

    lora_h = h_q if h_q is not None else h_v

    if cache is None:
        # ---- full-sequence causal path ----
        if s >= 2 * Q_CHUNK:
            out = _chunked_attention(q, k, v, cfg, positions, window, causal)
        else:
            out = _dense_attention(q, k, v, cfg, positions, window, causal)
        y = dense_apply(params["wo"], out.astype(x.dtype), compute_dtype=cfg.compute_dtype)
        return y, None, lora_h

    # ---- decode path: one new token against one layer's cache ----
    assert s == 1, "decode mode expects one new token"
    kv, g = cfg.num_kv_heads, cfg.q_per_kv
    f32 = jnp.float32
    qg = q.reshape(b, kv, g, hd).astype(f32)
    k_new = k.reshape(b, kv, hd).astype(cache.k.dtype)
    v_new = v.reshape(b, kv, hd).astype(cache.v.dtype)
    scale = hd**-0.5
    # the cache is read once, where it lies and in its stored layout: the
    # scores contract Dh, the output contracts C
    scores = jnp.einsum("bkgd,bkdc->bkgc", qg, cache.k.astype(f32)) * scale
    score_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new.astype(f32)) * scale
    slot = cache.length % cache.pos.shape[0]
    valid = (cache.pos >= 0) & (jnp.arange(cache.pos.shape[0]) != slot)
    if window is not None:
        valid &= cache.pos > cache.length - window
    scores = jnp.where(valid, scores, _NEG_INF)
    # softmax over the cache's valid slots and the new row
    top = jnp.maximum(jnp.max(scores, axis=-1), score_new)
    e = jnp.exp(scores - top[..., None])
    e_new = jnp.exp(score_new - top)
    out = jnp.einsum("bkgc,bkdc->bkgd", e, cache.v.astype(f32))
    out += e_new[..., None] * v_new.astype(f32)[:, :, None]
    out /= (jnp.sum(e, axis=-1) + e_new)[..., None]
    out = out.reshape(b, 1, kv * g * hd).astype(x.dtype)
    y = dense_apply(params["wo"], out, compute_dtype=cfg.compute_dtype)
    return y, KVRow(k=k_new, v=v_new), lora_h


def write_kv_rows(cache: KVCache, rows: KVRow) -> KVCache:
    """Write each layer's new K/V row into the layer-stacked cache, in
    place: one single-slot update per tensor.

    ``cache`` leaves carry a leading layer axis R (k/v (R, B, Kv, Dh, C));
    ``rows`` are (R, B, Kv, Dh).  Every decode step writes every layer, so
    all layers share one length and one slot.
    """
    length = cache.length[0]
    slot = (length % cache.k.shape[-1]).astype(jnp.int32)
    r = cache.k.shape[0]
    return KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, rows.k[..., None], (0, 0, 0, 0, slot)),
        v=jax.lax.dynamic_update_slice(cache.v, rows.v[..., None], (0, 0, 0, 0, slot)),
        pos=jax.lax.dynamic_update_slice(cache.pos, jnp.full((r, 1), length, jnp.int32), (0, slot)),
        length=cache.length + 1,
    )


def cross_attn_apply(
    params: dict,
    x: jax.Array,
    enc_out: jax.Array,
    cfg: ModelConfig,
    *,
    lora: dict | None = None,
) -> jax.Array:
    """Encoder-decoder cross attention (no mask, no cache mutation).

    ``enc_out``: (B, T_enc, D) encoder output; K/V recomputed each call in
    training; serving precomputes them once per request outside this fn.
    """
    q_flat, _ = _project(params, x, "q", cfg, lora)
    k_flat, _ = _project(params, enc_out, "k", cfg, lora)
    v_flat, _ = _project(params, enc_out, "v", cfg, lora)
    b, s, _ = x.shape
    t = enc_out.shape[1]
    hd = cfg.head_dim
    q = q_flat.reshape(b, s, cfg.num_heads, hd)
    k = k_flat.reshape(b, t, cfg.num_kv_heads, hd)
    v = v_flat.reshape(b, t, cfg.num_kv_heads, hd)
    scores = _gqa_scores(q, k, cfg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_output(probs, v, cfg).astype(x.dtype)
    return dense_apply(params["wo"], out, compute_dtype=cfg.compute_dtype)
