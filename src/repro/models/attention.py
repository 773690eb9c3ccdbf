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

Multi-head latent attention (``cfg.mla``, DeepSeek-V2/V3 without query
compression) caches one row per token for all heads: the RMS-normalised
latent ``c`` (``kv_rank`` wide) and the RoPE key that every head shares,
``(B, kv_rank + rope_dim, C)`` with C minor (``LatentCache``).  A
full-sequence pass expands keys ``[c·W_uk, k_pe]`` and values ``c·W_uv``
per head; decode uses the absorbed form: ``W_uk`` is folded into the
query, scores and the weighted sum read the latent rows as they are, and
``W_uv`` (with the ``v`` adapter) is applied to the weighted latent.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_apply, dense_init, norm_apply, norm_init

__all__ = [
    "KVCache", "KVRow", "LatentCache", "LatentRow", "attn_init", "attn_apply",
    "init_kv_cache", "mla_init", "mla_apply", "init_latent_cache", "empty_rows",
    "write_kv_rows", "cross_attn_apply",
]

_NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, Kv, Dh, C) — RoPE already applied
    v: jax.Array  # (B, Kv, Dh, C)
    pos: jax.Array  # (C,) absolute position of each slot, -1 = empty
    length: jax.Array  # () int32 — tokens seen so far (absolute)


class KVRow(NamedTuple):
    """One decode step's new K/V entry of one layer, or with a trailing
    slot axis T a prefill chunk's T entries."""

    k: jax.Array  # (B, Kv, Dh), or (B, Kv, Dh, T) — RoPE already applied
    v: jax.Array  # (B, Kv, Dh), or (B, Kv, Dh, T)
    # a chunk's absolute position of each row, -1 for a pad row (set by the
    # layer loop, which knows the pads); None for a decode step's one row
    pos: jax.Array | None = None


class LatentCache(NamedTuple):
    latent: jax.Array  # (B, kv_rank + rope_dim, C): normalised latent, then rotated RoPE key
    pos: jax.Array  # (C,) absolute position of each slot, -1 = empty
    length: jax.Array  # () int32 — tokens seen so far (absolute)
    # decode inside a layer loop: ``latent`` is the whole layer stack
    # (R, B, W, C) and ``layer`` the index of this layer, so that the two
    # reads (scores over all W rows, the weighted sum over the latent's)
    # each slice the stack where it lies; one slice shared by both would be
    # copied out whole
    layer: jax.Array | None = None


class LatentRow(NamedTuple):
    """One decode step's new latent row of one MLA layer, or with a
    trailing slot axis T a prefill chunk's T rows."""

    latent: jax.Array  # (B, kv_rank + rope_dim), or (B, kv_rank + rope_dim, T)
    pos: jax.Array | None = None  # as ``KVRow.pos``


def empty_rows(cache: KVCache | LatentCache, rows: int | None = None):
    """Zero rows of a layer-stacked cache's row type: one per layer and
    request, the cache's shape without its last (slot) axis, or with
    ``rows`` in its place (a prefill chunk's)."""
    def zeros(a):
        return jnp.zeros(a.shape[:-1] + ((rows,) if rows else ()), a.dtype)

    if isinstance(cache, LatentCache):
        return LatentRow(latent=zeros(cache.latent))
    return KVRow(k=zeros(cache.k), v=zeros(cache.v))


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


def _chunk_attention(q, keys, values, keys_new, values_new, pos, scale):
    """A prefill chunk's attention over a cache and over its own rows.

    ``q (B, ..., T, Dq)``, its leading axes after B those of the cache's
    reads: ``keys (B, ..., Dq, C)`` and ``values (B, ..., Dv, C)``, read in
    place (the decode layout, slots minor), and the chunk's own
    ``keys_new (B, ..., Dq, T)``, ``values_new (B, ..., Dv, T)``; a GQA
    group axis of q broadcasts over one K/V head.  Row i attends over the
    cache's filled slots (``pos >= 0``, all before the chunk) and over the
    chunk's rows ``<= i``, in one softmax.  Returns ``(B, ..., T, Dv)``
    float32."""
    f32 = jnp.float32
    t = q.shape[-2]
    lead = "kg" if q.ndim == 5 else "h"  # GQA (Kv, G) or MLA heads
    kv = lead[0] if q.ndim == 5 else ""  # the cache reads' head axis
    q = q.astype(f32)
    scores = jnp.einsum(f"b{lead}td,b{kv}dc->b{lead}tc", q, keys.astype(f32)) * scale
    scores = jnp.where(pos >= 0, scores, _NEG_INF)
    own = jnp.einsum(f"b{lead}td,b{kv}ds->b{lead}ts", q, keys_new.astype(f32)) * scale
    own = jnp.where(jnp.tril(jnp.ones((t, t), bool)), own, _NEG_INF)
    top = jnp.maximum(jnp.max(scores, axis=-1), jnp.max(own, axis=-1))[..., None]
    e, e_own = jnp.exp(scores - top), jnp.exp(own - top)
    out = jnp.einsum(f"b{lead}tc,b{kv}vc->b{lead}tv", e, values.astype(f32))
    out += jnp.einsum(f"b{lead}ts,b{kv}vs->b{lead}tv", e_own, values_new.astype(f32))
    return out / (jnp.sum(e, axis=-1) + jnp.sum(e_own, axis=-1))[..., None]


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

    Prefill-chunk mode (cache given, x (B, T, D) with T > 1): T new rows at
    slots ``[length, length + T)``, which the cache must hold without
    wrapping (no window).  Each row attends over the cache's filled slots
    and, causally, over the chunk's rows before it (``_chunk_attention``);
    the T new entries come back as one ``KVRow`` with a trailing slot axis.
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

    if s > 1:  # ---- prefill chunk: s new rows against one layer's cache ----
        kv, g = cfg.num_kv_heads, cfg.q_per_kv
        qg = q.reshape(b, s, kv, g, hd).transpose(0, 2, 3, 1, 4)  # (B, Kv, G, T, Dh)
        k_new = k.transpose(0, 2, 3, 1).astype(cache.k.dtype)  # (B, Kv, Dh, T)
        v_new = v.transpose(0, 2, 3, 1).astype(cache.v.dtype)
        out = _chunk_attention(qg, cache.k, cache.v, k_new, v_new, cache.pos, hd**-0.5)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, kv * g * hd).astype(x.dtype)
        y = dense_apply(params["wo"], out, compute_dtype=cfg.compute_dtype)
        return y, KVRow(k=k_new, v=v_new), lora_h

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


def write_kv_rows(cache: KVCache | LatentCache, rows: KVRow | LatentRow):
    """Write each layer's new K/V (or latent) row into the layer-stacked
    cache, in place: one single-slot update per tensor.

    ``cache`` leaves carry a leading layer axis R (k/v (R, B, Kv, Dh, C),
    latent (R, B, kv_rank + rope_dim, C)); ``rows`` are the same without
    C.  Every decode step writes every layer, so all layers share one
    length and one slot.

    A prefill chunk's rows (``rows.pos`` set) carry T slots in place of C
    and go to slots ``[length, length + T)`` with one T-slot update per
    tensor; a pad row's slot keeps ``pos`` -1, and the length advances by
    the real rows only.
    """
    length = cache.length[0]
    slot = (length % cache.pos.shape[-1]).astype(jnp.int32)
    r = cache.pos.shape[0]
    chunk = rows.pos is not None
    written = {
        f: jax.lax.dynamic_update_slice(
            getattr(cache, f), row if chunk else row[..., None],
            (0,) * (row.ndim - chunk) + (slot,))
        for f, row in rows._asdict().items() if f != "pos"
    }
    if chunk:
        pos, count = rows.pos, jnp.sum(rows.pos[0] >= 0, dtype=jnp.int32)
    else:
        pos, count = jnp.full((r, 1), length, jnp.int32), 1
    return cache._replace(
        **written,
        pos=jax.lax.dynamic_update_slice(cache.pos, pos, (0, slot)),
        length=cache.length + count,
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


# ---- multi-head latent attention (cfg.mla) --------------------------------


def mla_init(rng: jax.Array, cfg: ModelConfig) -> dict:
    """q_proj ``wq``; kv_a_proj_with_mqa ``wkv_a`` (latent, then the shared
    RoPE key); kv_a_layernorm ``kv_norm``; kv_b_proj split into its key half
    ``wk_b`` (W_uk) and value half ``wv_b`` (W_uv); o_proj ``wo``."""
    m, h, d, dt = cfg.mla, cfg.num_heads, cfg.d_model, cfg.param_dtype
    keys = jax.random.split(rng, 5)
    return {
        "wq": dense_init(keys[0], d, h * (m.nope_dim + m.rope_dim), dtype=dt),
        "wkv_a": dense_init(keys[1], d, m.kv_rank + m.rope_dim, dtype=dt),
        "kv_norm": norm_init(m.kv_rank, kind="rmsnorm", dtype=dt),
        "wk_b": dense_init(keys[2], m.kv_rank, h * m.nope_dim, dtype=dt),
        "wv_b": dense_init(keys[3], m.kv_rank, h * m.v_dim, dtype=dt),
        "wo": dense_init(keys[4], h * m.v_dim, d, dtype=dt),
    }


def init_latent_cache(
    cfg: ModelConfig, batch: int, cache_len: int, *, dtype: str | None = None
) -> LatentCache:
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    width = cfg.mla.kv_rank + cfg.mla.rope_dim
    return LatentCache(
        latent=jnp.zeros((batch, width, cache_len), dt),
        pos=jnp.full((cache_len,), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


def _lora_per_head(lora_p: dict, o: jax.Array, *, heads: int, alpha: float, rank: int,
                   compute_dtype: str) -> jax.Array:
    """The LoRA term ``(alpha/r)·(o·A)·B`` of a projection whose output is
    split into heads, for an input ``o (B, H, i)`` that differs per head:
    head h reads only its own columns of B.  Shared (A (i, r)) or
    per-request (A (B, i, r)) adapters, as ``_lora_delta``."""
    cd = jnp.dtype(compute_dtype)
    a, bm = lora_p["A"].astype(cd), lora_p["B"].astype(cd)
    if a.ndim == 3:
        h = jnp.einsum("bhi,bir->bhr", o.astype(cd), a)
        bm = bm.reshape(bm.shape[0], bm.shape[1], heads, -1)
        return jnp.einsum("bhr,brhv->bhv", h, bm) * (alpha / rank)
    h = jnp.einsum("bhi,ir->bhr", o.astype(cd), a)
    bm = bm.reshape(bm.shape[0], heads, -1)
    return jnp.einsum("bhr,rhv->bhv", h, bm) * (alpha / rank)


def _latent_reads(cache: LatentCache, kv_rank: int):
    """One layer's latent cache read where it lies: all ``kv_rank +
    rope_dim`` values of each slot (for the scores) and the latent's first
    ``kv_rank`` (for the weighted sum), each sliced from the layer stack on
    its own."""
    if cache.layer is None:
        return cache.latent, cache.latent[:, :kv_rank]
    stack = cache.latent
    latent = jax.lax.dynamic_index_in_dim(stack, cache.layer, 0, keepdims=False)
    values = jax.lax.dynamic_slice(
        stack, (cache.layer, 0, 0, 0), (1,) + stack.shape[1:2] + (kv_rank,)
        + stack.shape[3:])[0]
    return latent, values


def mla_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    window: int | None = None,
    cache: LatentCache | None = None,
    lora: dict | None = None,
    causal: bool = True,
) -> tuple[jax.Array, LatentRow | None, jax.Array | None]:
    """Multi-head latent attention.  Returns (output, new latent row(s) or
    None, lora_h), as ``attn_apply``, whose decode and prefill-chunk modes
    it has, both in the absorbed form over the latent cache.  LoRA ``q``
    adapts ``wq``; ``v`` adapts the value half of kv_b_proj,
    ``c·(W_uv + (alpha/r)·A·B)``, which is linear in the latent and so
    exact in the absorbed form."""
    m, heads = cfg.mla, cfg.num_heads
    cd = cfg.compute_dtype
    b, s, _ = x.shape
    q_flat, h_q = _project(params, x, "q", cfg, lora)
    q = q_flat.reshape(b, s, heads, m.nope_dim + m.rope_dim)
    q_nope, q_pe = q[..., :m.nope_dim], q[..., m.nope_dim:]
    kv = dense_apply(params["wkv_a"], x, compute_dtype=cd)
    c = norm_apply(params["kv_norm"], kv[..., :m.kv_rank], kind="rmsnorm", eps=cfg.norm_eps)
    q_pe = apply_rope(q_pe, positions, theta=cfg.rope_theta)
    k_pe = apply_rope(kv[..., None, m.kv_rank:], positions, theta=cfg.rope_theta)[..., 0, :]
    v_lora = lora.get("v") if lora is not None else None
    scale = (m.nope_dim + m.rope_dim) ** -0.5

    if cache is None:
        # ---- full sequence: keys and values expanded per head ----
        k_nope = dense_apply(params["wk_b"], c, compute_dtype=cd).reshape(b, s, heads, m.nope_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], (b, s, heads, m.rope_dim))], axis=-1)
        v = dense_apply(params["wv_b"], c, compute_dtype=cd)
        if v_lora is not None:
            v = v + _lora_delta(v_lora, c, alpha=cfg.lora.alpha, rank=cfg.lora.rank,
                                compute_dtype=cd)[0]
        v = v.reshape(b, s, heads, m.v_dim)
        qk = jnp.concatenate([q_nope, q_pe], axis=-1)
        attend = _chunked_attention if s >= 2 * Q_CHUNK else _dense_attention
        out = attend(qk, k, v, cfg, positions, window, causal)
        y = dense_apply(params["wo"], out.astype(x.dtype), compute_dtype=cd)
        return y, None, h_q

    if s > 1:  # ---- prefill chunk, absorbed: s new rows against the latent cache ----
        f32 = jnp.float32
        w_uk = params["wk_b"]["w"].reshape(m.kv_rank, heads, m.nope_dim)
        q_lat = jnp.einsum("bshn,chn->bhsc", q_nope.astype(f32), w_uk.astype(f32))
        q_all = jnp.concatenate([q_lat, q_pe.transpose(0, 2, 1, 3).astype(f32)], axis=-1)
        rows = jnp.concatenate([c, k_pe], axis=-1).transpose(0, 2, 1).astype(cache.latent.dtype)
        latent, values = _latent_reads(cache, m.kv_rank)
        o_lat = _chunk_attention(q_all, latent, values, rows, rows[:, :m.kv_rank], cache.pos,
                                 scale).transpose(0, 2, 1, 3)  # (B, T, H, kv_rank)
        w_uv = params["wv_b"]["w"].reshape(m.kv_rank, heads, m.v_dim)
        out = jnp.einsum("bshc,chv->bshv", o_lat.astype(jnp.dtype(cd)),
                         w_uv.astype(jnp.dtype(cd)))
        if v_lora is not None:
            out = out + jax.vmap(
                lambda o: _lora_per_head(v_lora, o, heads=heads, alpha=cfg.lora.alpha,
                                         rank=cfg.lora.rank, compute_dtype=cd),
                in_axes=1, out_axes=1)(o_lat)
        out = out.reshape(b, s, heads * m.v_dim).astype(x.dtype)
        y = dense_apply(params["wo"], out, compute_dtype=cd)
        return y, LatentRow(latent=rows), h_q

    # ---- decode, absorbed: one new token against one layer's latent cache ----
    assert s == 1, "decode mode expects one new token"
    f32 = jnp.float32
    w_uk = params["wk_b"]["w"].reshape(m.kv_rank, heads, m.nope_dim)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(f32), w_uk.astype(f32))
    q_all = jnp.concatenate([q_lat, q_pe[:, 0].astype(f32)], axis=-1)  # (B, H, 576)
    row = jnp.concatenate([c[:, 0], k_pe[:, 0]], axis=-1).astype(cache.latent.dtype)
    latent, values = _latent_reads(cache, m.kv_rank)
    scores = jnp.einsum("bhc,bct->bht", q_all, latent.astype(f32)) * scale
    score_new = jnp.einsum("bhc,bc->bh", q_all, row.astype(f32)) * scale
    slot = cache.length % cache.pos.shape[0]
    valid = (cache.pos >= 0) & (jnp.arange(cache.pos.shape[0]) != slot)
    if window is not None:
        valid &= cache.pos > cache.length - window
    scores = jnp.where(valid, scores, _NEG_INF)
    top = jnp.maximum(jnp.max(scores, axis=-1), score_new)
    e = jnp.exp(scores - top[..., None])
    e_new = jnp.exp(score_new - top)
    o_lat = jnp.einsum("bht,bct->bhc", e, values.astype(f32))
    o_lat += e_new[..., None] * row[:, None, :m.kv_rank].astype(f32)
    o_lat /= (jnp.sum(e, axis=-1) + e_new)[..., None]  # (B, H, kv_rank)
    w_uv = params["wv_b"]["w"].reshape(m.kv_rank, heads, m.v_dim)
    out = jnp.einsum("bhc,chv->bhv", o_lat.astype(jnp.dtype(cd)), w_uv.astype(jnp.dtype(cd)))
    if v_lora is not None:
        out = out + _lora_per_head(v_lora, o_lat, heads=heads, alpha=cfg.lora.alpha,
                                   rank=cfg.lora.rank, compute_dtype=cd)
    out = out.reshape(b, 1, heads * m.v_dim).astype(x.dtype)
    y = dense_apply(params["wo"], out, compute_dtype=cd)
    return y, LatentRow(latent=row), h_q
