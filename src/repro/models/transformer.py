"""Transformer stacks: decoder-only, encoder-decoder, hybrid (Jamba-style).

Layer layout is a repeating **period** (DESIGN §4):

  * uniform families (dense/moe/ssm/vlm): period = 1;
  * hybrid: period = lcm(attn_every, moe_every) — Jamba 1.5's 1-attn-per-8
    with MoE every 2 gives an 8-layer period repeated num_layers/8 times.

Parameters for each position-in-period are **stacked over repeats** and the
stack runs under ``jax.lax.scan`` (one compiled period regardless of depth —
72-layer Jamba compiles like an 8-layer model).  ``cfg.remat=True`` wraps the
scan body in ``jax.checkpoint`` for activation rematerialisation.

Caches (decode) follow the same layout: a period-dict of per-position cache
pytrees, each stacked over repeats, read per repeat alongside the params
(``stack_apply``: attention caches in place, new K/V or latent rows written
after the layer loop).  MoE layers also return their routing counters and
the experts each request was routed to (``moe_apply``), which decode
gathers per MoE position into ``StackState.moe_stats``: ``counts``
``(repeats, 3)`` and ``experts`` ``(repeats, B, k)``.

With ``cfg.mla`` the attention is multi-head latent attention
(``attention.mla_apply``) under ``jax.named_scope("mla")``; MoE MLPs run
under ``jax.named_scope("moe")``, so a compiled step names each fusion's
part in its ``op_name``.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (
    KVCache,
    LatentCache,
    attn_apply,
    attn_init,
    cross_attn_apply,
    empty_rows,
    init_kv_cache,
    init_latent_cache,
    mla_apply,
    mla_init,
    write_kv_rows,
)
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.models.moe import moe_apply, moe_init
from repro.models.ssm import init_ssm_cache, ssm_apply, ssm_init

__all__ = ["StackState", "period_of", "stack_init", "stack_apply", "init_stack_cache",
           "init_moe_stats"]

import os as _os

_UNROLL = _os.environ.get("REPRO_UNROLL", "0") == "1"


class StackState(NamedTuple):
    """Carry through the layer scan."""

    x: jax.Array  # (B, S, D) activations
    moe_aux: jax.Array  # () accumulated load-balance loss
    lora_h: jax.Array | None  # (B, r) most recent LoRA projection or None
    # decode only: per MoE position, {"counts": (repeats, 3), "experts":
    # (repeats, B, k)} int32
    moe_stats: dict | None = None


def period_of(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid":
        return 1
    p = cfg.attn_every
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe_every)
    assert cfg.num_layers % p == 0, (
        f"{cfg.name}: num_layers={cfg.num_layers} not divisible by period {p}"
    )
    return p


def _repeats(cfg: ModelConfig, num_layers: int) -> tuple[int, int]:
    """(period, repeats) of a stack of ``num_layers`` layers.  A stack of
    another length than the model's (an encoder, the leading dense layers,
    the layers after them) is uniform: period 1."""
    p = period_of(cfg) if num_layers == cfg.num_layers else 1
    return p, num_layers // p


def _layer_kinds(cfg: ModelConfig, j: int) -> tuple[str, str | None]:
    """(mixer kind, mlp kind) for position-in-period j."""
    mixer = "attn" if cfg.is_attention_layer(j) else "ssm"
    if cfg.family == "ssm":
        return mixer, None  # Mamba2 stacks: no separate MLP
    mlp = "moe" if cfg.is_moe_layer(j) else "dense"
    return mixer, mlp


def _layer_init(rng: jax.Array, cfg: ModelConfig, j: int, *, cross: bool) -> dict:
    mixer, mlp = _layer_kinds(cfg, j)
    keys = jax.random.split(rng, 8)
    params: dict[str, Any] = {"norm1": norm_init(cfg.d_model, kind=cfg.norm, dtype=cfg.param_dtype)}
    if mixer == "attn":
        params["attn"] = mla_init(keys[0], cfg) if cfg.mla is not None else attn_init(keys[0], cfg)
        if cfg.lora is not None:
            params["lora"] = _lora_init(keys[1], cfg)
    else:
        params["ssm"] = ssm_init(keys[0], cfg)
    if cross:
        params["norm_x"] = norm_init(cfg.d_model, kind=cfg.norm, dtype=cfg.param_dtype)
        params["cross"] = attn_init(keys[2], cfg)
    if mlp is not None:
        params["norm2"] = norm_init(cfg.d_model, kind=cfg.norm, dtype=cfg.param_dtype)
        params["mlp"] = mlp_init(
            keys[3], cfg.d_model, cfg.d_ff, activation=cfg.activation, use_bias=cfg.use_bias, dtype=cfg.param_dtype
        ) if mlp == "dense" else moe_init(keys[3], cfg)
    return params


def _lora_init(rng: jax.Array, cfg: ModelConfig) -> dict:
    """LoRA A/B for the configured attention targets (default q, v)."""
    lc = cfg.lora
    hd = cfg.head_dim
    d = cfg.d_model
    # (in, out) of each target; MLA's v adapts the value half of kv_b_proj,
    # whose input is the latent
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (d, d)}
    if cfg.mla is not None:
        m = cfg.mla
        dims = {"q": (d, cfg.num_heads * (m.nope_dim + m.rope_dim)),
                "v": (m.kv_rank, cfg.num_heads * m.v_dim)}
    params = {}
    attn_targets = [t for t in lc.targets if t in dims]  # 'head' lives at top level
    keys = jax.random.split(rng, max(1, len(attn_targets)))
    for key, tgt in zip(keys, attn_targets):
        d_in, d_out = dims[tgt]
        a = jax.random.normal(key, (d_in, lc.rank), jnp.float32) * (1.0 / d_in**0.5)
        params[tgt] = {
            "A": a.astype(jnp.dtype(cfg.param_dtype)),
            "B": jnp.zeros((lc.rank, d_out), jnp.dtype(cfg.param_dtype)),
        }
    return params


def stack_init(
    rng: jax.Array, cfg: ModelConfig, num_layers: int, *, cross: bool = False, causal: bool = True
) -> dict:
    """Init a stack as a period-dict of repeat-stacked layer params."""
    del causal  # same params either way
    p, repeats = _repeats(cfg, num_layers)
    out = {}
    for j in range(p):
        keys = jax.random.split(jax.random.fold_in(rng, j), repeats)
        out[f"pos{j}"] = jax.vmap(lambda k: _layer_init(k, cfg, j, cross=cross))(keys)
    return out


def _apply_one(
    params: dict,
    state: StackState,
    cfg: ModelConfig,
    j: int,
    *,
    positions: jax.Array,
    window: int | None,
    cache: Any | None,
    enc_out: jax.Array | None,
    causal: bool,
) -> tuple[StackState, Any | None, jax.Array | None]:
    """One layer: (state, new cache row or SSM cache, MoE stats or None:
    counters and the experts each request's last token went to)."""
    mixer, mlp = _layer_kinds(cfg, j)
    x = state.x
    moe_aux = state.moe_aux
    lora_h = state.lora_h
    stats = None

    h_in = norm_apply(params["norm1"], x, kind=cfg.norm, eps=cfg.norm_eps)
    if mixer == "attn":
        kwargs = dict(positions=positions, window=window, cache=cache,
                      lora=params.get("lora"), causal=causal)
        if cfg.mla is not None:
            with jax.named_scope("mla"):
                y, new_cache, h = mla_apply(params["attn"], h_in, cfg, **kwargs)
        else:
            y, new_cache, h = attn_apply(params["attn"], h_in, cfg, **kwargs)
        if h is not None:
            lora_h = jnp.mean(h, axis=1)  # (B, r) — pooled LoRA projection
    else:
        y, new_cache = ssm_apply(params["ssm"], h_in, cfg, cache=cache)
    x = x + y

    if enc_out is not None and "cross" in params:
        cx = norm_apply(params["norm_x"], x, kind=cfg.norm, eps=cfg.norm_eps)
        x = x + cross_attn_apply(params["cross"], cx, enc_out, cfg)

    if mlp is not None:
        h2 = norm_apply(params["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
        if mlp == "moe":
            with jax.named_scope("moe"):
                y2, aux, counts, experts = moe_apply(params["mlp"], h2, cfg)
            stats = {"counts": counts, "experts": experts[:, -1]}
            moe_aux = moe_aux + aux
        else:
            y2 = mlp_apply(params["mlp"], h2, activation=cfg.activation, compute_dtype=cfg.compute_dtype)
        x = x + y2

    return StackState(x=x, moe_aux=moe_aux, lora_h=lora_h), new_cache, stats


def init_stack_cache(
    cfg: ModelConfig, num_layers: int, batch: int, cache_len: int, *, window: int | None = None
) -> dict:
    """Period-dict of repeat-stacked caches for decode."""
    p, repeats = _repeats(cfg, num_layers)
    c = min(cache_len, window) if window is not None else cache_len
    out = {}
    for j in range(p):
        mixer, _ = _layer_kinds(cfg, j)
        if mixer == "attn":
            one = init_latent_cache(cfg, batch, c) if cfg.mla is not None else init_kv_cache(cfg, batch, c)
        else:
            one = init_ssm_cache(cfg, batch)
        out[f"pos{j}"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (repeats,) + a.shape), one)
    return out


def init_moe_stats(cfg: ModelConfig, num_layers: int, batch: int,
                   slots: int | None = None) -> dict:
    """Zero routing stats of a stack's MoE positions: ``counts`` (repeats,
    3) int32 (pairs routed to held experts, held experts used, pairs
    dropped) and ``experts`` int32, (repeats, B, k) as one decode step
    returns them (``StackState.moe_stats``), or with ``slots`` (repeats,
    B, k, slots), a decode cache's record of every slot."""
    p, r = _repeats(cfg, num_layers)
    return {f"pos{j}": {"counts": jnp.zeros((r, 3), jnp.int32),
                        "experts": jnp.zeros((r, batch, cfg.moe.top_k) + ((slots,) if slots else ()),
                                             jnp.int32)}
            for j in range(p) if _layer_kinds(cfg, j)[1] == "moe"}


def stack_apply(
    stack_params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    num_layers: int,
    *,
    positions: jax.Array,
    window: int | None = None,
    caches: dict | None = None,
    enc_out: jax.Array | None = None,
    causal: bool = True,
) -> tuple[StackState, dict | None]:
    """Run the full stack.  Returns (final state, updated caches or None)."""
    p, repeats = _repeats(cfg, num_layers)

    lora_h0 = None
    if cfg.lora is not None and any(
        _layer_kinds(cfg, j)[0] == "attn" for j in range(p)
    ):
        lora_h0 = jnp.zeros((x.shape[0], cfg.lora.rank), jnp.dtype(cfg.compute_dtype))
    state0 = StackState(x=x, moe_aux=jnp.zeros((), jnp.float32), lora_h=lora_h0)

    def body(state, xs):
        params_slice, cache_slice = xs
        new_caches, new_counts = {}, {}
        for j in range(p):
            cache_j = cache_slice[f"pos{j}"] if cache_slice is not None else None

            def one(params_j, state, cache_j, j=j):
                return _apply_one(
                    params_j, state, cfg, j,
                    positions=positions, window=window, cache=cache_j,
                    enc_out=enc_out, causal=causal,
                )

            if cfg.remat and p > 1:
                # nested remat: periods with many sublayers (jamba: 8) would
                # otherwise hold every sublayer's residuals at once during
                # the period's backward (§Perf iteration 6)
                one = jax.checkpoint(one, prevent_cse=False)
            state, nc, counts = one(params_slice[f"pos{j}"], state, cache_j)
            if nc is not None:
                new_caches[f"pos{j}"] = nc
            if counts is not None:
                new_counts[f"pos{j}"] = counts
        return state, (new_caches or None, new_counts)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    if caches is None:
        if _UNROLL:
            # cost-mode (REPRO_UNROLL=1): python loop so HLO cost analysis
            # sees every repeat (XLA counts while bodies once; launch/dryrun.py)
            state = state0
            for r in range(repeats):
                state, _ = body(state, (jax.tree.map(lambda a: a[r], stack_params), None))
            return state, None

        # scan can't carry a None in xs leaves; substitute per-step None via
        # a length marker: replicate None structure by scanning params only.
        def body_nocache(state, params_slice):
            s, _ = body(state, (params_slice, None))
            return s, None

        final, _ = jax.lax.scan(body_nocache, state0, stack_params, length=repeats)
        return final, None

    # Decode: a fori_loop over the repeats.  Attention layers read their KV
    # (or latent) cache in place (a loop invariant, never copied out or
    # written back) and hand back their new row, which the carry gathers
    # into an (R, ...) stack of rows; after the loop one update per tensor
    # writes every layer's row into its slot (``write_kv_rows``).  MoE
    # layers' counters are gathered the same way.  SSM caches are
    # rewritten whole by their nature: they ride in the carry and each
    # layer's state is replaced in place.  While-loop carries alias; a scan
    # emitting new caches as ys holds BOTH the old stack (xs) and the new
    # stack (ys) plus in-flight copies — ~3x cache in temp memory at
    # decode_32k (§Perf iteration 9).
    def index(tree, r):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False), tree)

    is_kv = {n: isinstance(c, (KVCache, LatentCache)) for n, c in caches.items()}
    carried0 = {  # per position: every repeat's new row, or the SSM cache
        n: empty_rows(c) if is_kv[n] else c for n, c in caches.items()
    }
    counts0 = init_moe_stats(cfg, num_layers, x.shape[0]) if cfg.moe else {}

    def layer_cache(c, r):
        """Layer r's cache; a latent stack stays whole (``LatentCache.layer``)."""
        if isinstance(c, LatentCache):
            return c._replace(pos=index(c.pos, r), length=index(c.length, r), layer=r)
        return index(c, r)

    def body_carry(r, carry):
        state, carried, counts = carry
        read = {n: layer_cache(caches[n], r) if is_kv[n] else index(carried[n], r)
                for n in caches}
        state, (new_r, counts_r) = body(state, (index(stack_params, r), read))
        at_r = lambda full, n: jax.lax.dynamic_update_index_in_dim(full, n, r, 0)  # noqa: E731
        return state, jax.tree.map(at_r, carried, new_r), jax.tree.map(at_r, counts, counts_r)

    carry = (state0, carried0, counts0)
    if _UNROLL:  # cost-mode, as above
        for r in range(repeats):
            carry = body_carry(r, carry)
    else:
        carry = jax.lax.fori_loop(0, repeats, body_carry, carry)
    final, carried, counts = carry
    new_caches = {
        n: write_kv_rows(caches[n], carried[n]) if is_kv[n] else carried[n] for n in caches
    }
    return final._replace(moe_stats=counts or None), new_caches
