"""Top-level model: embeddings + stack(s) + LM head, for every family.

API (all pure functions of (params, inputs)):

  init(rng, cfg)                                  -> params
  forward(params, cfg, batch)                     -> (logits, Aux)
  init_cache(cfg, batch, cache_len, window=None)  -> cache pytree
  decode_step(params, cfg, cache, token)          -> (logits, cache)
  prefill(params, cfg, batch, cache_len)          -> (logits, cache)

``batch``:
  tokens   (B, S) int32                        — always
  frontend (B, F, d_model) float               — vlm (prepended) / audio (encoder)

Aux carries moe load-balance loss and the pooled LoRA projection ``h``
(paper eq. 8) for the distillation objective.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.frontends import synth_frontend_embeddings
from repro.models.layers import embedding_init, norm_apply, norm_init
from repro.models.transformer import (
    init_moe_stats,
    init_stack_cache,
    stack_apply,
    stack_init,
)

__all__ = ["Aux", "init", "forward", "init_cache", "decode_step", "prefill", "input_token_len"]


class Aux(NamedTuple):
    moe_aux: jax.Array  # () load-balance loss
    lora_h: jax.Array | None  # (B, r) pooled LoRA projection (paper eq. 8)


def input_token_len(cfg: ModelConfig, seq_len: int) -> int:
    """Text tokens per sample given the assigned shape's seq_len.

    For VLM the frontend patches occupy part of the sequence budget, so the
    text stream is seq_len - frontend_len (total processed length stays at
    the assigned seq_len).
    """
    if cfg.family == "vlm":
        return seq_len - cfg.frontend_len
    return seq_len


def _stacks(cfg: ModelConfig) -> list[tuple[str, ModelConfig, int]]:
    """(params key, config, layers) of each decoder layer stack, in order:
    the leading dense layers (``cfg.first_dense_layers``, DeepSeek-V3's
    first_k_dense_replace) as a stack of their own, then the period stack.
    A decode cache keeps each stack's layers under ``<key>_layers``."""
    k = cfg.first_dense_layers
    if not k:
        return [("stack", cfg, cfg.num_layers)]
    return [("prefix", cfg.with_overrides(moe=None), k), ("stack", cfg, cfg.num_layers - k)]


def _cache_key(name: str) -> str:
    return "layers" if name == "stack" else f"{name}_layers"


def init(rng: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(rng, 6)
    params: dict[str, Any] = {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dtype=cfg.param_dtype),
        "final_norm": norm_init(cfg.d_model, kind=cfg.norm, dtype=cfg.param_dtype),
    }
    for i, (name, c, n) in enumerate(_stacks(cfg)):
        key = keys[1] if name == "stack" else jax.random.fold_in(keys[1], i + 1)
        params[name] = stack_init(key, c, n, cross=cfg.cross_attention)
    if cfg.positional == "learned":
        params["pos_embed"] = embedding_init(keys[2], cfg.max_seq_len, cfg.d_model, dtype=cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = embedding_init(keys[3], cfg.vocab_size, cfg.d_model, dtype=cfg.param_dtype)
    if cfg.lora is not None and "head" in cfg.lora.targets:
        import jax.numpy as _jnp

        a = jax.random.normal(keys[5], (cfg.d_model, cfg.lora.rank), _jnp.float32)
        params["lora_head"] = {
            "A": (a / cfg.d_model**0.5).astype(_jnp.dtype(cfg.param_dtype)),
            "B": _jnp.zeros((cfg.lora.rank, cfg.vocab_size), _jnp.dtype(cfg.param_dtype)),
        }
    if cfg.encoder_layers > 0:
        params["encoder"] = stack_init(keys[4], cfg, cfg.encoder_layers, cross=False)
        params["enc_norm"] = norm_init(cfg.d_model, kind=cfg.norm, dtype=cfg.param_dtype)
    return params


def _embed_tokens(params: dict, cfg: ModelConfig, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    from repro import sharding as _sh

    cd = jnp.dtype(cfg.compute_dtype)
    if _sh.rules_installed() and tokens.ndim == 2 and tokens.shape[1] > 1:
        # one-hot matmul instead of gather: SPMD partitions the contraction
        # over the vocab shards (a gather on the model-sharded table forces
        # involuntary replication of the whole embedding — §Perf iteration 5).
        # The one-hot MUST be vocab-sharded and rematerialised: an unsharded
        # (B,S,V) one-hot stored as a backward residual per microbatch cost
        # +32 GB/chip at seamless train (§Perf iteration 10 regression fix).
        def embed(tok, table):
            onehot = jax.nn.one_hot(tok, cfg.vocab_size, dtype=cd)
            onehot = _sh.constrain(onehot, "batch", None, "vocab")
            return jnp.einsum("bsv,vd->bsd", onehot, table.astype(cd))

        x = jax.checkpoint(embed)(tokens, params["embed"])
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if cfg.positional == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(cd)
    return x


def _lm_logits(
    params: dict, cfg: ModelConfig, x: jax.Array, head_cols: int | None = None
) -> jax.Array:
    """LM-head logits; ``head_cols`` restricts the head to its FIRST
    ``head_cols`` vocab columns (each retained logit is the identical dot
    product, so this equals slicing the full output — at head_cols/V of the
    FLOPs).  The classification readout (paper §IV: class logits = the first
    num_classes vocab ids) only ever consumes those columns."""
    cd = jnp.dtype(cfg.compute_dtype)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if head_cols is not None:
        head = head[:head_cols]
    logits = jnp.einsum("bsd,vd->bsv", x.astype(cd), head.astype(cd))
    if "lora_head" in params:  # LoRA on the LM head (PEFT-standard target)
        lh = params["lora_head"]
        if lh["A"].ndim == 3:  # per-request adapters (repro.serve): (B, d, r)
            lb = lh["B"] if head_cols is None else lh["B"][:, :, :head_cols]
            h = jnp.einsum("bsd,bdr->bsr", x.astype(cd), lh["A"].astype(cd))
            delta = jnp.einsum("bsr,brv->bsv", h, lb.astype(cd))
        else:
            lb = lh["B"] if head_cols is None else lh["B"][:, :head_cols]
            h = jnp.einsum("bsd,dr->bsr", x.astype(cd), lh["A"].astype(cd))
            delta = jnp.einsum("bsr,rv->bsv", h, lb.astype(cd))
        logits = logits + delta * (cfg.lora.alpha / cfg.lora.rank)
    return logits


def _run_encoder(params: dict, cfg: ModelConfig, frontend: jax.Array) -> jax.Array:
    pos = jnp.arange(frontend.shape[1], dtype=jnp.int32)
    st, _ = stack_apply(
        params["encoder"],
        frontend.astype(jnp.dtype(cfg.compute_dtype)),
        cfg,
        cfg.encoder_layers,
        positions=pos,
        causal=False,
    )
    return norm_apply(params["enc_norm"], st.x, kind=cfg.norm)


def backbone(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    *,
    window: int | None = None,
    last_only: bool = False,
) -> tuple[jax.Array, Aux]:
    """Full-sequence hidden states (post final-norm, pre LM head).

    For VLM the returned hidden covers the TEXT region only (frontend
    positions are processed but dropped before the head).  Training uses
    this + chunked cross-entropy so (B, S, vocab) logits never materialise.

    ``last_only=True`` returns the FINAL text position only, shape
    ``(B, 1, d_model)``: the stack still processes every position (causal
    mixing needs them) but the final norm — and, downstream, the LM head —
    touch one position instead of S.  ``Aux`` is identical to the full
    forward: the pooled LoRA projection (paper eq. 8) always pools over the
    whole sequence.
    """
    tokens = batch["tokens"]
    b, s_text = tokens.shape
    window = window if window is not None else cfg.sliding_window

    enc_out = None
    if cfg.family == "audio":
        frontend = batch.get("frontend")
        if frontend is None:
            frontend = synth_frontend_embeddings(cfg, b)
        enc_out = _run_encoder(params, cfg, frontend)

    if cfg.family == "vlm":
        frontend = batch.get("frontend")
        if frontend is None:
            frontend = synth_frontend_embeddings(cfg, b)
        f = frontend.shape[1]
        pos = jnp.arange(f + s_text, dtype=jnp.int32)
        x_text = _embed_tokens(params, cfg, tokens, pos[f:])
        x = jnp.concatenate([frontend.astype(x_text.dtype), x_text], axis=1)
    else:
        pos = jnp.arange(s_text, dtype=jnp.int32)
        x = _embed_tokens(params, cfg, tokens, pos)

    moe_aux, lora_h = jnp.zeros((), jnp.float32), None
    for name, c, n in _stacks(cfg):
        st, _ = stack_apply(
            params[name], x, c, n, positions=pos, window=window, enc_out=enc_out
        )
        x, moe_aux = st.x, moe_aux + st.moe_aux
        lora_h = st.lora_h if st.lora_h is not None else lora_h
    x_out = x
    if cfg.family == "vlm":
        x_out = x_out[:, frontend.shape[1] :]  # text region only
    # The SSM fallback projection pools over the FULL normalized sequence, so
    # that path must norm every position even under last_only.
    need_fallback_h = lora_h is None and "lora_head" in params
    if last_only and not need_fallback_h:
        h = norm_apply(params["final_norm"], x_out[:, -1:], kind=cfg.norm, eps=cfg.norm_eps)
    else:
        h = norm_apply(params["final_norm"], x_out, kind=cfg.norm, eps=cfg.norm_eps)
        if need_fallback_h:
            # attention-free families (SSM) have no q/v adapters; the paper's
            # projection h = A·x (eq. 8) comes from the head adapter instead —
            # any low-rank adapter satisfies the cross-family exchange contract.
            cd = jnp.dtype(cfg.compute_dtype)
            lora_h = jnp.mean(
                jnp.einsum("bsd,dr->bsr", h.astype(cd), params["lora_head"]["A"].astype(cd)),
                axis=1,
            )
        if last_only:
            h = h[:, -1:]
    return h, Aux(moe_aux=moe_aux, lora_h=lora_h)


def forward(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    *,
    window: int | None = None,
    last_only: bool = False,
    head_cols: int | None = None,
) -> tuple[jax.Array, Aux]:
    """Full-sequence forward returning (B, S_text, vocab) logits.

    ``last_only=True`` computes the LM head on the final position only and
    returns ``(B, vocab)`` — identical (to float tolerance) to
    ``forward(...)[0][:, -1, :]`` at ~1/S of the head FLOPs/memory.  This is
    the mode every federated phase uses: the task convention (paper §IV)
    reads class and distillation logits at the last position exclusively.

    ``head_cols=k`` computes only the first k head columns (bit-identical to
    slicing ``[..., :k]`` of the full logits) — the supervised
    classification losses/eval read ``num_classes`` of the 50k+ vocab
    logits, a ~V/num_classes head-FLOP cut on those phases.
    """
    h, aux = backbone(params, cfg, batch, window=window, last_only=last_only)
    logits = _lm_logits(params, cfg, h, head_cols)
    if last_only:
        return logits[:, 0], aux
    return logits, aux


def init_cache(
    cfg: ModelConfig,
    batch: int,
    cache_len: int,
    *,
    window: int | None = None,
    enc_out: jax.Array | None = None,
) -> dict:
    """Decode cache: per-layer KV/latent/SSM caches of each stack +
    absolute length + optional fixed encoder output (audio cross-attention)
    + the MoE layers' routing stats (``moe``, per MoE position: ``counts``
    (repeats, 3) int32 summed over the steps: pairs routed to held experts,
    held experts used, pairs dropped; ``experts`` (repeats, B, k, C) int32:
    the experts each request's token in each slot was routed to)."""
    window = window if window is not None else cfg.sliding_window
    cache = {
        _cache_key(name): init_stack_cache(c, n, batch, cache_len, window=window)
        for name, c, n in _stacks(cfg)
    }
    cache["length"] = jnp.zeros((), jnp.int32)
    if cfg.moe is not None:
        _, c, n = _stacks(cfg)[-1]
        slots = min(cache_len, window) if window is not None else cache_len
        cache["moe"] = init_moe_stats(c, n, batch, slots)
    if cfg.family == "audio":
        if enc_out is None:
            enc_out = jnp.zeros((batch, cfg.frontend_len, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        cache["enc_out"] = enc_out
    return cache


def decode_step(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    token: jax.Array,  # (B,) int32 — the newly sampled token
    *,
    window: int | None = None,
) -> tuple[jax.Array, dict]:
    """One serving step: consume `token`, return next-token logits + cache."""
    window = window if window is not None else cfg.sliding_window
    b = token.shape[0]
    length = cache["length"]
    pos = jnp.broadcast_to(length[None], (1,)).astype(jnp.int32)
    x = _embed_tokens(params, cfg, token[:, None], pos)
    enc_out = cache.get("enc_out")

    new_cache = dict(cache)
    for name, c, n in _stacks(cfg):
        st, new_cache[_cache_key(name)] = stack_apply(
            params[name],
            x,
            c,
            n,
            positions=pos,
            window=window,
            caches=cache[_cache_key(name)],
            enc_out=enc_out,
        )
        x = st.x
    if st.moe_stats is not None:
        new_cache["moe"] = {
            n: {"counts": cache["moe"][n]["counts"] + s["counts"],
                "experts": jax.lax.dynamic_update_slice(
                    cache["moe"][n]["experts"], s["experts"][..., None],
                    (0, 0, 0, length % cache["moe"][n]["experts"].shape[-1]))}
            for n, s in st.moe_stats.items()}
    h = norm_apply(params["final_norm"], x, kind=cfg.norm, eps=cfg.norm_eps)
    logits = _lm_logits(params, cfg, h)[:, 0]  # (B, V)

    new_cache["length"] = length + 1
    return logits, new_cache


def prefill(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    *,
    window: int | None = None,
) -> tuple[jax.Array, Aux]:
    """Prefill: full forward over the prompt, returning only the
    LAST-position logits (B, vocab) — what sampling needs.  (Cache writes
    during prefill are a serving-runtime concern; the full-sequence compute
    here dominates prefill cost, which is what the dry-run measures.)"""
    return forward(params, cfg, batch, window=window, last_only=True)
