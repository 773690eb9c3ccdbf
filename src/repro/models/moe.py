"""Mixture-of-Experts MLP with top-k routing and capacity-factor dispatch.

TPU-native design (DESIGN §4): experts are stacked on a leading axis that is
sharded over the ``model`` mesh axis; dispatch/combine are dense einsums over
one-hot routing tensors (GShard/Switch style), which XLA lowers to
all-to-all-shaped collectives between the token-sharded and expert-sharded
operands.  This keeps the MoE layer a single differentiable graph — no
ragged buffers — at the cost of the capacity-factor padding, which the
roofline accounts for explicitly.

Tokens are processed in **groups** (GShard's group dimension): the dispatch
tensor is (G, Tg, E, C) with per-group capacity C = cf·k·Tg/E, so its size
grows as T·Tg·k·cf instead of the ungrouped T²·k·cf — the difference between
335 MB and 21 GB at the train_4k shape.

Routing: softmax router, top-k experts per token, renormalized gates,
position-in-expert via cumulative sum (slot-major, group-local), tokens
beyond capacity dropped (standard Switch behaviour).  An auxiliary
load-balance loss (Switch Transformer eq. 4) is returned for the trainer.

Sigmoid routing (``moe.scoring == "sigmoid"``, DeepSeek-V3's noaux_tc with
one group): gates are ``sigmoid(logits)``; the top-k experts are chosen by
``sigmoid(logits) + score_bias``, a per-expert bias that only selects;
the chosen gates are renormalised and scaled (``routed_scale``).  Shared
experts (``shared_d_ff``) are one SwiGLU that every token passes through,
added to the routed part.

Expert parallelism: a layer told ``experts_held = (count, first)`` holds
only those experts' weights.  It routes over all ``num_experts`` (the
gates are normalised over the k chosen, held or not) and returns the held
experts' part of the result plus the shared experts; the exchange with the
chips that hold the rest is not this layer's.

A decode step (one token per request), and a prefill chunk, route
**dropless**: the pairs routed to held experts are sorted by expert and run
as grouped products (``jax.lax.ragged_dot``) over rows enough for all of
them, so no pair is ever dropped — a dropped token would be a different
answer.  Every call also returns counters ``(3,) int32`` (pairs routed to
held experts, held experts that received any, pairs dropped) and the
experts each token was routed to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init

__all__ = ["moe_init", "moe_apply", "route"]

# Tokens per dispatch group.  Chosen so the (G,Tg,E,C) dispatch tensor stays
# O(100MB) at the largest assigned shapes while C stays MXU-aligned-ish.
GROUP_SIZE = 1024


def moe_init(rng: jax.Array, cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    moe = cfg.moe
    e, d, f = moe.held[0], cfg.d_model, moe.d_ff
    keys = jax.random.split(rng, 5)

    def stack_init(key, din, dout):
        std = 1.0 / (din**0.5)
        w = jax.random.truncated_normal(key, -2.0, 2.0, (e, din, dout), jnp.float32) * std
        return w.astype(jnp.dtype(cfg.param_dtype))

    params = {
        "router": dense_init(keys[0], d, moe.num_experts, use_bias=False, dtype=cfg.param_dtype),
        "up": stack_init(keys[1], d, f),
        "down": stack_init(keys[2], f, d),
    }
    if cfg.activation == "swiglu":
        params["gate"] = stack_init(keys[3], d, f)
    if moe.scoring == "sigmoid":
        params["score_bias"] = jnp.zeros((moe.num_experts,), jnp.dtype(cfg.param_dtype))
    if moe.shared_d_ff:
        params["shared"] = mlp_init(keys[4], d, moe.shared_d_ff, activation=cfg.activation,
                                    dtype=cfg.param_dtype)
    return params


def route(params: dict, tokens: jax.Array, moe: MoEConfig, compute_dtype: str):
    """Routing of ``tokens (..., D)``: ``(gates (..., k) f32, experts (...,
    k) int32, probs (..., E) f32)``; ``probs`` feed the load-balance loss."""
    cd = jnp.dtype(compute_dtype)
    logits = jnp.einsum("...d,de->...e", tokens.astype(cd),
                        params["router"]["w"].astype(cd)).astype(jnp.float32)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + params["score_bias"].astype(jnp.float32), moe.top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, moe.top_k)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    if moe.routed_scale != 1.0:
        gates = gates * moe.routed_scale
    return gates, experts, probs


def _experts(params: dict, x: jax.Array, cfg: ModelConfig, dot) -> jax.Array:
    """The expert MLP with products ``dot(x, weight stack)``."""
    up = dot(x, params["up"])
    if "gate" in params:
        hidden = jax.nn.silu(dot(x, params["gate"])) * up
    else:
        hidden = jax.nn.gelu(up)
    return dot(hidden, params["down"])


def _dropless(params: dict, tokens: jax.Array, gates, experts, cfg: ModelConfig,
              real: jax.Array | None = None):
    """Every (token, held expert) pair, sorted by expert and run as grouped
    products; ``tokens (T, D)`` -> (held experts' part (T, D), counters).
    With ``real (T,) bool`` the other tokens (a prefill chunk's pads) route
    nowhere: they form no pair and count nowhere.

    The products run over twice the rows an even routing sends to the held
    experts (``budget``), or, in a step that routes more pairs there, over
    every row a token can send (``rows``): dropless either way."""
    moe = cfg.moe
    cd = jnp.dtype(cfg.compute_dtype)
    count, first = moe.held
    t, k = experts.shape
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    if real is not None:
        held &= jnp.repeat(real, k)
    key = jnp.where(held, local, count)  # count: not held
    sizes = jnp.bincount(key, length=count + 1)[:count]
    routed = jnp.sum(sizes)
    order = jnp.argsort(key, stable=True)
    rows = t * min(k, count)  # a token routes to each expert at most once
    budget = min(rows, -(-2 * t * k * count // moe.num_experts // 8) * 8)

    def grouped(n):
        pairs = order[:n]
        token = pairs // k
        dot = lambda a, w: jax.lax.ragged_dot(a, w.astype(cd), sizes)  # noqa: E731
        y = _experts(params, tokens[token], cfg, dot)
        y = jnp.where((jnp.arange(n) < routed)[:, None],
                      y * gates.reshape(-1)[pairs][:, None].astype(cd), 0)
        return jnp.zeros_like(tokens).at[token].add(y.astype(tokens.dtype))

    if budget < rows:
        out = jax.lax.cond(routed <= budget, lambda: grouped(budget), lambda: grouped(rows))
    else:
        out = grouped(rows)
    counters = jnp.stack([routed, jnp.sum(sizes > 0), routed - jnp.minimum(routed, rows)])
    return out, counters.astype(jnp.int32)


def _capacity(params: dict, tokens: jax.Array, gates, experts, cfg: ModelConfig):
    """GShard dispatch of ``tokens (G, Tg, D)`` with a capacity per expert
    and group; pairs beyond it are dropped."""
    moe = cfg.moe
    cd = jnp.dtype(cfg.compute_dtype)
    count, first = moe.held
    g, tg, _ = tokens.shape
    capacity = int(max(4, round(moe.capacity_factor * moe.top_k * tg / moe.num_experts)))
    capacity = min(capacity, tg)

    # one-hot over experts per routing slot: (G, Tg, K, E)
    onehot = jax.nn.one_hot(experts, moe.num_experts, dtype=jnp.float32)
    # position of each (token, slot) within its expert queue, slot-major so
    # every token's first choice is served before any second choice.
    flat = onehot.transpose(0, 2, 1, 3).reshape(g, moe.top_k * tg, moe.num_experts)
    pos_flat = jnp.cumsum(flat, axis=1) - flat  # (G, K*Tg, E)
    position = pos_flat.reshape(g, moe.top_k, tg, moe.num_experts).transpose(0, 2, 1, 3)
    position_in_expert = jnp.sum(position * onehot, axis=-1)  # (G,Tg,K)
    keep = position_in_expert < capacity
    gates = gates * keep.astype(gates.dtype)

    # dispatch/combine tensors (G, Tg, E, C), over the held experts
    cap_onehot = jax.nn.one_hot(position_in_expert, capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("gtke,gtkc->gtec", onehot * keep[..., None], cap_onehot)
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gates, onehot, cap_onehot)
    if count != moe.num_experts:
        dispatch = dispatch[:, :, first:first + count]
        combine = combine[:, :, first:first + count]

    # ---- expert computation (E is the model-sharded axis) ----
    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch.astype(cd), tokens)  # (G,E,C,D)
    dot = lambda a, w: jnp.einsum("gecd,edf->gecf", a, w.astype(cd))  # noqa: E731
    expert_out = _experts(params, expert_in, cfg, dot)
    out = jnp.einsum("gtec,gecd->gtd", combine.astype(cd), expert_out)  # (G,Tg,D)

    held = onehot[..., first:first + count]
    routed = jnp.sum(held).astype(jnp.int32)
    dropped = routed - jnp.sum(held * keep[..., None]).astype(jnp.int32)
    served = jnp.sum(jnp.any(dispatch > 0, axis=(1, 3))).astype(jnp.int32)
    return out, jnp.stack([routed, served, dropped])


def moe_apply(
    params: dict, x: jax.Array, cfg: ModelConfig, real: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> (output (B,S,D), aux load-balance loss scalar,
    counters (3,) int32, experts chosen (B,S,k) int32).  One token per
    request (S == 1, a decode step) routes dropless, and so does a prefill
    chunk (``real (S,) bool``: its real rows; pad rows route nowhere); a
    full sequence dispatches with a capacity."""
    assert cfg.moe is not None
    moe = cfg.moe
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    t = b * s
    if s == 1 or real is not None:
        tokens = x.reshape(t, d).astype(cd)
        gates, experts, probs = route(params, tokens, moe, cfg.compute_dtype)
        if real is not None:
            real = jnp.broadcast_to(real, (b, s)).reshape(t)
        out, counters = _dropless(params, tokens, gates, experts, cfg, real)
    else:
        tg = min(GROUP_SIZE, t)
        assert t % tg == 0, f"token count {t} not divisible by group size {tg}"
        tokens = x.reshape(t // tg, tg, d).astype(cd)
        gates, experts, probs = route(params, tokens, moe, cfg.compute_dtype)
        out, counters = _capacity(params, tokens, gates, experts, cfg)
    out = out.reshape(b, s, d)
    if "shared" in params:
        out = out + mlp_apply(params["shared"], x.astype(cd), activation=cfg.activation,
                              compute_dtype=cfg.compute_dtype)

    # ---- Switch load-balance auxiliary loss ----
    top1 = jax.nn.one_hot(experts[..., 0], moe.num_experts, dtype=jnp.float32)
    f_e = jnp.mean(top1.reshape(-1, moe.num_experts), axis=0)
    p_e = jnp.mean(probs.reshape(-1, moe.num_experts), axis=0)
    aux = moe.num_experts * jnp.sum(f_e * p_e)

    return (out.astype(x.dtype), aux.astype(jnp.float32), counters,
            experts.reshape(b, s, moe.top_k))
