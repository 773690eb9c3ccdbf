"""Plain reference for the Moonlight / DeepSeek-V3 block: multi-head latent
attention and sigmoid-routed experts with shared experts, one sequence at a
time, in float32 at the highest matmul precision.

Straightforward ``jax.numpy`` with no cache, no absorbed form, no kernels
and no batching over requests; it imports nothing of the program.  Keys
and values are expanded per head, ``k = [c·W_uk, rope(k_pe)]`` and
``v = c·W_uv + (alpha/r)·(c·A)·B``; every held expert runs on every token
and is weighted by its gate where the router chose it.

It reads weights in the program's tree layout (``prefix`` and ``stack``,
each layer stacked on a leading axis; ``attn/{wq,wkv_a,kv_norm,wk_b,wv_b,
wo}``; ``mlp/{router,score_bias,gate,up,down,shared}``), and one request's
adapter as the same tree of ``lora/{q,v}/{A,B}`` leaves.

Departures from the published description (Moonlight-16B-A3B's
``config.json`` and DeepSeek-V3's modelling code):

* RoPE pairs dimension i with i + d/2 (the two halves); DeepSeek's code
  pairs neighbours (2i, 2i+1).  With random weights this is a fixed
  permutation of the RoPE columns of ``wq`` and ``wkv_a``, the same
  mathematics;
* RMSNorm epsilon is the configuration's (1e-5 for Moonlight);
* the expert share: a layer holds ``held = (count, first)`` of the routed
  experts; routing still scores all of them and normalises the gates over
  the k chosen, and only the held experts' part is added (with the shared
  experts), as one chip of an expert-parallel deployment computes it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Spec:
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    top_k: int
    routed_scale: float
    held: tuple[int, int]  # (count, first id) of the routed experts held
    rope_theta: float
    eps: float
    lora_scale: float  # alpha / r


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate ``x (T, ..., d)`` by position t = 0..T-1, halves paired."""
    t, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang).reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    sin = jnp.sin(ang).reshape(cos.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _adapted(x, w, lora, name, spec):
    y = x @ w
    if lora is not None and name in lora:
        y = y + spec.lora_scale * (x @ lora[name]["A"]) @ lora[name]["B"]
    return y


def _attention(x, p, lora, spec):
    t = x.shape[0]
    h, n, r, vd = spec.heads, spec.nope_dim, spec.rope_dim, spec.v_dim
    q = _adapted(x, p["wq"]["w"], lora, "q", spec).reshape(t, h, n + r)
    kv = x @ p["wkv_a"]["w"]
    c = _rms(kv[:, : spec.kv_rank], p["kv_norm"]["scale"], spec.eps)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], spec.rope_theta)], axis=-1)
    k_pe = _rope(kv[:, spec.kv_rank:], spec.rope_theta)
    k_nope = (c @ p["wk_b"]["w"]).reshape(t, h, n)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (t, h, r))], axis=-1)
    v = _adapted(c, p["wv_b"]["w"], lora, "v", spec).reshape(t, h, vd)
    s = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(n + r))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v).reshape(t, h * vd)
    return o @ p["wo"]["w"]


def route(x, p, spec):
    """(gates (T, E), margin (T,)): each token's gate per expert, zero where
    the router did not choose it, and the k-th less the (k+1)-th biased
    score, the router's distance from a tie."""
    scores = jax.nn.sigmoid(x @ p["router"]["w"])
    biased = scores + p["score_bias"]
    ranked = jax.lax.top_k(biased, spec.top_k + 1)[0]
    chosen = biased >= ranked[:, spec.top_k - 1: spec.top_k]
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * spec.routed_scale
    return gates, ranked[:, spec.top_k - 1] - ranked[:, spec.top_k]


def moe(x, p, spec):
    """One MoE layer's output for ``x (T, D)`` (the held experts' part and
    the shared experts), its router margins ``(T,)`` and the number of
    (token, held expert) pairs the router chose."""
    gates, margin = route(x, p, spec)
    count, first = spec.held
    out = _swiglu(x, p["shared"]["gate"]["w"], p["shared"]["up"]["w"], p["shared"]["down"]["w"])
    for e in range(count):
        out = out + gates[:, first + e: first + e + 1] * _swiglu(
            x, p["gate"][e], p["up"][e], p["down"][e])
    return out, margin, jnp.sum(gates[:, first:first + count] > 0)


def forward(params: dict, tokens: jax.Array, spec: Spec, lora: dict | None = None):
    """For one sequence ``tokens (T,)``: logits ``(T, V)``, each position's
    smallest router margin over the MoE layers ``(T,)``, and each MoE
    layer's count of (token, held expert) pairs ``(layers,)``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        margin = jnp.full(tokens.shape, jnp.inf)
        pairs = []
        for stack in ("prefix", "stack"):
            layers = params[stack]["pos0"]
            for i in range(layers["norm1"]["scale"].shape[0]):
                p = jax.tree.map(lambda a: a[i], layers)  # noqa: B023
                lo = None
                if lora is not None:
                    lo = jax.tree.map(lambda a: a[i], lora[stack]["pos0"]["lora"])  # noqa: B023
                x = x + _attention(_rms(x, p["norm1"]["scale"], spec.eps), p["attn"], lo, spec)
                h = _rms(x, p["norm2"]["scale"], spec.eps)
                m = p["mlp"]
                if "router" in m:
                    y, layer_margin, n = moe(h, m, spec)
                    margin = jnp.minimum(margin, layer_margin)
                    pairs.append(n)
                else:
                    y = _swiglu(h, m["gate"]["w"], m["up"]["w"], m["down"]["w"])
                x = x + y
        h = _rms(x, params["final_norm"]["scale"], spec.eps)
        return h @ params["lm_head"].T, margin, jnp.stack(pairs)
