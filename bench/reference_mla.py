"""The plain reference of the Moonlight cell: the Moonlight / DeepSeek-V3
block (multi-head latent attention, one dense SwiGLU layer, then
sigmoid-routed experts with shared experts), a full forward pass over each
served request with its own LoRA adapter on q and v.

Straightforward ``jax.numpy``; no cache, no absorbed form, no kernels, no
batching over requests; it imports nothing of the program.  Keys and
values are expanded per head, ``k = [c·W_uk, rope(k_pe)]`` and ``v =
c·W_uv + (alpha/r)·(c·A)·B``; every held expert runs on every token and is
weighted by its gate where the router chose it.  Departures from the
published description, which the program shares: RoPE pairs dimension i
with i + d/2 (DeepSeek's code pairs neighbours; with random weights a
fixed permutation of the RoPE columns); the chip's share of the experts
(``held`` of ``router_experts``, the router still scoring all of them and
normalising the gates over the k chosen) and of the vocabulary.

``prec`` and ``matmul`` select the precision as in ``bench/reference.py``
(the same products); the reference is ``"float32"`` at the precision the
configuration states, a control the same mathematics one step down.

Routing near-ties.  With random weights two experts' biased scores can lie
within rounding of each other, and a program computing in another order
may then choose the other one, a choice as right as the reference's.  So
the reference can be handed the experts the program chose (``routes``) and
follows them wherever they are a choice it could have made itself: every
expert whose biased score lies more than ``tie`` above the (k+1)-th is in
the set, and none lies more than ``tie`` below the k-th.  Anywhere else
the program's choice is a mismatch, counted, and the reference keeps its
own.  Which choices pass is decided by the reference's scores alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import _act, _mm


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate ``x (T, ..., d)`` by ``pos (T,)``, halves paired."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _route(h, m, s, mm, forced, tie):
    """(gates (T, E), own choice (T, k), mismatches (T,), followed (T,)):
    the experts chosen are ``forced (T, k)`` where the reference could have
    chosen them within ``tie`` (module docstring), else its own top k."""
    k, e = s["top_k"], m["router"]["w"].shape[-1]
    scores = jax.nn.sigmoid(mm("td,de->te", h, m["router"]["w"]).astype(jnp.float32))
    biased = scores + m["score_bias"]
    ranked, own = jax.lax.top_k(biased, k + 1)
    own_set = jnp.sum(jax.nn.one_hot(own[:, :k], e, dtype=jnp.int32), axis=1) > 0
    if forced is None:
        chosen = own_set
        mismatch = followed = jnp.zeros(h.shape[:1], bool)
    else:
        given = jnp.sum(jax.nn.one_hot(forced, e, dtype=jnp.int32), axis=1) > 0
        must = biased > ranked[:, k:k + 1] + tie
        may = biased >= ranked[:, k - 1:k] - tie
        ok = (jnp.sum(given, -1) == k) & jnp.all(given | ~must, -1) & jnp.all(may | ~given, -1)
        chosen = jnp.where(ok[:, None], given, own_set)
        mismatch, followed = ~ok, ok & jnp.any(given != own_set, -1)
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * s["routed_scale"]
    return gates, own[:, :k], mismatch, followed


def _layer(x, lp, lora, pos, s, mm, act, dense, forced=None, tie=0.0):
    t = x.shape[0]
    h, n, r, vd, rank = s["heads"], s["nope"], s["rope"], s["v_dim"], s["kv_rank"]
    eps, ls = s["eps"], s["lora_scale"]

    def adapted(inp, w, name):
        y = mm("ti,io->to", inp, w)
        if name in lora:
            y = y + ls * mm("tr,ro->to", mm("ti,ir->tr", inp, lora[name]["A"]), lora[name]["B"])
        return y

    a = lp["attn"]
    hn = _rms(x, lp["norm1"]["scale"], eps)
    q = adapted(hn, a["wq"]["w"], "q").reshape(t, h, n + r)
    kv = mm("td,dc->tc", hn, a["wkv_a"]["w"])
    c = _rms(kv[:, :rank], a["kv_norm"]["scale"], eps)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], pos, s["theta"])], axis=-1)
    k_pe = _rope(kv[:, rank:], pos, s["theta"])
    k = jnp.concatenate([mm("tc,co->to", c, a["wk_b"]["w"]).reshape(t, h, n),
                         jnp.broadcast_to(k_pe[:, None], (t, h, r))], axis=-1)
    v = adapted(c, a["wv_b"]["w"], "v").reshape(t, h, vd)
    sc = mm("shd,thd->hst", q, k).astype(jnp.float32) / jnp.sqrt(float(n + r))
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -1e30)
    probs = jax.nn.softmax(sc, axis=-1).astype(act)
    o = mm("hst,thd->shd", probs, v).reshape(t, h * vd)
    x = x + mm("to,od->td", o, a["wo"]["w"])
    h2 = _rms(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]

    def swiglu(g, u, d):
        return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h2, g)) * mm("td,df->tf", h2, u), d)

    if dense:
        return x + swiglu(m["gate"]["w"], m["up"]["w"], m["down"]["w"]), None
    gates, own, mismatch, followed = _route(h2, m, s, mm, forced, tie)
    count, first = s["held"]
    held = jax.lax.dynamic_slice_in_dim(gates, first, count, axis=1).astype(act)  # (T, count)
    y = swiglu(m["shared"]["gate"]["w"], m["shared"]["up"]["w"], m["shared"]["down"]["w"])
    g = mm("td,edf->etf", h2, m["gate"])
    u = mm("td,edf->etf", h2, m["up"])
    ye = mm("etf,efd->etd", jax.nn.silu(g) * u, m["down"])
    y = y + jnp.sum(held.T[:, :, None] * ye, axis=0)
    return x + y, (own, mismatch, followed)


@functools.partial(jax.jit, static_argnames=("spec", "prec", "matmul"))
def served_logits(frozen, lora, tokens, positions, routes=None, tie=0.0, *, spec,
                  prec="float32", matmul="highest"):
    """For ``tokens (B, T)``: next-token logits ``(B, P, V)`` at ``positions
    (B, P)``, and per MoE layer and position the reference's own top k
    ``(B, layers, T, k)``, the mismatches and the near-ties followed
    ``(B, layers, T)``.  ``routes (B, layers, T, k)``: the experts the
    program chose, which the reference follows within ``tie``.  Row b
    applies its own adapter, whose leaves carry the rows on their leading
    axis.  ``spec`` is a tuple of ``(name, value)`` pairs (``spec_of``)."""
    s = dict(spec)
    act = _act(prec)
    mm = functools.partial(_mm, prec=prec, matmul=matmul)

    def one(row_lora, toks, pos, *row_routes):
        t = jnp.arange(toks.shape[0])
        x = frozen["embed"][toks].astype(act)
        x, _ = jax.lax.scan(lambda x, layer: (_layer(x, *layer, t, s, mm, act, True)[0], None),
                            x, (frozen["prefix"]["pos0"], row_lora["prefix"]))

        def body(x, layer):
            lp, lo, *forced = layer
            return _layer(x, lp, lo, t, s, mm, act, False, forced=forced[0] if forced else None,
                          tie=tie)

        x, routing = jax.lax.scan(body, x, (frozen["stack"]["pos0"], row_lora["stack"],
                                            *row_routes))
        hn = _rms(x, frozen["final_norm"]["scale"], s["eps"])[pos]
        return (mm("pd,vd->pv", hn, frozen["lm_head"]).astype(jnp.float32),) + routing

    given = () if routes is None else (routes,)  # else its own choices throughout
    return jax.lax.map(lambda a: one(*a), (lora, tokens, positions, *given))


def spec_of(cfg: dict) -> tuple:
    """The sizes and constants of a configuration file, hashable."""
    lora = cfg["lora"]
    return tuple(sorted(dict(
        heads=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        top_k=cfg["num_experts_per_tok"], routed_scale=cfg["routed_scaling_factor"],
        held=(cfg["n_routed_experts"], cfg["first_held_expert"]), theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], lora_scale=lora["alpha"] / lora["rank"]).items()))
