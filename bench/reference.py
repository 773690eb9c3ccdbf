"""The plain reference: GPT-2 with LoRA on q and v, a full forward pass over
each served request.

Straightforward ``jax.numpy``; no kernels, no cache, no batching over
requests.  It imports nothing of the program.  It follows the program's
stated mathematics where that departs from the published GPT-2: LayerNorm
epsilon 1e-6 and the tanh form of GELU.  LoRA is applied as the paper
writes it, ``x·W + (alpha / r)·(x·A)·B``, each request with its own adapter.

``prec`` selects the type values are kept in:

* ``"float32"``: every value in float32, every product at the matmul
  precision ``matmul``: ``"highest"`` is float32 on a TPU and the CPU
  alike, ``"high"`` three bfloat16 passes (each operand split into a high
  and a low bfloat16 part, every product but low times low, float32
  accumulation; written out here, so that the CPU computes it as a TPU
  does), ``"default"`` one bfloat16 pass on a TPU and float32 on the CPU;
* ``"bfloat16"``: operands, products and activations in bfloat16.

The reference is ``"float32"`` at the precision the configuration states;
a control is the same mathematics one step down.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
PRECISION = {"default": jax.lax.Precision.DEFAULT, "highest": jax.lax.Precision.HIGHEST}


def _act(prec):
    """The type activations are kept in."""
    return jnp.bfloat16 if prec == "bfloat16" else jnp.float32


def _split(x):
    """``x`` as a high and a low bfloat16 part.  The high part is ``x`` with
    its low 16 bits cleared, by bits: a compiler that may keep excess
    precision would otherwise fold ``x - float32(bfloat16(x))`` to zero."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _mm(spec, *xs, prec, matmul):
    if prec == "bfloat16":
        return jnp.einsum(spec, *[x.astype(jnp.bfloat16) for x in xs],
                          preferred_element_type=jnp.bfloat16)
    if matmul == "high":
        (ah, al), (bh, bl) = _split(xs[0]), _split(xs[1])
        return sum(jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
                   for a, b in ((ah, bh), (ah, bl), (al, bh)))
    return jnp.einsum(spec, *[x.astype(jnp.float32) for x in xs],
                      precision=PRECISION[matmul], preferred_element_type=jnp.float32)


def _layer_norm(x, p):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _block(x, lp, lora, *, heads, scale, prec, matmul):
    """One GPT-2 layer of one sequence ``x (T, d)`` with LoRA on q and v."""
    t, d = x.shape
    hd = d // heads
    mm = functools.partial(_mm, prec=prec, matmul=matmul)
    act = _act(prec)
    hn = _layer_norm(x, lp["norm1"])

    def proj(name):
        w = lp["attn"][f"w{name}"]
        y = mm("td,do->to", hn, w["w"]) + w["b"].astype(act)
        if name in lora:
            y = y + scale * mm("tr,ro->to", mm("td,dr->tr", hn, lora[name]["A"]),
                               lora[name]["B"])
        return y.reshape(t, heads, hd)

    q, k, v = proj("q"), proj("k"), proj("v")
    s = mm("shd,thd->hst", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(act)
    o = mm("hst,thd->shd", probs, v).reshape(t, d)
    wo = lp["attn"]["wo"]
    x = x + mm("td,do->to", o, wo["w"]) + wo["b"].astype(act)
    h2 = _layer_norm(x, lp["norm2"])
    up, down = lp["mlp"]["up"], lp["mlp"]["down"]
    u = jax.nn.gelu(mm("td,df->tf", h2, up["w"]) + up["b"].astype(act), approximate=True)
    return x + mm("tf,fd->td", u, down["w"]) + down["b"].astype(act)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "prec", "matmul"))
def served_logits(frozen, lora, tokens, positions, *, heads, scale, prec="float32",
                  matmul="highest"):
    """Next-token logits ``(B, P, V)`` of ``tokens (B, T)`` at ``positions
    (B, P)``; row b applies its own adapter, the leaves of ``lora`` carry
    the rows on their leading axis ``(B, layers, ...)``."""
    layers = frozen["stack"]["pos0"]

    def one(row_lora, toks, pos):
        x = (frozen["embed"][toks] + frozen["pos_embed"][:toks.shape[0]]).astype(_act(prec))

        def body(x, layer):
            lp, lo = layer
            return _block(x, lp, lo, heads=heads, scale=scale, prec=prec, matmul=matmul), None

        x, _ = jax.lax.scan(body, x, (layers, row_lora))
        h = _layer_norm(x, frozen["final_norm"])[pos]
        return _mm("pd,vd->pv", h, frozen["embed"], prec=prec, matmul=matmul).astype(jnp.float32)

    return jax.lax.map(lambda a: one(*a), (lora, tokens, positions))
