"""Seeded random weights for the Moonlight / DeepSeek-V3 block with LoRA on
q and v, in the program's tree layout: ``embed``, ``lm_head``,
``final_norm``, the leading dense layers under ``prefix/pos0`` and the MoE
layers under ``stack/pos0``, every layer stacked on a leading axis.  The
benchmark hands the same arrays to the system under test and to its plain
reference.  Everything is made on the device in one jitted call from the
seed, in float32, the type the configuration runs in.

Scales: projections by fan-in; RMSNorm scales 1 + N(0, 0.1); the router's
logits of unit spread (its input is normalised), where the sigmoid scores
of the 6th and 7th expert lie furthest apart; the score bias N(0, 0.01);
tenant adapters' B of std 0.05, far from a fresh adapter's zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TENANT_B_STD = 0.05


def sizes(cfg: dict) -> tuple:
    """The hashable sizes of a configuration file."""
    lora = cfg["lora"]
    return (cfg["first_k_dense_replace"], cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["router_experts"], cfg["n_routed_experts"],
            cfg["n_shared_experts"], cfg["vocab_size"], lora["rank"], tuple(lora["targets"]))


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def lora_dims(s: tuple) -> dict:
    """(in, out) of each adapter target: q on W_q, v on kv_b_proj's value half."""
    _, _, d, h, rank, nope, rope, vd, *_ = s
    return {"q": (d, h * (nope + rope)), "v": (rank, h * vd)}


def _adapter(key, n, s, std_b):
    r, targets = s[-2], s[-1]
    ks = iter(jax.random.split(key, 2 * len(targets)))
    return {t: {"A": _normal(next(ks), (n,) + (lora_dims(s)[t][0], r), lora_dims(s)[t][0] ** -0.5),
                "B": _normal(next(ks), (n, r, lora_dims(s)[t][1]), std_b)}
            for t in targets}


def _layers(key, n, s, dense):
    _, _, d, h, rank, nope, rope, vd, d_ff, f, experts, held, shared, *_ = s
    keys = iter(jax.random.split(key, 24))
    L = (n,)

    def w(i, o):
        return {"w": _normal(next(keys), L + (i, o), i ** -0.5)}

    def norm(width):
        return {"scale": 1.0 + _normal(next(keys), L + (width,), 0.1)}

    if dense:
        mlp = {"gate": w(d, d_ff), "up": w(d, d_ff), "down": w(d_ff, d)}
    else:
        mlp = {
            "router": w(d, experts),
            "score_bias": _normal(next(keys), L + (experts,), 0.01),
            "gate": _normal(next(keys), L + (held, d, f), d ** -0.5),
            "up": _normal(next(keys), L + (held, d, f), d ** -0.5),
            "down": _normal(next(keys), L + (held, f, d), f ** -0.5),
            "shared": {"gate": w(d, shared * f), "up": w(d, shared * f),
                       "down": w(shared * f, d)},
        }
    return {
        "norm1": norm(d),
        "attn": {"wq": w(d, h * (nope + rope)), "wkv_a": w(d, rank + rope),
                 "kv_norm": norm(rank), "wk_b": w(rank, h * nope), "wv_b": w(rank, h * vd),
                 "wo": w(h * vd, d)},
        "lora": _adapter(next(keys), n, s, TENANT_B_STD),
        "norm2": norm(d),
        "mlp": mlp,
    }


def seed_key(seed: int, stream: int):
    """A key for one named stream of a run's seed (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed % 2**31)
    return jax.random.fold_in(jax.random.fold_in(key, seed // 2**31), stream)


@functools.partial(jax.jit, static_argnums=(1, 2))
def serving(key, s: tuple, tenants: int):
    """The served model and every tenant's adapter: ``{prefix|stack: {q|v:
    {A, B}}}``, each leaf stacked on a leading tenant axis."""
    first_dense, n_layers, d = s[0], s[1], s[2]
    vocab = s[13]
    k = jax.random.split(key, 6)
    params = {
        "embed": _normal(k[0], (vocab, d), 1.0),
        "lm_head": _normal(k[1], (vocab, d), d ** -0.5),
        "final_norm": {"scale": 1.0 + _normal(k[2], (d,), 0.1)},
        "prefix": {"pos0": _layers(k[3], first_dense, s, True)},
        "stack": {"pos0": _layers(k[4], n_layers - first_dense, s, False)},
    }

    def one(kt):
        a, b = jax.random.split(kt)
        return {"prefix": _adapter(a, first_dense, s, TENANT_B_STD),
                "stack": _adapter(b, n_layers - first_dense, s, TENANT_B_STD)}

    return params, jax.vmap(one)(jax.random.split(k[5], tenants))
