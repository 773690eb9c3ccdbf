"""Seeded random weights for a GPT-2 block stack with LoRA on q and v.

The tree has the layout the program's dense models take (``embed``,
``pos_embed``, ``final_norm``, and ``stack/pos0`` with every layer stacked
on a leading axis), so the benchmark hands the same arrays to the system
under test and to its plain reference.  Everything is made on the device
in one jitted call from the seed, in float32, the type the configuration
runs in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Tenant adapters that have been trained: B far from a fresh adapter's zero,
# so that serving a request with another tenant's adapter moves its logits.
TENANT_B_STD = 0.05


def model_key(spec: dict) -> tuple:
    """The hashable sizes of one model entry of a configuration file."""
    lora = spec["lora"]
    return (spec["num_layers"], spec["d_model"], spec["d_ff"],
            spec["vocab_size"], spec["max_seq_len"], lora["rank"],
            tuple(lora["targets"]))


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _params(key, sizes):
    n_layers, d, d_ff, vocab, max_seq, rank, targets = sizes
    keys = iter(jax.random.split(key, 32))
    L = (n_layers,)

    def dense(i, o):
        return {"w": _normal(next(keys), L + (i, o), i ** -0.5),
                "b": _normal(next(keys), L + (o,), 0.02)}

    def norm(shape):
        return {"scale": 1.0 + _normal(next(keys), shape, 0.1),
                "bias": _normal(next(keys), shape, 0.02)}

    layer = {
        "norm1": norm(L + (d,)),
        "attn": {"wq": dense(d, d), "wk": dense(d, d), "wv": dense(d, d),
                 "wo": dense(d, d)},
        "lora": {t: {"A": _normal(next(keys), L + (d, rank), d ** -0.5),
                     "B": _normal(next(keys), L + (rank, d), TENANT_B_STD)}
                 for t in targets},
        "norm2": norm(L + (d,)),
        "mlp": {"up": dense(d, d_ff), "down": dense(d_ff, d)},
    }
    return {
        "embed": _normal(next(keys), (vocab, d), 0.02),
        "pos_embed": _normal(next(keys), (max_seq, d), 0.02),
        "final_norm": norm((d,)),
        "stack": {"pos0": layer},
    }


def seed_key(seed: int, stream: int):
    """A key for one named stream of a run's seed (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed % 2**31)
    return jax.random.fold_in(jax.random.fold_in(key, seed // 2**31), stream)


@functools.partial(jax.jit, static_argnums=(1, 2))
def serving(key, sizes: tuple, tenants: int):
    """The served model and every tenant's adapter, each adapter leaf
    stacked on a leading axis."""
    n_layers, d, _, _, _, rank, targets = sizes
    kp, ka = jax.random.split(key)

    def one(k):
        ks = jax.random.split(k, 2 * len(targets))
        return {t: {"A": _normal(ks[2 * i], (n_layers, d, rank), d ** -0.5),
                    "B": _normal(ks[2 * i + 1], (n_layers, rank, d), TENANT_B_STD)}
                for i, t in enumerate(targets)}

    return _params(kp, sizes), jax.vmap(one)(jax.random.split(ka, tenants))
