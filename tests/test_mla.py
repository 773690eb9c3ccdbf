"""Moonlight's block on the serving path against the plain reference
(``repro.models.reference_mla``), at the smoke size on the CPU: latent
attention decoded in the absorbed form over the latent cache, sigmoid
routing with a selection-only bias, shared experts, a held share of the
experts, dropless decode routing and its counters.

Tolerances: every comparison is float32 on both sides, the program's
decode (absorbed, one token at a time, sums in another order) against the
reference's full forward; 2e-4 on logits of magnitude about one is a few
hundred float32 roundings of the sums over the 3 layers, and a wrong
mechanism moves them by 1e-2 or more.  Near-ties in routing: where the
reference's k-th and (k+1)-th biased scores lie within 1e-4 the two sides
may choose different experts, so positions after a request's first
near-tie are not compared (at most half of them may go).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.moonlight_16b_a3b import SMOKE_CONFIG
from repro.lora import lora_template, split_lora
from repro.models import init as model_init
from repro.models import reference_mla
from repro.models.attention import init_latent_cache, mla_apply
from repro.models.moe import moe_apply
from repro.serve import AdapterCache, ServeConfig, ServeSession

TOL = 2e-4
TIE = 1e-4
TENANTS, BATCH, STEPS = 6, 4, 12


def _params(cfg, seed=0):
    """Seeded random weights: the program's init with a random score bias,
    norms off one and nonzero adapters (a fresh adapter's B is zero)."""
    params = model_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)

    def perturb(path, x):
        nonlocal key
        key, sub = jax.random.split(key)
        name = jax.tree_util.keystr(path)
        if "score_bias" in name:
            return 0.3 * jax.random.normal(sub, x.shape)
        if "scale" in name:
            return x + 0.1 * jax.random.normal(sub, x.shape)
        if name.endswith("['B']"):
            return 0.05 * jax.random.normal(sub, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(perturb, params)


def _spec(cfg):
    m, moe = cfg.mla, cfg.moe
    return reference_mla.Spec(
        heads=cfg.num_heads, kv_rank=m.kv_rank, nope_dim=m.nope_dim, rope_dim=m.rope_dim,
        v_dim=m.v_dim, top_k=moe.top_k, routed_scale=moe.routed_scale, held=moe.held,
        rope_theta=cfg.rope_theta, eps=cfg.norm_eps, lora_scale=cfg.lora.alpha / cfg.lora.rank)


class Tenants:
    """Each tenant's adapter: the served params' adapter, re-drawn per id."""

    def __init__(self, params, count):
        lora, _ = split_lora(params)
        self.num_adapters = count
        self.rows = [
            jax.tree.map(lambda x, c=c: x if x is None else x + 0.05 * jax.random.normal(
                jax.random.PRNGKey(100 + c), x.shape), lora, is_leaf=lambda x: x is None)
            for c in range(count)]

    def lora_row(self, cid):
        return self.rows[cid]


def _serve(cfg, params, tenants, ids, tokens):
    """Every step's logits (B, T, V) of ``tokens (B, T)`` fed one token a
    step through the session's stacked decode step, and its stats."""
    cache = AdapterCache(tenants, like=lora_template(params), slots=BATCH)
    sess = ServeSession(ServeConfig(model=cfg, batch=BATCH, cache_len=32), params,
                        adapters=cache)
    sess.attach(ids)
    logits = [sess.step(tokens[:, t]) for t in range(tokens.shape[1])]
    return np.stack([np.asarray(x) for x in logits], 1), sess.stats()


def _reference(cfg, params, tenants, ids, tokens):
    """Logits (B, T, V), router margins (B, T) and held pairs per MoE layer
    summed over the batch, one request at a time with its own adapter."""
    spec = _spec(cfg)
    out = [reference_mla.forward(params, jnp.asarray(tokens[b]), spec, tenants.rows[c])
           for b, c in enumerate(ids)]
    return (np.stack([np.asarray(o[0]) for o in out]), np.stack([np.asarray(o[1]) for o in out]),
            sum(np.asarray(o[2]) for o in out))


def _held(cfg, count, first):
    return cfg.with_overrides(moe=dataclasses.replace(cfg.moe, experts_held=(count, first)))


@pytest.fixture(scope="module")
def served():
    cfg = _held(SMOKE_CONFIG, 4, 2)  # a chip's share: experts 2-5 of 8
    params = _params(cfg)
    tenants = Tenants(params, TENANTS)
    ids = [3, 0, 5, 3]
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (BATCH, STEPS), 0,
                                           cfg.vocab_size))
    got, stats = _serve(cfg, params, tenants, ids, tokens)
    ref, margin, pairs = _reference(cfg, params, tenants, ids, tokens)
    return dict(cfg=cfg, params=params, got=got, stats=stats, ref=ref, margin=margin,
                pairs=pairs)


def test_decode_through_latent_cache_matches_reference(served):
    got, ref, margin = served["got"], served["ref"], served["margin"]
    compared = 0
    for b in range(BATCH):
        ties = np.flatnonzero(margin[b] < TIE)
        upto = ties[0] if ties.size else STEPS
        np.testing.assert_allclose(got[b, :upto], ref[b, :upto], atol=TOL, rtol=TOL)
        compared += upto
    assert compared >= BATCH * STEPS // 2
    # different adapters give different answers for the same tokens
    assert np.abs(ref[0] - ref[1]).max() > 1e-2


def test_routed_pair_counter_equals_reference(served):
    # the session's device-side counters, read once: pairs routed to the
    # held experts per MoE layer, as the reference's router chose them
    assert served["stats"]["moe_routed_pairs"] == served["pairs"].tolist()
    assert served["stats"]["moe_dropped_pairs"] == 0
    assert min(served["stats"]["moe_routed_pairs"]) > 0


def test_absorbed_lora_equals_explicit():
    # decode (absorbed: W_uk folded into the query, W_uv and the v adapter
    # after the weighted latent) against the full-sequence pass (keys and
    # values expanded per head), each request with its own q and v adapter
    cfg = SMOKE_CONFIG
    params = _params(cfg, seed=3)
    attn = jax.tree.map(lambda a: a[0], params["stack"]["pos0"]["attn"])
    lora = jax.tree.map(lambda a: jnp.stack([a[0] + 0.05 * i for i in range(BATCH)]),
                        params["stack"]["pos0"]["lora"])
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, STEPS, cfg.d_model))
    full, _, _ = mla_apply(attn, x, cfg, positions=jnp.arange(STEPS), lora=lora)
    cache = init_latent_cache(cfg, BATCH, 16)
    steps = []
    for t in range(STEPS):
        y, row, _ = mla_apply(attn, x[:, t:t + 1], cfg, positions=jnp.array([t]),
                              cache=cache, lora=lora)
        steps.append(y)
        slot = t % 16
        cache = cache._replace(latent=cache.latent.at[:, :, slot].set(row.latent),
                               pos=cache.pos.at[slot].set(t), length=cache.length + 1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(steps, 1)), np.asarray(full),
                               atol=TOL, rtol=TOL)
    # the v adapter moves the output: the absorbed path applies it
    plain, _, _ = mla_apply(attn, x, cfg, positions=jnp.arange(STEPS), lora={"q": lora["q"]})
    assert np.abs(np.asarray(plain) - np.asarray(full)).max() > 1e-3


def _moe_layer(params, first=0, count=None):
    """Layer 0 of the MoE stack, its routed experts cut to [first, first+count)."""
    layer = jax.tree.map(lambda a: a[0], params["stack"]["pos0"]["mlp"])
    if count is not None:
        for k in ("gate", "up", "down"):
            layer[k] = layer[k][first:first + count]
    return layer


def test_expert_shares_add_up_to_the_uncut_layer():
    # four chips of two experts each: their results, with the shared expert
    # (which every chip computes alike) counted once, are the whole layer
    cfg = SMOKE_CONFIG
    params = _params(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 1, cfg.d_model))
    layer = _moe_layer(params)
    shared = _moe_layer(params, 0, 0)
    none = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, experts_held=(0, 0)))
    only_shared, _, _, _ = moe_apply(shared, x, none)
    total = -3 * only_shared
    for first in range(0, 8, 2):
        part, _, _, _ = moe_apply(_moe_layer(params, first, 2), x, _held(cfg, 2, first))
        total = total + part
    whole, _, _ = reference_mla.moe(x[:, 0], layer, _spec(cfg))
    np.testing.assert_allclose(np.asarray(total[:, 0]), np.asarray(whole), atol=TOL, rtol=TOL)


@pytest.mark.parametrize("held", [None, (2, 0)])
def test_decode_batch_drops_no_pair(held):
    # every token's router prefers expert 0 (and 1) by far: a capacity of
    # cf·k·T/E slots would drop most of them, the decode step drops none;
    # a chip holding experts 0 and 1 gets more pairs than an even routing
    # would send it, and takes the grouped products' larger shape
    cfg = SMOKE_CONFIG if held is None else _held(SMOKE_CONFIG, *held)
    params = _params(cfg, seed=7)
    layer = _moe_layer(params)
    layer["score_bias"] = layer["score_bias"].at[:2].set(10.0)
    tokens = 128
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 1, cfg.d_model))
    out, _, counters, _ = moe_apply(layer, x, cfg)
    routed, used, dropped = np.asarray(counters).tolist()
    assert (routed, dropped) == (tokens * min(cfg.moe.top_k, cfg.moe.held[0]), 0)
    assert used <= cfg.moe.num_experts
    whole, _, pairs = reference_mla.moe(x[:, 0], layer, _spec(cfg))
    assert int(pairs) == routed
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(whole), atol=TOL, rtol=TOL)
