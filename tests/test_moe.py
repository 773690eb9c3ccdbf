"""MoE routing: capacity behaviour, gate normalisation, load-balance aux."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_apply, moe_init

pytestmark = pytest.mark.slow  # model-zoo/layer suites ride the slow tier


def _cfg(experts=4, top_k=2, cf=1.25):
    return ModelConfig(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=64,
        moe=MoEConfig(num_experts=experts, top_k=top_k, d_ff=64, capacity_factor=cf),
    )


def test_moe_shapes_and_finite():
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    out, aux, _, _ = moe_apply(params, x, cfg)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(aux) > 0.0


def test_aux_loss_near_one_for_balanced_router():
    """Switch aux = E * sum(f_e * p_e) ~= 1 when routing is uniform."""
    cfg = _cfg(experts=8, top_k=1)
    params = moe_init(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256, 32))
    _, aux, _, _ = moe_apply(params, x, cfg)
    assert 0.8 < float(aux) < 2.0  # heavily imbalanced would be >> E/2


def test_tiny_capacity_drops_tokens():
    """With capacity_factor→0 the capacity floor (4) binds and most tokens
    are dropped: output magnitude shrinks."""
    cfg_full = _cfg(cf=8.0)
    cfg_tiny = _cfg(cf=1e-6)
    params = moe_init(jax.random.PRNGKey(4), cfg_full)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 512, 32))
    out_full, _, _, _ = moe_apply(params, x, cfg_full)
    out_tiny, _, _, _ = moe_apply(params, x, cfg_tiny)
    assert float(jnp.mean(jnp.abs(out_tiny))) < float(jnp.mean(jnp.abs(out_full)))


def test_moe_differentiable():
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(6), cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 32, 32))

    def loss(p):
        out, aux, _, _ = moe_apply(p, x, cfg)
        return jnp.sum(out**2) + aux

    grads = jax.grad(loss)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gnorm > 0 and jnp.isfinite(gnorm)


def test_capacity_is_per_group():
    """Group-local dispatch: token count per group bounds the dispatch tensor
    (regression test for the O(T^2) ungrouped form)."""
    from repro.models.moe import GROUP_SIZE

    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(8), cfg)
    t = GROUP_SIZE * 2
    x = jax.random.normal(jax.random.PRNGKey(9), (1, t, 32))
    out, _, _, _ = moe_apply(params, x, cfg)
    assert out.shape == (1, t, 32)
