"""Launch-layer steps: chunked CE correctness, train convergence, microbatching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.gpt2_paper import REDUCED_CLIENT
from repro.launch.steps import chunked_lm_loss, make_train_step
from repro.models import backbone, init
from repro.models.model import _lm_logits
from repro.optim import adamw_init
from repro.serve import make_decode_step

pytestmark = pytest.mark.slow  # model-zoo/layer suites ride the slow tier


def test_chunked_ce_equals_naive():
    cfg = get_smoke_config("yi-9b")
    params = init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 37), 0, cfg.vocab_size)
    h, _ = backbone(params, cfg, {"tokens": tokens})
    targets = tokens[:, 1:]
    mask = jnp.ones_like(targets, jnp.float32)
    got = chunked_lm_loss(params, cfg, h[:, :-1], targets, mask)

    logits = _lm_logits(params, cfg, h[:, :-1]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_train_loss_decreases():
    cfg = REDUCED_CLIENT.with_overrides(num_layers=2, d_model=128, num_heads=4,
                                        num_kv_heads=4, d_ff=256, lora=None)
    params = init(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, lr=3e-3))
    # overfit one small batch
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
    losses = []
    for _ in range(12):
        params, opt, m = step(params, opt, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_microbatched_grads_match_full():
    cfg = get_smoke_config("stablelm-1.6b")
    params = init(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)

    p1, _, m1 = jax.jit(make_train_step(cfg.with_overrides(microbatches=1), lr=1e-3))(
        params, opt, {"tokens": tokens}
    )
    p4, _, m4 = jax.jit(make_train_step(cfg.with_overrides(microbatches=4), lr=1e-3))(
        params, opt, {"tokens": tokens}
    )
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-4)
    # parameters after one step agree (fp32 accumulation at smoke scale)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(p1), jax.tree_util.tree_leaves_with_path(p4)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5)


def test_serve_step_updates_length():
    cfg = get_smoke_config("stablelm-1.6b")
    params = init(jax.random.PRNGKey(0), cfg)
    from repro.models import init_cache

    cache = init_cache(cfg, 2, 16)
    step = jax.jit(make_decode_step(cfg))
    logits, cache = step(params, cache, jnp.array([1, 2]))
    assert logits.shape == (2, cfg.vocab_size)
    assert int(cache["length"]) == 1
