"""Per-architecture smoke tests (assignment requirement): every assigned
arch instantiates its REDUCED same-family variant, runs one forward and one
train step on CPU, asserting output shapes + no NaNs; plus decode-vs-forward
equivalence for each mixer type."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_smoke_config
from repro.launch.steps import make_train_step
from repro.models import decode_step, forward, init, init_cache
from repro.models.frontends import synth_frontend_embeddings
from repro.optim import adamw_init

pytestmark = pytest.mark.slow  # model-zoo/layer suites ride the slow tier

ALL_ARCHS = list(ARCHITECTURES)


def _batch(cfg, b=2, s=32, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend != "none":
        batch["frontend"] = synth_frontend_embeddings(cfg, b, seed=seed)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 4 and cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4
    params = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: non-finite logits"
    assert bool(jnp.isfinite(aux.moe_aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params, state_dtype=cfg.optimizer_state_dtype)
    step = jax.jit(make_train_step(cfg, lr=1e-3))
    batch = _batch(cfg)
    p1, o1, m1 = step(params, opt, batch)
    p2, o2, m2 = step(p1, o1, _batch(cfg, seed=1))
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    # params actually moved
    moved = jax.tree.reduce(
        lambda acc, ab: acc or bool(jnp.any(ab)),
        jax.tree.map(lambda a, b: jnp.any(a != b), params, p1),
        False,
    )
    assert moved, f"{arch}: train step did not update parameters"


@pytest.mark.parametrize(
    "arch", ["stablelm-1.6b", "mamba2-130m", "jamba-1.5-large-398b", "granite-moe-1b-a400m",
             "seamless-m4t-large-v2", "internvl2-76b", "gpt2-paper"]
)
def test_decode_matches_forward(arch):
    """KV-cache / SSM-state decode reproduces the teacher-forced forward."""
    cfg = get_smoke_config(arch)
    if cfg.family == "vlm":
        pytest.skip("vlm decode starts after a prefill with patches; covered by serve path")
    if cfg.moe is not None:
        # decouple from Switch capacity-drop semantics: decode routes tiny
        # groups (nothing dropped) while full-seq groups may drop tokens at
        # popular experts — a legitimate difference, not a cache bug.
        import dataclasses

        cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init(jax.random.PRNGKey(0), cfg)
    steps = 10
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, steps), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    enc_out = None
    if cfg.family == "audio":
        from repro.models.model import _run_encoder

        batch["frontend"] = synth_frontend_embeddings(cfg, 2)
        enc_out = _run_encoder(params, cfg, batch["frontend"])
    full, _ = forward(params, cfg, batch)
    cache = init_cache(cfg, 2, 32, enc_out=enc_out)
    outs = []
    for t in range(steps):
        lg, cache = decode_step(params, cfg, cache, tokens[:, t])
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("batch", [1, 3])
def test_sliding_window_decode_matches(batch):
    cfg = get_smoke_config("yi-9b").with_overrides(sliding_window=6)
    params = init(jax.random.PRNGKey(0), cfg)
    steps = 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, steps), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, {"tokens": tokens})
    cache = init_cache(cfg, batch, 64)  # ring buffer sized by window
    assert cache["layers"]["pos0"].k.shape[-1] == 6
    outs = []
    for t in range(steps):
        lg, cache = decode_step(params, cfg, cache, tokens[:, t])
        outs.append(lg)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), rtol=2e-3, atol=2e-3
    )


def test_vlm_consumes_patches():
    cfg = get_smoke_config("internvl2-76b")
    params = init(jax.random.PRNGKey(0), cfg)
    b = _batch(cfg)
    logits, _ = forward(params, cfg, b)
    # changing the image must change text logits (early fusion is real)
    b2 = dict(b)
    b2["frontend"] = b["frontend"] + 1.0
    logits2, _ = forward(params, cfg, b2)
    assert float(jnp.max(jnp.abs(logits - logits2))) > 1e-4


def test_audio_encoder_feeds_decoder():
    cfg = get_smoke_config("seamless-m4t-large-v2")
    params = init(jax.random.PRNGKey(0), cfg)
    b = _batch(cfg)
    logits, _ = forward(params, cfg, b)
    b2 = dict(b)
    b2["frontend"] = b["frontend"] * -1.0
    logits2, _ = forward(params, cfg, b2)
    assert float(jnp.max(jnp.abs(logits - logits2))) > 1e-4
