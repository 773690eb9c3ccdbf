"""Parity of the ``last_only`` LM-head mode against the full forward.

The federated phases (public inference, fine-tune/distill losses, eval)
read ONLY the last-position logits, so ``forward(..., last_only=True)``
computes the head on the final hidden state — ~seq_len× fewer head FLOPs.
These tests pin the contract across every model family in the zoo smoke
set (dense transformer, MoE, SSM, hybrid), with and without LoRA, and for
the Aux outputs (``moe_aux`` and the pooled projection ``lora_h`` must be
identical to the full forward: eq. 8 pools over the whole sequence).
"""

import jax
import numpy as np
import pytest

from repro.configs import LoRAConfig, get_smoke_config
from repro.configs.gpt2_paper import REDUCED_CLIENT
from repro.fed.steps import public_logits
from repro.models import forward, init, prefill

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))

# one representative per family: dense transformer, MoE, SSM, hybrid
FAMILY_ARCHS = [
    "stablelm-1.6b",
    "granite-moe-1b-a400m",
    "mamba2-130m",
    "jamba-1.5-large-398b",
]


def _cfg(arch, lora):
    return get_smoke_config(arch).with_overrides(lora=lora)


def _batch(cfg, b=2, s=16, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab_size)
    return {"tokens": tokens}


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
@pytest.mark.parametrize("lora", [None, LORA], ids=["plain", "lora"])
def test_last_only_matches_full_forward(arch, lora):
    cfg = _cfg(arch, lora)
    params = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    full, aux_full = forward(params, cfg, batch)
    last, aux_last = forward(params, cfg, batch, last_only=True)
    assert last.shape == (2, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full[:, -1, :]), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        float(aux_last.moe_aux), float(aux_full.moe_aux), rtol=1e-6
    )
    if lora is None:
        assert aux_full.lora_h is None and aux_last.lora_h is None
    else:
        # the pooled LoRA projection (paper eq. 8) pools over the WHOLE
        # sequence — last_only must not change it (for SSM it comes from the
        # head adapter over the full normalized hidden states)
        assert aux_last.lora_h is not None
        np.testing.assert_allclose(
            np.asarray(aux_last.lora_h), np.asarray(aux_full.lora_h),
            rtol=1e-5, atol=1e-6,
        )


def test_last_only_matches_on_reduced_client_lora():
    """The actual federated client config (GPT-2 family + LoRA head)."""
    cfg = REDUCED_CLIENT.with_overrides(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
        vocab_size=256, max_seq_len=32, lora=LORA,
    )
    params = init(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg, s=12, seed=3)
    full, aux_full = forward(params, cfg, batch)
    last, aux_last = forward(params, cfg, batch, last_only=True)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full[:, -1, :]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(aux_last.lora_h), np.asarray(aux_full.lora_h), rtol=1e-5, atol=1e-6
    )


def test_public_logits_modes_agree():
    """public_logits — the upload content, computed on the last position
    only — equals the last position of the full (B, T, V) forward."""
    cfg = REDUCED_CLIENT.with_overrides(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
        vocab_size=256, max_seq_len=32, lora=LORA,
    )
    params = init(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 12), 0, cfg.vocab_size)
    fast_logits, fast_h = public_logits(params, cfg, tokens)
    full, aux_full = forward(params, cfg, {"tokens": tokens}, last_only=False)
    slow_logits, slow_h = full[:, -1, :], aux_full.lora_h
    np.testing.assert_allclose(
        np.asarray(fast_logits), np.asarray(slow_logits), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(fast_h), np.asarray(slow_h), rtol=1e-5, atol=1e-6
    )


def test_prefill_is_last_only_forward():
    cfg = _cfg("stablelm-1.6b", None)
    params = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, s=8)
    p_logits, _ = prefill(params, cfg, batch)
    f_logits, _ = forward(params, cfg, batch, last_only=True)
    np.testing.assert_allclose(np.asarray(p_logits), np.asarray(f_logits), atol=0)
    assert p_logits.shape == (2, cfg.vocab_size)
