"""Backbone pretraining for the FL experiments.

The paper fine-tunes PRETRAINED GPT-2 (small on clients, large on the
server); LoRA's low-rank delta rides on meaningful features.  In this
offline container the checkpoints are a data gate (DESIGN §1), so we
*simulate pretraining*: full-parameter supervised training on a disjoint
pretraining split of the synthetic corpus, stopped at moderate accuracy so
federated distillation still has headroom to demonstrate transfer.  The
resulting backbone is the shared frozen W' of paper eq. 1; FL then trains
only θ_n = {A_n, B_n}.

Pretrained params are cached per (config, seed, steps) so the four method
presets in the benchmarks reuse one backbone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.pipeline import epoch_batches
from repro.data.synthetic import IntentDataset
from repro.fed import steps as fed_steps
from repro.lora import split_lora
from repro.models import init as model_init
from repro.optim import adamw_init, adamw_update

__all__ = ["pretrain_classifier"]

_CACHE: dict = {}


def _owned(tree):
    """Deep-copy the leaf buffers of a cached param tree.

    Callers hand pretrained params to engines whose compiled steps DONATE
    input buffers (fused/fused_e2e); returning the cache's own arrays lets
    the first donation delete the cached buffers and poison every later
    run in the process ("buffer has been deleted or donated").  An
    identity ``tree.map`` is NOT enough — it copies the tree structure but
    aliases the same device buffers."""
    return jax.tree.map(jnp.copy, tree)


def _supervised_step(cfg: ModelConfig, num_classes: int, lr: float):
    def loss_fn(params, batch):
        # classification reads the final position exclusively, and only the
        # num_classes head columns (bit-identical to slicing)
        last, aux = fed_steps.last_logits(
            params, cfg, {"tokens": batch["tokens"]}, head_cols=num_classes
        )
        cls = fed_steps.class_logits(last, num_classes)
        logp = jax.nn.log_softmax(cls.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).mean()
        acc = jnp.mean((jnp.argmax(cls, -1) == batch["labels"]).astype(jnp.float32))
        return nll + 0.01 * aux.moe_aux, acc

    @jax.jit
    def step(params, opt, batch):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        params, opt = adamw_update(grads, opt, params, lr=lr, weight_decay=1e-4)
        return params, opt, {"loss": loss, "acc": acc}

    return step


def pretrain_classifier(
    cfg: ModelConfig,
    pretrain_data: IntentDataset,
    *,
    num_classes: int,
    steps: int = 150,
    lr: float = 2e-3,
    batch_size: int = 64,
    seed: int = 0,
    verbose: bool = False,
):
    """Full-parameter supervised pretraining; returns params with fresh
    (zero-delta) LoRA adapters on top — the shared W' + θ_0 of eq. 1."""
    key = (cfg.name, cfg.num_layers, cfg.d_model, steps, lr, seed, len(pretrain_data),
           num_classes, batch_size)
    if key in _CACHE:
        return _owned(_CACHE[key])

    params = model_init(jax.random.PRNGKey(seed), cfg)
    opt = adamw_init(params, state_dtype=cfg.optimizer_state_dtype)
    step = _supervised_step(cfg, num_classes, lr)
    rng = np.random.default_rng(seed)
    done = 0
    metrics = {}
    while done < steps:
        for batch in epoch_batches(pretrain_data, batch_size, rng=rng):
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, metrics = step(params, opt, jb)
            done += 1
            if verbose and done % 25 == 0:
                print(f"[pretrain {cfg.name}] step {done}: "
                      f"loss={float(metrics['loss']):.3f} acc={float(metrics['acc']):.3f}")
            if done >= steps:
                break

    # reset LoRA to the zero-delta init (pretraining moved A/B too; the FL
    # protocol starts from W' + B=0)
    fresh = model_init(jax.random.PRNGKey(seed + 1), cfg)
    fresh_lora, _ = split_lora(fresh)
    from repro.lora import merge_lora

    _, frozen = split_lora(params)
    params = merge_lora(fresh_lora, frozen)

    _CACHE[key] = params
    return _owned(params)


def pretrain_lm(
    cfg: ModelConfig,
    pretrain_data: IntentDataset,
    *,
    steps: int = 60,
    lr: float = 2e-3,
    batch_size: int = 64,
    seed: int = 0,
    verbose: bool = False,
):
    """LM-only (next-token) pretraining: builds token/keyword FEATURES with
    no label information — the paper's server LLM analogue (a generically
    pretrained model whose task knowledge arrives via distillation)."""
    key = ("lm", cfg.name, cfg.num_layers, cfg.d_model, steps, lr, seed, len(pretrain_data))
    if key in _CACHE:
        return _owned(_CACHE[key])

    from repro.launch.steps import make_train_step

    params = model_init(jax.random.PRNGKey(seed), cfg)
    opt = adamw_init(params, state_dtype=cfg.optimizer_state_dtype)
    step = jax.jit(make_train_step(cfg, lr=lr, weight_decay=1e-4))
    rng = np.random.default_rng(seed)
    done = 0
    while done < steps:
        for batch in epoch_batches(pretrain_data, batch_size, rng=rng):
            params, opt, metrics = step(params, opt, {"tokens": jnp.asarray(batch["tokens"])})
            done += 1
            if verbose and done % 25 == 0:
                print(f"[pretrain-lm {cfg.name}] step {done}: loss={float(metrics['loss']):.3f}")
            if done >= steps:
                break

    fresh_lora, _ = split_lora(model_init(jax.random.PRNGKey(seed + 1), cfg))
    from repro.lora import merge_lora

    _, frozen = split_lora(params)
    params = merge_lora(fresh_lora, frozen)
    _CACHE[key] = params
    return _owned(params)
