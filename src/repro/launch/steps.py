"""Training step functions lowered by the dry-run and drivers.

  train_step — next-token LM loss (chunked CE: (B,S,V) logits never
               materialise), full-param AdamW, optional gradient-
               accumulation microbatches (cfg.microbatches).

Pure (params/opt/batch in, params/opt/metrics out) and pjit-compatible;
shardings are attached by the caller (dryrun/train).  The serving step
factories live in :mod:`repro.serve.steps`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import backbone
from repro.models.model import _lm_logits  # internal head reuse (framework-private)
from repro.optim import AdamWState, adamw_update

__all__ = ["chunked_lm_loss", "make_train_step"]

CE_CHUNK = 512  # sequence positions per cross-entropy chunk

# REPRO_UNROLL=1: python-unroll the CE chunk scan (HLO cost-mode; the while
# loop body is otherwise counted once by XLA cost analysis).
import os as _os  # noqa: E402

_UNROLL = _os.environ.get("REPRO_UNROLL", "0") == "1"


def chunked_lm_loss(
    params: dict, cfg: ModelConfig, h: jax.Array, targets: jax.Array, mask: jax.Array
) -> jax.Array:
    """Next-token CE summed over (B, S) in chunks over S.

    h (B,S,D) hidden states; targets/mask (B,S).  Each chunk computes
    its own head matmul + log-softmax, so peak memory is
    (B, CE_CHUNK, V/model_shards) instead of (B, S, V/model_shards).
    """
    b, s, d = h.shape
    chunk = min(CE_CHUNK, s)
    # pad S to a multiple of chunk (mask padding out)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = h.shape[1] // chunk
    h_c = h.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    t_c = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    m_c = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    vocab_iota = jnp.arange(cfg.vocab_size, dtype=targets.dtype)

    def one(carry, xs):
        hc, tc, mc = xs
        logits = _lm_logits(params, cfg, hc).astype(jnp.float32)  # (B, chunk, V)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        # target logit via masked reduction, NOT take_along_axis: the gather
        # lowers to full-logits all-gathers under SPMD (§Perf iteration 3),
        # while this form partitions cleanly over the vocab shards.
        tgt_logit = jnp.sum(
            jnp.where(vocab_iota[None, None, :] == tc[..., None], logits, 0.0), axis=-1
        )
        nll = (logz - tgt_logit) * mc
        return carry + jnp.sum(nll), None

    if _UNROLL:
        total = jnp.zeros((), jnp.float32)
        for i in range(nc):
            total, _ = one(total, (h_c[i], t_c[i], m_c[i]))
    else:
        total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (h_c, t_c, m_c))
    return total / jnp.maximum(1.0, jnp.sum(mask))


def make_train_step(
    cfg: ModelConfig,
    *,
    lr: float = 3e-4,
    weight_decay: float = 0.1,
    router_aux_weight: float = 0.01,
) -> Callable:
    """LM pre-training/fine-tuning step over a {"tokens": (B, S)} batch
    (+optional "frontend").  Full-parameter AdamW."""

    def loss_fn(params, batch):
        h, aux = backbone(params, cfg, batch)
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        loss = chunked_lm_loss(params, cfg, h[:, :-1], targets, mask)
        return loss + router_aux_weight * aux.moe_aux, loss

    def grads_of(params, batch):
        (loss, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, ce, grads

    def train_step(params, opt: AdamWState, batch):
        bsz = batch["tokens"].shape[0]
        m = cfg.microbatches
        if m > bsz or bsz % m != 0:
            m = 1  # smoke-scale batches: accumulate-free step
        if m <= 1:
            loss, ce, grads = grads_of(params, batch)
        else:
            # gradient accumulation: scan over microbatches, accumulate in
            # the param dtype (bf16 for the HBM-limited giants, DESIGN §4)
            def split(x):
                bsz = x.shape[0]
                return x.reshape((m, bsz // m) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_fn(carry, mb):
                acc, loss_sum = carry
                loss, _, grads = grads_of(params, mb)
                acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc, grads)
                return (acc, loss_sum + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
            (gsum, loss_sum), _ = jax.lax.scan(acc_fn, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / m, gsum)
            loss = ce = loss_sum / m

        new_params, new_opt = adamw_update(
            grads, opt, params, lr=lr, weight_decay=weight_decay
        )
        return new_params, new_opt, {"loss": loss, "ce": ce}

    return train_step


