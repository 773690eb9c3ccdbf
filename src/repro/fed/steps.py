"""Jitted step functions for the FL runtime (Algorithm 1).

Task convention (paper §IV): decoder-only LM fine-tuned for Banking77
intent detection — class logits are the LM-head logits over the first
``num_classes`` vocab ids at the LAST sequence position.  Distillation
(paper eqs. 9-10) operates on the FULL last-position vocab logits (the
high-dimensional vector the adaptive Top-k sparsifies).

All steps train the LoRA subset only (paper §II-A): gradients flow through
``split_lora`` so the frozen backbone never enters the optimizer.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.aggregation import AggregationMode, aggregate_wire
from repro.core.distill import (
    kl_divergence_from_log_probs,
    teacher_log_probs,
    total_distill_loss,
)
from repro.core.topk import QuantizedWire, SparseWire, sparsify_wire, topk_mask_dynamic
from repro.lora import merge_lora, split_lora
from repro.models import forward
from repro.optim import AdamWState, adamw_init, adamw_update

__all__ = [
    "class_logits",
    "public_logits",
    "last_logits",
    "make_finetune_step",
    "make_distill_step",
    "make_batched_finetune_step",
    "make_batched_distill_step",
    "make_batched_public_logits",
    "make_fused_round_fn",
    "make_bucket_client_phase_fn",
    "make_server_phase_fn",
    "make_fused_e2e_round_fn",
    "make_eval_fn",
    "make_scan_eval_fn",
    "make_channel_step_fn",
    "init_lora_opt",
]


def class_logits(logits_last: jax.Array, num_classes: int) -> jax.Array:
    """(B, V) last-position logits -> (B, num_classes) class readout."""
    return logits_last[..., :num_classes]


def _cast_params(params, compute_dtype: str):
    """Cast float params to the round body's compute dtype (bf16-buffer
    pattern): the fp32 LoRA stays the master copy — this cast sits inside
    the differentiated graph, so its VJP accumulates the low-precision
    grads back into fp32 before AdamW sees them.  ``float32`` is the
    identity (no graph change)."""
    if compute_dtype == "float32":
        return params
    dt = jnp.dtype(compute_dtype)
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


def last_logits(params, cfg: ModelConfig, batch: dict, *, head_cols: int | None = None):
    """(B, V) last-position logits + Aux.

    The LM head runs on the final hidden state only — a ~seq_len× cut in
    head FLOPs/memory, which dominates at the paper's 50k+ vocabularies.
    ``head_cols=k`` computes only the first k head columns — bit-identical
    to slicing, at k/V of the head FLOPs; the supervised class losses/eval
    read ``num_classes`` columns only.
    """
    return forward(params, cfg, batch, last_only=True, head_cols=head_cols)


@functools.partial(jax.jit, static_argnames=("cfg",))
def public_logits(params, cfg: ModelConfig, tokens: jax.Array):
    """Last-position vocab logits + pooled LoRA projection on a public batch.

    Returns (logits (B, V), h (B, r) or None) — the client/server upload
    content (Algorithm 1 lines 4, 14).
    """
    logits, aux = last_logits(params, cfg, {"tokens": tokens})
    return logits, aux.lora_h


def init_lora_opt(params, cfg: ModelConfig) -> AdamWState:
    lora, _ = split_lora(params)
    return adamw_init(lora, state_dtype=cfg.optimizer_state_dtype)


def _finetune_loss_fn(
    cfg: ModelConfig, num_classes: int, compute_dtype: str = "float32"
) -> Callable:
    """loss(lora, frozen, batch) -> (nll + moe_aux, acc) — the shared core
    of the sequential step, the batched cohort step and the fused round.

    The supervised loss reads ``num_classes`` class logits only, so the LM
    head is restricted to those columns (``head_cols`` — bit-identical
    logits/gradients at num_classes/V of the head FLOPs)."""

    def loss_fn(lora, frozen, batch):
        params = _cast_params(merge_lora(lora, frozen), compute_dtype)
        last, aux = last_logits(
            params, cfg, {"tokens": batch["tokens"]}, head_cols=num_classes
        )
        cls = class_logits(last, num_classes)
        logp = jax.nn.log_softmax(cls.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).mean()
        acc = jnp.mean((jnp.argmax(cls, -1) == batch["labels"]).astype(jnp.float32))
        return nll + 0.01 * aux.moe_aux, acc

    return loss_fn


def _finetune_step_fn(
    cfg: ModelConfig,
    num_classes: int,
    lr: float,
    weight_decay: float,
) -> Callable:
    """Unjitted single-client fine-tune step over merged params."""

    loss_fn = _finetune_loss_fn(cfg, num_classes)

    def step(params, opt, batch):
        lora, frozen = split_lora(params)
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora, frozen, batch)
        new_lora, new_opt = adamw_update(
            grads, opt, lora, lr=lr, weight_decay=weight_decay
        )
        return merge_lora(new_lora, frozen), new_opt, {"loss": loss, "acc": acc}

    return step


@functools.lru_cache(maxsize=64)
def make_finetune_step(
    cfg: ModelConfig,
    num_classes: int,
    *,
    lr: float = 1e-3,
    weight_decay: float = 1e-3,
) -> Callable:
    """Supervised local fine-tuning on private data (paper eq. 2), LoRA-only.

    step(params, opt, batch{tokens,labels}) -> (params, opt, metrics)
    """
    return jax.jit(_finetune_step_fn(cfg, num_classes, lr, weight_decay))


@functools.lru_cache(maxsize=64)
def make_batched_finetune_step(
    cfg: ModelConfig,
    num_classes: int,
    *,
    lr: float = 1e-3,
    weight_decay: float = 1e-3,
    shared_backbone: bool = True,
) -> Callable:
    """One fine-tune update for a whole cohort at once.

    step(lora (C,...), frozen, opt (C,...), batch {tokens (C,B,L), labels (C,B)})
    -> (lora, opt, metrics (C,))

    Client-axis vmap over the same loss/update core as
    :func:`make_finetune_step`, so every client's update (including its own
    grad-clip global norm) is computed exactly as in the sequential path.
    With ``shared_backbone`` (the paper's setting: one pretrained W' under
    per-client LoRA deltas) the frozen tree is broadcast (``in_axes=None``)
    — XLA then fuses the cohort's backbone matmuls into single wide ops
    instead of C small ones, which is where the batched engine's speedup
    comes from.  LoRA/opt buffers are donated.
    """

    loss_fn = _finetune_loss_fn(cfg, num_classes)

    def step(lora, frozen, opt, batch):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora, frozen, batch)
        new_lora, new_opt = adamw_update(
            grads, opt, lora, lr=lr, weight_decay=weight_decay
        )
        return new_lora, new_opt, {"loss": loss, "acc": acc}

    frozen_ax = None if shared_backbone else 0
    return jax.jit(jax.vmap(step, in_axes=(0, frozen_ax, 0, 0)), donate_argnums=(0, 2))


def _distill_loss_fn(
    cfg: ModelConfig,
    temperature: float,
    lam: float,
    restrict_to_support: bool,
    compute_dtype: str = "float32",
) -> Callable:
    """loss(lora, frozen, tokens, g_logits, g_h) -> (L_total, parts)."""

    use_h = cfg.lora is not None

    def loss_fn(lora, frozen, tokens, g_logits, g_h):
        params = _cast_params(merge_lora(lora, frozen), compute_dtype)
        own, aux = last_logits(params, cfg, {"tokens": tokens})
        loss, parts = total_distill_loss(
            g_logits,
            own,
            g_h if use_h else None,
            aux.lora_h if use_h else None,
            temperature=temperature,
            lam=lam,
            restrict_to_support=restrict_to_support,
        )
        return loss + 0.01 * aux.moe_aux, parts

    return loss_fn


def _distill_loss_cached_fn(
    cfg: ModelConfig,
    temperature: float,
    lam: float,
    compute_dtype: str = "float32",
) -> Callable:
    """loss(lora, frozen, tokens, t_logp, th_logp, support_mask) with the
    TEACHER log-probs precomputed (:func:`repro.core.distill.
    teacher_log_probs`) — the round-fused engines compute them once per
    round instead of once per (client, step).  Bit-identical losses and
    gradients to :func:`_distill_loss_fn` on the same teacher inputs (the
    teacher side is a constant of the round; only the student side carries
    gradients)."""

    use_h = cfg.lora is not None

    def loss_fn(lora, frozen, tokens, t_logp, th_logp, support_mask):
        params = _cast_params(merge_lora(lora, frozen), compute_dtype)
        own, aux = last_logits(params, cfg, {"tokens": tokens})
        loss = kl_divergence_from_log_probs(
            t_logp, own, temperature, mask=support_mask
        )
        if use_h and th_logp is not None:
            loss = loss + lam * kl_divergence_from_log_probs(
                th_logp, aux.lora_h, temperature
            )
        return loss + 0.01 * aux.moe_aux, {}

    return loss_fn


def _distill_step_fn(
    cfg: ModelConfig,
    lr: float,
    temperature: float,
    lam: float,
    restrict_to_support: bool,
) -> Callable:
    """Unjitted single-model distillation step over merged params."""

    loss_fn = _distill_loss_fn(cfg, temperature, lam, restrict_to_support)

    def step(params, opt, tokens, g_logits, g_h):
        lora, frozen = split_lora(params)
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            lora, frozen, tokens, g_logits, g_h
        )
        new_lora, new_opt = adamw_update(grads, opt, lora, lr=lr)
        return merge_lora(new_lora, frozen), new_opt, {"loss": loss, **parts}

    return step


@functools.lru_cache(maxsize=64)
def make_distill_step(
    cfg: ModelConfig,
    *,
    lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
) -> Callable:
    """Knowledge-distillation update against global teacher knowledge
    (Algorithm 1 lines 5-7 / 16): LoRA-only gradient on L_total (eq. 10).

    step(params, opt, public_tokens, g_logits, g_h) -> (params, opt, metrics)
    ``g_h`` may be None -> the λ-term drops (the 'Adaptive' baseline).
    """
    return jax.jit(_distill_step_fn(cfg, lr, temperature, lam, restrict_to_support))


@functools.lru_cache(maxsize=64)
def make_batched_distill_step(
    cfg: ModelConfig,
    *,
    lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
    shared_backbone: bool = True,
) -> Callable:
    """Cohort distillation against one broadcast teacher.

    step(lora (C,...), frozen, opt (C,...), tokens (P,L), g_logits (P,V), g_h)
    -> (lora, opt, metrics (C,))

    Teacher knowledge AND public tokens are broadcast (in_axes=None) —
    every client distills against the same {K_g, h_g}, exactly as
    Algorithm 1 lines 5-7; with ``shared_backbone`` the frozen W' is
    broadcast too (see :func:`make_batched_finetune_step`).
    """
    loss_fn = _distill_loss_fn(cfg, temperature, lam, restrict_to_support)

    def step(lora, frozen, opt, tokens, g_logits, g_h):
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            lora, frozen, tokens, g_logits, g_h
        )
        new_lora, new_opt = adamw_update(grads, opt, lora, lr=lr)
        return new_lora, new_opt, {"loss": loss, **parts}

    frozen_ax = None if shared_backbone else 0
    return jax.jit(
        jax.vmap(step, in_axes=(0, frozen_ax, 0, None, None, None)),
        donate_argnums=(0, 2),
    )


@functools.lru_cache(maxsize=64)
def make_batched_public_logits(cfg: ModelConfig, *, shared_backbone: bool = True) -> Callable:
    """Cohort public-set inference: (lora (C,...), frozen, tokens (P,L)) ->
    (logits (C,P,V), h (C,P,r) or None) — Algorithm 1 line 9 for the whole
    round's selected clients in one compiled call."""

    def one(lora, frozen, tokens):
        last, aux = last_logits(merge_lora(lora, frozen), cfg, {"tokens": tokens})
        return last, aux.lora_h

    frozen_ax = None if shared_backbone else 0
    return jax.jit(jax.vmap(one, in_axes=(0, frozen_ax, None)))


def _client_round_core(
    cfg: ModelConfig,
    num_classes: int,
    *,
    lr: float,
    weight_decay: float,
    distill_lr: float,
    temperature: float,
    lam: float,
    restrict_to_support: bool,
    local_steps: int,
    distill_steps: int,
    gate_distill: bool,
    kd_loss: Callable | None = None,
    compute_dtype: str = "float32",
) -> Callable:
    """Per-client round body shared by the fused and fused-e2e round fns:
    ``distill_steps`` distillation updates, ``local_steps`` supervised
    updates (``lax.scan``), public last-position inference.

    ``gate_distill=True`` makes the cold-server round DATA instead of
    control flow: the distillation updates always run, and the traced bool
    ``g_valid`` selects between the distilled and the untouched
    (lora, opt) — one executable serves round 0 (no broadcast exists yet)
    and every later round.  With ``gate_distill=False`` the caller bakes
    ``distill_steps`` statically (the PR-2 two-variant scheme) and
    ``g_valid`` is ignored.

    ``kd_loss`` overrides the distillation loss; it is called as
    ``kd_loss(lora, frozen, g_tokens, *kd_args)`` where ``kd_args`` is the
    opaque teacher-knowledge tuple the caller threads through ``client_round``
    (default: ``(g_logits, g_h)`` into :func:`_distill_loss_fn`; the e2e
    round passes precomputed teacher log-probs into
    :func:`_distill_loss_cached_fn` instead).
    """
    ft_loss = _finetune_loss_fn(cfg, num_classes, compute_dtype)
    if kd_loss is None:
        kd_loss = _distill_loss_fn(
            cfg, temperature, lam, restrict_to_support, compute_dtype
        )

    def client_round(lora, frozen, opt, g_tokens, kd_args, g_valid, batches, pub_tokens):
        # -- lines 5-7: local distillation against the broadcast knowledge --
        lora0, opt0 = lora, opt
        for _ in range(distill_steps):
            (_, _), grads = jax.value_and_grad(kd_loss, has_aux=True)(
                lora, frozen, g_tokens, *kd_args
            )
            lora, opt = adamw_update(grads, opt, lora, lr=distill_lr)
        if gate_distill and distill_steps:
            pick = lambda new, old: jnp.where(g_valid, new, old)
            lora = jax.tree.map(pick, lora, lora0)
            opt = jax.tree.map(pick, opt, opt0)

        # -- line 8: local fine-tuning, scanned over the step axis --
        def train_body(carry, batch):
            lora, opt = carry
            (_, _), grads = jax.value_and_grad(ft_loss, has_aux=True)(
                lora, frozen, batch
            )
            lora, opt = adamw_update(grads, opt, lora, lr=lr, weight_decay=weight_decay)
            return (lora, opt), None

        (lora, opt), _ = jax.lax.scan(train_body, (lora, opt), batches, length=local_steps)

        # -- line 9: public last-position inference --
        last, aux = last_logits(
            _cast_params(merge_lora(lora, frozen), compute_dtype), cfg,
            {"tokens": pub_tokens},
        )
        return lora, opt, last, aux.lora_h

    return client_round


@functools.lru_cache(maxsize=64)
def make_fused_round_fn(
    cfg: ModelConfig,
    num_classes: int,
    *,
    lr: float = 1e-3,
    weight_decay: float = 1e-3,
    distill_lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
    local_steps: int = 4,
    distill_steps: int = 2,
    shared_backbone: bool = True,
    use_kernels: bool = False,
    compute_dtype: str = "float32",
) -> Callable:
    """The whole client phase of Algorithm 1 as ONE function.

    fn(lora (C,...), frozen, opt (C,...), g_tokens (P,L), g_logits (P,V),
       g_h (P,r)|None, batches {tokens (C,S,B,L), labels (C,S,B)},
       pub_tokens (P,L), ks (C,) int32)
    -> (lora, opt, dense (C,P,V), h (C,P,r)|None)

    Fuses lines 5-11 — ``distill_steps`` distillation updates against the
    broadcast knowledge, ``local_steps`` supervised updates (``lax.scan``
    over the per-step batch axis), public-set last-position inference (all
    vmapped over the client axis), and the per-client adaptive Top-k
    sparsification with the budget as DATA — so the round body is a single
    compiled program: per-round dispatches drop from
    O(distill_steps + local_steps + phases) to O(1) and no intermediate
    state round-trips through the host.  The sparsifier is the pure-jnp
    threshold bisection (:func:`repro.core.topk.topk_mask_dynamic`) or,
    with ``use_kernels``, the per-row-budget Pallas kernel
    (:func:`repro.kernels.ops.topk_mask_dynamic`) — identical threshold
    (ties-kept) semantics.  ``distill_steps=0`` builds the cold-start
    variant (round 0: no broadcast exists yet; the g_* operands are passed
    but unused and DCE'd).  Returned unjitted so the round engine chooses
    the compilation wrapper (plain ``jax.jit`` or a ``shard_map`` placement
    of the client axis over devices).
    """
    client_round = _client_round_core(
        cfg, num_classes, lr=lr, weight_decay=weight_decay, distill_lr=distill_lr,
        temperature=temperature, lam=lam, restrict_to_support=restrict_to_support,
        local_steps=local_steps, distill_steps=distill_steps,
        gate_distill=False, compute_dtype=compute_dtype,
    )

    frozen_ax = None if shared_backbone else 0
    vm = jax.vmap(client_round, in_axes=(0, frozen_ax, 0, None, None, None, 0, None))

    def fn(lora, frozen, opt, g_tokens, g_logits, g_h, batches, pub_tokens, ks):
        lora, opt, last, h = vm(
            lora, frozen, opt, g_tokens, (g_logits, g_h), True, batches, pub_tokens
        )
        # -- line 10: adaptive top-k, one budget per client row (k is data;
        # applied outside the client vmap so the Pallas path stays a plain
        # 2-D pallas_call) --
        if use_kernels:
            from repro.kernels import ops as kops

            dense = kops.topk_mask_dynamic(
                last, jnp.broadcast_to(ks[:, None], last.shape[:-1])
            )
        else:
            dense = topk_mask_dynamic(last, ks[:, None])
        return lora, opt, dense, h

    return fn


def _teacher_cache_fn(
    temperature: float, restrict_to_support: bool, use_h: bool
) -> Callable:
    """teacher_cache(logits, h) -> (t_logp, th_logp, support) — the once-per
    round softmax of a distillation teacher (eq. 9's constant side), shared
    by the e2e round, the bucketed hetero client phase and the server phase
    so every consumer of the same teacher computes the identical cache."""

    def teacher_cache(logits, h):
        support = (logits != 0) if restrict_to_support else None
        t_logp = teacher_log_probs(logits, temperature, mask=support)
        th_logp = (
            teacher_log_probs(h, temperature) if (use_h and h is not None) else None
        )
        return t_logp, th_logp, support

    return teacher_cache


@functools.lru_cache(maxsize=64)
def make_bucket_client_phase_fn(
    cfg: ModelConfig,
    num_classes: int,
    *,
    k_cap: int,
    lr: float = 1e-3,
    weight_decay: float = 1e-3,
    distill_lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
    local_steps: int = 4,
    distill_steps: int = 2,
    shared_backbone: bool = True,
    quantize: bool = False,
    compute_dtype: str = "float32",
) -> Callable:
    """One FAMILY BUCKET's whole client phase as ONE function: the vmapped
    per-client round bodies (distill -> fine-tune -> public inference) plus
    the sparse-wire sparsifier, for a homogeneous sub-cohort of clients that
    all run ``cfg``.

    fn(lora (C,...), frozen, opt (C,...), g_tokens (P,L), g_logits (P,V),
       g_h (P,r)|None, g_valid () bool,
       batches {tokens (C,S,B,L), labels (C,S,B)}, pub_tokens (P,L),
       ks (C,) int32)
    -> (lora, opt, values (C,P,k_cap), indices (C,P,k_cap),
        mask (C,P,k_cap), scale (C,P)|None, h (C,P,r)|None)

    ``quantize=True`` emits the int8 :class:`repro.core.topk.QuantizedWire`
    straight from the sparsifier — ``values`` is then int8 and ``scale``
    carries the per-(client, sample) dequantization factors (``None`` on
    the float wire).  ``compute_dtype`` selects the round body's forward/
    backward precision (bf16-buffer pattern: fp32 LoRA/optimizer master,
    low-precision compute).

    This is the per-bucket executable of the heterogeneous round engine
    (:class:`repro.fed.engines.HeteroFusedE2EEngine`): the fleet is
    partitioned into homogeneous family buckets (`repro.fed.cohort`), each
    bucket runs this function with its own ``cfg``/backbone layout
    (``shared_backbone=False`` stacks the frozen trees on the client axis —
    the same ``frozen_ax=0`` vmap the batched engine uses), and the buckets'
    wires are concatenated into one vocab-indexed union wire for the
    family-agnostic server phase (:func:`make_server_phase_fn`).  The
    broadcast teacher's log-softmax is computed once per bucket call —
    bit-identical per client to the homogeneous e2e round, because the
    teacher side is a constant of the round.  ``gate_distill`` semantics:
    the cold-server round is DATA (``g_valid``), one executable serves every
    round of a run (per ``k_cap`` bucket).
    """
    cached_kd = _distill_loss_cached_fn(cfg, temperature, lam, compute_dtype)
    client_round = _client_round_core(
        cfg, num_classes, lr=lr, weight_decay=weight_decay,
        distill_lr=distill_lr, temperature=temperature, lam=lam,
        restrict_to_support=restrict_to_support, local_steps=local_steps,
        distill_steps=distill_steps, gate_distill=True,
        kd_loss=cached_kd, compute_dtype=compute_dtype,
    )
    frozen_ax = None if shared_backbone else 0
    vm = jax.vmap(
        client_round, in_axes=(0, frozen_ax, 0, None, None, None, 0, None)
    )
    teacher_cache = _teacher_cache_fn(
        temperature, restrict_to_support, cfg.lora is not None
    )

    def fn(lora, frozen, opt, g_tokens, g_logits, g_h, g_valid, batches,
           pub_tokens, ks):
        t_cache = teacher_cache(g_logits, g_h)
        lora, opt, last, h = vm(
            lora, frozen, opt, g_tokens, t_cache, g_valid, batches, pub_tokens
        )
        wire = sparsify_wire(last, ks, k_cap, quantize=quantize)
        scale = wire.scale if quantize else None
        return lora, opt, wire.values, wire.indices, wire.mask, scale, h

    return fn


@functools.lru_cache(maxsize=64)
def make_server_phase_fn(
    server_cfg: ModelConfig,
    *,
    vocab: int,
    distill_lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
    server_distill_steps: int = 12,
    aggregation: AggregationMode = "adaptive",
    send_h: bool = True,
    use_kernels: bool = False,
    quantize: bool = False,
    compute_dtype: str = "float32",
) -> Callable:
    """The whole SERVER phase of one round as ONE function (Algorithm 1
    lines 13-16 + the next round's broadcast recompute), consuming the
    cohort's sparse uplink wire.

    fn(s_lora, s_frozen, s_opt,
       values (N,P,k_cap), indices (N,P,k_cap), mask (N,P,k_cap),
       scale (N,P)|None, h (N,P,r)|None, ks (N,) int32, pub_tokens (P,L))
    -> (s_lora, s_opt, b_logits (P,V), b_h (P,r)|None, d_loss ())

    ``quantize=True`` reads the uplink as the int8
    :class:`repro.core.topk.QuantizedWire` (``values`` int8 + per-row
    ``scale``); aggregation then runs the dequantize-fused route of
    :func:`repro.core.aggregation.aggregate_wire` (the Pallas kernel with
    ``use_kernels``) — the float wire ignores ``scale`` (pass ``None``).

    ``vocab`` is the fleet's SHARED vocabulary — the wire's indices address
    it directly, which is exactly why heterogeneous families interoperate
    here: the union wire of several family buckets aggregates identically to
    one homogeneous cohort's (the server never sees architectures, only
    vocab-indexed logits and rank-aligned eq.-8 projections).  A round where
    every client dropped (all ``ks == 0``) discards the server update as
    DATA and reports a NaN ``d_loss``; the broadcast still refreshes on the
    current public batch, exactly like the host round loop.
    """
    server_kd_loss = _distill_loss_cached_fn(server_cfg, temperature, lam, compute_dtype)
    teacher_cache = _teacher_cache_fn(temperature, restrict_to_support, True)

    def fn(s_lora, s_frozen, s_opt, values, indices, mask, scale, h, ks,
           pub_tokens):
        if quantize:
            wire = QuantizedWire(
                values=values, scale=scale, indices=indices, mask=mask,
                vocab=vocab,
            )
        else:
            wire = SparseWire(values=values, indices=indices, mask=mask, vocab=vocab)
        n_tx = jnp.sum((ks > 0).astype(jnp.int32))

        # -- line 15: aggregation from the wire (eqs. 6-7) --
        k_g = aggregate_wire(
            wire, aggregation, num_transmitters=n_tx, use_kernel=use_kernels
        )
        if send_h and h is not None:
            tx = (ks > 0).astype(h.dtype)[:, None, None]
            h_g = jnp.sum(h * tx, axis=0) / jnp.maximum(n_tx, 1).astype(h.dtype)
        else:
            h_g = None

        # -- line 16: server-side distillation, scanned over its steps; the
        # aggregated teacher is softmaxed ONCE for all steps --
        kg_logp, kg_h_logp, kg_support = teacher_cache(k_g, h_g)

        def server_body(carry, _):
            sl, so = carry
            (loss, _), grads = jax.value_and_grad(server_kd_loss, has_aux=True)(
                sl, s_frozen, pub_tokens, kg_logp, kg_h_logp, kg_support
            )
            sl, so = adamw_update(grads, so, sl, lr=distill_lr)
            return (sl, so), loss

        (new_sl, new_so), losses = jax.lax.scan(
            server_body, (s_lora, s_opt), None, length=server_distill_steps
        )
        # every selected client dropped -> no aggregation, no server update
        has_tx = n_tx > 0
        keep = lambda new, old: jnp.where(has_tx, new, old)  # noqa: E731
        s_lora = jax.tree.map(keep, new_sl, s_lora)
        s_opt = jax.tree.map(keep, new_so, s_opt)
        # observability tap: the final server-distill loss of the round
        # (NaN when no client transmitted — the server never distilled)
        d_loss = jnp.where(
            has_tx,
            losses[-1] if server_distill_steps else jnp.float32(jnp.nan),
            jnp.nan,
        )

        # -- lines 1-2 of the NEXT round: refreshed broadcast knowledge --
        b_last, b_aux = last_logits(
            _cast_params(merge_lora(s_lora, s_frozen), compute_dtype),
            server_cfg, {"tokens": pub_tokens},
        )
        return s_lora, s_opt, b_last, b_aux.lora_h, d_loss

    return fn


@functools.lru_cache(maxsize=64)
def make_fused_e2e_round_fn(
    client_cfg: ModelConfig,
    server_cfg: ModelConfig,
    num_classes: int,
    *,
    k_cap: int,
    lr: float = 1e-3,
    weight_decay: float = 1e-3,
    distill_lr: float = 1e-3,
    temperature: float = 2.0,
    lam: float = 0.03,
    restrict_to_support: bool = False,
    local_steps: int = 4,
    distill_steps: int = 2,
    server_distill_steps: int = 12,
    aggregation: AggregationMode = "adaptive",
    send_h: bool = True,
    shared_backbone: bool = True,
    use_kernels: bool = False,
    shard_clients: bool = False,
    quantize: bool = False,
    compute_dtype: str = "float32",
) -> Callable:
    """ONE whole federated round — client phase AND server phase — as ONE
    function (Fig. 1 steps 1-10 / Algorithm 1 lines 3-16).

    fn(lora (C,...), frozen, opt (C,...),
       s_lora, s_frozen, s_opt,                       # server LLM state
       g_tokens (P,L), g_logits (P,V), g_h (P,r)|None, g_valid () bool,
       batches {tokens (C,S,B,L), labels (C,S,B)}, pub_tokens (P,L),
       ks (C,) int32)
    -> (lora, opt, s_lora, s_opt,
        values (C,P,k_cap), indices (C,P,k_cap),      # sparse uplink wire
        scale (C,P)|None,                             # int8 wire dequant rows
        b_logits (P,V), b_h (P,r)|None,               # next-round broadcast
        d_loss ())                                    # last server-distill loss

    ``quantize=True`` carries the uplink as the int8
    :class:`repro.core.topk.QuantizedWire` (values int8 + per-(client,
    sample) scale) and aggregates through the dequantize-fused route;
    ``compute_dtype`` selects the round body's forward/backward precision
    (fp32 LoRA/optimizer state stays the master copy).

    Extends :func:`make_fused_round_fn` past the server boundary:

    * the uplink leaves the client phase as the sparse wire format
      ``(values, indices, transmit mask)`` of static width ``k_cap`` (one
      ``lax.top_k``; per-client adaptive ``k`` enters as int32 DATA and
      becomes the mask) — the ``(C, P, V)`` densified stack of the PR-2
      path is never built;
    * adaptive aggregation (eqs. 6-7) scatter-accumulates straight from the
      wire (:func:`repro.core.aggregation.aggregate_wire`; the Pallas
      scatter kernel with ``use_kernels``) — the single ``(P, V)``
      densification of the round is the aggregated teacher itself;
    * the server-side distillation (line 16) runs as a
      ``server_distill_steps``-long ``lax.scan``, and the next round's
      broadcast knowledge (line 1) is recomputed in-program;
    * the two data-dependent control decisions of the round loop are DATA,
      not Python branches: ``g_valid=False`` (cold server, round 0)
      discards the client distillation updates, and a round where every
      selected client dropped (all ``ks == 0``) discards the server
      update — the broadcast still refreshes on the current public batch,
      exactly as the host round loop behaves (``d_loss`` is NaN then, like
      the host ledger's never-written field).

    One executable therefore serves every round of a run (per ``k_cap``
    bucket), and a steady-state round is a single dispatch.

    ``shard_clients=True`` places the CLIENT phase's leading cohort axis over
    the process's devices with ``shard_map`` (mesh
    :func:`repro.sharding.cohort_mesh`): the per-client round bodies and the
    uplink sparsifier run device-parallel, and only the O(C·P·k_cap) sparse
    wire (plus the (C, P, r) projections) crosses back — the server phase
    (wire aggregation, the server-distill scan, the broadcast recompute) is
    a single-model computation and stays OUTSIDE the shard_map, replicated
    by XLA's SPMD partitioner.  The cohort size must divide the device
    count; the round engine pads short/odd cohorts with masked ``k = 0``
    duplicate rows (they transmit nothing, the all-False wire mask excludes
    them from aggregation, and the engine discards their advanced state), so
    the function body itself needs no padding logic.

    Round-level CSE the split pipeline cannot do: the teacher side of every
    distillation KL (eq. 9) is a CONSTANT of the round, so its log-softmax
    is computed ONCE here — the broadcast teacher is reused across all C
    clients × ``distill_steps`` updates, the aggregated teacher across all
    ``server_distill_steps`` — instead of once per (model, step) as the
    per-step host pipeline does.  Bit-identical losses/gradients (the
    teacher carries no gradient).
    """
    use_h = client_cfg.lora is not None
    cached_kd = _distill_loss_cached_fn(client_cfg, temperature, lam, compute_dtype)
    client_round = _client_round_core(
        client_cfg, num_classes, lr=lr, weight_decay=weight_decay,
        distill_lr=distill_lr, temperature=temperature, lam=lam,
        restrict_to_support=restrict_to_support, local_steps=local_steps,
        distill_steps=distill_steps, gate_distill=True,
        kd_loss=cached_kd, compute_dtype=compute_dtype,
    )
    frozen_ax = None if shared_backbone else 0
    vm = jax.vmap(
        client_round, in_axes=(0, frozen_ax, 0, None, None, None, 0, None)
    )
    teacher_cache = _teacher_cache_fn(temperature, restrict_to_support, use_h)
    server_phase = make_server_phase_fn(
        server_cfg, vocab=client_cfg.vocab_size, distill_lr=distill_lr,
        temperature=temperature, lam=lam,
        restrict_to_support=restrict_to_support,
        server_distill_steps=server_distill_steps, aggregation=aggregation,
        send_h=send_h, use_kernels=use_kernels,
        quantize=quantize, compute_dtype=compute_dtype,
    )

    def client_phase(lora, frozen, opt, g_tokens, t_cache, g_valid,
                     batches, pub_tokens, ks):
        """Lines 3-11 for (a device's shard of) the cohort: the vmapped
        per-client round bodies + the sparse-wire sparsifier.  Everything
        here is per-client-independent, so it shards cleanly over the
        cohort axis; the wire it returns (plus the quantized wire's scale
        rows) is the ONLY client-phase product the (replicated) server
        phase reads besides ``h``."""
        lora, opt, last, h = vm(
            lora, frozen, opt, g_tokens, t_cache, g_valid, batches, pub_tokens
        )
        wire = sparsify_wire(last, ks, k_cap, quantize=quantize)
        scale = wire.scale if quantize else None
        return lora, opt, wire.values, wire.indices, wire.mask, scale, h

    if shard_clients:
        from repro.sharding import COHORT_AXIS, cohort_mesh

        c, r = jax.sharding.PartitionSpec(COHORT_AXIS), jax.sharding.PartitionSpec()
        frozen_spec = r if shared_backbone else c
        client_phase = jax.shard_map(
            client_phase,
            mesh=cohort_mesh(),
            in_specs=(c, frozen_spec, c, r, r, r, c, r, c),
            out_specs=(c, c, c, c, c, c, c),
            check_vma=False,
        )

    def fn(lora, frozen, opt, s_lora, s_frozen, s_opt,
           g_tokens, g_logits, g_h, g_valid, batches, pub_tokens, ks):
        # -- client phase (lines 3-11); broadcast teacher softmaxed ONCE,
        # then the whole phase device-parallel over the cohort axis when
        # shard_clients; the uplink leaves it as the sparse wire --
        lora, opt, w_values, w_indices, w_mask, w_scale, h = client_phase(
            lora, frozen, opt, g_tokens, teacher_cache(g_logits, g_h), g_valid,
            batches, pub_tokens, ks
        )
        # -- server phase (lines 13-16 + next-round broadcast), replicated --
        s_lora, s_opt, b_last, b_h, d_loss = server_phase(
            s_lora, s_frozen, s_opt, w_values, w_indices, w_mask, w_scale, h,
            ks, pub_tokens,
        )
        return (lora, opt, s_lora, s_opt, w_values, w_indices, w_scale,
                b_last, b_h, d_loss)

    return fn


# Host-eval batch size: make_eval_fn walks whole batches of this size and
# drops the remainder; the in-scan eval tap truncates its eval arrays with
# the SAME constant so both paths read the same samples.
EVAL_BATCH = 64


def _eval_correct_fn(cfg: ModelConfig, num_classes: int) -> Callable:
    """correct(params, tokens, labels) -> () float32 count of correct
    last-position class predictions — the ONE copy of the eval math shared
    by the host-side batched evaluator and the in-scan eval tap (their 1e-6
    parity contract rests on this being literally the same function)."""

    def correct(params, tokens, labels):
        last, _ = last_logits(params, cfg, {"tokens": tokens}, head_cols=num_classes)
        cls = class_logits(last, num_classes)
        return jnp.sum((jnp.argmax(cls, -1) == labels).astype(jnp.float32))

    return correct


@functools.lru_cache(maxsize=64)
def make_scan_eval_fn(cfg: ModelConfig, num_classes: int) -> Callable:
    """Traceable accuracy for the in-scan eval tap (``run_rounds``).

    acc(lora, frozen, tokens (B, L), labels (B,)) -> () float32 — the same
    per-sample math as :func:`make_eval_fn`'s batched host loop (shared via
    :func:`_eval_correct_fn`), traceable inside a ``lax.scan`` body.
    Unjitted: the multi-round driver traces it into the scanned round
    program.  Eval splits that divide :data:`EVAL_BATCH` are walked in
    ``lax.map`` chunks of that size — the host loop's bounded activation
    footprint, not one (B, L, d) forward over the whole split inside the
    compiled program (the per-chunk correct-counts are integers, so the
    chunked sum is exact).
    """
    correct = _eval_correct_fn(cfg, num_classes)

    def acc(lora, frozen, tokens, labels):
        params = merge_lora(lora, frozen)
        n = int(labels.shape[0])
        if n == 0 or n % EVAL_BATCH:
            # fail at trace time rather than silently diverge from the host
            # evaluator's whole-batch walk (the 1e-6 parity contract)
            raise ValueError(
                f"eval split must be a non-empty multiple of "
                f"EVAL_BATCH={EVAL_BATCH}, got {n}"
            )
        if n == EVAL_BATCH:
            total = correct(params, tokens, labels)
        else:
            tb = tokens.reshape((n // EVAL_BATCH, EVAL_BATCH) + tokens.shape[1:])
            lb = labels.reshape(n // EVAL_BATCH, EVAL_BATCH)
            total = jnp.sum(
                jax.lax.map(lambda tl: correct(params, tl[0], tl[1]), (tb, lb))
            )
        return total / n

    return acc


@functools.lru_cache(maxsize=64)
def make_eval_fn(
    cfg: ModelConfig,
    num_classes: int,
    *,
    batch_size: int = EVAL_BATCH,
) -> Callable:
    """Accuracy over an IntentDataset (numpy arrays), batched + jitted."""

    batch_acc = jax.jit(_eval_correct_fn(cfg, num_classes))

    def evaluate(params, tokens, labels) -> float:
        n = tokens.shape[0]
        correct = 0.0
        for i in range(0, n - batch_size + 1, batch_size):
            correct += float(
                batch_acc(params, tokens[i : i + batch_size], labels[i : i + batch_size])
            )
        seen = (n // batch_size) * batch_size
        return correct / max(1, seen)

    return evaluate


def make_channel_step_fn() -> Callable:
    """One in-scan channel-dynamics step (``repro.core.scenario`` replica).

    channel_step(z, bad, w, u, base_snr_db, rho, p_gb, p_bg, fade_scale)
        -> (z', bad', snr_db)

    Pure jnp, traced into the multi-round scan body: the AR(1) fading carry
    ``z`` and Gilbert-Elliott outage carry ``bad`` evolve from the host's
    precomputed copula normals ``w`` and outage uniforms ``u``
    (:meth:`repro.core.channel.ChannelSimulator.scan_channel_inputs`).  All
    scenario parameters are f32 DATA operands — ``rho = 0`` replays the
    i.i.d. channel, ``fade_scale = 0`` a fading-free one, the
    i.i.d.-equivalent ``(p_gb, p_bg)`` a memoryless dropout coin — so ONE
    compiled executable serves every scenario preset.

    This is the observability replica of the host-side f64 realisation
    (the k/byte budgets stay host-side scalar math, ledger-exact); it taps
    each round's realised SNR/outage into the trajectory.  f32 recursion
    tracks the f64 chain to ~1e-2 dB over a scan block (the AR(1) map is
    contracting, so rounding does not accumulate).
    """

    def channel_step(z, bad, w, u, base_snr_db, rho, p_gb, p_bg, fade_scale):
        z = rho * z + jnp.sqrt(jnp.maximum(1.0 - rho * rho, 0.0)) * w
        u_fade = jnp.clip(jax.scipy.special.ndtr(z), 1e-7, 1.0 - 1e-7)
        power = -jnp.log1p(-u_fade)
        fade_db = 10.0 * jnp.log10(jnp.maximum(power, 1e-6))
        bad = jnp.where(bad, u < 1.0 - p_bg, u < p_gb)
        snr_db = jnp.where(bad, -jnp.inf, base_snr_db + fade_scale * fade_db)
        return z, bad, snr_db

    return channel_step
