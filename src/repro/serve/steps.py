"""Serving step factories (pure functions, jitted by ServeSession).

  decode_step         — one token against the cache, single shared adapter
                        (the pre-redesign ``make_serve_step``, re-exported
                        from ``repro.launch.steps`` for compatibility).
  stacked_decode_step — one token, per-request adapters: gathers row
                        ``idx[b]`` of the adapter slab for request b and
                        merges into the shared frozen backbone INSIDE the
                        step, so one compiled executable serves any tenant
                        mix (``idx`` is traced int32 data).
  prefill_chunk       — T prompt tokens per request into the decode cache
                        in one call, single shared adapter.
  stacked_prefill_chunk — the same with per-request adapters, gathered
                        from the slab as the stacked decode step does.
  prefill_step        — full forward over a prompt, last-position logits.
"""

from __future__ import annotations

from typing import Callable

from repro.configs.base import ModelConfig
from repro.lora import merge_lora
from repro.models import decode_step as model_decode_step, prefill as model_prefill
from repro.models.model import prefill_chunk as model_prefill_chunk
from repro.serve.adapters import gather_adapters

__all__ = ["make_decode_step", "make_stacked_decode_step", "make_prefill_chunk",
           "make_stacked_prefill_chunk", "make_prefill_step"]


def make_decode_step(cfg: ModelConfig, *, window: int | None = None) -> Callable:
    def decode_step(params, cache, token):
        return model_decode_step(params, cfg, cache, token, window=window)

    return decode_step


def make_stacked_decode_step(cfg: ModelConfig, *, window: int | None = None) -> Callable:
    """(frozen, slab, idx, cache, token) -> (logits, cache) — the
    multi-tenant decode step.  ``frozen``: the shared backbone
    (split_lora()[1]); ``slab``: the adapter slab (slots leading axis);
    ``idx (B,) int32``: slab slot per request."""

    def stacked_decode_step(frozen, slab, idx, cache, token):
        params = merge_lora(gather_adapters(slab, idx), frozen)
        return model_decode_step(params, cfg, cache, token, window=window)

    return stacked_decode_step


def make_prefill_chunk(cfg: ModelConfig) -> Callable:
    """(params, cache, tokens (B, T), count) -> (logits, cache): one
    prefill chunk (``repro.models.model.prefill_chunk``)."""

    def prefill_chunk(params, cache, tokens, count):
        return model_prefill_chunk(params, cfg, cache, tokens, count)

    return prefill_chunk


def make_stacked_prefill_chunk(cfg: ModelConfig) -> Callable:
    """(frozen, slab, idx, cache, tokens (B, T), count) -> (logits, cache):
    one prefill chunk with per-request adapters, as
    ``make_stacked_decode_step``."""

    def stacked_prefill_chunk(frozen, slab, idx, cache, tokens, count):
        params = merge_lora(gather_adapters(slab, idx), frozen)
        return model_prefill_chunk(params, cfg, cache, tokens, count)

    return stacked_prefill_chunk


def make_prefill_step(cfg: ModelConfig, *, window: int | None = None) -> Callable:
    def prefill_step(params, batch):
        logits, _ = model_prefill(params, cfg, batch, window=window)
        return logits

    return prefill_step
