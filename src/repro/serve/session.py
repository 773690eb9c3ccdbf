"""ServeSession: the public serving API (ROADMAP "Personalized-adapter
serving at fleet scale").

One session = one shared frozen backbone + one decode cache + (optionally)
an :class:`repro.serve.AdapterCache` of tenant adapters.  The redesign
replaces the hand-rolled ``make_serve_step`` loops in ``launch/serve.py``
and ``launch/dryrun.py`` (kept importable via shims):

    cfg = ServeConfig(model=model_cfg, batch=8, slots=8)
    sess = ServeSession(cfg, params, adapters=cache)
    sess.attach([17, 3, 3, 99, ...])      # tenant id per request
    sess.prefill(prompts)                  # (B, L) int32
    tokens = sess.decode(32)               # (B, 32) greedy/sampled
    sess.stats()                           # cache hits/misses, compile time

Each layer boundary is a ``jax.profiler.TraceAnnotation`` span, recorded
only while a profiler session is active: ``serve.attach``, ``serve.reset``,
``serve.prefill``, ``serve.prefill_chunk`` (tags ``pos``, the chunk's
start, and ``valid``, its real rows) and ``serve.step`` here,
``serve.page_in`` in the cache.  Every span of one batch carries
``batch=<n>``, the count of ``attach`` calls so far.

Prefill runs a ``(B, L)`` prompt as ``ceil(L / T)`` calls of one chunk
executable, T prompt tokens per request each (``chunk_len``: the batch
sets T), the last chunk padded; each fills the decode cache and returns
the logits after its last real token.  Where the model cannot be prefilled
so (``repro.models.model.chunked_prefill_ok``: a layer that is not
attention, an encoder, a sliding-window ring) or the padded prompt does not
fit the cache, the prompt is teacher-forced through the decode step, one
call per token.

Compilation contract: a session compiles at most one decode executable and
one prefill-chunk executable per mode — single-adapter (detached) and
stacked multi-tenant (attached), four in all.  Tenant mix, slot
assignment, token values, prompt length and a chunk's real rows are all
traced data, so serving any number of tenants and prompt lengths costs one
compile of each.  Every executable donates the decode cache (updated in
place).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.lora import split_lora
from repro.models import init_cache
from repro.models.frontends import synth_frontend_embeddings
from repro.models.model import _run_encoder, chunked_prefill_ok
from repro.serve.cache import AdapterCache
from repro.serve.steps import (
    make_decode_step,
    make_prefill_chunk,
    make_stacked_decode_step,
    make_stacked_prefill_chunk,
)

__all__ = ["ServeConfig", "ServeSession", "chunk_len"]


def chunk_len(batch: int) -> int:
    """Prompt tokens per request in one prefill chunk: the largest power of
    two that keeps a chunk's ``batch * T`` tokens within 8,192, from 16 to
    128 (batch 8: 128; batch 128: 64).  A longer chunk pads a short prompt
    further and holds larger attention scores; a shorter one pays a call's
    fixed cost more often."""
    return 1 << min(7, max(4, (8192 // batch).bit_length() - 1))


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Frozen serving knobs (hashable; safe to close jits over)."""

    model: ModelConfig
    batch: int = 4  # requests per decode step
    cache_len: int = 128  # decode-cache capacity (prompt + generated)
    temperature: float = 0.0  # 0 = greedy
    window: int | None = None  # sliding-window override (None = cfg default)
    seed: int = 0  # sampling PRNG seed


class ServeSession:
    """Stateful serving loop over pure jitted steps (see module docstring)."""

    def __init__(
        self,
        cfg: ServeConfig,
        params: Any,
        *,
        adapters: AdapterCache | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.adapters = adapters
        self._lora, self._frozen = split_lora(params)
        mc = cfg.model
        self._single = jax.jit(
            make_decode_step(mc, window=cfg.window), donate_argnums=(1,)
        )
        self._stacked = jax.jit(
            make_stacked_decode_step(mc, window=cfg.window), donate_argnums=(3,)
        )
        self._single_chunk = jax.jit(make_prefill_chunk(mc), donate_argnums=(1,))
        self._stacked_chunk = jax.jit(make_stacked_prefill_chunk(mc), donate_argnums=(3,))
        self.chunk_len = chunk_len(cfg.batch)
        self._chunkable = chunked_prefill_ok(mc, cfg.window)
        self._prefill = dict.fromkeys(
            ("prefill_chunks", "prefill_tokens", "prefill_padded", "prefill_teacher_forced"), 0)
        self._slot_idx: jax.Array | None = None  # (B,) int32 when attached
        self._cache: dict | None = None
        self._logits: jax.Array | None = None
        self._key = jax.random.PRNGKey(cfg.seed)
        self._batch = 0  # span tag: the number of attach() calls so far
        self._pos = 0  # span tag: the decode-cache position of the next step
        # per-executable first-call wall time (compile + first run)
        self._first_s: dict[str, float] = {}

    # -- adapter attach / evict -----------------------------------------
    def attach(self, adapter_ids: Sequence[int], *, reset: bool = True) -> np.ndarray:
        """Bind tenant ``adapter_ids[b]`` to request b (len == batch),
        paging misses through the AdapterCache.  Resets the decode cache by
        default — new tenants mean new requests.  Returns the slot map."""
        if self.adapters is None:
            raise ValueError(
                "ServeSession was built without an AdapterCache — pass "
                "adapters= to serve per-request tenants"
            )
        if len(adapter_ids) != self.cfg.batch:
            raise ValueError(
                f"got {len(adapter_ids)} adapter ids for batch {self.cfg.batch}"
            )
        self._batch += 1
        with TraceAnnotation("serve.attach", batch=self._batch):
            slots = self.adapters.lookup(adapter_ids, batch=self._batch)
            self._slot_idx = jnp.asarray(slots, jnp.int32)
            if reset:
                self.reset()
        return slots

    def detach(self) -> None:
        """Back to single-adapter mode (the session's own ``params``)."""
        self._slot_idx = None

    @property
    def attached(self) -> bool:
        return self._slot_idx is not None

    # -- decode-cache lifecycle -----------------------------------------
    def reset(self, *, frontend: jax.Array | None = None) -> None:
        """Fresh decode cache (and encoder pass for audio families).  The
        MoE routing counters carry over: they count the whole session."""
        mc = self.cfg.model
        with TraceAnnotation("serve.reset", batch=self._batch):
            enc_out = None
            if mc.family == "audio":
                if frontend is None:
                    frontend = synth_frontend_embeddings(mc, self.cfg.batch)
                enc_out = _run_encoder(self.params, mc, frontend)
            moe = (self._cache or {}).get("moe")
            self._cache = init_cache(
                mc, self.cfg.batch, self.cfg.cache_len,
                window=self.cfg.window, enc_out=enc_out,
            )
            if moe is not None:
                for n, stats in moe.items():
                    self._cache["moe"][n]["counts"] = stats["counts"]
        self._logits = None
        self._pos = 0

    # -- the one decode step --------------------------------------------
    def _timed(self, name: str, fn, *args):
        """Run a decode executable.  Only its first call waits for the
        device, to time compile + first run (``first_step_s``); later calls
        return as soon as they are dispatched."""
        if name in self._first_s:
            return fn(*args)
        t0 = time.perf_counter()
        logits, cache = fn(*args)
        logits.block_until_ready()
        self._first_s[name] = time.perf_counter() - t0
        return logits, cache

    def step(self, tokens) -> jax.Array:
        """Feed one token per request, return next-token logits (B, V)."""
        with TraceAnnotation("serve.step", batch=self._batch, pos=self._pos):
            if self._cache is None:
                self.reset()
            tok = jnp.asarray(tokens, jnp.int32)
            if self._slot_idx is not None:
                self._logits, self._cache = self._timed(
                    "stacked", self._stacked,
                    self._frozen, self.adapters.slab, self._slot_idx,
                    self._cache, tok,
                )
            else:
                self._logits, self._cache = self._timed(
                    "single", self._single, self.params, self._cache, tok
                )
            self._pos += 1
        return self._logits

    def _chunk(self, tokens: np.ndarray, valid: int) -> jax.Array:
        """Feed one prefill chunk, ``tokens (B, T)`` of which the first
        ``valid`` per request are real; return the logits after them."""
        with TraceAnnotation("serve.prefill_chunk", batch=self._batch, pos=self._pos,
                             valid=valid):
            tok, count = jnp.asarray(tokens, jnp.int32), np.int32(valid)
            if self._slot_idx is not None:
                self._logits, self._cache = self._timed(
                    "stacked_chunk", self._stacked_chunk,
                    self._frozen, self.adapters.slab, self._slot_idx,
                    self._cache, tok, count,
                )
            else:
                self._logits, self._cache = self._timed(
                    "single_chunk", self._single_chunk, self.params, self._cache, tok, count
                )
            self._pos += valid
        return self._logits

    # -- serving loops ---------------------------------------------------
    def prefill(self, prompts) -> jax.Array:
        """Prefill ``prompts (B, L) int32`` into a fresh decode cache;
        returns the last position's next-token logits (B, V).  Chunked
        (``ceil(L / chunk_len)`` calls) where the model and the cache allow
        it, else teacher-forced through the decode step (module
        docstring)."""
        prompts = np.asarray(prompts)
        b, length = prompts.shape
        t = self.chunk_len
        padded = -(-length // t) * t
        with TraceAnnotation("serve.prefill", batch=self._batch):
            self.reset()
            if not (self._chunkable and 0 < padded <= self.cfg.cache_len):
                self._prefill["prefill_teacher_forced"] += length
                for i in range(length):
                    logits = self.step(prompts[:, i])
                return logits
            chunks = np.zeros((b, padded), np.int32)
            chunks[:, :length] = prompts
            for p in range(0, length, t):
                logits = self._chunk(chunks[:, p:p + t], min(t, length - p))
            self._prefill["prefill_chunks"] += padded // t
            self._prefill["prefill_tokens"] += length
            self._prefill["prefill_padded"] += padded - length
        return logits

    def decode(self, num_tokens: int, *, temperature: float | None = None):
        """Generate ``num_tokens`` per request from the current state.
        Returns ``(tokens (B, num_tokens) np.int32, last logits)``."""
        if self._logits is None:
            raise RuntimeError("decode() before prefill()/step() — no logits yet")
        temp = self.cfg.temperature if temperature is None else temperature
        out = []
        logits = self._logits
        for _ in range(num_tokens):
            if temp > 0:
                self._key, sub = jax.random.split(self._key)
                nxt = jax.random.categorical(sub, logits / temp, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            out.append(np.asarray(nxt))
            logits = self.step(nxt)
        return np.stack(out, axis=1).astype(np.int32), logits

    # -- stats taps ------------------------------------------------------
    def executables(self) -> dict:
        """Compiled executable count of the decode step (``single``,
        ``stacked``) and the prefill chunk (``single_chunk``,
        ``stacked_chunk``) per mode: each stays at 1 across tenant mixes and
        prompt lengths."""
        out = {}
        for name, fn in (("single", self._single), ("stacked", self._stacked),
                         ("single_chunk", self._single_chunk),
                         ("stacked_chunk", self._stacked_chunk)):
            size = getattr(fn, "_cache_size", None)
            out[name] = int(size()) if callable(size) else -1
        return out

    def routes(self) -> dict | None:
        """The experts each MoE layer routed each request's token in each
        cache slot to, since the last ``reset``: per MoE position, (layers,
        B, k, C) int32 device arrays; None for a model without experts.
        Read them before the next ``step``, which donates the cache."""
        if self._cache is None or "moe" not in self._cache:
            return None
        return {n: m["experts"] for n, m in self._cache["moe"].items()}

    def stats(self) -> dict:
        s = {
            "first_step_s": dict(self._first_s),
            "executables": self.executables(),
            "attached": self.attached,
            # over the session: prefill chunks run, their real and pad
            # rows, and prompt tokens teacher-forced through the decode step
            **self._prefill,
        }
        if self._cache is not None and "moe" in self._cache:
            # per MoE layer over the session's decode steps and prefill
            # chunks: pairs routed to held experts, held experts used (summed
            # over the calls), pairs dropped
            counts = np.concatenate([np.asarray(m["counts"]) for m in self._cache["moe"].values()])
            s["moe_routed_pairs"] = counts[:, 0].tolist()
            s["moe_experts_used"] = counts[:, 1].tolist()
            s["moe_dropped_pairs"] = int(counts[:, 2].sum())
        if self.adapters is not None:
            s["adapter_cache"] = self.adapters.stats.as_dict()
            s["adapter_slots"] = self.adapters.slots
            s["resident_adapters"] = list(self.adapters.resident())
        return s
