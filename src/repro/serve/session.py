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
``serve.prefill`` and ``serve.step`` here, ``serve.page_in`` in the cache.
Every span of one batch carries ``batch=<n>``, the count of ``attach``
calls so far.

Compilation contract: a session compiles at most TWO decode executables —
the single-adapter step (detached mode) and the stacked multi-tenant step
(attached mode).  Tenant mix, slot assignment, and token values are all
traced data; prefill teacher-forces the prompt through the SAME decode
executable, so serving any number of tenants costs one compile.  Both
steps donate the decode cache (in-place ring-buffer update).
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
from repro.models.model import _run_encoder
from repro.serve.cache import AdapterCache
from repro.serve.steps import make_decode_step, make_stacked_decode_step

__all__ = ["ServeConfig", "ServeSession"]


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

    # -- serving loops ---------------------------------------------------
    def prefill(self, prompts) -> jax.Array:
        """Teacher-force ``prompts (B, L) int32`` through the decode step
        (resetting the cache first); returns last-position logits (B, V).
        Smoke-scale prefill — the production full-sequence prefill shapes
        are proven by the dry-run (``make_prefill_step``)."""
        prompts = np.asarray(prompts)
        with TraceAnnotation("serve.prefill", batch=self._batch):
            self.reset()
            for t in range(prompts.shape[1]):
                logits = self.step(prompts[:, t])
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
        """Compiled decode-executable count per mode (the 'one donated
        decode step' invariant: stays at 1 per mode across tenant mixes)."""
        out = {}
        for name, fn in (("single", self._single), ("stacked", self._stacked)):
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
        }
        if self._cache is not None and "moe" in self._cache:
            # per MoE layer over the session's decode steps: pairs routed to
            # held experts, held experts used (summed over steps), pairs dropped
            counts = np.concatenate([np.asarray(m["counts"]) for m in self._cache["moe"].values()])
            s["moe_routed_pairs"] = counts[:, 0].tolist()
            s["moe_experts_used"] = counts[:, 1].tolist()
            s["moe_dropped_pairs"] = int(counts[:, 2].sum())
        if self.adapters is not None:
            s["adapter_cache"] = self.adapters.stats.as_dict()
            s["adapter_slots"] = self.adapters.slots
            s["resident_adapters"] = list(self.adapters.resident())
        return s
