"""Serving traffic: a closed loop of callers, one batch of requests at a
time, through the program's ``ServeSession`` and ``AdapterCache``.

``batch`` callers each send their next request when their reply is
complete, so every batch starts together: its requests' tenants are
``attach``ed (adapter misses page in from host memory), its prompts are
prefilled, and tokens are decoded greedily until the request with the most
output tokens has them all.  The loop is closed because the session has no
request queue: an open loop would measure this harness's own scheduler.
One prompt length serves the whole batch, since ``prefill`` takes one
``(batch, length)`` array; each request has an output length of its own.

Tenants are drawn Zipf(``zipf_s``) over ``tenants`` adapters.  Lengths are
uniform on ``[min_*, max_*]``, drawn as quantiles so that every seed serves
the same lengths: a batch's prompt length comes from the ``length_set``
quantiles in a fixed bit-reversed order, from the longest, that spreads
every stretch of batches over the set, and its requests take the ``batch`` quantiles of the
output lengths, each seed dealing them out in an order of its own.  Prompt
tokens are uniform over the vocabulary.

Time to first token runs from a batch's send to its first output token on
the host; the gaps between tokens run from one token on the host to the
next, once for each request that is still producing.  The check runs the
plain reference over a sample of the finished requests, with the longest
among them, and compares the logit the program gave each served token.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, reference, weights
from bench.system import model_config


class HostAdapters:
    """The tenants' adapters, resident in host memory (``AdapterSource``)."""

    def __init__(self, leaves: dict, count: int):
        self.leaves, self.num_adapters = leaves, count

    def lora_row(self, cid: int):
        return {"stack": {"pos0": {"lora": {
            t: {ab: self.leaves[t][ab][cid] for ab in self.leaves[t]}
            for t in self.leaves}}}}


def quantiles(lo: int, hi: int, n: int) -> list[int]:
    """The ``n`` midpoint quantiles of the uniform lengths on ``[lo, hi]``."""
    return [int(round(lo + (i + 0.5) / n * (hi - lo))) for i in range(n)]


def prompt_lengths(mix: dict) -> list[int]:
    """One cycle of the fixed length set (a power of two of quantiles) in
    bit-reversed order from the longest: every stretch of the cycle from its
    start spreads over the quantiles, so a window that ends inside a cycle
    still served about the mean length, every seed's window serves the same
    lengths, and each window holds the longest prompt whether or not one
    more batch fits into it."""
    n = mix["length_set"]
    bits = n.bit_length() - 1
    if n != 1 << bits:
        raise ValueError(f"length_set must be a power of two, got {n}")
    lens = quantiles(mix["min_prompt"], mix["max_prompt"], n)[::-1]
    return [lens[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(n)]


class Requests:
    """A run's batches of requests: each request's tenant and output length,
    and the batch's prompts, one length from the fixed set for the batch."""

    def __init__(self, mix: dict, batch: int, vocab: int, seed: int):
        self.mix, self.batch, self.vocab = mix, batch, vocab
        p = 1.0 / np.arange(1, mix["tenants"] + 1) ** mix["zipf_s"]
        self.tenant_p = p / p.sum()
        self.outputs = np.array(quantiles(mix["min_output"], mix["max_output"], batch))
        self.rng = np.random.default_rng(seed)
        self.cycle = []

    def next(self, length: int | None = None, output: int | None = None):
        """``(tenants (batch,), prompts (batch, length), outputs (batch,))``;
        the lengths come from the fixed sets unless given."""
        if length is None:
            if not self.cycle:
                self.cycle = prompt_lengths(self.mix)
            length = self.cycle.pop(0)
        tenants = self.rng.choice(self.mix["tenants"], size=self.batch, p=self.tenant_p)
        prompts = self.rng.integers(0, self.vocab, (self.batch, length), dtype=np.int32)
        outputs = self.rng.permutation(self.outputs)
        if output is not None:
            outputs = np.full(self.batch, output)
        return tenants, prompts, outputs


@jax.jit
def pick(logits):
    """Each row's greedy token and the logit the program gave it."""
    return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int, rec, seconds: float):
        from repro.lora import lora_template
        from repro.serve import AdapterCache, ServeConfig, ServeSession

        del seconds  # the loop is closed: no rate or horizon to plan for
        self.mix, self.seed, self.rec = mix, seed, rec
        self.spec = config["models"]["served"]
        self.matmul = config["matmul_precision"]
        # the program's products at the precision the configuration states
        jax.config.update("jax_default_matmul_precision", self.matmul)
        cfg = model_config(self.spec)
        self.batch = config["batch"]
        self.vocab = self.spec["vocab_size"]
        params, leaves = self._weights()
        self.leaves = jax.tree.map(np.asarray, leaves)
        cache = AdapterCache(HostAdapters(self.leaves, mix["tenants"]),
                             like=lora_template(params), slots=config["slots"])
        self.session = ServeSession(
            ServeConfig(model=cfg, batch=self.batch, cache_len=config["cache_len"], seed=seed),
            params, adapters=cache)
        self.requests = Requests(mix, self.batch, self.vocab, seed + 5)
        self.served = []
        warm = Requests(mix, self.batch, self.vocab, seed + 6)
        for _ in range(mix["warm_batches"]):
            self._batch(*warm.next(mix["min_prompt"], mix["min_output"]), keep=False)
        cache.reset_stats()

    def _weights(self):
        sizes = weights.model_key(self.spec)
        return weights.serving(weights.seed_key(self.seed, 0), sizes, self.mix["tenants"])

    def _batch(self, tenants, prompts, outputs, keep=True):
        """One batch of requests, from send to the last token on the host."""
        span, sess = self.rec.span, self.session
        t_send = time.perf_counter()
        with span("bench.attach"):
            sess.attach(tenants.tolist())
        with span("bench.prefill"):
            logits = sess.prefill(prompts)
        tok, top = (np.asarray(x) for x in pick(logits))
        t_prev = time.perf_counter()
        ttft, gaps, toks, tops = t_prev - t_send, [], [tok], [top]
        for s in range(1, int(outputs.max())):
            with span("bench.step"):
                logits = sess.step(tok)
            tok, top = (np.asarray(x) for x in pick(logits))
            now = time.perf_counter()
            gaps += [now - t_prev] * int(np.sum(outputs > s))
            t_prev = now
            toks.append(tok)
            tops.append(top)
        if keep:
            self.served.append((tenants, prompts, outputs, np.stack(toks, 1), np.stack(tops, 1)))
        return ttft, gaps

    def window(self, seconds: float) -> dict:
        ttfts, gaps = [], []
        first = len(self.served)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ttft, g = self._batch(*self.requests.next())
            ttfts += [ttft] * self.batch
            gaps += g
        elapsed = time.perf_counter() - t0
        batches = self.served[first:]
        requests = len(batches) * self.batch
        tokens = sum(int(b[2].sum()) for b in batches)
        stats = self.session.adapters.stats
        step_flops = step_bytes = n_steps = 0
        for tenants, prompts, outputs, _, _ in batches:
            length = prompts.shape[1]
            for pos in range(length + int(outputs.max()) - 1):
                # prefill needs every row; a decode step only the rows whose
                # requests still want tokens
                live = np.ones(self.batch, bool) if pos < length else outputs > pos - length + 1
                filled = [pos] * int(live.sum())
                step_flops += flops.decode_step_flops(self.spec, filled)
                step_bytes += flops.decode_step_bytes(self.spec, filled,
                                                      len(set(tenants[live].tolist())))
                n_steps += 1
        return {
            "window_s": elapsed, "attempted": requests, "failed": 0,
            "metrics": {
                "serve_tokens_per_s": tokens / elapsed,
                "ttft_p95_ms": 1e3 * float(np.percentile(ttfts, 95)),
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            },
            "counters": {
                "requests": requests, "batches": len(batches), "steps": n_steps,
                "step_flops": step_flops, "step_bytes": step_bytes,
                "hits": stats.hits, "misses": stats.misses,
                "step_module": "jit_stacked_decode_step",
                "session_modules": ["jit_stacked_decode_step", "jit_slab_set_row"],
            },
        }

    def free(self):
        self.__dict__.pop("session", None)

    def readings(self, prec=None) -> dict:
        """Over a seeded sample of the finished requests, with the longest
        among them, at each served token: the gap between the logit the
        program gave it and the reference's, widest (``logit_gap``) and mean
        (``logit_gap_mean``), and ``greedy_gap``, the widest gap by which its
        reference logit lies below the reference's best.  With ``prec`` the
        reference computed at that lower precision takes the program's
        place: the token it puts first, and its logit."""
        return served_gaps(self, prec)

    def check(self, limits: dict) -> dict:
        """``{name: (value, limit)}`` of the numbers the limits file holds."""
        got = self.readings()
        return {k: (got[k], lim) for k, lim in limits.items()}


def sample(cell: Cell) -> list[tuple]:
    """(tenant, prompt, served tokens, their program logits) of the sampled
    requests; the one with the most tokens is among them."""
    rng = np.random.default_rng(cell.seed + 7)
    flat = [(b, r) for b in range(len(cell.served)) for r in range(cell.batch)]

    def size(br):
        prompts, outputs = cell.served[br[0]][1], cell.served[br[0]][2]
        return prompts.shape[1] + outputs[br[1]]

    picks = {max(flat, key=size)}
    for i in rng.permutation(len(flat)):
        if len(picks) >= cell.mix["check_requests"]:
            break
        picks.add(flat[i])
    out = []
    for b, r in sorted(picks):
        tenants, prompts, outputs, toks, tops = cell.served[b]
        n = int(outputs[r])
        out.append((int(tenants[r]), prompts[r], toks[r, :n], tops[r, :n]))
    return out


# a control: the reference one precision step down, as (values, products)
CONTROLS = {"high": ("float32", "high"), "bfloat16": ("bfloat16", "highest")}


@functools.partial(jax.jit, static_argnames=("heads", "scale", "matmul", "control"))
def _block_gaps(frozen, lora, toks, pos, valid, chosen, top, *, heads, scale, matmul,
                control):
    ref = reference.served_logits(frozen, lora, toks, pos, heads=heads, scale=scale,
                                  matmul=matmul)
    if control:
        values, products = CONTROLS[control]
        low = reference.served_logits(frozen, lora, toks, pos, heads=heads, scale=scale,
                                      prec=values, matmul=products)
        chosen, top = jnp.argmax(low, -1), jnp.max(low, -1)
    at = jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    gap = jnp.where(valid, jnp.abs(top - at), 0.0)
    greedy_gap = jnp.where(valid, ref.max(-1) - at, 0.0).max()
    return gap.max(), gap.sum(), greedy_gap


def served_gaps(cell: Cell, prec=None) -> dict:
    """The check's numbers, in blocks of ``check_block`` requests, each
    block one shape: prompts and tokens padded to the longest a request can
    be, positions to the most output tokens."""
    if prec is not None and prec not in CONTROLS:
        raise ValueError(f"no control at precision {prec!r}")
    frozen, _ = cell._weights()
    mix = cell.mix
    scale = cell.spec["lora"]["alpha"] / cell.spec["lora"]["rank"]
    width, n_out = mix["max_prompt"] + mix["max_output"], mix["max_output"]
    worst = {"logit_gap": 0.0, "greedy_gap": 0.0}
    total = count = 0.0
    picks = sample(cell)
    size = mix["check_block"]
    for lo in range(0, len(picks), size):
        block = picks[lo:lo + size]
        real = len(block)
        block += block[:1] * (size - real)  # one shape for every call
        toks = np.zeros((size, width), np.int32)
        pos = np.zeros((size, n_out), np.int32)
        valid = np.zeros((size, n_out), bool)
        chosen = np.zeros((size, n_out), np.int32)
        top = np.zeros((size, n_out), np.float32)
        for i, (_, prompt, out, logit) in enumerate(block):
            seq = np.concatenate([prompt, out[:-1]])
            toks[i, :seq.size] = seq
            pos[i, :out.size] = np.arange(prompt.size - 1, seq.size)
            valid[i, :out.size] = i < real
            chosen[i, :out.size], top[i, :out.size] = out, logit
        lora = {t: {ab: jnp.asarray(np.stack([cell.leaves[t][ab][c] for c, *_ in block]))
                    for ab in ("A", "B")} for t in cell.leaves}
        gaps = _block_gaps(frozen, lora, toks, pos, valid, chosen, top,
                           heads=cell.spec["num_heads"], scale=scale, matmul=cell.matmul,
                           control=prec)
        widest, summed, greedy = (float(x) for x in gaps)
        worst["logit_gap"] = max(worst["logit_gap"], widest)
        worst["greedy_gap"] = max(worst["greedy_gap"], greedy)
        total, count = total + summed, count + valid.sum()
    return dict(worst, logit_gap_mean=total / count)
