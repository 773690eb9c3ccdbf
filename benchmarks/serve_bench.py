"""Multi-tenant serving benchmark (PR 10) — writes BENCH_serve[.quick].json.

The claim under test: ONE donated jitted decode step + ONE shared frozen
backbone serve a mixed batch of tenants (each request applying its own
LoRA adapter via the slab gather) at (within noise of) single-adapter
throughput, bit-identically to running each request alone with its
adapter merged the classic way.  Three regimes:

* ``single_adapter``        — the pre-redesign layout: one adapter merged
                              into the params, batch B, classic decode.
                              The throughput baseline.
* ``stacked_multi_tenant``  — B DISTINCT tenants in one batch through the
                              stacked decode step (adapter slab + per-
                              request int32 slot gather), warm cache.
                              Parity-probed bitwise, row-by-row, against
                              equal-batch classic merged-adapter decode.
* ``cache_thrash``          — more tenants than device slots: every
                              segment rotates the batch to 8 cold tenants,
                              so each attach pages 8 misses through LRU
                              eviction.  Throughput INCLUDES the host->
                              device paging, isolating the paging tax.

benchmarks/check_bench.py gates on this record: parity flag true,
adapters/batch >= 8, one stacked decode executable, and stacked steady
throughput >= 0.9x single-adapter at equal batch.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--quick]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")

BATCH = 8          # requests per decode step == adapters per batch
SLOTS = 8          # device adapter-cache slots
TENANTS = 24       # thrash fleet: 3x oversubscribed vs SLOTS
PROMPT = 8
PROBE = 8          # parity-probe decode length (bitwise, always run)


class _RandomSource:
    """Synthetic tenant fleet: tenant cid = adapter with randomized A AND
    B (fresh-init B is zero — every tenant's delta would vanish and the
    parity probe would be vacuous)."""

    def __init__(self, params, num_adapters: int, seed: int = 7):
        from repro.lora import map_lora, split_lora

        self._lora, _ = split_lora(params)
        self.num_adapters = int(num_adapters)
        self._seed = seed
        self._map_lora = map_lora

    def lora_row(self, cid: int):
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), int(cid))
        counter = [0]

        def rnd(x):
            counter[0] += 1
            k = jax.random.fold_in(key, counter[0])
            return 0.05 * jax.random.normal(k, x.shape).astype(x.dtype)

        return self._map_lora(rnd, self._lora)


def _build():
    from repro.configs.base import LoRAConfig
    from repro.configs.gpt2_paper import REDUCED_CLIENT
    from repro.models import init as model_init

    lora = LoRAConfig(rank=4, alpha=32.0, dropout=0.0,
                      targets=("q", "v", "o", "head"))
    # big enough that the backbone dominates a decode step (at toy widths
    # the unmerged per-request LoRA einsums are a visible fraction)
    cfg = REDUCED_CLIENT.with_overrides(
        num_layers=4, d_model=256, num_heads=4, num_kv_heads=4, d_ff=512,
        vocab_size=1024, max_seq_len=256, lora=lora,
    )
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _session(cfg, params, tokens, *, adapters=None):
    from repro.serve import ServeConfig, ServeSession

    scfg = ServeConfig(model=cfg, batch=BATCH,
                       cache_len=PROMPT + PROBE + tokens + 8)
    return ServeSession(scfg, params, adapters=adapters)


def _prompts(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (BATCH, PROMPT)).astype(np.int32)


def _burst(sess, prompts, tokens):
    """One timed decode burst (prefill untimed)."""
    sess.prefill(prompts)
    t0 = time.perf_counter()
    sess.decode(tokens)
    return time.perf_counter() - t0


def _regime(best_s, tokens, sess, reps):
    steady = best_s / tokens
    return {
        "tok_s": round(BATCH / steady, 1),
        "ms_per_step": round(steady * 1e3, 3),
        "compile_first_step_s": round(
            max(sess.stats()["first_step_s"].values()), 3
        ),
        "reps": reps,
    }


def _parity_probe(cfg, params, source, stacked_toks, stacked_logits, prompts):
    """Bitwise at EQUAL batch: stacked row b == row b of a classic decode
    with tenant b's adapter merged into the params the pre-redesign way.
    Equal batch isolates what the adapter machinery can and must
    guarantee — the per-request slab gather adds ZERO deviation over
    merge_lora — because XLA is not bit-stable across batch SIZES at this
    width even with no adapters in play (~1 ulp on CPU; measured).  The
    strict solo batch-1 claim is proven in tests/test_serve.py at a width
    where the backbone itself is batch-stable."""
    from repro.lora import merge_lora, split_lora
    from repro.models import init_cache
    from repro.serve import make_decode_step

    _, frozen = split_lora(params)
    step = jax.jit(make_decode_step(cfg))  # ONE compile, reused per tenant
    ok = True
    for b in range(BATCH):
        merged = merge_lora(source.lora_row(b), frozen)
        cache = init_cache(cfg, BATCH, PROMPT + PROBE + 2)
        logits = None
        for t in range(PROMPT):
            logits, cache = step(merged, cache, prompts[:, t])
        rows = []
        for _ in range(PROBE):
            nxt = jnp.argmax(logits, axis=-1)
            rows.append(int(np.asarray(nxt)[b]))
            logits, cache = step(merged, cache, nxt)
        ok = ok and rows == stacked_toks[b, :PROBE].tolist()
        ok = ok and np.array_equal(np.asarray(logits)[b], stacked_logits[b])
    return bool(ok)


def _thrash(cfg, params, tokens, segments):
    """Rotate the batch to 8 cold tenants every segment: each attach pages
    BATCH misses through LRU eviction.  Wall-clock includes the paging."""
    from repro.lora import lora_template
    from repro.serve import AdapterCache

    source = _RandomSource(params, TENANTS)
    cache = AdapterCache(source, like=lora_template(params), slots=SLOTS)
    sess = _session(cfg, params, tokens, adapters=cache)
    prompts = _prompts(cfg)

    def segment(s):
        ids = [(s * BATCH + i) % TENANTS for i in range(BATCH)]
        sess.attach(ids)
        sess.prefill(prompts)
        sess.decode(tokens)

    segment(0)  # warmup: compiles the stacked step + cold-fills the cache
    cache.reset_stats()
    t0 = time.time()
    for s in range(1, segments + 1):
        segment(s)
    wall = time.time() - t0
    total = segments * BATCH * (PROMPT + tokens)
    return {
        "tok_s_incl_paging": round(total / wall, 1),
        "adapters_per_batch": BATCH,
        "distinct_tenants": TENANTS,
        "slots": SLOTS,
        "segments_timed": segments,
        "cache": sess.adapters.stats.as_dict(),
    }


def bench_serve(quick: bool = True, out_json: str | None = None):
    from repro.lora import lora_template, merge_lora, split_lora
    from repro.serve import AdapterCache

    cfg, params = _build()
    tokens = 24 if quick else 64
    segments = 4 if quick else 8
    reps = 5 if quick else 7
    source = _RandomSource(params, BATCH)
    prompts = _prompts(cfg)

    # -- single_adapter vs stacked_multi_tenant, PAIRED bursts ------------
    # single: one tenant merged classic (pre-redesign layout), batch B;
    # stacked: B distinct tenants through the one stacked decode step.
    # Bursts are interleaved single/stacked per rep so a localized stall
    # on this noisy container hits both regimes, not just one side of the
    # throughput ratio; min-of-reps per regime.
    _, frozen = split_lora(params)
    merged = merge_lora(source.lora_row(0), frozen)
    s_sess = _session(cfg, merged, tokens)
    cache = AdapterCache(source, like=lora_template(params), slots=SLOTS)
    sess = _session(cfg, params, tokens, adapters=cache)
    sess.attach(list(range(BATCH)))
    _burst(s_sess, prompts, tokens)  # compile + warmup, both modes
    _burst(sess, prompts, tokens)
    best_single = best_stacked = float("inf")
    for _ in range(reps):
        best_single = min(best_single, _burst(s_sess, prompts, tokens))
        best_stacked = min(best_stacked, _burst(sess, prompts, tokens))
    single = _regime(best_single, tokens, s_sess, reps)
    single["adapters_per_batch"] = 1
    stacked = _regime(best_stacked, tokens, sess, reps)
    stacked["adapters_per_batch"] = BATCH
    stacked["cache"] = sess.adapters.stats.as_dict()

    # parity probe: a PROBE-length stacked decode (reuses the SAME compiled
    # step) vs each tenant served alone at batch 1, bitwise per row
    sess.attach(list(range(BATCH)))
    sess.prefill(prompts)
    ptoks, plogits = sess.decode(PROBE)
    stacked["decode_executables"] = sess.stats()["executables"]["stacked"]
    parity = _parity_probe(cfg, params, source, ptoks, np.asarray(plogits),
                           prompts)

    # -- cache_thrash: oversubscribed fleet, paging on every attach -------
    thrash = _thrash(cfg, params, tokens, segments)

    ratio = round(stacked["tok_s"] / single["tok_s"], 3)
    shape = (f"B{BATCH};L{cfg.num_layers};d{cfg.d_model};V{cfg.vocab_size};"
             f"P{PROMPT};T{tokens};rank{cfg.lora.rank};slots{SLOTS}")

    if out_json:
        record = {
            "bench": "serve",
            "shape": shape,
            "quick": quick,
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "parity": {
                "multi_tenant_bit_identical": parity,
                "adapters_per_batch": BATCH,
                "probe_tokens": PROBE,
                "baseline": (
                    "equal-batch classic merge_lora decode (row b of an "
                    "all-tenant-b batch); solo batch-1 parity is proven in "
                    "tests/test_serve.py at a batch-stable width"
                ),
            },
            "regimes": {
                "single_adapter": single,
                "stacked_multi_tenant": stacked,
                "cache_thrash": thrash,
            },
            "speedups": {"stacked_vs_single": ratio},
            "notes": (
                "Steady-state decode throughput = best of timed decode "
                "bursts after a compile + warmup burst, with single/"
                "stacked bursts INTERLEAVED per rep so a localized stall "
                "on this noisy CPU container hits both sides of the "
                "throughput ratio (the pre-redesign script folded XLA "
                "compile into tok/s).  single_adapter merges one "
                "tenant into the "
                "params (pre-redesign layout); stacked_multi_tenant "
                f"serves {BATCH} DISTINCT tenants per batch via the "
                "adapter-slab gather in ONE compiled decode step, parity-"
                "probed bitwise per row against equal-batch classic "
                "merge_lora decode (XLA is not bit-stable across batch "
                "SIZES at this width even adapter-free, so equal batch is "
                "the honest claim here; solo batch-1 parity is proven in "
                "tests/test_serve.py).  "
                "cache_thrash oversubscribes the device slots "
                f"({TENANTS} tenants, {SLOTS} slots) and rotates the "
                "batch to 8 cold tenants per segment, so wall-clock "
                "includes host->device adapter paging + LRU eviction."
            ),
        }
        with open(out_json, "w") as f:
            json.dump(record, f, indent=1)

    return [
        ("serve_single_adapter", 1e6 * BATCH / single["tok_s"],
         f"{shape};tok_s={single['tok_s']}"),
        ("serve_stacked_8tenant", 1e6 * BATCH / stacked["tok_s"],
         f"{shape};tok_s={stacked['tok_s']};vs_single={ratio}x;"
         f"parity={parity}"),
        ("serve_cache_thrash", 1e6 * BATCH / thrash["tok_s_incl_paging"],
         f"{shape};tok_s={thrash['tok_s_incl_paging']};"
         f"misses={thrash['cache']['misses']};"
         f"evictions={thrash['cache']['evictions']}"),
    ]


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = "--quick" in sys.argv
    suffix = "quick.json" if quick else "json"
    out = os.path.join(_REPO_ROOT, f"BENCH_serve.{suffix}")
    for name, us, derived in bench_serve(quick=quick, out_json=out):
        print(f"{name},{us:.0f},{derived}")
    with open(out) as f:
        rec = json.load(f)
    print(f"parity (8-tenant bitwise vs classic merged): "
          f"{rec['parity']['multi_tenant_bit_identical']}")
    print(f"stacked vs single throughput: "
          f"{rec['speedups']['stacked_vs_single']:.2f}x")
    print(f"-> {out}")
