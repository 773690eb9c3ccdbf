"""The serving path's profiler spans, read back from a CPU trace.

A tiny two-batch session runs under ``jax.profiler.trace``; its host plane
must hold the layer boundaries ``serve.attach`` / ``serve.page_in`` /
``serve.reset`` / ``serve.prefill`` / ``serve.step``, nested as the calls
nest, every span of one batch tagged with the same ``batch``, one
``serve.step`` per prompt token inside ``serve.prefill`` and one
``serve.page_in`` per adapter-cache miss.  Only an executable's first call
waits for the device.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import LoRAConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT
from repro.lora import lora_template
from repro.models import init as model_init
from repro.serve import AdapterCache, ServeConfig, ServeSession

CFG = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=64,
    lora=LoRAConfig(rank=4, alpha=8.0, dropout=0.0, targets=("q", "v")),
)
BATCH, SLOTS, GEN = 4, 4, 3
# (tenants, prompt length) of each batch: the second evicts and pages in
BATCHES = [([0, 1, 1, 2], 5), ([3, 4, 0, 5], 7)]
SPANS = {"serve.attach", "serve.page_in", "serve.reset", "serve.prefill", "serve.step"}


class Rows:
    """Tenant ``cid``'s adapter: the template's leaves filled with ``cid``."""

    def __init__(self, like, count):
        self.like, self.num_adapters = like, count

    def lora_row(self, cid):
        return jax.tree.map(lambda x: np.full(x.shape, 0.01 * cid, x.dtype), self.like)


def _session():
    params = model_init(jax.random.PRNGKey(0), CFG)
    like = lora_template(params)
    cache = AdapterCache(Rows(like, 8), like=like, slots=SLOTS)
    return ServeSession(ServeConfig(model=CFG, batch=BATCH, cache_len=16), params,
                        adapters=cache)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(session, [(name, start_ns, end_ns, tags)] of the serve.* host spans)."""
    from jax.profiler import ProfileData

    sess = _session()
    rng = np.random.default_rng(0)
    out = str(tmp_path_factory.mktemp("serve_trace"))
    with jax.profiler.trace(out):
        for tenants, length in BATCHES:
            sess.attach(tenants)
            sess.prefill(rng.integers(0, CFG.vocab_size, (BATCH, length), dtype=np.int32))
            _, logits = sess.decode(GEN)
            logits.block_until_ready()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                          for e in line.events if e.name.startswith("serve.")]
    return sess, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _of(spans, name):
    return [s for s in spans if s[0] == name]


def test_span_names_and_nesting(traced):
    _, spans = traced
    assert {s[0] for s in spans} == SPANS
    attaches, prefills = _of(spans, "serve.attach"), _of(spans, "serve.prefill")
    assert len(attaches) == len(prefills) == len(BATCHES)
    for page_in in _of(spans, "serve.page_in"):
        assert any(_inside(page_in, a) for a in attaches)
    # a batch's cache is reset by its attach and again by its prefill
    for reset in _of(spans, "serve.reset"):
        assert any(_inside(reset, o) for o in attaches + prefills)
    assert len(_of(spans, "serve.reset")) == 2 * len(BATCHES)
    for step in _of(spans, "serve.step"):
        assert not any(_inside(step, a) for a in attaches)


def test_one_batch_shares_its_batch_tag(traced):
    _, spans = traced
    attaches = _of(spans, "serve.attach")
    bounds = [a[1] for a in attaches] + [float("inf")]
    for n, (lo, hi) in enumerate(zip(bounds, bounds[1:]), start=1):
        assert attaches[n - 1][3] == {"batch": n}
        batch = [s for s in spans if lo <= s[1] < hi]
        assert {s[3]["batch"] for s in batch} == {n}
        assert {s[0] for s in batch} == SPANS  # each batch pages something in


def test_prefill_runs_one_step_per_prompt_token(traced):
    _, spans = traced
    steps = _of(spans, "serve.step")
    for prefill, (_, length) in zip(_of(spans, "serve.prefill"), BATCHES):
        inner = [s for s in steps if _inside(s, prefill)]
        assert len(inner) == length
        assert [s[3]["pos"] for s in inner] == list(range(length))
        # the decode steps that follow continue the positions
        after = [s for s in steps if s[1] > prefill[2]][:GEN]
        assert [s[3]["pos"] for s in after] == list(range(length, length + GEN))
        assert {s[3]["batch"] for s in inner + after} == {prefill[3]["batch"]}


def test_one_page_in_per_cache_miss(traced):
    sess, spans = traced
    page_ins = _of(spans, "serve.page_in")
    assert len(page_ins) == sess.adapters.stats.misses > 0
    for s in page_ins:
        tenants = BATCHES[s[3]["batch"] - 1][0]
        assert s[3]["tenant"] in tenants and 0 <= s[3]["slot"] < SLOTS
    assert sess.adapters.stats.evictions > 0  # the second batch evicted


def test_stats_keep_no_steady_timing(traced):
    sess, _ = traced
    s = sess.stats()
    assert not {"steady_step_s", "steady_steps", "tokens_decoded"} & set(s)
    assert set(s["first_step_s"]) == {"stacked"} and s["first_step_s"]["stacked"] > 0
    assert s["executables"]["stacked"] == 1
    assert {"adapter_cache", "adapter_slots", "resident_adapters"} <= set(s)


def test_step_waits_for_the_device_only_on_an_executables_first_call():
    sess = _session()
    sess.attach([0, 1, 2, 3])
    real, waits = sess._stacked, []

    class Logits:
        def __init__(self, x):
            self.x = x

        def block_until_ready(self):
            waits.append(1)
            return self

    def spy(*args):
        logits, cache = real(*args)
        return Logits(logits), cache

    sess._stacked = spy
    for t in range(4):
        sess.step(np.full(BATCH, t, np.int32))
    assert len(waits) == 1 and set(sess.stats()["first_step_s"]) == {"stacked"}
