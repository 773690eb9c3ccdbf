"""The reduction on the clock that run ids give (``bench/aligned.py``).

``small.xplane.pb``: a jitted matmul-tanh-matmul run five times on a v5e
chip (see ``test_bench_trace.py``).  ``serve_small.xplane.pb``: the serving
cell at the size of ``bench_tiny.py`` on a v5e chip, two batches with the
program's ``serve.*`` spans inside the harness's ``bench.*`` spans
(``record_serve_small.py``).
"""

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import record_serve_small  # noqa: E402
from bench import aligned, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SERVE = os.path.join(DATA, "serve_small.xplane.pb")


def _runs(path):
    """Per device ordinal: [(start, end, enqueue, callbacks)] in ns of the
    module runs that have both host events of their ``run_id``."""
    from jax.profiler import ProfileData

    spans, devices, host = aligned.read_planes(ProfileData.from_file(path))
    out = {}
    for ordinal, _, modules in devices:
        keys = [((aligned.ENQUEUE, ordinal, r), (aligned.COMPLETE, ordinal, r))
                for _, _, _, r in modules]
        out[ordinal] = [(s, e, host[a], host[b]) for (_, s, e, _), (a, b) in zip(modules, keys)
                        if a in host and b in host]
        # some runs have no CompleteCallbacks event (in the serving traces,
        # some of the token upload's jit_convert_element_type): most pair
        assert len(out[ordinal]) > len(modules) / 2
    return out


@pytest.fixture(scope="module")
def small():
    return aligned.reduce(SMALL)


@pytest.fixture(scope="module")
def serve():
    return aligned.reduce(SERVE)


@pytest.mark.parametrize("path", [SMALL, SERVE], ids=["small", "serve_small"])
def test_every_run_lies_between_its_enqueue_and_its_callbacks(path):
    tr = aligned.reduce(path)
    runs = _runs(path)
    assert set(runs) == {o for o, _, _ in tr.clocks}
    for ordinal, shift_s, bracket_s in tr.clocks:
        shift = shift_s * 1e9
        assert runs[ordinal] and bracket_s is not None and bracket_s >= 0
        for start, end, enqueue, callbacks in runs[ordinal]:
            assert enqueue <= start + shift + 1 and end + shift <= callbacks
        # the shift is the tightest lower bound: some run starts as its enqueue does
        assert min(start + shift - enqueue for start, _, enqueue, _ in runs[ordinal]) < 1


def test_bracket_of_the_small_trace(small):
    assert small.clock_bracket_s == pytest.approx(0.422e-3, abs=0.01e-3)
    [(_, shift_s, _)] = small.clocks
    assert shift_s == pytest.approx(1.249e-3, abs=0.01e-3)


def test_the_aligned_clock_moves_no_device_time(small):
    old = trace.reduce(SMALL)
    assert small.busy_s == pytest.approx(old.busy_s, rel=1e-9)
    assert small.modules == old.modules and small.ops == pytest.approx(old.ops)
    idle = sum(t for _, t in small.gaps)
    assert idle == pytest.approx(sum(t for _, t in old.gaps), rel=1e-9)
    assert idle == pytest.approx(small.window_s - small.busy_s, rel=1e-6)


def test_without_run_id_pairs_the_old_shift_holds(monkeypatch):
    monkeypatch.setattr(aligned, "ENQUEUE", "no such event")
    tr, old = aligned.reduce(SMALL), trace.reduce(SMALL)
    assert tr.clock_bracket_s is None
    # bench/trace.py moves the first operation to the window's start
    [(_, shift_s, _)] = tr.clocks
    assert shift_s == pytest.approx(1.032e-3, abs=0.001e-3)
    assert tr.ops == old.ops and tr.busy_s == old.busy_s and tr.modules == old.modules
    assert sum(t for _, t in tr.gaps) == pytest.approx(sum(t for _, t in old.gaps), rel=1e-9)


def test_an_idle_gap_is_split_at_span_edges():
    spans = [("bench.window", 0, 100), ("bench.step", 10, 30), ("serve.step", 12, 20),
             ("serve.reset", 14, 16), ("bench.attach", 40, 50)]
    pieces = aligned.innermost(spans)
    assert pieces == [(10, 12, "bench.step"), (12, 14, "serve.step"), (14, 16, "serve.reset"),
                      (16, 20, "serve.step"), (20, 30, "bench.step"), (40, 50, "bench.attach")]
    parts = aligned.split([(5, 13), (15, 45), (60, 70)], pieces)
    assert parts == [("none", 5), ("bench.step", 2), ("serve.step", 1),
                     ("serve.reset", 1), ("serve.step", 4), ("bench.step", 10), ("none", 10),
                     ("bench.attach", 5), ("none", 10)]


def test_program_spans_read_from_the_serving_trace(serve):
    names = {n for n, _, _ in serve.spans}
    assert {"serve.attach", "serve.page_in", "serve.reset", "serve.prefill",
            "serve.step"} <= names
    assert {"bench.window", "bench.attach", "bench.prefill", "bench.step"} <= names
    values = [aligned.step_host_ms(serve), aligned.prefill_ms_per_token(serve),
              aligned.page_in_ms(serve), aligned.program_idle_share(serve)]
    assert all(v is not None and math.isfinite(v) and v >= 0 for v in values), values
    prefills = [(s, e) for n, s, e in serve.spans if n == "serve.prefill"]
    steps = [(s, e) for n, s, e in serve.spans if n == "serve.step"]
    in_prefill = [st for st in steps if any(a <= st[0] and st[1] <= b for a, b in prefills)]
    assert len(in_prefill) == sum(length for length, _ in record_serve_small.BATCHES)
    assert len(steps) == sum(length + out - 1 for length, out in record_serve_small.BATCHES)


def test_idle_by_span_sums_to_the_windows_idle(serve):
    idle = dict(serve.breakdown(top=100)["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(serve.window_s - serve.busy_s, rel=1e-6)
    device_idle = 100.0 * (1.0 - serve.busy_s / serve.window_s)
    program = aligned.program_idle_share(serve)
    assert program == pytest.approx(
        100.0 * sum(t for k, t in idle.items() if k.startswith("serve.")) / serve.window_s)
    assert 0 < program <= device_idle


def test_program_readings_of_a_hand_made_trace():
    spans = [("bench.window", 0.0, 10.0),
             ("serve.attach", 0.0, 1.0), ("serve.page_in", 0.1, 0.3), ("serve.page_in", 0.4, 0.8),
             ("serve.prefill", 1.0, 3.0), ("serve.reset", 1.0, 1.2),
             ("serve.step", 1.2, 1.6), ("serve.step", 2.0, 2.2),
             ("bench.step", 4.0, 5.0), ("serve.step", 4.0, 4.5), ("serve.reset", 4.0, 4.1)]
    gaps = [("serve.step", 0.2), ("bench.step", 0.3), ("none", 0.5)]
    tr = trace.Trace(window_s=10.0, busy_s=9.0, devices=1, modules={}, ops={}, gaps=gaps,
                     spans=spans)
    # the decode step alone: the two steps inside serve.prefill are its
    assert aligned.step_host_ms(tr) == pytest.approx(1e3 * 0.4)
    assert aligned.prefill_ms_per_token(tr) == pytest.approx(1e3 * 2.0 / 2)
    assert aligned.page_in_ms(tr) == pytest.approx(1e3 * 0.3)
    assert aligned.program_idle_share(tr) == pytest.approx(2.0)
    bare = trace.Trace(window_s=10.0, busy_s=9.0, devices=1, modules={}, ops={}, gaps=gaps[1:],
                       spans=[s for s in spans if s[0].startswith("bench.")])
    assert [aligned.step_host_ms(bare), aligned.prefill_ms_per_token(bare),
            aligned.page_in_ms(bare), aligned.program_idle_share(bare)] == [None] * 4
