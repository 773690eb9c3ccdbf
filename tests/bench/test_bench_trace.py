"""The trace reduction on a small trace recorded on a v5e chip: a jitted
matmul-tanh-matmul run five times, each in a ``bench.step`` span and each
followed by a 20 ms ``bench.sleep``, all inside ``bench.window``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.reduce(SMALL)


def test_executables_and_spans(small):
    assert small.devices == 1
    runs, secs = small.module("jit__lambda")
    assert runs == 5 and 0 < secs < 1e-3
    names = [n for n, _, _ in small.spans]
    assert names.count("bench.step") == names.count("bench.sleep") == 5
    assert 0.10 < small.window_s < 0.12


def test_busy_is_the_union_of_the_device_operations(small):
    from jax.profiler import ProfileData

    ops = []
    for plane in ProfileData.from_file(SMALL).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    ops.sort()
    busy, end = 0.0, -1.0
    for s, e in ops:  # a plain sweep over the sorted intervals
        if e > end:
            busy += e - max(s, end)
            end = e
    assert small.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    # one executable at a time: busy time is its device time
    assert small.busy_s == pytest.approx(small.module("jit__lambda")[1], rel=1e-3)


def test_idle_gaps_are_put_down_to_the_host_span_around_them(small):
    out = small.breakdown()
    idle = dict(out["idle_gaps"])
    total = sum(idle.values())
    assert total == pytest.approx(small.window_s - small.busy_s, rel=1e-6)
    assert idle["bench.sleep"] > 0.9 * total
    assert 5 * 0.02 <= idle["bench.sleep"] < small.window_s


def test_top_operations_by_self_time(small):
    ops = small.breakdown()["device_ops"]
    assert len(ops) <= 10
    assert all(name.startswith("jit__lambda:") for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) == pytest.approx(small.busy_s, rel=1e-3)


def test_self_time_leaves_out_nested_operations():
    ops = [("%while.1 = ...", 0, 10), ("%a = ...", 2, 5), ("%b = ...", 6, 8), ("%c = ...", 12, 13)]
    own = {trace._instruction(n): t for n, _, t in trace._self_times(ops)}
    assert own == {"while.1": 5, "a": 3, "b": 2, "c": 1}
