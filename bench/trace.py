"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The JAX profiler writes one plane per device (``/device:TPU:<n>``) whose
``XLA Modules`` line holds one event per executable run and whose
``XLA Ops`` line holds one event per operation, and one host plane whose
thread lines hold the benchmark's ``jax.profiler.TraceAnnotation`` spans
(all named ``bench.*``).  The device's clock can sit a millisecond or so
off the host's; since the device runs nothing but the traced window while
the profiler is on, a device whose events start before the window (or end
after it) is shifted by that much onto it.

* busy time: the union of a device's operation intervals inside the traced
  window (``bench.window``), averaged over the devices that ran anything;
* idle gaps: the holes in that union, each put down to the innermost
  ``bench.*`` span that covers its midpoint;
* device time per executable: the summed durations of its module events;
* top operations: self time (an operation's duration less that of the
  operations nested in it, as a loop's body is in the loop) summed per
  ``<executable>:<instruction>`` name.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    """What a traced window holds, in seconds."""

    window_s: float
    busy_s: float
    devices: int
    modules: dict  # executable name -> [runs, seconds]
    ops: dict  # operation name -> seconds
    gaps: list  # [(covering span, seconds)] for every idle gap
    spans: list  # [(name, start_s, end_s)] host spans inside the window

    def module(self, name: str) -> tuple[int, float]:
        """(runs, device seconds) of the executables called ``name``, or
        whose name starts with ``name`` and a dot or a bracket."""
        runs = secs = 0
        for key, (n, s) in self.modules.items():
            if key == name or key.startswith(name + ".") or key.startswith(name + "("):
                runs, secs = runs + n, secs + s
        return runs, secs

    def breakdown(self, top: int = 10) -> dict:
        """The longest device operations and the idle time by host span."""
        idle: dict = {}
        for span, secs in self.gaps:
            idle[span] = idle.get(span, 0.0) + secs
        by = lambda kv: -kv[1]  # noqa: E731
        return {
            "device_ops": [[k, v] for k, v in sorted(self.ops.items(), key=by)[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=by)[:top]],
        }


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _instruction(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(ops):
    """(name, start, self seconds) of each operation: its duration less the
    durations of the operations directly nested in it."""
    out, stack = [], []  # stack of [end, index in out]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[2] -= e - s
        out.append([name, s, e - s])
        stack.append([e, len(out) - 1])
    return out


def _cover(spans, t):
    """The innermost host span around time ``t``."""
    best, width = "none", float("inf")
    for name, s, e in spans:
        if s <= t <= e and e - s < width and name != WINDOW_SPAN:
            best, width = name, e - s
    return best


def reduce(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            if ops or modules:
                devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith("bench."))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"{path} holds no {WINDOW_SPAN} span")
    lo, hi = window[0]
    spans = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    busy = 0.0
    gaps, mods, op_time = [], {}, {}
    used = 0
    for ops, modules in devices:
        if not ops:
            continue
        first, last = min(s for _, s, _ in ops), max(e for _, _, e in ops)
        shift = lo - first if first < lo else -min(last - hi, first - lo) if last > hi else 0
        ops = [(n, s + shift, e + shift) for n, s, e in ops]
        modules = [(n, s + shift, e + shift) for n, s, e in modules]
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        if not ops:
            continue
        used += 1
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_cover(spans, (s + e) / 2), (e - s) * 1e-9))
        modules = sorted((s, e, _MODULE_ID.sub("", n)) for n, s, e in modules)
        starts = [m[0] for m in modules]
        for n, s, own in _self_times(ops):
            i = bisect.bisect_right(starts, s) - 1
            where = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            key = f"{where}:{_instruction(n)}"
            op_time[key] = op_time.get(key, 0.0) + own * 1e-9
        for s, e, key in modules:
            if e > lo and s < hi:
                runs, secs = mods.get(key, (0, 0.0))
                mods[key] = (runs + 1, secs + (e - s) * 1e-9)
    if not used:
        raise ValueError(f"{path}: no operation ran on a device in the window")
    return Trace(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / used, devices=used,
        modules={k: list(v) for k, v in mods.items()}, ops=op_time,
        gaps=gaps, spans=[(n, (s - lo) * 1e-9, (e - lo) * 1e-9) for n, s, e in spans],
    )
