"""A profiler trace reduced on one clock, with the program's own spans.

``bench/trace.py`` puts a device on the host's clock by moving its first
operation to the start of ``bench.window``.  Here the clocks are tied by
the runtime's own records instead: each run of an executable on a device
(an ``XLA Modules`` event) carries a ``run_id``, and so do the host events
``DoEnqueueProgram`` (the host begins to enqueue that run) and
``CompleteCallbacks`` (the host learns it has finished), with the device's
ordinal.  A run starts after its enqueue began and ends before its
callbacks start, so the device-to-host offset lies in

    [max(enqueue - run start), min(callbacks - run end)]

over the device's paired runs.  The device is moved by the lower bound,
which is tight for any run that found the device idle, as a decode step
that waits for its token does; ``clock_bracket_s`` is the bracket's width.
A device with no paired run is moved as ``bench/trace.py`` moves it.

Host spans are the harness's (``bench.*``) and the program's (``serve.*``,
``jax.profiler.TraceAnnotation`` in ``repro/serve``).  Each idle gap is cut
at every span edge inside it, and each part goes to the innermost span over
it (``none`` where only ``bench.window`` is); the program's spans nest
inside the harness's, so a program span wins where there is one.
(``bench/trace.py`` puts a whole gap down to the span around its midpoint,
which swings from one span to the other when gaps straddle a span's edge,
as a decode step's wait for its token does.)  Everything else is reduced as
``bench/trace.py`` reduces it, into the same ``Trace``.

The functions at the end read the program's spans (``None`` on a trace
that has none):

* ``step_host_ms``: mean self time (less the spans nested in it) of the
  ``serve.step`` spans outside ``serve.prefill``: the decode steps' token
  upload and dispatch.  A prefill step's span also holds the host's wait
  for room in the runtime's queue of runs in flight, since prefill
  dispatches step after step without reading anything back; that time is
  ``prefill_ms_per_token``'s;
* ``prefill_ms_per_token``: summed ``serve.prefill`` time over the
  ``serve.step`` spans nested in it;
* ``page_in_ms``: mean ``serve.page_in`` time (one adapter-cache miss);
* ``program_idle_share``: the share of the window, in %, in which the device
  is idle and the innermost host span is the program's.
"""

from __future__ import annotations

import bisect
import dataclasses

from bench.trace import (MODULES_LINE, OPS_LINE, WINDOW_SPAN, Trace, _clip, _instruction,
                         _MODULE_ID, _self_times, _union)

SPAN_PREFIXES = ("bench.", "serve.")
PROGRAM = "serve."
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


@dataclasses.dataclass
class AlignedTrace(Trace):
    """A ``Trace`` with the clock each device was put on."""

    clocks: list = dataclasses.field(default_factory=list)
    # [(device ordinal, shift s, bracket width s or None without pairs)]

    @property
    def clock_bracket_s(self) -> float | None:
        """The widest offset bracket of the devices with paired runs."""
        widths = [b for _, _, b in self.clocks if b is not None]
        return max(widths) if widths else None


def _ordinal(plane_name: str) -> int:
    """``/device:TPU:3`` -> 3."""
    return int(plane_name.rsplit(":", 1)[1])


def offset_bounds(runs, host, ordinal):
    """``[(enqueue - start, callbacks - end)]`` in ns for each run
    ``(start, end, run_id)`` of device ``ordinal`` that ``host``, keyed
    ``(event name, ordinal, run_id)`` -> first start, pairs."""
    out = []
    for s, e, run in runs:
        enq, done = host.get((ENQUEUE, ordinal, run)), host.get((COMPLETE, ordinal, run))
        if enq is not None and done is not None:
            out.append((enq - s, done - e))
    return out


def innermost(spans):
    """The host spans cut at every span edge: sorted, disjoint ``(start, end,
    innermost span)`` pieces wherever a span other than the window is."""
    spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    out, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            out.append((a, b, min(active, key=lambda sp: sp[1] - sp[0])[2]))
    return out


def split(holes, pieces):
    """``[(span, length)]`` of the sorted, disjoint ``holes`` cut by the
    ``pieces`` of ``innermost``; a part under no piece is ``none``'s."""
    out, j = [], 0
    for s, e in holes:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            if a > t:
                out.append(("none", a - t))
            out.append((name, min(b, e) - max(a, t)))
            t = min(b, e)
            if b > e:
                break
            k += 1
        if t < e:
            out.append(("none", e - t))
    return out


def read_planes(data):
    """``(spans, devices, host)`` of a ``ProfileData``: the host spans
    ``(name, start, end)``; per device ``(ordinal, ops, modules)`` with
    ``modules`` as ``(name, start, end, run_id)``; and the first start of
    each ``(ENQUEUE|COMPLETE, device ordinal, run_id)``."""
    spans, devices, host = [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats).get("run_id")) for e in line.events]
            if ops or modules:
                devices.append((_ordinal(plane.name), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name in (ENQUEUE, COMPLETE):
                        stats = dict(e.stats)
                        key = (e.name, stats.get("device_ordinal"), stats.get("run_id"))
                        host[key] = min(host.get(key, e.start_ns), e.start_ns)
    return spans, devices, host


def reduce(path: str) -> AlignedTrace:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    spans, devices, host = read_planes(ProfileData.from_file(path))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"{path} holds no {WINDOW_SPAN} span")
    lo, hi = window[0]
    spans = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    pieces = innermost(spans)
    busy = 0.0
    gaps, mods, op_time, clocks = [], {}, {}, []
    used = 0
    for ordinal, ops, modules in devices:
        if not ops:
            continue
        bounds = offset_bounds([(s, e, r) for _, s, e, r in modules], host, ordinal)
        if bounds:
            shift = max(b for b, _ in bounds)
            clocks.append((ordinal, shift * 1e-9, (min(u for _, u in bounds) - shift) * 1e-9))
        else:
            first, last = min(s for _, s, _ in ops), max(e for _, _, e in ops)
            shift = lo - first if first < lo else -min(last - hi, first - lo) if last > hi else 0
            clocks.append((ordinal, shift * 1e-9, None))
        ops = [(n, s + shift, e + shift) for n, s, e in ops]
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        if not ops:
            continue
        used += 1
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        holes = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        gaps += [(name, t * 1e-9) for name, t in split(holes, pieces)]
        modules = sorted((s + shift, e + shift, _MODULE_ID.sub("", n)) for n, s, e, _ in modules)
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
    return AlignedTrace(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / used, devices=used,
        modules={k: list(v) for k, v in mods.items()}, ops=op_time,
        gaps=gaps, spans=[(n, (s - lo) * 1e-9, (e - lo) * 1e-9) for n, s, e in spans],
        clocks=clocks,
    )


def _named(tr: Trace, name: str) -> list:
    return [(s, e) for n, s, e in tr.spans if n == name]


def _inside(t: float, intervals) -> bool:
    return any(s <= t <= e for s, e in intervals)


def step_host_ms(tr: Trace) -> float | None:
    prefills = _named(tr, "serve.prefill")
    own = [t for n, s, t in _self_times(tr.spans)
           if n == "serve.step" and not _inside(s, prefills)]
    return 1e3 * sum(own) / len(own) if own else None


def prefill_ms_per_token(tr: Trace) -> float | None:
    prefills = _named(tr, "serve.prefill")
    tokens = sum(1 for s, _ in _named(tr, "serve.step") if _inside(s, prefills))
    return 1e3 * sum(e - s for s, e in prefills) / tokens if tokens else None


def page_in_ms(tr: Trace) -> float | None:
    page_ins = _named(tr, "serve.page_in")
    return 1e3 * sum(e - s for s, e in page_ins) / len(page_ins) if page_ins else None


def program_idle_share(tr: Trace) -> float | None:
    if not any(n.startswith(PROGRAM) for n, _, _ in tr.spans):
        return None
    idle = sum(t for span, t in tr.gaps if span.startswith(PROGRAM))
    return 100.0 * idle / tr.window_s
