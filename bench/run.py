"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; everything else is found by name:

* ``bench/configs/<config>.json``  the model configuration, as it is run;
* ``bench/traffic/<traffic>.json`` the traffic mix: parameters, and the
  ``driver`` that reads them (``bench/traffic/<driver>.py``);
* ``bench/limits/<cell>.json``     the limits of the correctness check;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A run builds the system from the seed and warms every shape its traffic
uses (set-up, ``setup_s``), measures for ``--seconds`` (``--trace 1``: a
shorter window under the profiler, read into the per-layer metrics), then
frees the system and compares what the timed path produced with the plain
reference (``correct``).  The last line of standard output is one JSON
object; the numbers compared are also the last lines of standard error.
There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    path = os.path.abspath(path)
    stem = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    name = f"bench_file_{stem}_{abs(hash(path)):x}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_files(root: str, name: str) -> dict:
    """The cell's entry and files, found by the names in BENCHMARK.json."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    mix = load_json(root, "bench", "traffic", cell["traffic"] + ".json")

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": load_json(root, "bench", "configs", cell["config"] + ".json"),
        "mix": mix,
        "limits": load_json(root, "bench", "limits", name + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


_LOWERED = [0]


def _on_event(name, _secs, **_kw):
    if name == LOWERING_EVENT:
        _LOWERED[0] += 1


class Recorder:
    """Host spans (``jax.profiler.TraceAnnotation``) and the count of
    executables JAX lowers, which a warm window keeps at zero."""

    _listening = False

    def __init__(self):
        import jax

        self._jax = jax
        if not Recorder._listening:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            Recorder._listening = True

    @property
    def lowered(self) -> int:
        return _LOWERED[0]

    def span(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)


class Run:
    """What a per-layer metric reader sees."""

    def __init__(self, trace, counters, peaks, chips):
        self.trace, self.counters, self.peaks, self.chips = trace, counters, peaks, chips


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t0: float | None = None) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    files = cell_files(root, name)
    chips = files["cell"]["chips"]
    devices = jax.devices()[:chips]
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if devices[0].platform == "tpu" else {}
    driver = load_module(os.path.join(root, "bench", "traffic", files["mix"]["driver"] + ".py"))
    rec = Recorder()

    cell = driver.Cell(files["config"], files["mix"], seed, rec, seconds)
    setup_s = time.perf_counter() - t0
    print(f"[bench] set-up {setup_s:.3f}s ({rec.lowered} executables lowered)", flush=True)

    trace_dir = None
    window = seconds
    if trace:
        window = min(seconds, files["mix"]["trace_seconds"])
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    before = rec.lowered
    try:
        with rec.span("bench.window"):
            result = cell.window(window)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = rec.lowered - before
    print(f"[bench] window {result['window_s']:.3f}s: {in_window} executables "
          f"lowered inside it", flush=True)
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(memory)}
    metrics, breakdown = {}, None
    if trace:
        from bench.trace import find_xplane, reduce

        tr = reduce(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(tr, result["counters"], peaks, chips)
        for m in files["per_layer"]:
            value = load_module(os.path.join(root, "bench", "metrics", m["name"] + ".py")).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        for m in files["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    cell.free()
    gc.collect()
    checks = cell.check(files["limits"])
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    for key, (v, lim) in checks.items():
        print(f"[check] {key}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"[check] correct: {correct}", file=sys.stderr, flush=True)

    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    setup_paths()
    files = cell_files(ROOT, args.workload)
    # the compile cache lives at a fixed place inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"[bench] device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("[bench] no TPU: this benchmark has no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < files["cell"]["chips"]:
        print(f"[bench] the cell needs {files['cell']['chips']} chips, "
              f"{len(devices)} found", file=sys.stderr)
        return 2
    peaks_for(d0.device_kind)
    from repro.compile_cache import enable_compile_cache

    print(f"[bench] compile cache: {enable_compile_cache()}", flush=True)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0=t0)
    print(json.dumps(out), flush=True)
    return 0


def setup_paths(root: str = ROOT) -> None:
    """The program under test (``src``) and the benchmark's own package;
    the directory of this script leaves the path, so that its modules are
    imported as ``bench.<name>`` only."""
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    for path in (root, os.path.join(root, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)


if __name__ == "__main__":
    sys.exit(main())
