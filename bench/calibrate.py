"""Readings that the limits of a cell's check are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n,n,...> [--controls high,bfloat16] [--fault-seeds <n,...>] \
        [--faults <name,...>] [--program-controls high,default] [--out <file.jsonl>]

For every seed the cell is set up and run for a short window as the
benchmark runs it, and its check's numbers are read:

* ``--seeds``: the program as the configuration states it (the lower
  readings), and on the same runs each of ``--controls``: the reference at
  that lower precision in the program's place (the upper readings);
* ``--fault-seeds``: each fault of ``bench/faults.py`` (or those named by
  ``--faults``) planted in the program, and the program itself run at each
  matmul precision of ``--program-controls`` (JAX's
  ``default_matmul_precision``) in place of the one the configuration
  states.

One JSON line per reading goes to ``--out`` and to standard output.  The
process holds the chip; compiled programs are shared between the seeds.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    sys.path.insert(0, _p)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

import jax  # noqa: E402

from bench import faults  # noqa: E402
from bench import run as bench_run  # noqa: E402


def reading(name: str, seed: int, seconds: float, *, fault=None, controls=(),
            matmul=None) -> dict:
    files = bench_run.cell_files(ROOT, name)
    config, mix = files["config"], files["mix"]
    driver = bench_run.load_module(os.path.join(ROOT, "bench", "traffic", mix["driver"] + ".py"))
    t0 = time.perf_counter()
    with contextlib.ExitStack() as planted:
        if fault:
            planted.enter_context(faults.planted(fault))
        if matmul:
            planted.enter_context(jax.default_matmul_precision(matmul))
        cell = driver.Cell(config, mix, seed, bench_run.Recorder(), seconds)
        t1 = time.perf_counter()
        result = cell.window(seconds)
    cell.free()
    gc.collect()
    t2 = time.perf_counter()
    out = {"cell": name, "seed": seed, "fault": fault, "matmul": matmul,
           "checks": cell.readings(),
           "metrics": result["metrics"], "attempted": result["attempted"]}
    t3 = time.perf_counter()
    for prec in controls:
        out[prec] = cell.readings(prec)
    out["seconds"] = {"setup": t1 - t0, "window": t2 - t1, "check": t3 - t2,
                      "controls": time.perf_counter() - t3}
    del cell
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="high,bfloat16")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--program-controls", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    print(f"[calibrate] devices {jax.devices()}; cache {enable_compile_cache()}", flush=True)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    controls = tuple(x for x in args.controls.split(",") if x)
    plan = [dict(seed=s, controls=controls) for s in seeds(args.seeds)]
    fault_names = args.faults.split(",") if args.faults else faults.FAULTS
    plan += [dict(seed=s, fault=f) for f in fault_names for s in seeds(args.fault_seeds)]
    plan += [dict(seed=s, matmul=m) for m in args.program_controls.split(",") if m
             for s in seeds(args.fault_seeds)]
    sink = open(args.out, "a") if args.out else None
    for p in plan:
        line = json.dumps(reading(args.workload, seconds=args.seconds, **p))
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
