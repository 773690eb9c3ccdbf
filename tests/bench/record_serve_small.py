"""Record ``data/serve_small.xplane.pb``: the serving cell at the size of
``bench_tiny.py``, two batches under the profiler inside ``bench.window``,
with the harness's ``bench.*`` spans around the program's ``serve.*``
spans.  It needs the chip: the trace's device plane is the TPU's.  The
Python tracer and the executables' HLO protos are off, which keeps the
file small; the harness's traces hold both.

    python3 tests/bench/record_serve_small.py tests/bench/data/serve_small.xplane.pb
"""

import os
import shutil
import sys
import tempfile

import bench_tiny

CELL = "serve.gpt2s.slora"
SEED = 3_000_000_021
# (prompt length, output tokens) of each traced batch
BATCHES = [(3, 2), (2, 2)]
HOST_TRACER_LEVEL = 2


def record(out: str, seed: int = SEED) -> None:
    import jax

    from bench import run as bench_run
    from bench.trace import find_xplane

    bench_run.setup_paths(bench_tiny.ROOT)
    root = bench_tiny.make_root(tempfile.mkdtemp(prefix="serve_small_"))
    files = bench_run.cell_files(root, CELL)
    driver = bench_run.load_module(
        os.path.join(root, "bench", "traffic", files["mix"]["driver"] + ".py"))
    rec = bench_run.Recorder()
    cell = driver.Cell(files["config"], files["mix"], seed, rec, 0.0)
    trace_dir = tempfile.mkdtemp(prefix="serve_small_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    options.host_tracer_level = HOST_TRACER_LEVEL
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with rec.span("bench.window"):
            for length, output in BATCHES:
                cell._batch(*cell.requests.next(length, output))
    finally:
        jax.profiler.stop_trace()
    shutil.copy(find_xplane(trace_dir), out)
    print(f"{out}: {os.path.getsize(out)} bytes on {jax.devices()[0].device_kind}, "
          f"adapter cache {cell.session.adapters.stats.as_dict()}")


if __name__ == "__main__":
    record(sys.argv[1])
