"""A copy of the benchmark at a size a CPU test run holds: the same files,
the configuration cut to two layers of width 64 and the traffic to a handful
of requests.  Limits are the committed ones."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = dict(family="dense", positional="learned", norm="layernorm", activation="gelu",
            use_bias=True, tie_embeddings=True, max_seq_len=64, vocab_size=256,
            num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
            lora=dict(rank=4, alpha=8.0, dropout=0.1, targets=["q", "v"]))


def _edit(path, **changes):
    with open(path) as f:
        obj = json.load(f)
    obj.update(changes)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp: str, *, serve_model=None) -> str:
    """``tmp`` holding BENCHMARK.json and ``bench/`` at the tiny size."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    cfg = os.path.join(tmp, "bench", "configs")
    _edit(os.path.join(cfg, "gpt2-small-serve.json"), batch=4, slots=4, cache_len=64,
          models={"served": dict(TINY, name="tiny-served", **(serve_model or {}))})
    traffic = os.path.join(tmp, "bench", "traffic")
    _edit(os.path.join(traffic, "slora_closed8.json"), tenants=8, min_prompt=2, max_prompt=40,
          min_output=2, max_output=16, length_set=4, check_requests=4, check_block=2)
    return tmp


def readings(root: str, cell: str, seed: int, prec=None) -> dict:
    """Set a cell up, run a short window and read its check's numbers
    against the committed limits; with ``prec``, the control: the
    reference at that lower precision in the program's place."""
    from bench import run as bench_run

    files = bench_run.cell_files(root, cell)
    driver = bench_run.load_module(
        os.path.join(root, "bench", "traffic", files["mix"]["driver"] + ".py"))
    run = driver.Cell(files["config"], files["mix"], seed, bench_run.Recorder(), 0.5)
    run.window(0.5)
    run.free()
    got = run.readings(prec)
    return {k: (got[k], lim) for k, lim in files["limits"].items()}
