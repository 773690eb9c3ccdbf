"""The benchmark harness on the CPU: no chip, no result; traffic made from
the seed alone; cells, configurations and metrics found by name."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402

SERVE = bench_run.load_module(os.path.join(ROOT, "bench", "traffic", "serve_closed.py"))
SEEDS = (3_000_000_001, 3_000_000_002)


def mix(name):
    return bench_run.load_json(ROOT, "bench", "traffic", name + ".json")


def test_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for workload in [w["name"] for w in bench_run.load_json(ROOT, "BENCHMARK.json")["workloads"]]:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", workload,
             "--seed", str(SEEDS[0]), "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        assert "platform=cpu" in proc.stdout


def test_serving_requests_follow_the_seed():
    m = mix("slora_closed8")
    cycle = m["length_set"]

    def batches(seed):
        r = SERVE.Requests(m, 8, 50257, seed)
        return [r.next() for _ in range(2 * cycle)]

    a, b, c = batches(SEEDS[0]), batches(SEEDS[0]), batches(SEEDS[1])
    assert all(all(np.array_equal(u, v) for u, v in zip(x, y)) for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    assert not any(np.array_equal(x[1], y[1]) for x, y in zip(a, c))
    assert not all(np.array_equal(x[2], y[2]) for x, y in zip(a, c))
    # every batch asks for the same output lengths, the 8 quantiles of the
    # uniform lengths, dealt out to its requests in an order of the seed's
    outputs = sorted(a[0][2].tolist())
    assert all(sorted(x[2].tolist()) == outputs for x in a + c)
    assert outputs == SERVE.quantiles(m["min_output"], m["max_output"], 8)
    assert m["min_output"] <= outputs[0] and outputs[-1] <= m["max_output"]
    lengths = lambda bs: [x[1].shape[1] for x in bs]  # noqa: E731
    # every seed serves the same prompt lengths, cycle after cycle
    assert lengths(a) == lengths(c) == lengths(a)[:cycle] * 2
    assert all(m["min_prompt"] <= n <= m["max_prompt"] for n in lengths(a))
    assert len(set(lengths(a))) == cycle
    # the cycle starts with the longest prompt, and its first 2, 4, 8, ...
    # batches take one length from each half, quarter, eighth, ... of the
    # sorted set
    assert lengths(a)[0] == max(lengths(a))
    ranks = np.argsort(np.argsort(lengths(a)[:cycle]))
    k = 2
    while k <= cycle:
        assert sorted(ranks[:k] // (cycle // k)) == list(range(k))
        k *= 2


def test_peaks_are_keyed_by_device_kind():
    assert bench_run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench_run.peaks_for("TPU v99 imaginary")


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in ["BENCHMARK.json"] + [os.path.join(d, f) for d, _, fs in
                                             os.walk(os.path.join(root, "bench"))
                                             for f in fs]}
    # new files only, and new entries in BENCHMARK.json
    cfg = bench_run.load_json(root, "bench", "configs", "gpt2-small-serve.json")
    cfg["batch"] = 8
    with open(os.path.join(root, "bench", "configs", "gpt2-small-serve-b8.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "limits", "serve.b8.slora.json"), "w") as f:
        json.dump({"logit_gap": 1.0}, f)
    with open(os.path.join(root, "bench", "metrics", "serve.b8_batches.py"), "w") as f:
        f.write("def read(run):\n    return float(run.counters['batches'])\n")
    bench = bench_run.load_json(root, "BENCHMARK.json")
    bench["configs"].append(dict(bench["configs"][0], name="gpt2-small-serve-b8",
                                 file="bench/configs/gpt2-small-serve-b8.json"))
    bench["workloads"].append({"name": "serve.b8.slora", "config": "gpt2-small-serve-b8",
                               "traffic": "slora_closed8", "chips": 1, "why": "batch 8"})
    bench["per_layer"].append({"name": "serve.b8_batches", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "session host loop",
                               "moves": "serve_tokens_per_s", "workloads": ["serve.b8.slora"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "serve.gpt2s.slora" in m["workloads"]:
            m["workloads"].append("serve.b8.slora")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    files = bench_run.cell_files(root, "serve.b8.slora")
    assert files["config"]["batch"] == 8
    assert files["mix"]["driver"] == "serve_closed"
    assert files["limits"] == {"logit_gap": 1.0}
    assert {m["name"] for m in files["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    assert [m["name"] for m in files["per_layer"]] == ["serve.b8_batches"]
    reader = bench_run.load_module(os.path.join(root, "bench", "metrics", "serve.b8_batches.py"))
    assert reader.read(bench_run.Run(None, {"batches": 7}, {}, 1)) == 7.0
    driver = bench_run.load_module(os.path.join(root, "bench", "traffic", "serve_closed.py"))
    assert hasattr(driver, "Cell")
    # the files that were there are untouched, apart from BENCHMARK.json's new entries
    for path, data in before.items():
        if path != "BENCHMARK.json":
            assert open(os.path.join(root, path), "rb").read() == data
    # the metrics the original cells report are still theirs alone
    assert "serve.b8_batches" not in {
        m["name"] for m in bench_run.cell_files(root, "serve.gpt2s.slora")["per_layer"]}
