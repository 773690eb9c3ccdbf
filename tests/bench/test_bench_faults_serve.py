"""A serving run at the tiny size, driven through the harness without the
chip: sound, it is correct; with a token altered where it is produced, or a
decode step that leaves its cache as it was, or with a control (the
reference one precision step down) in the program's place, it is not."""

import os

import pytest

import bench_tiny
from bench import faults
from bench import run as bench_run

SEED = 3_000_000_013
CELL = "serve.gpt2s.slora"


def run(root):
    return bench_run.run_cell(CELL, SEED, 0.5, False, root=root)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("tiny_serve")))


def test_sound_run_is_correct(root):
    out = run(root)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_serving_is_not_correct(root, fault):
    with faults.planted(fault):
        out = run(root)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("control", ["high", "bfloat16"])
def test_lower_precision_control_is_not_correct(tmp_path, control):
    # the configuration states float32 at the highest precision; one step
    # down is three bfloat16 passes (the contract's control), and bfloat16
    # storage below it.  The control's error grows with the context and the
    # width: GPT-2 small's widths and vocabulary, 2 of its layers, prompts of
    # 100-140 tokens
    root = bench_tiny.make_root(str(tmp_path), serve_model=dict(
        d_model=768, num_heads=12, num_kv_heads=12, d_ff=3072, vocab_size=50257,
        num_layers=2, max_seq_len=512))
    bench_tiny._edit(os.path.join(root, "bench", "configs", "gpt2-small-serve.json"),
                     cache_len=512)
    bench_tiny._edit(os.path.join(root, "bench", "traffic", "slora_closed8.json"),
                     min_prompt=100, max_prompt=140, min_output=8, max_output=8)
    checks = bench_tiny.readings(root, CELL, SEED, prec=control)
    assert any(v > lim for v, lim in checks.values()), checks
