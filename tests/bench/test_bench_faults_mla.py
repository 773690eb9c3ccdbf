"""The Moonlight serving cell at a tiny size, driven through the harness
without the chip: sound, it is correct; with any part of the block's
mathematics broken in the program (softmax scoring in place of sigmoid,
the score bias left out of selection, the shared experts or the latent's
RMSNorm skipped, a decode step that does not write its latent row), or
with a control (the reference one precision step down) in the program's
place, it is not.  Which routing choices the reference accepts from the
program is decided by the reference's own scores."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import run as bench_run

SEED = 3_000_000_019
CELL = "serve.moonlight.slora128"
CONFIG = "moonlight-16b-a3b-ep8.json"

# the block at a size a CPU test run holds: every mechanism, small widths
TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, router_experts=8,
            n_routed_experts=4, first_held_expert=2, num_experts_per_tok=3, vocab_size=256,
            max_position_embeddings=64, batch=4, slots=4, cache_len=64,
            lora=dict(rank=4, alpha=8.0, targets=["q", "v"]))


def make_root(tmp: str, **config) -> str:
    """``tmp`` holding BENCHMARK.json and ``bench/`` with the Moonlight cell
    at the tiny size."""
    shutil.copytree(os.path.join(bench_tiny.ROOT, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp)
    bench_tiny._edit(os.path.join(tmp, "bench", "configs", CONFIG), **dict(TINY, **config))
    bench_tiny._edit(os.path.join(tmp, "bench", "traffic", "slora_closed128.json"),
                     tenants=8, min_prompt=2, max_prompt=40, min_output=2, max_output=16,
                     length_set=4, check_requests=4, check_block=2)
    return tmp


def run(root):
    return bench_run.run_cell(CELL, SEED, 0.5, False, root=root)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("tiny_mla")))


def test_sound_run_is_correct(root):
    out = run(root)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    assert out["checks"]["dropped_pairs"]["value"] == 0
    assert out["checks"]["route_mismatches"]["value"] == 0


def _softmax_scoring(mp):
    from repro.models import moe

    orig = moe.route
    mp.setattr(moe, "route", lambda p, t, cfg, cd: orig(
        p, t, dataclasses.replace(cfg, scoring="softmax"), cd))


def _bias_left_out(mp):
    from repro.models import moe

    orig = moe.route
    mp.setattr(moe, "route", lambda p, t, cfg, cd: orig(
        dict(p, score_bias=jnp.zeros_like(p["score_bias"])), t, cfg, cd))


def _shared_skipped(mp):
    from repro.models import moe

    mp.setattr(moe, "mlp_apply", lambda params, x, **kw: jnp.zeros_like(x))


def _latent_norm_skipped(mp):
    from repro.models import attention

    mp.setattr(attention, "norm_apply", lambda params, x, **kw: x)


def _latent_row_unwritten(mp):
    from repro.models import transformer

    orig = transformer.write_kv_rows
    mp.setattr(transformer, "write_kv_rows",
               lambda cache, rows: orig(cache, rows)._replace(latent=cache.latent))


FAULTS = {f.__name__.lstrip("_"): f for f in (
    _softmax_scoring, _bias_left_out, _shared_skipped, _latent_norm_skipped,
    _latent_row_unwritten)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_block_is_not_correct(root, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(root)
    print(fault, out["checks"])
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("control", ["high", "bfloat16"])
def test_lower_precision_control_is_not_correct(root, control):
    checks = readings(root, prec=control)
    print(control, checks)
    assert any(v > lim for v, lim in checks.values()), checks


def readings(root, prec=None):
    """The check's numbers of a short run against the committed limits;
    with ``prec`` the control in the program's place."""
    files = bench_run.cell_files(root, CELL)
    driver = bench_run.load_module(
        os.path.join(root, "bench", "traffic", files["mix"]["driver"] + ".py"))
    cell = driver.Cell(files["config"], files["mix"], SEED, bench_run.Recorder(), 0.5)
    cell.window(0.5)
    cell.free()
    got = cell.readings(prec)
    return {k: (got[k], lim) for k, lim in files["limits"].items() if k not in driver.NOT_CHECKS}


def test_routes_are_accepted_by_the_reference_margins_alone():
    # one position's route in the last MoE layer swaps the reference's k-th
    # expert for its (k+1)-th: within a margin wider than their gap the
    # reference follows it (and that position's logits move), within a
    # narrower one it is a mismatch and the reference keeps its own; the
    # program's numbers play no part
    from bench import reference_mla, router_gap, weights_mla

    cfg = dict(json.load(open(os.path.join(bench_tiny.ROOT, "bench", "configs", CONFIG))),
               **TINY)
    frozen, tenants = weights_mla.serving(weights_mla.seed_key(SEED, 0), weights_mla.sizes(cfg),
                                          1)
    spec = reference_mla.spec_of(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, cfg["vocab_size"])
    pos = jnp.arange(24)[None]
    logits, own, _, _ = reference_mla.served_logits(frozen, tenants, toks, pos, spec=spec)
    last = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] - 1
    scores = router_gap.reference_scores(frozen, tenants, toks, spec)[last]  # (T, E)
    ranked = np.sort(scores, -1)[:, ::-1]
    t = int(np.argmin(ranked[:, 2] - ranked[:, 3]))  # the narrowest k-th / (k+1)-th gap
    gap = ranked[t, 2] - ranked[t, 3]
    swapped = np.array(own)
    order = np.argsort(-scores[t])
    swapped[0, last, t] = order[[0, 1, 3]]
    for tie, mismatches, followed in ((2 * gap, 0, 1), (gap / 2, 1, 0)):
        got, _, mis, fol = reference_mla.served_logits(frozen, tenants, toks, pos,
                                                       jnp.asarray(swapped), tie, spec=spec)
        assert (int(mis.sum()), int(fol.sum())) == (mismatches, followed), tie
        moved = np.flatnonzero(np.abs(np.asarray(got - logits)[0]).max(-1) > 0)
        assert moved.tolist() == ([t] if followed else []), (tie, moved)


def test_tie_margin_is_ten_times_the_router_gap(root):
    # the committed near-tie margin against the widest difference between
    # the program's and the reference's biased router scores at this size
    from bench import router_gap

    got = router_gap.measure(root, CELL, SEED, rows=2)
    print(got)
    assert got["positions"] > 0
    files = bench_run.cell_files(root, CELL)
    assert 10 * got["widest"] <= files["limits"]["tie_margin"], got
