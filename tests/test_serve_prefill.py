"""Chunked prefill into the decode cache against the teacher-forced loop.

``ServeSession.prefill`` runs a ``(B, L)`` prompt as ``ceil(L / T)`` calls
of one chunk executable (T from the batch: 32 at batch 256).  For a small
GPT-2-like block (multi-head attention over a K/V cache) and a small
Moonlight block (latent attention over a latent cache, sigmoid-routed
held and shared experts), in the single-adapter and the stacked
multi-tenant mode, and at prompt lengths 1, T-1, T, T+1 and 2T+3, the
chunked prefill and a few decode steps after it must match the prompt fed
one token a step through the decode step:

* logits after the prompt and after every decode step within
  ``LOGIT_RTOL`` of their scale (``tests/test_serve.py``'s tolerance: the
  two paths are different executables, whose float32 sums run in another
  order), and the same greedy tokens;
* the cache's K/V or latent rows within the same tolerance on the real
  slots, their ``pos`` equal there and -1 on the last chunk's pad slots;
* the same experts on every real slot, no pair dropped, and the same
  count of routed pairs.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LoRAConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT
from repro.configs.moonlight_16b_a3b import SMOKE_CONFIG as MOONLIGHT_SMOKE
from repro.lora import lora_template, split_lora
from repro.models import init as model_init
from repro.models.model import chunked_prefill_ok
from repro.serve import AdapterCache, ServeConfig, ServeSession
from repro.serve.session import chunk_len

LOGIT_RTOL = 1e-4  # as tests/test_serve.py
BATCH, GEN, TENANTS = 256, 4, 4
T = chunk_len(BATCH)
LENGTHS = [1, T - 1, T, T + 1, 2 * T + 3]
CACHE = 3 * T + GEN  # holds 2T+3 padded to 3T, and the decode steps after it

MHA = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128, vocab_size=256,
    max_seq_len=CACHE,
    lora=LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "o", "head")),
)
MLA = MOONLIGHT_SMOKE.with_overrides(
    moe=dataclasses.replace(MOONLIGHT_SMOKE.moe, experts_held=(4, 2)))  # experts 2-5 of 8


def _params(cfg, seed=0):
    """Seeded weights with nonzero adapters and, for sigmoid routing, a
    random score bias (a fresh init has B = 0 and bias 0)."""
    params = model_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)

    def perturb(path, x):
        nonlocal key
        key, sub = jax.random.split(key)
        name = jax.tree_util.keystr(path)
        if "score_bias" in name:
            return 0.3 * jax.random.normal(sub, x.shape)
        if name.endswith("['B']"):
            return 0.05 * jax.random.normal(sub, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(perturb, params)


class Tenants:
    """Each tenant's adapter: the params' adapter, re-drawn per id."""

    def __init__(self, params):
        lora, _ = split_lora(params)
        self.num_adapters = TENANTS
        self.rows = [jax.tree.map(
            lambda x, c=c: x if x is None else x + 0.05 * jax.random.normal(
                jax.random.PRNGKey(100 + c), x.shape), lora, is_leaf=lambda x: x is None)
            for c in range(TENANTS)]

    def lora_row(self, cid):
        return self.rows[cid]


@pytest.fixture(scope="module", params=["mha", "mla"])
def model(request):
    cfg = {"mha": MHA, "mla": MLA}[request.param]
    return cfg, _params(cfg)


@pytest.fixture(scope="module", params=["single", "stacked"])
def session(request, model):
    cfg, params = model
    adapters = AdapterCache(Tenants(params), like=lora_template(params), slots=TENANTS)
    sess = ServeSession(ServeConfig(model=cfg, batch=BATCH, cache_len=CACHE), params,
                        adapters=adapters)
    if request.param == "stacked":
        sess.attach([b % TENANTS for b in range(BATCH)])
    return sess


def _snapshot(sess):
    """The decode cache on the host: ``{(path, last key): array}``."""
    leaves = jax.tree_util.tree_leaves_with_path(sess._cache)
    return {(jax.tree_util.keystr(p), str(getattr(p[-1], "key", getattr(p[-1], "name", "")))):
            np.asarray(x) for p, x in leaves}


def _routed(sess):
    s = sess.stats()
    return np.array(s.get("moe_routed_pairs", [])), s.get("moe_dropped_pairs", 0)


def _serve(sess, prompts, chunked):
    """(logits after the prompt, cache after it, greedy tokens and last
    logits of GEN decode steps, cache after them, routed pairs, dropped)."""
    sess.reset()  # the routing counters carry over: they count the session
    before, _ = _routed(sess)
    if chunked:
        first = np.asarray(sess.prefill(prompts))
    else:
        for t in range(prompts.shape[1]):
            first = np.asarray(sess.step(prompts[:, t]))
    after_prompt = _snapshot(sess)
    toks, last = sess.decode(GEN)
    routed, dropped = _routed(sess)
    return first, after_prompt, toks, np.asarray(last), _snapshot(sess), routed - before, dropped


def _close(got, ref):
    scale = float(np.abs(ref).max()) or 1.0
    np.testing.assert_allclose(got, ref, rtol=LOGIT_RTOL, atol=LOGIT_RTOL * scale)


@pytest.mark.parametrize("length", LENGTHS)
def test_chunked_prefill_matches_teacher_forced(session, length):
    sess = session
    prompts = np.random.default_rng(length).integers(
        0, sess.cfg.model.vocab_size, (BATCH, length)).astype(np.int32)
    chunks_before = sess.stats()["prefill_chunks"]
    ref = _serve(sess, prompts, chunked=False)
    got = _serve(sess, prompts, chunked=True)
    padded = -(-length // T) * T
    assert sess.stats()["prefill_chunks"] - chunks_before == padded // T
    assert sess.stats()["prefill_teacher_forced"] == 0

    _close(got[0], ref[0])
    np.testing.assert_array_equal(got[2], ref[2])  # the greedy tokens
    _close(got[3], ref[3])
    for after in (1, 4):  # the cache after the prompt, then after decoding
        filled = length + (GEN if after == 4 else 0)
        seen = set()
        for (path, name), ref_x in ref[after].items():
            x = got[after][path, name]
            seen.add(name)
            if name in ("k", "v", "latent"):  # (R, B, ..., C) rows
                _close(x[..., :filled], ref_x[..., :filled])
            elif name == "pos":  # (R, C)
                np.testing.assert_array_equal(
                    x[:, :filled], np.broadcast_to(np.arange(filled), (len(x), filled)))
                if after == 1:
                    assert (x[:, length:] == -1).all()  # the pad slots
            elif name == "experts":  # (R, B, k, C): routes
                np.testing.assert_array_equal(x[..., :filled], ref_x[..., :filled])
            elif name == "length":  # every stack's and the model's
                np.testing.assert_array_equal(x, filled)
        mla = sess.cfg.model.mla is not None
        assert seen >= {"pos", "length"} | ({"latent", "experts"} if mla else {"k", "v"})
    np.testing.assert_array_equal(got[5], ref[5])  # routed pairs per MoE layer
    assert got[6] == ref[6] == 0  # no pair dropped


def test_prefill_chunk_counters(session):
    sess = session
    before = {k: v for k, v in sess.stats().items() if k.startswith("prefill_")}
    sess.prefill(np.ones((BATCH, T + 3), np.int32))
    after = sess.stats()
    assert after["prefill_chunks"] - before["prefill_chunks"] == 2
    assert after["prefill_tokens"] - before["prefill_tokens"] == T + 3
    assert after["prefill_padded"] - before["prefill_padded"] == T - 3
    assert after["prefill_teacher_forced"] == before["prefill_teacher_forced"]


def test_one_chunk_executable_per_mode_across_prompt_lengths():
    params = _params(MHA)
    adapters = AdapterCache(Tenants(params), like=lora_template(params), slots=TENANTS)
    sess = ServeSession(ServeConfig(model=MHA, batch=BATCH, cache_len=3 * T), params,
                        adapters=adapters)
    for mode in ("single", "stacked"):
        if mode == "stacked":
            sess.attach([b % TENANTS for b in range(BATCH)])
        for length in range(1, 3 * T + 1):
            sess.prefill(np.full((BATCH, length), length % 7, np.int32))
        assert sess.executables()[f"{mode}_chunk"] == 1
        assert sess.executables()[mode] == 0  # no prompt token took a decode step
    assert sess.stats()["prefill_teacher_forced"] == 0
    assert set(sess.stats()["first_step_s"]) == {"single_chunk", "stacked_chunk"}


def test_a_prompt_that_does_not_fit_the_cache_is_teacher_forced():
    params = _params(MHA)
    sess = ServeSession(ServeConfig(model=MHA, batch=BATCH, cache_len=T + 8), params)
    sess.prefill(np.ones((BATCH, T + 1), np.int32))  # two chunks would need 2T slots
    s = sess.stats()
    assert s["prefill_teacher_forced"] == T + 1 and s["prefill_chunks"] == 0
    assert s["executables"]["single"] == 1 and s["executables"]["single_chunk"] == 0


@pytest.mark.parametrize("name,window,chunked", [
    ("gpt2-paper", None, True), ("moonlight-16b-a3b", None, True),
    ("granite-moe-1b-a400m", None, True), ("gpt2-paper", 128, False),
    ("mamba2-130m", None, False), ("jamba-1.5-large-398b", None, False),
    ("seamless-m4t-large-v2", None, False),
])
def test_which_models_prefill_in_chunks(name, window, chunked):
    assert chunked_prefill_ok(get_config(name), window) is chunked
