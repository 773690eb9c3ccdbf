"""Multi-tenant personalized-adapter serving (PR 10).

The contract under test: ONE donated jitted decode step + ONE shared
frozen backbone serve a mixed batch of tenants — each request applying its
own client's LoRA adapter via a slab gather — with the same greedy tokens
as running every request alone with its adapter merged the classic way,
and both decodes' logits within float32 rounding of a full-sequence
reference;
the AdapterCache pages adapters through LRU slots with exact hit/miss/
eviction accounting and re-pages evicted adapters to identical outputs;
and a federation checkpoint (``step_N.fleet/`` shards or monolithic npz)
is directly servable through ``export_adapters`` with no new format.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig, SSMConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT
from repro.configs.mamba2_130m import SMOKE_CONFIG as MAMBA_SMOKE
from repro.fed.store import DeviceFleetStore, HostFleetStore
from repro.lora import lora_template, map_lora, merge_lora, split_lora
from repro.models import init as model_init
from repro.serve import (
    AdapterCache,
    ServeConfig,
    ServeSession,
    export_adapters,
    serving_params,
)
from repro.serve.export import MonolithicSource, ShardDirSource

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "o", "head"))
DENSE = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=64, lora=LORA,
)
# attention-free family: adapters exist only on the LM head
SSM = MAMBA_SMOKE.with_overrides(
    d_model=64, vocab_size=256, max_seq_len=64,
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, chunk_size=16),
    lora=LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("head",)),
)

PROMPT, GEN = 4, 6


def _adapter_row(params, cid: int, scale=0.05):
    """A distinct nontrivial adapter per tenant: randomize A AND B (the
    fresh init has B = 0, which would make every adapter's delta vanish
    and the parity suite vacuous)."""
    lora, _ = split_lora(params)
    key = jax.random.fold_in(jax.random.PRNGKey(7), cid)
    counter = [0]

    def rnd(x):
        counter[0] += 1
        return scale * jax.random.normal(
            jax.random.fold_in(key, counter[0]), x.shape
        ).astype(x.dtype)

    return map_lora(rnd, lora)


class ListSource:
    def __init__(self, rows):
        self.rows = list(rows)
        self.num_adapters = len(rows)
        self.reads = 0

    def lora_row(self, cid: int):
        self.reads += 1
        return self.rows[int(cid)]


def _session(cfg, params, *, batch, rows=None, slots=None):
    adapters = None
    if rows is not None:
        adapters = AdapterCache(
            ListSource(rows), like=lora_template(params), slots=slots or len(rows)
        )
    scfg = ServeConfig(model=cfg, batch=batch, cache_len=PROMPT + GEN)
    return ServeSession(scfg, params, adapters=adapters)


def _decode(sess, prompts):
    sess.prefill(prompts)
    toks, logits = sess.decode(GEN)
    return toks, np.asarray(logits)


def _prompts(cfg, batch, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, PROMPT)).astype(np.int32)


# ---------------------------------------------------------------------------
# multi-tenant parity: mixed batch == per-request single adapter, both
# against a float32 full-sequence reference
# ---------------------------------------------------------------------------

# Why a tolerance and not bit equality: the stacked decode (slab gather at
# batch 8), the single-adapter decode (merged weights, batch 1 for the dense
# family) and the reference (one full-sequence forward, no decode cache) are
# three different executables.  XLA orders the float32 reductions of each
# differently (fusion, batch size, cached vs full attention; measured ~1 ulp
# apart even with zero adapters), so their logits agree to float32 rounding
# accumulated over the layers, not bit for bit.  LOGIT_RTOL bounds that at
# 1e-4 of the logits' own scale — 100x the measured gap, and far below the
# O(1) change a wrong tenant's adapter makes (test_distinct_tenants_*).
LOGIT_RTOL = 1e-4


def _reference_logits(cfg, params, tokens):
    """Float32 full-sequence forward at highest matmul precision:
    ``tokens (L,)`` -> next-token logits at every position ``(L, V)``."""
    from repro.models import forward

    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, {"tokens": jnp.asarray(tokens)[None]})
    return np.asarray(logits[0], np.float32)


def _assert_logits_close(got, ref):
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(got, ref, rtol=LOGIT_RTOL, atol=LOGIT_RTOL * scale)


# The dense baseline runs each request truly ALONE (batch 1); the SSM
# baseline runs at equal batch (its backbone alone is not bit-stable across
# batch sizes either, so both sides of the comparison keep the batch).
@pytest.mark.parametrize("cfg,solo", [(DENSE, True), (SSM, False)], ids=["dense", "ssm"])
def test_stacked_batch_bit_identical_to_single_adapter(cfg, solo):
    n = 8
    params = model_init(jax.random.PRNGKey(0), cfg)
    rows = [_adapter_row(params, c) for c in range(n)]
    prompts = _prompts(cfg, n)

    sess = _session(cfg, params, batch=n, rows=rows)
    ids = list(range(n))
    sess.attach(ids)
    toks, logits = _decode(sess, prompts)
    assert sess.stats()["executables"]["stacked"] == 1

    # a different tenant permutation reuses the SAME compiled step
    sess.attach(ids[::-1])
    _decode(sess, prompts)
    assert sess.stats()["executables"]["stacked"] == 1

    # baselines: tenant b's adapter merged the classic single-adapter way
    _, frozen = split_lora(params)
    for b in range(n):
        base = merge_lora(rows[b], frozen)
        if solo:
            s1 = _session(cfg, base, batch=1)
            t1, l1 = _decode(s1, prompts[b : b + 1])
            t1, l1 = t1[0], l1[0]
        else:
            s1 = _session(cfg, base, batch=n)
            t1, l1 = _decode(s1, prompts)
            t1, l1 = t1[b], l1[b]
        # the reference reads the prompt plus the greedy continuation: its
        # argmax must reproduce that continuation, and its last position is
        # the logits both decodes end on
        ref = _reference_logits(cfg, base, np.concatenate([prompts[b], toks[b]]))
        np.testing.assert_array_equal(toks[b], ref[PROMPT - 1 : -1].argmax(-1))
        np.testing.assert_array_equal(toks[b], t1)
        _assert_logits_close(logits[b], ref[-1])
        _assert_logits_close(l1, ref[-1])


def test_distinct_tenants_distinct_outputs():
    """The parity suite would pass trivially if adapters had no effect —
    check different tenants actually diverge on the same prompt."""
    params = model_init(jax.random.PRNGKey(0), DENSE)
    rows = [_adapter_row(params, c, scale=0.3) for c in range(2)]
    prompts = np.broadcast_to(_prompts(DENSE, 1), (2, PROMPT)).copy()
    sess = _session(DENSE, params, batch=2, rows=rows)
    sess.attach([0, 1])
    _, logits = _decode(sess, prompts)
    assert not np.array_equal(logits[0], logits[1])


# ---------------------------------------------------------------------------
# AdapterCache: LRU accounting, eviction re-page parity, capacity-1 thrash
# ---------------------------------------------------------------------------


def test_cache_hit_miss_eviction_counts():
    params = model_init(jax.random.PRNGKey(0), DENSE)
    rows = [_adapter_row(params, c) for c in range(3)]
    src = ListSource(rows)
    cache = AdapterCache(src, like=lora_template(params), slots=2)

    cache.lookup([0, 1])  # cold: two misses
    assert (cache.stats.hits, cache.stats.misses, cache.stats.evictions) == (0, 2, 0)
    assert src.reads == 2

    cache.lookup([0, 1])  # warm: zero host traffic
    assert (cache.stats.hits, cache.stats.misses, cache.stats.evictions) == (2, 2, 0)
    assert src.reads == 2

    # 0 hits (and becomes MRU); 2 misses and evicts the LRU tenant 1
    cache.lookup([0, 2])
    assert (cache.stats.hits, cache.stats.misses, cache.stats.evictions) == (3, 3, 1)
    assert src.reads == 3
    assert set(cache.resident()) == {0, 2}

    # duplicates within a batch share a slot and count once
    cache.lookup([2, 2])
    assert cache.stats.hits == 4

    with pytest.raises(ValueError, match="distinct adapters"):
        cache.lookup([0, 1, 2])  # 3 distinct tenants > 2 slots


def test_lookup_never_evicts_pinned_slot():
    """A batch that hits slot A then misses must not evict slot A even
    when A is the LRU entry."""
    params = model_init(jax.random.PRNGKey(0), DENSE)
    rows = [_adapter_row(params, c) for c in range(3)]
    cache = AdapterCache(ListSource(rows), like=lora_template(params), slots=2)
    cache.lookup([0, 1])
    # 0 is LRU *before* this batch touches it; batch = [0, 2]: the miss on 2
    # must evict 1, not the just-pinned 0
    slots = cache.lookup([0, 2])
    assert set(cache.resident()) == {0, 2}
    assert len({int(s) for s in slots}) == 2


def test_evicted_adapter_repages_bit_identical():
    params = model_init(jax.random.PRNGKey(0), DENSE)
    rows = [_adapter_row(params, c) for c in range(3)]
    prompts = _prompts(DENSE, 2)
    sess = _session(DENSE, params, batch=2, rows=rows, slots=2)

    sess.attach([1, 1])
    t_before, l_before = _decode(sess, prompts)

    sess.attach([0, 2])  # evicts tenant 1
    _decode(sess, prompts)
    assert 1 not in sess.adapters.resident()

    sess.attach([1, 1])  # re-page from the source
    t_after, l_after = _decode(sess, prompts)
    np.testing.assert_array_equal(t_before, t_after)
    np.testing.assert_array_equal(l_before, l_after)
    st = sess.adapters.stats
    assert st.evictions >= 2
    assert sess.stats()["executables"]["stacked"] == 1


def test_capacity_one_thrash():
    params = model_init(jax.random.PRNGKey(0), DENSE)
    rows = [_adapter_row(params, c) for c in range(2)]
    src = ListSource(rows)
    cache = AdapterCache(src, like=lora_template(params), slots=1)
    for cid in (0, 1, 0, 1):
        cache.lookup([cid])
    assert (cache.stats.hits, cache.stats.misses, cache.stats.evictions) == (0, 4, 3)
    assert src.reads == 4
    with pytest.raises(ValueError, match="distinct adapters"):
        cache.lookup([0, 1])


# ---------------------------------------------------------------------------
# export_adapters: fleet checkpoints are directly servable
# ---------------------------------------------------------------------------


def _fleet_store(params, n, kind="host"):
    lora0, frozen = split_lora(params)
    loras = [_adapter_row(params, c) for c in range(n)]
    opts = [jax.tree.map(lambda x: np.zeros_like(np.asarray(x)), lora0)] * n
    cls = HostFleetStore if kind == "host" else DeviceFleetStore
    kw = {"prefetch": False} if kind == "host" else {}
    return cls(loras, [frozen] * n, opts, shared=True, **kw), loras


def _assert_rows_equal(src, loras):
    for c, row in enumerate(loras):
        got = src.lora_row(c)
        for a, b in zip(jax.tree.leaves(row), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_store_lora_rows_contract():
    params = model_init(jax.random.PRNGKey(0), DENSE)
    for kind in ("host", "device"):
        store, loras = _fleet_store(params, 3, kind)
        stacked = store.lora_rows([2, 0])
        want = jax.tree.map(lambda a, b: np.stack([a, b]), loras[2], loras[0])
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_export_from_shard_dir(tmp_path):
    params = model_init(jax.random.PRNGKey(0), DENSE)
    store, loras = _fleet_store(params, 3)
    d = str(tmp_path / "step_00000002.fleet")
    store.save_shards(d)

    src = export_adapters(d)
    assert isinstance(src, ShardDirSource)
    assert src.num_adapters == 3
    _assert_rows_equal(src, loras)
    # the shared backbone round-trips into full serving params
    rebuilt = serving_params(src, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_export_from_monolithic_ckpt(tmp_path):
    from repro.checkpoint.ckpt import save_step

    params = model_init(jax.random.PRNGKey(0), DENSE)
    store, loras = _fleet_store(params, 3)
    d = str(tmp_path)
    save_step(d, 1, {"fleet": store.state_dict()})

    src = export_adapters(d)
    assert isinstance(src, MonolithicSource)
    assert src.num_adapters == 3
    _assert_rows_equal(src, loras)
    rebuilt = serving_params(src, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_export_prefers_shards_over_monolithic(tmp_path):
    from repro.checkpoint.ckpt import fleet_shard_dir, save_step

    params = model_init(jax.random.PRNGKey(0), DENSE)
    store, loras = _fleet_store(params, 3)
    d = str(tmp_path)
    store.save_shards(fleet_shard_dir(d, 2))
    save_step(d, 2, {"server": {"x": np.zeros(1)}}, fleet_sharded=True)
    src = export_adapters(d)
    assert isinstance(src, ShardDirSource)
    _assert_rows_equal(src, loras)


def test_fed_ckpt_directly_servable(tmp_path):
    """End to end: fed_train-style run with a host fleet store + ckpt_dir,
    then serve every client's personalized adapter straight from the
    checkpoint, parity-checked against the live store's rows."""
    from repro.core import ChannelConfig
    from repro.data import make_banking77_like
    from repro.fed import FedConfig, run_federated

    ds = make_banking77_like(vocab_size=DENSE.vocab_size, seq_len=12, total=300, seed=0)
    server = DENSE.with_overrides(name="srv", d_model=96, d_ff=192)
    # pretrain_steps > 0: one pretrained backbone SHARED by the family's
    # clients (the paper's W' + per-client LoRA setting) — that shared tree
    # is what multi-tenant serving stacks the adapters against
    fed = FedConfig(
        method="adald", engine="batched", num_clients=4, clients_per_round=4,
        rounds=1, public_size=32, public_batch=16, eval_size=32,
        local_steps=1, distill_steps=1, server_distill_steps=1,
        pretrain_steps=1, seed=0, fleet_store="host",
        channel=ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0),
    )
    d = str(tmp_path)
    run_federated(DENSE, server, ds, fed, ckpt_dir=d)

    src = export_adapters(d)
    assert isinstance(src, ShardDirSource)
    assert src.num_adapters == 4

    params = serving_params(src, model_init(jax.random.PRNGKey(0), DENSE))
    cache = AdapterCache(src, like=lora_template(params), slots=2)
    scfg = ServeConfig(model=DENSE, batch=2, cache_len=PROMPT + GEN)
    sess = ServeSession(scfg, params, adapters=cache)
    prompts = _prompts(DENSE, 2)
    sess.attach([0, 1])
    _, logits = _decode(sess, prompts)
    assert np.isfinite(logits).all()
    sess.attach([2, 3])  # pages the cold half of the fleet through eviction
    _, logits = _decode(sess, prompts)
    assert np.isfinite(logits).all()
    assert cache.stats.misses == 4 and cache.stats.evictions == 2
    # trained adapters are nontrivial: B left zero would make tenants equal
    row = src.lora_row(0)
    assert any(
        float(np.abs(np.asarray(x)).max()) > 0 for x in jax.tree.leaves(row)
    )


# ---------------------------------------------------------------------------
# api_redesign shims
# ---------------------------------------------------------------------------


def test_launch_steps_shims():
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.serve.steps import (
        make_decode_step,
        make_prefill_step as serve_prefill,
    )

    assert make_serve_step is make_decode_step
    assert make_prefill_step is serve_prefill


def test_serve_config_frozen_and_hashable():
    scfg = ServeConfig(model=DENSE, batch=2)
    hash(scfg)
    with pytest.raises(dataclasses.FrozenInstanceError):
        scfg.batch = 4
