"""FleetStore (PR 9): out-of-core fleet state + streaming cohorts.

The contract under test: the ``"host"`` store runs every fast engine
BIT-IDENTICALLY to the default ``"device"`` store (same per-client
adaptive k, ledger bytes, accuracies) while keeping only the current
cohort on device; prefetch overlap never changes results (dirty-row
patching); checkpoints written under either store restore under the
other (the fleet rides per-client-range npz shards for the host store);
and duplicate cohort selections are rejected at the engine boundary
instead of resolving ``.at[sel].set`` writes in unspecified order.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
from repro.core import ChannelConfig
from repro.core.channel import BatchedChannelState, ChannelState
from repro.data import make_banking77_like
from repro.fed import FedConfig, run_federated
from repro.fed.client import Client
from repro.fed.engines import BatchedEngine, FusedE2EEngine, make_engine
from repro.fed.server import Server
from repro.fed.store import DeviceFleetStore, HostFleetStore, make_fleet_store

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
CLIENT = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
# Two dense families (different widths) for the bucketed hetero engines.
FAM_A = CLIENT.with_overrides(name="fam-a")
FAM_B = CLIENT.with_overrides(name="fam-b", d_model=96, d_ff=192)
# Constrained uplink so the adaptive k actually varies per client/round.
CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0)


def _dataset():
    return make_banking77_like(vocab_size=CLIENT.vocab_size, seq_len=12, total=500, seed=0)


def _cfg(engine, rounds=2, **kw):
    kw.setdefault("pretrain_steps", 0)
    return FedConfig(
        method="adald", engine=engine, num_clients=4, clients_per_round=2,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        local_steps=2, distill_steps=1, server_distill_steps=2,
        seed=0, channel=CHAN, **kw,
    )


def _assert_identical(a, b):
    assert a.server_acc == b.server_acc
    assert a.client_acc == b.client_acc
    assert a.per_client_k == b.per_client_k
    for ra, rb in zip(a.ledger.rounds, b.ledger.rounds):
        assert ra.uplink_bytes == rb.uplink_bytes
        assert ra.downlink_bytes == rb.downlink_bytes
        assert ra.num_transmitters == rb.num_transmitters


# ---------------------------------------------------------------------------
# raw-store unit tests (toy pytrees; no model in the loop)
# ---------------------------------------------------------------------------


def _toy(n, seed=0):
    """n deterministic per-client (lora, opt) rows + one shared frozen."""
    rng = np.random.default_rng(seed)
    row = lambda: {  # noqa: E731
        "w": rng.normal(size=(3, 2)).astype(np.float32),
        "b": {"v": rng.normal(size=(4,)).astype(np.float32)},
    }
    loras = [row() for _ in range(n)]
    opts = [row() for _ in range(n)]
    frozen = row()
    return loras, [frozen] * n, opts


def _mk_host(n=6, **kw):
    loras, frozens, opts = _toy(n)
    return HostFleetStore(loras, frozens, opts, shared=True, **kw)


def _assert_cohort_equal(a, b):
    """Compare two fetch results' lora+opt trees exactly."""
    for xa, xb in zip(jax.tree.leaves((a[1], a[3])), jax.tree.leaves((b[1], b[3]))):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_host_fetch_commit_roundtrip():
    loras, _, opts = _toy(6)
    st = _mk_host(prefetch=False)
    idx, lora, frozen, opt = st.fetch([1, 3])
    np.testing.assert_array_equal(np.asarray(idx), [1, 3])
    np.testing.assert_array_equal(np.asarray(lora["w"][0]), loras[1]["w"])
    np.testing.assert_array_equal(np.asarray(opt["b"]["v"][1]), opts[3]["b"]["v"])
    bump = lambda t: jax.tree.map(lambda x: x * 2.0 + 1.0, t)  # noqa: E731
    st.commit(idx, bump(lora), bump(opt))
    _, lora2, _, opt2 = st.fetch([1, 3])
    np.testing.assert_array_equal(np.asarray(lora2["w"]), np.asarray(bump(lora)["w"]))
    np.testing.assert_array_equal(
        np.asarray(opt2["b"]["v"]), np.asarray(bump(opt)["b"]["v"])
    )
    # untouched rows unaffected
    _, lora0, _, _ = st.fetch([0])
    np.testing.assert_array_equal(np.asarray(lora0["w"][0]), loras[0]["w"])


def test_host_prefetch_overlap_bit_identity():
    """A prefetched fetch returns exactly what an unprefetched one would,
    even when the prefetched cohort overlaps rows committed AFTER the
    prefetch snapshot (the dirty-row patch)."""
    a, b = _mk_host(prefetch=True), _mk_host(prefetch=False)
    sel0, sel1 = [0, 1], [1, 2]  # round r, round r+1 — overlap on client 1
    fa, fb = a.fetch(sel0), b.fetch(sel0)
    a.prefetch(sel1)  # staged BEFORE round r's rows are committed
    bump = lambda t: jax.tree.map(lambda x: x * 2.0 + 1.0, t)  # noqa: E731
    a.commit(fa[0], bump(fa[1]), bump(fa[3]))
    b.commit(fb[0], bump(fb[1]), bump(fb[3]))
    _assert_cohort_equal(a.fetch(sel1), b.fetch(sel1))


def test_host_prefetch_double_buffer_driver_order():
    """The round driver hints round r+1 BEFORE it fetches round r's
    already-staged cohort (rounds.py draws the next cohort first).  The
    store must hold BOTH staged entries — the later hint must not evict
    the current round's — and stay bit-identical to no prefetch."""
    a, b = _mk_host(prefetch=True), _mk_host(prefetch=False)
    bump = lambda t: jax.tree.map(lambda x: x * 2.0 + 1.0, t)  # noqa: E731
    sels = [[0, 1], [1, 2], [2, 3], [0, 3]]  # consecutive overlaps
    a.prefetch(sels[0])
    for r, sel in enumerate(sels):
        if r + 1 < len(sels):
            a.prefetch(sels[r + 1])  # the driver's order: hint, THEN fetch
        assert tuple(sel) in a._pf  # this round's entry survived the hint
        fa, fb = a.fetch(sel), b.fetch(sel)
        assert tuple(sel) not in a._pf  # consumed, not re-staged
        _assert_cohort_equal(fa, fb)
        a.commit(fa[0], bump(fa[1]), bump(fa[3]))
        b.commit(fb[0], bump(fb[1]), bump(fb[3]))


def test_host_prefetch_hint_miss_falls_back():
    """A prefetch hint for a DIFFERENT cohort (even a reordering) is
    discarded; the fetch still returns the right rows."""
    a, b = _mk_host(prefetch=True), _mk_host(prefetch=False)
    a.prefetch([2, 3])
    _assert_cohort_equal(a.fetch([3, 2]), b.fetch([3, 2]))


def test_host_commit_duplicate_rejected():
    st = _mk_host(prefetch=False)
    idx, lora, _, opt = st.fetch([1, 1])  # reads may repeat; writes may not
    with pytest.raises(ValueError, match="duplicate"):
        st.commit(idx, lora, opt)


def test_host_store_has_no_stacked_device_tree():
    st = _mk_host()
    with pytest.raises(RuntimeError, match="scan"):
        st.lora  # noqa: B018
    with pytest.raises(RuntimeError, match="scan"):
        st.opt  # noqa: B018


def test_shard_roundtrip_cross_store(tmp_path):
    """Sharded fleet persistence is store-agnostic: shards written by the
    device store restore into the host store bit-identically, and back."""
    loras, frozens, opts = _toy(5)
    blank = lambda rows: [jax.tree.map(np.zeros_like, r) for r in rows]  # noqa: E731
    dev = DeviceFleetStore(loras, frozens, opts, shared=True)
    dev.shard_size = 2  # 3 shard files for 5 clients
    d1 = str(tmp_path / "dev")
    dev.save_shards(d1)
    assert sorted(os.listdir(d1)) == [
        "fleet_00000000_00000002.npz", "fleet_00000002_00000004.npz",
        "fleet_00000004_00000005.npz", "fleet_frozen.npz",
    ]
    host = HostFleetStore(
        blank(loras), blank(frozens), blank(opts), shared=True, prefetch=False
    )
    host.load_shards(d1)
    for k in ("lora", "opt", "frozen"):
        for xa, xb in zip(jax.tree.leaves(dev.state_dict()[k]),
                          jax.tree.leaves(host.state_dict()[k])):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # and back: host shards (different shard_size) -> fresh device store
    host.shard_size = 3
    d2 = str(tmp_path / "host")
    host.save_shards(d2)
    dev2 = DeviceFleetStore(blank(loras), blank(frozens), blank(opts), shared=True)
    dev2.load_shards(d2)
    for k in ("lora", "opt", "frozen"):
        for xa, xb in zip(jax.tree.leaves(dev.state_dict()[k]),
                          jax.tree.leaves(dev2.state_dict()[k])):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_incomplete_shards_rejected(tmp_path):
    loras, frozens, opts = _toy(5)
    st = _mk_host(5, prefetch=False)
    st.shard_size = 2
    d = str(tmp_path)
    st.save_shards(d)
    os.remove(os.path.join(d, "fleet_00000002_00000004.npz"))
    with pytest.raises(ValueError, match="cover"):
        _mk_host(5, prefetch=False).load_shards(d)


def test_spill_dir_pages_fleet_to_disk(tmp_path):
    """spill_dir mode: host stacks live as npz shards (host_bytes == 0);
    commits across more shards than the cache cap force write-back
    eviction, and every row still round-trips exactly."""
    ref = _mk_host(10, prefetch=False)
    sp = _mk_host(10, prefetch=False, spill_dir=str(tmp_path), shard_size=1)
    assert sp.host_bytes() == 0
    assert any(f.startswith("spill_") for f in os.listdir(str(tmp_path)))
    bump = lambda t: jax.tree.map(lambda x: x + 1.0, t)  # noqa: E731
    for cid in range(10):  # 10 shards > cache cap of 4
        for st in (ref, sp):
            idx, lora, _, opt = st.fetch([cid])
            st.commit(idx, bump(lora), bump(opt))
    for cid in range(10):
        _assert_cohort_equal(sp.fetch([cid]), ref.fetch([cid]))


def test_from_template_lazy_rows():
    """from_template: every row reads the template until its first commit;
    committed rows persist; device residency is independent of N."""
    loras, frozens, opts = _toy(1, seed=7)
    mk = lambda n: HostFleetStore.from_template(  # noqa: E731
        loras[0], frozens[0], opts[0], num_clients=n, prefetch=False
    )
    st = mk(8)
    _, lora, _, opt = st.fetch([2, 5])
    for j in range(2):
        np.testing.assert_array_equal(np.asarray(lora["w"][j]), loras[0]["w"])
        np.testing.assert_array_equal(
            np.asarray(opt["b"]["v"][j]), opts[0]["b"]["v"]
        )
    new_l = jax.tree.map(lambda x: x[:1] * 3.0, lora)
    new_o = jax.tree.map(lambda x: x[:1] * 3.0, opt)
    st.commit(jnp.asarray([2]), new_l, new_o)
    _, lora2, _, _ = st.fetch([2])
    np.testing.assert_array_equal(np.asarray(lora2["w"]), np.asarray(new_l["w"]))
    _, lora5, _, _ = st.fetch([5])  # still the template
    np.testing.assert_array_equal(np.asarray(lora5["w"][0]), loras[0]["w"])
    # O(1)-in-N construction and device residency (the shared backbone)
    big = mk(100_000)
    assert big.device_bytes() == st.device_bytes()
    assert big.num_clients == 100_000


def test_host_store_device_bytes_below_device_store():
    """The fleet leaves the device: between rounds the host store keeps only
    the shared backbone there, strictly less than the device store's
    stacked fleet at N=10."""
    loras, frozens, opts = _toy(10)
    kw = dict(loras=loras, frozens=frozens, opts=opts, shared=True)
    host = make_fleet_store("host", **kw).device_bytes()
    assert 0 < host < make_fleet_store("device", **kw).device_bytes()


def test_make_fleet_store_spec():
    loras, frozens, opts = _toy(3)
    kw = dict(loras=loras, frozens=frozens, opts=opts, shared=True)
    assert make_fleet_store(None, **kw).kind == "device"
    assert make_fleet_store("device", **kw).kind == "device"
    assert make_fleet_store("host", **kw).kind == "host"
    st = HostFleetStore(loras, frozens, opts, shared=True)
    assert make_fleet_store(st, **kw) is st
    with pytest.raises(ValueError, match="fleet_store"):
        make_fleet_store("gpu", **kw)


# ---------------------------------------------------------------------------
# engine + driver integration
# ---------------------------------------------------------------------------


def _cohort(n, ds, cfgs=None):
    cfgs = cfgs or [CLIENT]
    return [
        Client(i, cfgs[i % len(cfgs)], ds.subset(np.arange(i * 60, (i + 1) * 60)),
               num_classes=ds.num_classes, seed=i, local_steps=1, distill_steps=1)
        for i in range(n)
    ]


@pytest.mark.parametrize("engine", ["batched", "fused", "fused_e2e"])
def test_host_store_run_parity(engine):
    """fleet_store='host' reproduces the device-store run bit-identically:
    the streamed cohort rows round-trip host<->device losslessly and feed
    the exact same compiled round."""
    ds = _dataset()
    dev = run_federated(CLIENT, SERVER, ds, _cfg(engine))
    host = run_federated(CLIENT, SERVER, ds, _cfg(engine, fleet_store="host"))
    _assert_identical(host, dev)


@pytest.mark.parametrize("engine", ["batched", "fused_e2e"])
def test_hetero_host_store_run_parity(engine):
    """Family-bucketed engines stream per-bucket cohorts through host
    stores (one store per bucket) at bit-parity with the device stores."""
    ds = _dataset()
    fams = [FAM_A, FAM_B]
    dev = run_federated(fams, SERVER, ds, _cfg(engine))
    host = run_federated(fams, SERVER, ds, _cfg(engine, fleet_store="host"))
    _assert_identical(host, dev)


def test_scan_rounds_host_store_falls_back():
    """scan_rounds needs the fleet as a donated device scan carry; with a
    host store the driver falls back to the per-round loop and must match
    the explicit per-round host run bit-identically."""
    ds = _dataset()
    loop = run_federated(CLIENT, SERVER, ds,
                         _cfg("fused_e2e", rounds=3, fleet_store="host"))
    scan = run_federated(
        CLIENT, SERVER, ds,
        _cfg("fused_e2e", rounds=3, scan_rounds=True, fleet_store="host"),
    )
    _assert_identical(scan, loop)


def test_engine_rejects_duplicate_cohort():
    ds = _dataset()
    eng = BatchedEngine(
        _cohort(4, ds), CLIENT, num_classes=ds.num_classes,
        local_steps=1, distill_steps=1,
    )
    pub = jnp.asarray(ds.tokens[:16])
    states = BatchedChannelState.from_states(
        [ChannelState(1e6, 10.0, 0.5, 1.0)] * 2
    )
    with pytest.raises(ValueError, match="duplicate client ids"):
        eng.run_round([1, 1], pub, None, states, adaptive_k=True, send_h=True)


def test_run_rounds_requires_device_store():
    """The multi-round lax.scan driver donates the stacked fleet into one
    compiled scan — it must refuse a host store up front (rounds.py falls
    back to the per-round driver instead)."""
    ds = _dataset()
    eng = FusedE2EEngine(
        _cohort(4, ds), CLIENT,
        server=Server(SERVER, aggregation="adaptive", distill_steps=2),
        num_classes=ds.num_classes, local_steps=1, distill_steps=1,
        server_distill_steps=2, fleet_store="host",
    )
    pub = jnp.asarray(ds.tokens[:16])
    states = BatchedChannelState.from_states(
        [ChannelState(1e6, 10.0, 0.5, 1.0)] * 2
    )
    with pytest.raises(RuntimeError, match="fleet_store='device'"):
        eng.run_rounds([[0, 1]], [pub], [states], adaptive_k=True, send_h=True)


def test_sequential_engine_rejects_host_store():
    ds = _dataset()
    with pytest.raises(NotImplementedError, match="sequential"):
        make_engine("sequential", _cohort(2, ds), CLIENT,
                    num_classes=ds.num_classes, fleet_store="host")


# ---------------------------------------------------------------------------
# sharded checkpoints + resume
# ---------------------------------------------------------------------------


def test_host_store_sharded_resume_bit_identical(tmp_path):
    """Kill after round 2, resume to 4: bit-identical to an uninterrupted
    host-store run — with the fleet persisted as per-client shards in a
    step-side .fleet dir, never as one monolithic tree in the step npz."""
    from repro.checkpoint import step_metadata

    ds = _dataset()
    full = run_federated(CLIENT, SERVER, ds,
                         _cfg("batched", rounds=4, fleet_store="host"))
    d = str(tmp_path)
    run_federated(CLIENT, SERVER, ds,
                  _cfg("batched", rounds=2, fleet_store="host"), ckpt_dir=d)
    fleet_dir = os.path.join(d, "step_00000002.fleet")
    assert os.path.isdir(fleet_dir)
    assert any(f.startswith("fleet_") for f in os.listdir(fleet_dir))
    assert step_metadata(d, 2)["fleet_sharded"] is True
    res = run_federated(CLIENT, SERVER, ds,
                        _cfg("batched", rounds=4, fleet_store="host"),
                        ckpt_dir=d, resume=True)
    _assert_identical(res, full)


def test_cross_store_resume(tmp_path):
    """fleet_store is excluded from the resume fingerprint: a checkpoint
    written under the host store (sharded) resumes under the device store
    bit-identically, and vice versa."""
    ds = _dataset()
    full = run_federated(CLIENT, SERVER, ds, _cfg("batched", rounds=4))
    # host-sharded checkpoint -> device-store resume
    d1 = str(tmp_path / "h2d")
    run_federated(CLIENT, SERVER, ds,
                  _cfg("batched", rounds=2, fleet_store="host"), ckpt_dir=d1)
    res = run_federated(CLIENT, SERVER, ds, _cfg("batched", rounds=4),
                        ckpt_dir=d1, resume=True)
    _assert_identical(res, full)
    # monolithic device checkpoint -> host-store resume
    d2 = str(tmp_path / "d2h")
    run_federated(CLIENT, SERVER, ds, _cfg("batched", rounds=2), ckpt_dir=d2)
    res = run_federated(CLIENT, SERVER, ds,
                        _cfg("batched", rounds=4, fleet_store="host"),
                        ckpt_dir=d2, resume=True)
    _assert_identical(res, full)


def test_hetero_host_store_sharded_resume(tmp_path):
    """Bucketed fleets persist per-bucket shard prefixes in one .fleet dir
    and resume bit-identically over host stores."""
    ds = _dataset()
    fams = [FAM_A, FAM_B]
    full = run_federated(fams, SERVER, ds,
                         _cfg("batched", rounds=4, fleet_store="host"))
    d = str(tmp_path)
    run_federated(fams, SERVER, ds,
                  _cfg("batched", rounds=2, fleet_store="host"), ckpt_dir=d)
    fleet_dir = os.path.join(d, "step_00000002.fleet")
    names = os.listdir(fleet_dir)
    assert any(f.startswith("bucket0_") for f in names)
    assert any(f.startswith("bucket1_") for f in names)
    res = run_federated(fams, SERVER, ds,
                        _cfg("batched", rounds=4, fleet_store="host"),
                        ckpt_dir=d, resume=True)
    _assert_identical(res, full)
