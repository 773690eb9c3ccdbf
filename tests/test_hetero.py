"""Heterogeneous federated distillation (the paper's FedD motivation):
clients with DIFFERENT architectures interoperate through the logit/
projection exchange — only vocab and LoRA rank are shared contracts.

Fast tier: the family-bucketed FAST engines (PR 5) — a mixed dense + SSM
fleet runs through ``batched``/``fused``/``fused_e2e`` and the multi-round
scan at parity with the sequential reference (identical per-client adaptive
k and ledger bytes, 1e-6 accuracies), the union sparse wire matches the
dense uplink, and every transmitted payload still fits its Shannon budget.
Slow tier: the original three-family sequential round (kept as the
engine-free reference scenario).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import LoRAConfig, SSMConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
from repro.core import ChannelConfig, ChannelSimulator
from repro.core.topk import wire_densify
from repro.data import make_banking77_like, make_fed_benchmark_dataset, split_public_private
from repro.fed import FedConfig, run_federated
from repro.fed.client import Client
from repro.fed.cohort import partition_fleet, split_cohort, validate_family_contracts
from repro.fed.engines import HeteroFusedE2EEngine, SequentialEngine
from repro.fed.server import Server

# ---------------------------------------------------------------------------
# fast tier: family-bucketed fast engines at reduced scale
# ---------------------------------------------------------------------------

FLORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
H_DENSE = REDUCED_CLIENT.with_overrides(
    name="h-dense", num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
    d_ff=128, vocab_size=256, max_seq_len=32, lora=FLORA,
)
H_SSM = get_smoke_config("mamba2-130m").with_overrides(
    name="h-ssm", d_model=64, vocab_size=256, max_seq_len=32, lora=FLORA,
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, chunk_size=4),
)
H_SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32, lora=FLORA,
)
FAMILIES = [H_DENSE, H_SSM]
# Constrained uplink so the adaptive k actually varies per client/round.
H_CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0)


def _dataset():
    return make_banking77_like(vocab_size=256, seq_len=12, total=500, seed=0)


def _cfg(engine, channel=H_CHAN, rounds=2, **kw):
    kw.setdefault("pretrain_steps", 0)
    return FedConfig(
        method="adald", engine=engine, num_clients=4, clients_per_round=2,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        local_steps=2, distill_steps=1, server_distill_steps=2,
        seed=0, channel=channel, **kw,
    )


def _mixed_cohort(n=4, ds=None):
    """n clients cycling dense/SSM families (per-client random backbones —
    each bucket carries frozen_ax=0 stacked frozens)."""
    ds = ds or _dataset()
    return ds, [
        Client(i, FAMILIES[i % 2], ds.subset(np.arange(i * 60, (i + 1) * 60)),
               num_classes=ds.num_classes, seed=i, local_steps=1,
               distill_steps=1)
        for i in range(n)
    ]


def test_partition_fleet_buckets_by_config():
    ds, clients = _mixed_cohort(5)
    buckets = partition_fleet(clients)
    assert [b.cfg.name for b in buckets] == ["h-dense", "h-ssm"]
    assert buckets[0].client_ids == (0, 2, 4)
    assert buckets[1].client_ids == (1, 3)
    # per-client random backbones: nothing is identity-shared
    assert not any(b.shared_backbone for b in buckets)
    validate_family_contracts(buckets, server_cfg=H_SERVER)
    parts = split_cohort(buckets, [3, 0, 4])
    assert [(b.index, pos, local) for b, pos, local in parts] == [
        (0, [1, 2], [0, 2]), (1, [0], [1]),
    ]


def test_family_contracts_fail_fast():
    ds, clients = _mixed_cohort(2)
    odd_vocab = [
        clients[0],
        Client(1, H_SSM.with_overrides(vocab_size=512),
               ds.subset(np.arange(60, 120)), num_classes=ds.num_classes,
               seed=1, local_steps=1, distill_steps=1),
    ]
    with pytest.raises(ValueError, match="vocab"):
        validate_family_contracts(partition_fleet(odd_vocab))
    odd_rank = [
        clients[0],
        Client(1, H_SSM.with_overrides(
            lora=LoRAConfig(rank=8, targets=("q", "v", "head"))),
            ds.subset(np.arange(60, 120)), num_classes=ds.num_classes,
            seed=1, local_steps=1, distill_steps=1),
    ]
    with pytest.raises(ValueError, match="rank"):
        validate_family_contracts(partition_fleet(odd_rank))


@pytest.mark.parametrize("engine", ["batched", "fused", "fused_e2e"])
def test_hetero_engine_parity_with_sequential(engine):
    """The family-bucketed fast engines reproduce the sequential reference
    on a mixed dense+SSM fleet: identical per-client adaptive k and ledger
    bytes, accuracies at 1e-6."""
    ds = _dataset()
    seq = run_federated(FAMILIES, H_SERVER, ds, _cfg("sequential"))
    oth = run_federated(FAMILIES, H_SERVER, ds, _cfg(engine))
    assert seq.per_client_k == oth.per_client_k
    for rs, ro in zip(seq.ledger.rounds, oth.ledger.rounds):
        assert rs.uplink_bytes == ro.uplink_bytes
        assert rs.downlink_bytes == ro.downlink_bytes
        assert rs.num_transmitters == ro.num_transmitters
    np.testing.assert_allclose(seq.server_acc, oth.server_acc, atol=1e-6)
    np.testing.assert_allclose(seq.client_acc, oth.client_acc, atol=1e-6)


def test_hetero_straggler_dropout_parity():
    """Mixed fleet + outage stragglers: the bucketed engines agree with the
    sequential reference on who dropped and on everything else."""
    chan = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0,
                         dropout_prob=0.5)
    ds = _dataset()
    seq = run_federated(FAMILIES, H_SERVER, ds, _cfg("sequential", chan, rounds=3))
    e2e = run_federated(FAMILIES, H_SERVER, ds, _cfg("fused_e2e", chan, rounds=3))
    all_ks = [k for ks in seq.per_client_k for k in ks]
    assert 0 in all_ks and any(k > 0 for k in all_ks)
    assert seq.per_client_k == e2e.per_client_k
    np.testing.assert_allclose(seq.server_acc, e2e.server_acc, atol=1e-6)
    np.testing.assert_allclose(seq.client_acc, e2e.client_acc, atol=1e-6)


def test_hetero_scan_rounds_matches_loop():
    """run_rounds on a heterogeneous fleet — R whole rounds, per-bucket
    executables inside ONE lax.scan dispatch — matches the per-round path
    (identical k/bytes, 1e-6 accuracies) and reports one eval-tap accuracy
    per family."""
    ds = _dataset()
    loop = run_federated(FAMILIES, H_SERVER, ds, _cfg("fused_e2e", rounds=3))
    scan = run_federated(
        FAMILIES, H_SERVER, ds, _cfg("fused_e2e", rounds=3, scan_rounds=True)
    )
    assert loop.per_client_k == scan.per_client_k
    for a, b in zip(loop.ledger.rounds, scan.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes
        assert a.num_transmitters == b.num_transmitters
    np.testing.assert_allclose(loop.server_acc, scan.server_acc, atol=1e-6)
    np.testing.assert_allclose(loop.client_acc, scan.client_acc, atol=1e-6)
    np.testing.assert_allclose(loop.distill_loss, scan.distill_loss, rtol=1e-4)
    # the per-family tap: one accuracy per bucket per round, and the
    # cohort-first client's family entry IS the reported client_acc
    assert scan.family_client_acc is not None
    assert len(scan.family_client_acc) == 3
    assert all(len(row) == len(FAMILIES) for row in scan.family_client_acc)
    for r, row in enumerate(scan.family_client_acc):
        assert scan.client_acc[r] in row


def test_hetero_union_wire_matches_dense_and_fits_budget():
    """Engine-level: the union sparse wire of a mixed cohort densifies to the
    sequential engine's per-client dense uploads, a k == 0 straggler is
    absent, per-row transmitted-entry counts equal the adaptive budgets, and
    every transmitted payload — LoRA projection included — satisfies
    PayloadSpec.fits for the channel state it was computed from."""
    ds, c_seq = _mixed_cohort(4)
    _, c_het = _mixed_cohort(4)
    server = Server(H_SERVER, aggregation="adaptive", distill_steps=2)
    seq = SequentialEngine(c_seq, H_DENSE, k_min=0)
    het = HeteroFusedE2EEngine(
        c_het, server=server, num_classes=ds.num_classes, local_steps=1,
        distill_steps=1, server_distill_steps=2, k_min=0,
    )
    sim = ChannelSimulator(
        4, ChannelConfig(bandwidth_hz=2e5, mean_snr_db=0.0, min_k=0), seed=1
    )
    pub = jnp.asarray(ds.tokens[:16])
    sel = [0, 1, 2, 3]
    for rnd in range(3):
        states = sim.states_batched(rnd, sel)
        ps = seq.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        pe = het.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        assert ps.ks == pe.ks
        assert [p.bytes for p in ps.payloads] == [p.bytes for p in pe.payloads]
        for payload in pe.payloads:
            st = states[payload.client_id]
            assert payload.spec.fits(st), (rnd, payload.client_id, payload.spec)
        tx = [i for i, k in enumerate(pe.ks) if k > 0]
        if not tx:
            assert pe.sparse is None
            continue
        wire = pe.sparse
        assert wire.values.shape[0] == len(tx)
        counts = np.asarray(jnp.sum(wire.mask, axis=-1))
        for row, i in enumerate(tx):
            assert set(np.unique(counts[row])) == {pe.ks[i]}
        if ps.dense is not None:
            np.testing.assert_allclose(
                np.asarray(wire_densify(wire)), np.asarray(ps.dense), atol=1e-5
            )


# ---------------------------------------------------------------------------
# slow tier: the original engine-free three-family sequential scenario
# ---------------------------------------------------------------------------

VOCAB = 512
LORA = LoRAConfig(rank=8, targets=("q", "v", "head"))


@pytest.fixture(scope="module")
def hetero_round():
    dense = get_smoke_config("yi-9b").with_overrides(
        name="h-dense", vocab_size=VOCAB, lora=LORA, max_seq_len=64)
    ssm = get_smoke_config("mamba2-130m").with_overrides(
        name="h-ssm", vocab_size=VOCAB, lora=LORA, max_seq_len=64)
    moe = get_smoke_config("granite-moe-1b-a400m").with_overrides(
        name="h-moe", vocab_size=VOCAB, lora=LORA, max_seq_len=64)
    ds = make_fed_benchmark_dataset(VOCAB, seed=0, total=600)
    public, private = split_public_private(ds, 96, seed=0)
    clients = [
        Client(i, cfg, private.subset(np.arange(i * 100, (i + 1) * 100)),
               num_classes=77, seed=i, local_steps=1, distill_steps=1)
        for i, cfg in enumerate([dense, ssm, moe])
    ]
    server = Server(REDUCED_SERVER.with_overrides(vocab_size=VOCAB, num_layers=2,
                                                  d_model=128, num_heads=4,
                                                  num_kv_heads=4, d_ff=256,
                                                  lora=LORA),
                    distill_steps=1)
    chan = ChannelSimulator(3, ChannelConfig(), seed=0)
    pub = jnp.asarray(public.tokens[:32])
    ups = []
    for c, st in zip(clients, chan.states(0, [0, 1, 2])):
        c.local_train()
        ups.append(c.upload(pub, st))
    k_g, h_g = server.aggregate_uploads(ups)
    metrics = server.distill(pub, k_g, h_g)
    g_logits, g_h, bits = server.broadcast(pub)
    for c in clients:
        c.local_distill(pub, g_logits, g_h)
    return ups, k_g, h_g, metrics


@pytest.mark.slow
def test_mixed_families_interoperate(hetero_round):
    ups, k_g, h_g, metrics = hetero_round
    assert k_g.shape == (32, VOCAB)
    assert bool(jnp.all(jnp.isfinite(k_g)))
    assert np.isfinite(metrics["loss"])


@pytest.mark.slow
def test_projections_align_across_families(hetero_round):
    """h = A·x has the same (batch, rank) shape for every architecture —
    the cross-family exchange contract of paper eq. 8."""
    ups, _, h_g, _ = hetero_round
    for up in ups:
        assert up.h is not None and up.h.shape == (32, LORA.rank)
    assert h_g.shape == (32, LORA.rank)


@pytest.mark.slow
def test_channel_budgets_differ_per_client(hetero_round):
    ups, _, _, _ = hetero_round
    ks = [u.k for u in ups]
    assert all(1 <= k <= VOCAB for k in ks)
    # Under the fixture's default channel every budget caps at k = vocab
    # (since the PR-4 per-(seed, round, cid) RNG re-keying), so the
    # different-fades-/-different-k property is asserted on a CONSTRAINED
    # uplink where the Shannon budget actually binds.
    chan = ChannelSimulator(
        3, ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0), seed=0
    )
    tight = chan.topk_for(0, [0, 1, 2], vocab_size=VOCAB, num_samples=32)
    assert len(set(tight)) > 1  # different fades -> different adaptive k


# ---- PR 7: correlated-channel scenarios -----------------------------------


def test_hetero_parity_correlated_scenario():
    """The family-bucketed engines reproduce the sequential reference on a
    mixed dense+SSM fleet under a gauss_markov correlated channel with
    outage-driven k=0 stragglers; the hetero multi-round scan carries the
    channel state too and exposes the in-scan tap."""
    ds = _dataset()
    chan = ChannelConfig(
        bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.25
    )
    kw = dict(channel=chan, rounds=3, scenario="gauss_markov")
    seq = run_federated(FAMILIES, H_SERVER, ds, _cfg("sequential", **kw))
    assert any(k == 0 for ks in seq.per_client_k for k in ks)
    for engine in ("batched", "fused_e2e"):
        oth = run_federated(FAMILIES, H_SERVER, ds, _cfg(engine, **kw))
        assert oth.per_client_k == seq.per_client_k, engine
        for a, b in zip(seq.ledger.rounds, oth.ledger.rounds):
            assert a.uplink_bytes == b.uplink_bytes, engine
            assert a.num_transmitters == b.num_transmitters, engine
        np.testing.assert_allclose(oth.server_acc, seq.server_acc, atol=1e-6)
        np.testing.assert_allclose(oth.client_acc, seq.client_acc, atol=1e-6)
    scan = run_federated(
        FAMILIES, H_SERVER, ds, _cfg("fused_e2e", scan_rounds=True, **kw)
    )
    assert scan.per_client_k == seq.per_client_k
    for a, b in zip(seq.ledger.rounds, scan.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
    np.testing.assert_allclose(scan.server_acc, seq.server_acc, atol=1e-6)
    assert len(scan.outage) == 3
    for ks, out in zip(scan.per_client_k, scan.outage):
        for k, o in zip(ks, out):
            if o:
                assert k == 0
