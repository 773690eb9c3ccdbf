"""Round-engine parity (sequential vs batched vs fused) + straggler/dropout
scenarios.

The keystone of the batched/fused client-execution engines: under the same
seed the engines must agree round-for-round — identical per-client adaptive
k, identical ledger bytes, matching accuracies (sequential↔batched bitwise;
the fused single-jit body is tolerance-compatible, see fed/engine.py).
Tiny configs (no backbone pretraining) keep this in the fast tier.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
from repro.core import ChannelConfig
from repro.core.channel import BatchedChannelState, ChannelState
from repro.core.protocol import PayloadSpec
from repro.core.topk import wire_densify
from repro.data import make_banking77_like
from repro.fed import (
    BatchedEngine,
    FedConfig,
    FusedE2EEngine,
    FusedEngine,
    SequentialEngine,
    run_federated,
)
from repro.fed.client import Client
from repro.fed.server import Server

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
CLIENT = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
# Constrained uplink so the adaptive k actually varies per client/round.
CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0)


def _dataset():
    return make_banking77_like(vocab_size=CLIENT.vocab_size, seq_len=12, total=500, seed=0)


def _cfg(engine, method="adald", channel=CHAN, rounds=2, **kw):
    kw.setdefault("pretrain_steps", 0)
    return FedConfig(
        method=method, engine=engine, num_clients=4, clients_per_round=2,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        local_steps=2, distill_steps=1, server_distill_steps=2,
        seed=0, channel=channel, **kw,
    )


@pytest.mark.parametrize("method", ["adald", "adaptive", "zeropad", "all_logits"])
def test_engine_parity(method):
    """Batched engine == sequential engine under the same seed: per-client k,
    ledger bytes, and accuracies match in every round."""
    ds = _dataset()
    seq = run_federated(CLIENT, SERVER, ds, _cfg("sequential", method))
    bat = run_federated(CLIENT, SERVER, ds, _cfg("batched", method))
    assert seq.per_client_k == bat.per_client_k
    assert seq.mean_k == bat.mean_k
    for rs, rb in zip(seq.ledger.rounds, bat.ledger.rounds):
        assert rs.uplink_bytes == rb.uplink_bytes
        assert rs.downlink_bytes == rb.downlink_bytes
        assert rs.num_transmitters == rb.num_transmitters
    np.testing.assert_allclose(seq.server_acc, bat.server_acc, atol=1e-6)
    np.testing.assert_allclose(seq.client_acc, bat.client_acc, atol=1e-6)


@pytest.mark.parametrize("method", ["adald", "zeropad"])
def test_three_way_engine_parity(method):
    """sequential vs fused vs fused_e2e: identical per-client adaptive k and
    ledger bytes (host-side scalar math is shared); accuracies match to
    float tolerance (the fused engines compile the round — for fused_e2e the
    WHOLE round including aggregation/server distill/broadcast — as one
    program, so op scheduling may differ in the last ulp; the e2e path also
    aggregates from the sparse wire instead of the dense stack)."""
    ds = _dataset()
    runs = {
        e: run_federated(CLIENT, SERVER, ds, _cfg(e, method, rounds=2))
        for e in ("sequential", "batched", "fused", "fused_e2e")
    }
    seq = runs["sequential"]
    for name in ("batched", "fused", "fused_e2e"):
        other = runs[name]
        assert seq.per_client_k == other.per_client_k, name
        for rs, ro in zip(seq.ledger.rounds, other.ledger.rounds):
            assert rs.uplink_bytes == ro.uplink_bytes
            assert rs.downlink_bytes == ro.downlink_bytes
            assert rs.num_transmitters == ro.num_transmitters
        np.testing.assert_allclose(seq.server_acc, other.server_acc, atol=1e-6)
        np.testing.assert_allclose(seq.client_acc, other.client_acc, atol=1e-6)


@pytest.mark.parametrize("engine", ["sequential", "batched", "fused", "fused_e2e"])
def test_single_round_completes(engine):
    """Regression for the old pub_tokens_prev/g_bits forward references: a
    1-round run (no broadcast ever happens) must complete cleanly."""
    run = run_federated(CLIENT, SERVER, _dataset(), _cfg(engine, rounds=1))
    assert len(run.server_acc) == 1
    assert run.ledger.rounds[0].downlink_bytes == 0
    assert run.ledger.rounds[0].uplink_bytes > 0


@pytest.mark.parametrize("engine", ["sequential", "batched", "fused", "fused_e2e"])
def test_straggler_dropout(engine):
    """With min_k=0 + outages, dropped clients transmit zero bytes: each
    round's uplink equals the payload bytes of the k>0 clients only."""
    chan = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.5)
    run = run_federated(CLIENT, SERVER, _dataset(), _cfg(engine, channel=chan, rounds=3))
    all_ks = [k for ks in run.per_client_k for k in ks]
    assert 0 in all_ks, "expected at least one dropped client at p=0.5 over 6 slots"
    assert any(k > 0 for k in all_ks)
    for ks, stats in zip(run.per_client_k, run.ledger.rounds):
        expected = sum(
            PayloadSpec(num_samples=16, vocab=CLIENT.vocab_size, k=k,
                        lora_rank=LORA.rank).uplink_bytes
            for k in ks if k > 0
        )
        assert stats.uplink_bytes == expected
        assert stats.num_transmitters == sum(1 for k in ks if k > 0)
        assert stats.num_selected == len(ks)


@pytest.mark.parametrize("other", ["batched", "fused", "fused_e2e"])
def test_dropout_parity(other):
    """The engines agree on which clients drop and on everything else."""
    chan = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.5)
    ds = _dataset()
    seq = run_federated(CLIENT, SERVER, ds, _cfg("sequential", channel=chan, rounds=3))
    oth = run_federated(CLIENT, SERVER, ds, _cfg(other, channel=chan, rounds=3))
    assert seq.per_client_k == oth.per_client_k
    np.testing.assert_allclose(seq.server_acc, oth.server_acc, atol=1e-6)
    np.testing.assert_allclose(seq.client_acc, oth.client_acc, atol=1e-6)


@pytest.mark.parametrize("engine", ["sequential", "batched", "fused", "fused_e2e"])
def test_all_clients_dropped_round(engine):
    """A round where every selected client is in outage must complete: zero
    uplink, zero transmitters, no aggregation/distillation that round.
    Outage (zero capacity) drops the client even at the default min_k=1 —
    the survival floor only applies to links that can transmit at all."""
    chan = ChannelConfig(dropout_prob=1.0)
    run = run_federated(CLIENT, SERVER, _dataset(), _cfg(engine, channel=chan, rounds=2))
    for stats in run.ledger.rounds:
        assert stats.uplink_bytes == 0
        assert stats.num_transmitters == 0
    assert all(np.isfinite(a) for a in run.server_acc)


def _mini_cohort(n=3):
    ds = _dataset()
    clients = [
        Client(i, CLIENT, ds.subset(np.arange(i * 60, (i + 1) * 60)),
               num_classes=ds.num_classes, seed=i, local_steps=1, distill_steps=1)
        for i in range(n)
    ]
    return ds, clients


def test_dropped_client_absent_from_aggregation():
    """Engine-level: a client in outage is excluded from the dense stack fed
    to aggregation (not zero-padded in), so 'zeropad' averages over the
    transmitters only."""
    ds, clients = _mini_cohort(3)
    engine = BatchedEngine(
        clients, CLIENT, num_classes=ds.num_classes,
        local_steps=1, distill_steps=1, k_min=0,
    )
    good = ChannelState(bandwidth_hz=1e6, snr_db=10.0, eta=0.5, deadline_s=1.0)
    out = ChannelState(bandwidth_hz=1e6, snr_db=-float("inf"), eta=0.5, deadline_s=1.0)
    states = BatchedChannelState.from_states([good, out, good])
    pub = jnp.asarray(ds.tokens[:16])
    phase = engine.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)
    assert phase.ks[1] == 0 and phase.ks[0] > 0 and phase.ks[2] > 0
    assert phase.dense.shape[0] == 2  # only the two transmitters
    assert phase.h.shape[0] == 2
    assert [p.client_id for p in phase.payloads] == [0, 2]

    server = Server(SERVER, aggregation="zeropad", distill_steps=1)
    k_g, _ = server.aggregate_dense(phase.dense, phase.h)
    np.testing.assert_allclose(
        np.asarray(k_g), np.asarray(jnp.mean(phase.dense, axis=0)), rtol=1e-6
    )


@pytest.mark.parametrize("engine_cls", [BatchedEngine, FusedEngine])
def test_engines_preserve_client_state(engine_cls):
    """After a batched/fused round, each client's params advance exactly as
    the sequential engine's would (the engine is the source of truth; read
    back through client_params)."""
    ds, c_seq = _mini_cohort(2)
    _, c_oth = _mini_cohort(2)
    states = BatchedChannelState.from_states([
        ChannelState(1e6, 10.0, 0.5, 1.0), ChannelState(1e6, 0.0, 0.5, 1.0),
    ])
    pub = jnp.asarray(ds.tokens[:16])
    seq = SequentialEngine(c_seq, CLIENT)
    oth = engine_cls(c_oth, CLIENT, num_classes=ds.num_classes,
                     local_steps=1, distill_steps=1)
    ps = seq.run_round([0, 1], pub, None, states, adaptive_k=True, send_h=True)
    po = oth.run_round([0, 1], pub, None, states, adaptive_k=True, send_h=True)
    assert ps.ks == po.ks
    np.testing.assert_allclose(np.asarray(ps.dense), np.asarray(po.dense), atol=1e-6)
    import jax

    for i in range(2):
        for x, y in zip(jax.tree.leaves(seq.client_params(i)),
                        jax.tree.leaves(oth.client_params(i))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_fused_use_kernels_matches_jnp_sparsifier():
    """use_kernels=True routes the fused uplink top-k through the per-row
    budget Pallas bisection kernel (interpret mode on CPU) — same threshold
    semantics, same dense output as the pure-jnp path."""
    ds, c_a = _mini_cohort(2)
    _, c_b = _mini_cohort(2)
    states = BatchedChannelState.from_states([
        ChannelState(1e6, 10.0, 0.5, 1.0), ChannelState(1e6, 0.0, 0.5, 1.0),
    ])
    pub = jnp.asarray(ds.tokens[:16])
    plain = FusedEngine(c_a, CLIENT, num_classes=ds.num_classes,
                        local_steps=1, distill_steps=1)
    kern = FusedEngine(c_b, CLIENT, num_classes=ds.num_classes,
                       local_steps=1, distill_steps=1, use_kernels=True)
    pp = plain.run_round([0, 1], pub, None, states, adaptive_k=True, send_h=True)
    pk = kern.run_round([0, 1], pub, None, states, adaptive_k=True, send_h=True)
    assert pp.ks == pk.ks
    np.testing.assert_allclose(np.asarray(pp.dense), np.asarray(pk.dense), atol=0)


def test_fused_dropped_client_absent_from_aggregation():
    """Fused engine: a k == 0 straggler yields a zeroed dense row inside the
    compiled body, and the host phase excludes it from the dense stack."""
    ds, clients = _mini_cohort(3)
    engine = FusedEngine(
        clients, CLIENT, num_classes=ds.num_classes,
        local_steps=1, distill_steps=1, k_min=0,
    )
    good = ChannelState(bandwidth_hz=1e6, snr_db=10.0, eta=0.5, deadline_s=1.0)
    out = ChannelState(bandwidth_hz=1e6, snr_db=-float("inf"), eta=0.5, deadline_s=1.0)
    states = BatchedChannelState.from_states([good, out, good])
    pub = jnp.asarray(ds.tokens[:16])
    phase = engine.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)
    assert phase.ks[1] == 0 and phase.ks[0] > 0 and phase.ks[2] > 0
    assert phase.dense.shape[0] == 2  # only the two transmitters
    assert phase.h.shape[0] == 2
    assert [p.client_id for p in phase.payloads] == [0, 2]


def _shared_cohort(n=3, seed=7):
    """Cohort riding ONE pretrained-like backbone W' (the paper's setting;
    what run_federated produces after pretraining) — required by the e2e
    multi-round scan driver."""
    import jax

    from repro.models import init as model_init

    ds = _dataset()
    backbone = model_init(jax.random.PRNGKey(seed), CLIENT)
    clients = [
        Client(i, CLIENT, ds.subset(np.arange(i * 60, (i + 1) * 60)),
               num_classes=ds.num_classes, seed=i, local_steps=1,
               distill_steps=1, initial_params=backbone)
        for i in range(n)
    ]
    return ds, clients


def _e2e_engine(clients, ds, **kw):
    from repro.fed.server import Server

    server = Server(SERVER, aggregation=kw.pop("aggregation", "adaptive"),
                    distill_steps=2)
    return FusedE2EEngine(
        clients, CLIENT, server=server, num_classes=ds.num_classes,
        local_steps=1, distill_steps=1, server_distill_steps=2, **kw,
    )


def test_fused_e2e_sparse_wire_matches_dense_uplink():
    """The e2e engine's sparse (values, indices, mask) uplink densifies to
    exactly the sequential engine's per-client dense upload (modulo float
    drift of the fused model math); a k == 0 straggler is absent from the
    wire, and each wire row carries exactly k transmitted entries."""
    ds, c_seq = _mini_cohort(3)
    _, c_e2e = _mini_cohort(3)
    good = ChannelState(bandwidth_hz=1e6, snr_db=10.0, eta=0.5, deadline_s=1.0)
    out = ChannelState(bandwidth_hz=1e6, snr_db=-float("inf"), eta=0.5, deadline_s=1.0)
    states = BatchedChannelState.from_states([good, out, good])
    pub = jnp.asarray(ds.tokens[:16])

    seq = SequentialEngine(c_seq, CLIENT, k_min=0)
    e2e = _e2e_engine(c_e2e, ds, k_min=0)
    ps = seq.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)
    pe = e2e.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)
    assert ps.ks == pe.ks and pe.ks[1] == 0
    assert pe.dense is None  # no densified stack exists on this path
    wire = pe.sparse
    assert wire.values.shape[0] == 2  # transmitters only
    # per-row transmitted-entry counts == the adaptive budgets
    counts = np.asarray(jnp.sum(wire.mask, axis=-1))
    assert set(np.unique(counts[0])) == {pe.ks[0]}
    assert set(np.unique(counts[1])) == {pe.ks[2]}
    np.testing.assert_allclose(
        np.asarray(wire_densify(wire)), np.asarray(ps.dense), atol=1e-5
    )

    # the Server's wire entry point == its dense path fed the densified wire
    server = Server(SERVER, aggregation="adaptive", distill_steps=1)
    k_g_wire, h_g = server.aggregate_sparse_wire(wire, ps.h)
    k_g_dense, h_g_dense = server.aggregate_dense(wire_densify(wire), ps.h)
    np.testing.assert_allclose(
        np.asarray(k_g_wire), np.asarray(k_g_dense), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(h_g), np.asarray(h_g_dense), atol=0)


def test_fused_e2e_run_rounds_matches_per_round():
    """run_rounds(R) — R whole rounds inside ONE lax.scan dispatch — leaves
    the fleet, the server and the broadcast exactly where R single
    run_round calls do, reports identical (ks, payload) accounting, and its
    IN-SCAN eval tap reproduces the per-round host evaluation at 1e-6."""
    import jax

    from repro.core import ChannelConfig as CC, ChannelSimulator
    from repro.fed.steps import make_eval_fn

    ds, c_a = _shared_cohort(4)
    _, c_b = _shared_cohort(4)
    a, b = _e2e_engine(c_a, ds), _e2e_engine(c_b, ds)
    sim = ChannelSimulator(4, CC(bandwidth_hz=2e5, mean_snr_db=2.0), seed=0)
    sels = [[0, 1], [2, 3]]
    pubs = [jnp.asarray(ds.tokens[:16]), jnp.asarray(ds.tokens[16:32])]
    states = [sim.states_batched(r, sels[r]) for r in range(2)]
    # one whole host-eval batch (64), so the host loop and the in-scan tap
    # read exactly the same samples
    ev_tok = jnp.asarray(ds.tokens[300:364])
    ev_lab = jnp.asarray(ds.labels[300:364])
    evaluate_s = make_eval_fn(SERVER, ds.num_classes)
    evaluate_c = make_eval_fn(CLIENT, ds.num_classes)

    # -- per-round reference: run_round + host-side eval after each round --
    want_s, want_c, want_d = [], [], []
    phases, bcast = [], None
    for r in range(2):
        phases.append(a.run_round(
            sels[r], pubs[r], bcast, states[r], adaptive_k=True, send_h=True
        ))
        bcast = a.broadcast_state(pubs[r])
        a.sync_server()
        want_s.append(evaluate_s(a.server.params, ev_tok, ev_lab))
        want_c.append(evaluate_c(a.client_params(sels[r][0]), ev_tok, ev_lab))
        want_d.append(a.last_distill_loss)
    p0, p1 = phases

    traj = b.run_rounds(
        sels, pubs, states, adaptive_k=True, send_h=True,
        eval_tokens=ev_tok, eval_labels=ev_lab,
    )
    b.sync_server()

    assert traj.ks == [p0.ks, p1.ks]
    assert [[p.bytes for p in pl] for pl in traj.payloads] == [
        [p.bytes for p in p0.payloads], [p.bytes for p in p1.payloads]
    ]
    # the in-scan eval tap == the per-round host evaluation
    np.testing.assert_allclose(traj.server_acc, want_s, atol=1e-6)
    np.testing.assert_allclose(traj.client_acc, want_c, atol=1e-6)
    np.testing.assert_allclose(traj.distill_loss, want_d, rtol=1e-4)
    np.testing.assert_allclose(
        traj.mean_k, [np.mean(p0.ks), np.mean(p1.ks)], rtol=1e-6
    )
    for i in range(4):
        for x, y in zip(jax.tree.leaves(a.client_params(i)),
                        jax.tree.leaves(b.client_params(i))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
    for x, y in zip(jax.tree.leaves(a.server.params),
                    jax.tree.leaves(b.server.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(a._b_logits), np.asarray(b._b_logits), atol=1e-4
    )


def test_e2e_aggregation_path_never_densifies_stack():
    """Trace-inspection acceptance check: at bench-like shapes, no
    intermediate of the sparse aggregation path — sub-jaxprs included —
    reaches the (N, B, V) dense stack's element count; the working set is
    O(N·B·k_cap) + the single (B, V) output.  Same shared inspection
    (max_intermediate_elems) as the BENCH_round.json record, for both the
    pure-jnp scatter and the Pallas kernel route."""
    import jax

    from repro.core.aggregation import aggregate_wire, max_intermediate_elems
    from repro.core.topk import SparseWire

    n, rows, vocab, k_cap = 10, 64, 8192, 256

    def make_agg(use_kernel):
        def agg(values, indices, mask, n_tx):
            wire = SparseWire(values=values, indices=indices, mask=mask, vocab=vocab)
            return aggregate_wire(
                wire, "adaptive", num_transmitters=n_tx, use_kernel=use_kernel
            )
        return agg

    for use_kernel in (False, True):
        jaxpr = jax.make_jaxpr(make_agg(use_kernel))(
            jnp.zeros((n, rows, k_cap)), jnp.zeros((n, rows, k_cap), jnp.int32),
            jnp.zeros((n, rows, k_cap), bool), jnp.int32(n),
        )
        worst = max_intermediate_elems(jaxpr)
        assert worst < n * rows * vocab, use_kernel
        # nothing bigger than the (B, V) output (num/den accumulators)
        assert worst <= rows * vocab, use_kernel


_SHARD_MAP_SCRIPT = textwrap.dedent(
    """
    import jax, numpy as np, jax.numpy as jnp
    assert jax.device_count() == 2, jax.device_count()
    from repro.configs.base import LoRAConfig
    from repro.configs.gpt2_paper import REDUCED_CLIENT
    from repro.core.channel import BatchedChannelState, ChannelState
    from repro.data import make_banking77_like
    from repro.fed.client import Client
    from repro.fed.engine import FusedEngine

    lora = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
    cfg = REDUCED_CLIENT.with_overrides(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
        vocab_size=256, max_seq_len=32, lora=lora,
    )
    ds = make_banking77_like(vocab_size=256, seq_len=12, total=200, seed=0)

    def cohort(n):
        return [Client(i, cfg, ds.subset(np.arange(i * 60, (i + 1) * 60)),
                       num_classes=ds.num_classes, seed=i,
                       local_steps=1, distill_steps=1) for i in range(n)]

    chans = [ChannelState(1e6, 10.0, 0.5, 1.0), ChannelState(1e6, 0.0, 0.5, 1.0),
             ChannelState(1e6, 5.0, 0.5, 1.0)]
    pub = jnp.asarray(ds.tokens[:16])
    # n=2 divides the 2 devices exactly; n=3 exercises the masked padding
    # (the pad row rides at k=0 and is discarded before the scatter-back).
    for n in (2, 3):
        states = BatchedChannelState.from_states(chans[:n])
        sel = list(range(n))
        plain = FusedEngine(cohort(n), cfg, num_classes=ds.num_classes,
                            local_steps=1, distill_steps=1)
        shard = FusedEngine(cohort(n), cfg, num_classes=ds.num_classes,
                            local_steps=1, distill_steps=1, shard_clients=True)
        pp = plain.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        ps = shard.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        assert pp.ks == ps.ks
        assert ps.dense.shape[0] == pp.dense.shape[0]
        np.testing.assert_allclose(np.asarray(pp.dense), np.asarray(ps.dense), atol=1e-5)
        for i in range(n):
            for a, b in zip(jax.tree.leaves(plain.client_params(i)),
                            jax.tree.leaves(shard.client_params(i))):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        print(f"SHARD_MAP_OK_{n}")
    """
)


def _run_two_device_subprocess(script: str) -> str:
    """Run a test script under 2 forced host devices (XLA_FLAGS must be set
    before jax initialises, hence the subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


def test_fused_shard_map_two_host_devices():
    """shard_clients=True places the client axis over devices (shard_map) and
    reproduces the single-device fused round — for an even cohort AND an odd
    cohort (client-axis padding)."""
    out = _run_two_device_subprocess(_SHARD_MAP_SCRIPT)
    assert "SHARD_MAP_OK_2" in out
    assert "SHARD_MAP_OK_3" in out


_E2E_SHARD_SCRIPT = textwrap.dedent(
    """
    import jax, numpy as np, jax.numpy as jnp
    assert jax.device_count() == 2, jax.device_count()
    from repro.configs.base import LoRAConfig
    from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
    from repro.core import ChannelConfig, ChannelSimulator
    from repro.data import make_banking77_like
    from repro.fed.client import Client
    from repro.fed.engine import FusedE2EEngine
    from repro.fed.server import Server
    from repro.models import init as model_init

    lora = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
    ccfg = REDUCED_CLIENT.with_overrides(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
        vocab_size=256, max_seq_len=32, lora=lora,
    )
    scfg = REDUCED_SERVER.with_overrides(
        num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
        vocab_size=256, max_seq_len=32, lora=lora,
    )
    ds = make_banking77_like(vocab_size=256, seq_len=12, total=500, seed=0)
    backbone = model_init(jax.random.PRNGKey(7), ccfg)

    def cohort(n):
        return [Client(i, ccfg, ds.subset(np.arange(i * 60, (i + 1) * 60)),
                       num_classes=ds.num_classes, seed=i, local_steps=1,
                       distill_steps=1, initial_params=backbone)
                for i in range(n)]

    def e2e(cl, shard):
        return FusedE2EEngine(
            cl, ccfg, server=Server(scfg, aggregation="adaptive", distill_steps=2),
            num_classes=ds.num_classes, local_steps=1, distill_steps=1,
            server_distill_steps=2, shard_clients=shard,
        )

    def assert_same_update(new_a, new_b, old):
        # The sharded and unsharded rounds are different executables, so
        # their reductions differ in the last bits — and Adam divides each
        # step by sqrt(v), so where a gradient sits at rounding level its
        # step can flip by up to 2*lr.  Compare the UPDATE each round made,
        # leaf by leaf, in norm: within 1% of the update's own size (a wrong
        # shard or pad row changes it at O(1)); untouched leaves stay equal.
        for a, b, o in zip(jax.tree.leaves(new_a), jax.tree.leaves(new_b),
                           jax.tree.leaves(old)):
            a, b, o = np.asarray(a), np.asarray(b), np.asarray(o)
            assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(a - o), (
                np.linalg.norm(a - b), np.linalg.norm(a - o))

    sim = ChannelSimulator(4, ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0), seed=0)
    pub = jnp.asarray(ds.tokens[:16])
    # n=2 divides the 2 devices exactly; n=3 exercises the masked k=0 padding
    # INSIDE the whole-round executable.
    for n in (2, 3):
        sel = list(range(n))
        states = sim.states_batched(0, sel)
        plain, shard = e2e(cohort(n), False), e2e(cohort(n), True)
        c0 = [jax.tree.map(np.asarray, plain.client_params(i)) for i in range(n)]
        s0 = jax.tree.map(np.asarray, plain._s_lora)
        pp = plain.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        ps = shard.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        assert pp.ks == ps.ks
        assert [p.bytes for p in pp.payloads] == [p.bytes for p in ps.payloads]
        np.testing.assert_allclose(
            np.asarray(ps.sparse.values), np.asarray(pp.sparse.values), atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(ps.sparse.mask), np.asarray(pp.sparse.mask))
        np.testing.assert_allclose(
            np.asarray(shard._b_logits), np.asarray(plain._b_logits), atol=1e-4)
        for i in range(n):
            assert_same_update(
                plain.client_params(i), shard.client_params(i), c0[i])
        assert_same_update(plain._s_lora, shard._s_lora, s0)
        print(f"E2E_SHARD_OK_{n}")

    # sharded run_rounds: odd cohorts padded inside the scanned executable,
    # eval tap matching the unsharded block at 1e-6
    sels = [[0, 1, 2], [1, 2, 3]]
    pubs = [jnp.asarray(ds.tokens[:16]), jnp.asarray(ds.tokens[16:32])]
    states = [sim.states_batched(r, sels[r]) for r in range(2)]
    ev_tok, ev_lab = jnp.asarray(ds.tokens[300:364]), jnp.asarray(ds.labels[300:364])
    a, b = e2e(cohort(4), False), e2e(cohort(4), True)
    ta = a.run_rounds(sels, pubs, states, adaptive_k=True, send_h=True,
                      eval_tokens=ev_tok, eval_labels=ev_lab)
    tb = b.run_rounds(sels, pubs, states, adaptive_k=True, send_h=True,
                      eval_tokens=ev_tok, eval_labels=ev_lab)
    assert ta.ks == tb.ks
    np.testing.assert_allclose(ta.server_acc, tb.server_acc, atol=1e-6)
    np.testing.assert_allclose(ta.client_acc, tb.client_acc, atol=1e-6)
    np.testing.assert_allclose(ta.distill_loss, tb.distill_loss, rtol=1e-4)
    print("E2E_SHARD_SCAN_OK")
    """
)


def test_fused_e2e_shard_map_two_host_devices():
    """fused_e2e + shard_clients=True: the client phase shards over 2 host
    devices INSIDE the whole-round executable (server phase replicated) and
    reproduces the unsharded engine — identical k/bytes, float-tolerance
    state — for an even cohort, an odd cohort (masked k=0 padding), and the
    multi-round run_rounds scan with its eval tap."""
    out = _run_two_device_subprocess(_E2E_SHARD_SCRIPT)
    assert "E2E_SHARD_OK_2" in out
    assert "E2E_SHARD_OK_3" in out
    assert "E2E_SHARD_SCAN_OK" in out


def test_same_seed_bit_identical_fedrun():
    """Channel-fix regression: two runs of the same config produce a
    bit-identical FedRun — per-client k, ledger bytes, accuracies.  (Before
    PR 4 this held only by accident of call order: the channel streams
    ignored the constructor seed and drew by cohort position.)"""
    ds = _dataset()
    r1 = run_federated(CLIENT, SERVER, ds, _cfg("batched", rounds=2))
    r2 = run_federated(CLIENT, SERVER, ds, _cfg("batched", rounds=2))
    assert r1.per_client_k == r2.per_client_k
    assert r1.server_acc == r2.server_acc
    assert r1.client_acc == r2.client_acc
    for a, b in zip(r1.ledger.rounds, r2.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes


def test_adald_payloads_respect_shannon_budget():
    """Budget-fix regression: with min_k=0 (no survival floor), every
    transmitted adald payload — LoRA projection included — fits the Shannon
    budget of the channel state it was computed from."""
    from repro.core import ChannelConfig as CC, ChannelSimulator

    ds, clients = _mini_cohort(3)
    engine = BatchedEngine(
        clients, CLIENT, num_classes=ds.num_classes,
        local_steps=1, distill_steps=1, k_min=0,
    )
    sim = ChannelSimulator(3, CC(bandwidth_hz=2e5, mean_snr_db=0.0, min_k=0), seed=1)
    pub = jnp.asarray(ds.tokens[:16])
    for rnd in range(3):
        states = sim.states_batched(rnd, [0, 1, 2])
        phase = engine.run_round(
            [0, 1, 2], pub, None, states, adaptive_k=True, send_h=True
        )
        for payload in phase.payloads:
            st = states[payload.client_id]
            assert payload.spec.fits(st), (rnd, payload.client_id, payload.spec)


def test_scan_rounds_matches_per_round_fedrun():
    """FedConfig.scan_rounds=True (one lax.scan dispatch for the whole run,
    in-scan eval tap) reproduces the per-round fused_e2e run: identical
    k/bytes, accuracies to float tolerance.  A (tiny) pretraining phase
    gives the fleet one shared backbone W' (the paper's setting)."""
    ds = _dataset()
    kw = dict(rounds=2, pretrain_steps=2, server_pretrain="none")
    loop = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e", **kw))
    scan = run_federated(
        CLIENT, SERVER, ds, _cfg("fused_e2e", scan_rounds=True, **kw)
    )
    assert loop.per_client_k == scan.per_client_k
    np.testing.assert_allclose(loop.mean_k, scan.mean_k, rtol=1e-6)
    for a, b in zip(loop.ledger.rounds, scan.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes
        assert a.num_transmitters == b.num_transmitters
    np.testing.assert_allclose(loop.server_acc, scan.server_acc, atol=1e-6)
    np.testing.assert_allclose(loop.client_acc, scan.client_acc, atol=1e-6)
    np.testing.assert_allclose(loop.distill_loss, scan.distill_loss, rtol=1e-4)


def test_scan_rounds_without_shared_backbone():
    """PR-5 guard lift: run_rounds no longer requires one shared frozen W'.
    With pretraining disabled every client carries its OWN random backbone
    (fleet-stacked frozens, frozen_ax=0 in the scanned executable); the
    multi-round scan still reproduces the per-round path exactly."""
    ds = _dataset()
    kw = dict(rounds=2, pretrain_steps=0)
    loop = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e", **kw))
    scan = run_federated(
        CLIENT, SERVER, ds, _cfg("fused_e2e", scan_rounds=True, **kw)
    )
    assert loop.per_client_k == scan.per_client_k
    for a, b in zip(loop.ledger.rounds, scan.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes
    np.testing.assert_allclose(loop.server_acc, scan.server_acc, atol=1e-6)
    np.testing.assert_allclose(loop.client_acc, scan.client_acc, atol=1e-6)
    np.testing.assert_allclose(loop.distill_loss, scan.distill_loss, rtol=1e-4)


# ---- PR 6: quantized wire + bf16 round body -------------------------------


def test_fused_e2e_quantized_wire_format_and_pricing():
    """quantize_wire=True swaps the e2e uplink to a QuantizedWire (int8
    values + per-row f32 scale), keeps the adaptive-k bookkeeping, prices
    every payload at 8-bit entries, and densifies to the float wire within
    the per-row quantization step (amax/127)."""
    from repro.core.topk import QUANT_LEVELS, QuantizedWire

    ds, c_q = _mini_cohort(3)
    _, c_f = _mini_cohort(3)
    # generous links: k saturates at vocab for both formats, so the wires
    # carry the SAME support and differ only in value encoding
    good = ChannelState(bandwidth_hz=1e7, snr_db=20.0, eta=0.5, deadline_s=1.0)
    states = BatchedChannelState.from_states([good] * 3)
    pub = jnp.asarray(ds.tokens[:16])

    quant = _e2e_engine(c_q, ds, quantize_wire=True)
    flt = _e2e_engine(c_f, ds)
    pq = quant.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)
    pf = flt.run_round([0, 1, 2], pub, None, states, adaptive_k=True, send_h=True)

    assert pq.ks == pf.ks  # identical k bookkeeping at saturated budgets
    assert pq.dense is None
    wire = pq.sparse
    assert isinstance(wire, QuantizedWire)
    assert wire.values.dtype == jnp.int8 and wire.scale.dtype == jnp.float32
    assert not isinstance(pf.sparse, QuantizedWire)

    # payload accounting: 8-bit entries, h kept at its own width, and a
    # strictly cheaper wire than the float run at identical k
    for qp, fp in zip(pq.payloads, pf.payloads):
        assert qp.spec.k == fp.spec.k
        assert qp.spec.value_bits == 8 and qp.spec.h_value_bits == 16
        assert qp.spec.uplink_bits < fp.spec.uplink_bits

    # the dequantized wire sits within one quantization step of the float
    # wire row-by-row (documented loosened tolerance for the int8 path)
    dq = np.asarray(wire_densify(wire))
    df = np.asarray(wire_densify(pf.sparse))
    step = np.max(np.abs(df), axis=-1, keepdims=True) / QUANT_LEVELS
    assert np.all(np.abs(dq - df) <= step + 1e-4)


def test_fused_e2e_quantized_run_matches_float_accuracy_shape():
    """Full fed run with quantize_wire=True: under the tight bench channel
    the 8-bit entry pricing buys a strictly LARGER adaptive k somewhere
    (never smaller anywhere), downlink is unchanged, and the accuracy
    trajectory stays within the loosened quant tolerance of the float
    run."""
    ds = _dataset()
    flt = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e"))
    qnt = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e", quantize_wire=True))

    kf = np.asarray(flt.per_client_k, dtype=float)
    kq = np.asarray(qnt.per_client_k, dtype=float)
    assert kq.shape == kf.shape
    assert np.all(kq >= kf), "8-bit pricing must never shrink k"
    assert np.any(kq > kf), "tight channel: cheaper entries must buy more k"
    for a, b in zip(flt.ledger.rounds, qnt.ledger.rounds):
        assert a.downlink_bytes == b.downlink_bytes
        assert a.num_transmitters == b.num_transmitters
    # same eval shape; quantization noise may move the tiny-scale accuracy
    # by a few eval samples, not wholesale
    np.testing.assert_allclose(qnt.server_acc, flt.server_acc, atol=0.15)
    np.testing.assert_allclose(qnt.client_acc, flt.client_acc, atol=0.15)


def test_fused_e2e_bf16_round_body_parity():
    """compute_dtype='bfloat16' (bf16 round body, fp32 master LoRA +
    optimizer state) keeps the k/bytes bookkeeping bit-identical to the
    fp32 run and the accuracies within the loosened bf16 tolerance."""
    ds = _dataset()
    f32 = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e"))
    bf = run_federated(
        CLIENT, SERVER, ds, _cfg("fused_e2e", compute_dtype="bfloat16")
    )
    # channel bookkeeping is value-independent: bit-identical
    assert f32.per_client_k == bf.per_client_k
    for a, b in zip(f32.ledger.rounds, bf.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes
    np.testing.assert_allclose(bf.server_acc, f32.server_acc, atol=0.15)
    np.testing.assert_allclose(bf.client_acc, f32.client_acc, atol=0.15)


def test_e2e_dequant_fused_aggregation_never_densifies_stack():
    """The quantized route's acceptance check, mirroring the float one: the
    dequantize-fused aggregation (int8 wire in, (B, V) teacher out) never
    materialises the (N, B, V) dense stack — dequantization lives inside
    the O(N·B·k_cap) working set — for both the pure-jnp scatter and the
    Pallas kernel route."""
    import jax

    from repro.core.aggregation import aggregate_wire, max_intermediate_elems
    from repro.core.topk import QuantizedWire

    n, rows, vocab, k_cap = 10, 64, 8192, 256

    def make_agg(use_kernel):
        def agg(values, scale, indices, mask, n_tx):
            wire = QuantizedWire(
                values=values, scale=scale, indices=indices, mask=mask, vocab=vocab
            )
            return aggregate_wire(
                wire, "adaptive", num_transmitters=n_tx, use_kernel=use_kernel
            )
        return agg

    for use_kernel in (False, True):
        jaxpr = jax.make_jaxpr(make_agg(use_kernel))(
            jnp.zeros((n, rows, k_cap), jnp.int8), jnp.ones((n, rows), jnp.float32),
            jnp.zeros((n, rows, k_cap), jnp.int32),
            jnp.zeros((n, rows, k_cap), bool), jnp.int32(n),
        )
        worst = max_intermediate_elems(jaxpr)
        assert worst < n * rows * vocab, use_kernel
        assert worst <= rows * vocab, use_kernel


# ---- PR 7: correlated-channel scenarios -----------------------------------


def test_four_way_engine_parity_correlated_scenario():
    """sequential/batched/fused/fused_e2e under a gauss_markov correlated
    channel with min_k=0 + memoryless outage (so straggler k=0 rounds
    occur): identical per-client adaptive k and ledger bytes, 1e-6
    accuracies.  The correlated budgets stay host-side scalar math shared
    by every engine, so correlation cannot split the engines."""
    ds = _dataset()
    chan = ChannelConfig(
        bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.25
    )
    runs = {
        e: run_federated(
            CLIENT, SERVER, ds,
            _cfg(e, channel=chan, rounds=3, scenario="gauss_markov"),
        )
        for e in ("sequential", "batched", "fused", "fused_e2e")
    }
    ref = runs["sequential"]
    # the constrained correlated channel must actually produce stragglers
    assert any(k == 0 for ks in ref.per_client_k for k in ks)
    for name, run in runs.items():
        assert run.per_client_k == ref.per_client_k, name
        for a, b in zip(ref.ledger.rounds, run.ledger.rounds):
            assert a.uplink_bytes == b.uplink_bytes, name
            assert a.downlink_bytes == b.downlink_bytes, name
            assert a.num_transmitters == b.num_transmitters, name
        np.testing.assert_allclose(run.server_acc, ref.server_acc, atol=1e-6)
        np.testing.assert_allclose(run.client_acc, ref.client_acc, atol=1e-6)


def test_scan_rounds_correlated_matches_per_round_fedrun():
    """scan_rounds under a jakes scenario: the one-dispatch scan (channel
    state as carry) reproduces the per-round fused_e2e host loop's k/bytes
    bit-for-bit and accuracies at 1e-6, and only the scan exposes the
    in-scan (snr_db, outage) channel tap."""
    ds = _dataset()
    chan = ChannelConfig(
        bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.25
    )
    kw = dict(channel=chan, rounds=3, scenario="jakes", pretrain_steps=0)
    loop = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e", **kw))
    scan = run_federated(
        CLIENT, SERVER, ds, _cfg("fused_e2e", scan_rounds=True, **kw)
    )
    assert loop.per_client_k == scan.per_client_k
    for a, b in zip(loop.ledger.rounds, scan.ledger.rounds):
        assert a.uplink_bytes == b.uplink_bytes
        assert a.downlink_bytes == b.downlink_bytes
        assert a.num_transmitters == b.num_transmitters
    np.testing.assert_allclose(loop.server_acc, scan.server_acc, atol=1e-6)
    np.testing.assert_allclose(loop.client_acc, scan.client_acc, atol=1e-6)
    # the tap is scan-only, shaped (rounds, cohort), outage <-> k == 0 of a
    # client whose budget was killed by -inf SNR
    assert loop.snr_db is None and loop.outage is None
    assert len(scan.snr_db) == 3 and len(scan.outage) == 3
    for ks, out in zip(scan.per_client_k, scan.outage):
        assert len(out) == len(ks)
        for k, o in zip(ks, out):
            if o:
                assert k == 0
