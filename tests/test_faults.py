"""Fault injection, wire quarantine and HARQ retransmission (PR 8).

Fast tier: keyed fault-stream determinism and permutation invariance,
host/scan-operand resolution parity, the server-side wire validation gate,
HARQ pricing against the Shannon budget (closed-form), and — on the tiny
no-pretrain configs — the end-to-end contracts: the "none" preset is
bit-identical to faults=None on every engine path, fault realisations agree
engine-for-engine (same k, bytes, quarantine counts), and the corruption
preset actually engages.

The golden trajectory — a committed tiny-fault record
(tests/data/faults_golden.json: per-round k / uplink bytes / quarantine and
crash counts / retransmission bytes for the corruption, crashes and lossy
presets at fixed seed) asserted identical between the per-round host loop
and the one-dispatch ``scan_rounds`` run.  Regenerate it (only after an
intentional change to the wire, its pricing or the fault streams):

    PYTHONPATH=src python tests/test_faults.py --regen
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig
from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER
from repro.core import ChannelConfig
from repro.core.faults import (
    FAULTS,
    FaultConfig,
    FaultSimulator,
    corrupt_wire,
    get_faults,
    quarantine_wire,
    validate_dense,
    validate_wire,
)
from repro.core.protocol import PayloadSpec
from repro.core.topk import sparsify_wire, wire_densify
from repro.data import make_banking77_like
from repro.fed import FedConfig, run_federated

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # dev-only dependency; property tests become no-ops
    HAVE_HYPOTHESIS = False

LORA = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
CLIENT = REDUCED_CLIENT.with_overrides(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
SERVER = REDUCED_SERVER.with_overrides(
    num_layers=2, d_model=96, num_heads=2, num_kv_heads=2, d_ff=192,
    vocab_size=256, max_seq_len=32, lora=LORA,
)
CHAN = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0)


def _dataset():
    return make_banking77_like(vocab_size=CLIENT.vocab_size, seq_len=12, total=500, seed=0)


def _cfg(engine, rounds=3, method="adald", **kw):
    kw.setdefault("pretrain_steps", 0)
    return FedConfig(
        method=method, engine=engine, num_clients=4, clients_per_round=2,
        rounds=rounds, public_size=64, public_batch=16, eval_size=64,
        local_steps=2, distill_steps=1, server_distill_steps=2,
        seed=0, channel=CHAN, **kw,
    )


# ---------------------------------------------------------------------------
# config / presets
# ---------------------------------------------------------------------------


def test_presets_resolve():
    assert get_faults(None) is None
    assert get_faults("corruption") is FAULTS["corruption"]
    cfg = FaultConfig(corrupt_prob=0.5)
    assert get_faults(cfg) is cfg
    with pytest.raises(ValueError):
        get_faults("no_such_preset")
    assert not FAULTS["none"].enabled
    assert all(FAULTS[n].enabled for n in ("corruption", "crashes", "bursty", "lossy"))


def test_config_validation():
    with pytest.raises(ValueError):
        FaultConfig(corrupt_prob=1.5)
    with pytest.raises(ValueError):
        FaultConfig(max_retries=-1)
    with pytest.raises(ValueError):
        FaultConfig(burst_enter=-0.1)


# ---------------------------------------------------------------------------
# keyed streams: determinism / cohort invariance / channel independence
# ---------------------------------------------------------------------------


def test_fault_streams_deterministic():
    """Two simulators with the same seed agree draw-for-draw."""
    cfg = FAULTS["lossy"]
    a = FaultSimulator(8, cfg, seed=3)
    b = FaultSimulator(8, cfg, seed=3)
    for rnd in range(4):
        ra = a.resolve_round(rnd, [0, 3, 5], [10, 10, 10], [100.0] * 3, [1e4] * 3)
        rb = b.resolve_round(rnd, [0, 3, 5], [10, 10, 10], [100.0] * 3, [1e4] * 3)
        assert ra == rb
    c = FaultSimulator(8, cfg, seed=4)
    diff = [
        c.resolve_round(r, list(range(8)), [10] * 8, [100.0] * 8, [1e4] * 8)
        != a.resolve_round(r, list(range(8)), [10] * 8, [100.0] * 8, [1e4] * 8)
        for r in range(8)
    ]
    assert any(diff), "a different seed must change some realisation"


def test_fault_verdict_cohort_invariant():
    """A client's verdict depends only on (seed, round, cid) and its own
    scalars: permuting the cohort or dropping other members cannot move it."""
    cfg = FAULTS["lossy"]
    sim = FaultSimulator(10, cfg, seed=0)
    full = sim.resolve_round(2, [1, 4, 7, 9], [10, 20, 30, 40],
                             [100.0, 200.0, 300.0, 400.0], [1e4] * 4)
    perm = FaultSimulator(10, cfg, seed=0).resolve_round(
        2, [9, 7, 4, 1], [40, 30, 20, 10],
        [400.0, 300.0, 200.0, 100.0], [1e4] * 4)
    assert full.delivered == perm.delivered[::-1]
    assert full.attempts == perm.attempts[::-1]
    assert full.reasons == perm.reasons[::-1]
    solo = FaultSimulator(10, cfg, seed=0).resolve_round(
        2, [4], [20], [200.0], [1e4])
    assert solo.delivered[0] == full.delivered[1]
    assert solo.attempts[0] == full.attempts[1]


def test_fault_rng_domains_disjoint_from_channel():
    """Enabling faults must never perturb the channel realisation: the fault
    simulator draws on stream domains 21-24, the channel on 7-10."""
    from repro.core.channel import ChannelSimulator

    chan = ChannelSimulator(4, CHAN, seed=0)
    baseline = [s.snr_db for s in chan.states_batched(0, [0, 1, 2, 3])]
    sim = FaultSimulator(4, FAULTS["lossy"], seed=0)
    sim.resolve_round(0, [0, 1, 2, 3], [10] * 4, [100.0] * 4, [1e4] * 4)
    chan2 = ChannelSimulator(4, CHAN, seed=0)
    after = [s.snr_db for s in chan2.states_batched(0, [0, 1, 2, 3])]
    assert baseline == after


def test_k_zero_is_not_a_fault():
    """A k = 0 straggler never transmitted: no attempts, no reason."""
    sim = FaultSimulator(4, FAULTS["lossy"], seed=0)
    res = sim.resolve_round(0, [0, 1], [0, 0], [100.0] * 2, [1e4] * 2)
    assert res.delivered == [False, False]
    assert res.attempts == [0, 0]
    assert res.reasons == [None, None]
    assert res.num_crashed == 0 and res.num_quarantined == 0


def test_scan_inputs_parity_with_host_resolution():
    """resolve_from_inputs over scan_fault_inputs operands is bit-identical
    to the per-round host path, including with a start_round offset."""
    cfg = FAULTS["lossy"]
    host = FaultSimulator(6, cfg, seed=5)
    scan = FaultSimulator(6, cfg, seed=5)
    inputs = scan.scan_fault_inputs(4, start_round=2)
    for j, rnd in enumerate(range(2, 6)):
        cohort = [0, 2, 5]
        ks = [7, 0, 31]
        pb = [70.0, 0.0, 310.0]
        bb = [500.0, 500.0, 500.0]
        a = host.resolve_round(rnd, cohort, ks, pb, bb)
        b = scan.resolve_from_inputs(inputs, j, cohort, ks, pb, bb)
        assert a == b


def test_step_faults_requires_contiguity():
    sim = FaultSimulator(4, FAULTS["bursty"], seed=0)
    carry = sim.init_fault_carry()
    with pytest.raises(ValueError, match="contiguous"):
        sim.step_faults(carry, 3)


def test_bursty_episodes_raise_corruption():
    """Inside a Gilbert-Elliott episode the corruption probability jumps to
    burst_corrupt_prob: across many rounds, burst rounds must corrupt more."""
    cfg = FaultConfig(name="t", corrupt_prob=0.02, max_retries=0,
                      burst_enter=0.3, burst_exit=0.3, burst_corrupt_prob=0.95)
    sim = FaultSimulator(16, cfg, seed=1)
    inputs = sim.scan_fault_inputs(40)
    in_burst, out_burst = [], []
    for r in range(40):
        res = sim.resolve_round(r, list(range(16)), [10] * 16,
                                [100.0] * 16, [1e4] * 16)
        for i in range(16):
            (in_burst if inputs["burst"][r][i] else out_burst).append(
                res.reasons[i] == "corrupt"
            )
    assert np.mean(in_burst) > 0.5 > np.mean(out_burst)


# ---------------------------------------------------------------------------
# HARQ pricing vs the Shannon budget
# ---------------------------------------------------------------------------


def _attempts_closed_form(corrupt_u, p, max_retries, payload_bits, budget_bits):
    """Reference HARQ walk: attempts keep re-spending the payload against
    the SAME budget; the first copy always fits."""
    affordable = max(1, int(np.floor(budget_bits / payload_bits)))
    allowed = min(1 + max_retries, affordable)
    for a in range(allowed):
        if not np.float32(corrupt_u[a]) < np.float32(p):
            return True, a + 1
    return False, allowed


def test_harq_budget_caps_retries():
    """With budget < 2 payloads the client gets exactly one attempt no
    matter how many retries the config allows."""
    cfg = FaultConfig(name="t", corrupt_prob=1.0, max_retries=5)
    sim = FaultSimulator(2, cfg, seed=0)
    res = sim.resolve_round(0, [0], [10], [100.0], [150.0])
    assert res.delivered == [False]
    assert res.attempts == [1]
    assert res.reasons == ["corrupt"]


def test_harq_attempts_match_closed_form():
    cfg = FaultConfig(name="t", corrupt_prob=0.6, max_retries=3)
    sim = FaultSimulator(8, cfg, seed=9)
    inputs = sim.scan_fault_inputs(6)
    for rnd in range(6):
        for budget in (100.0, 250.0, 1000.0):
            res = sim.resolve_round(
                rnd, list(range(8)), [10] * 8, [100.0] * 8, [budget] * 8
            )
            for i in range(8):
                d, a = _attempts_closed_form(
                    inputs["corrupt_u"][rnd][i], cfg.corrupt_prob,
                    cfg.max_retries, 100.0, budget,
                )
                assert (res.delivered[i], res.attempts[i]) == (d, a)


def test_harq_bytes_on_ledger():
    """attempts * spec.uplink_bytes is what lands on the wire ledger."""
    spec = PayloadSpec(num_samples=16, vocab=256, k=32, value_bits=16)
    from repro.core.protocol import UplinkPayload

    p = UplinkPayload(client_id=0, spec=spec, attempts=3)
    assert p.bytes == 3 * spec.uplink_bytes
    assert UplinkPayload(client_id=0, spec=spec).bytes == spec.uplink_bytes


if HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(
        p=st.floats(0.0, 1.0),
        max_retries=st.integers(0, 4),
        payload=st.floats(1.0, 1e4),
        budget=st.floats(0.0, 1e5),
        seed=st.integers(0, 1000),
    )
    def test_harq_property(p, max_retries, payload, budget, seed):
        """Property: attempts in [1, min(1+max_retries, affordable)] for any
        transmitter, and delivery implies the LAST attempt was clean."""
        cfg = FaultConfig(name="t", corrupt_prob=p, max_retries=max_retries)
        sim = FaultSimulator(1, cfg, seed=seed)
        res = sim.resolve_round(0, [0], [10], [payload], [budget])
        affordable = max(1, int(np.floor(budget / payload)))
        allowed = min(1 + max_retries, affordable)
        assert 1 <= res.attempts[0] <= allowed
        if not res.delivered[0]:
            assert res.attempts[0] == allowed
            assert res.reasons[0] == "corrupt"


# ---------------------------------------------------------------------------
# server-side wire validation / quarantine
# ---------------------------------------------------------------------------


def _wire(n=3, samples=4, vocab=64, k_cap=8, quantize=False):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(n, samples, vocab)).astype(np.float32))
    ks = jnp.asarray([k_cap] * n, jnp.int32)
    return sparsify_wire(logits, ks, k_cap, quantize=quantize)


@pytest.mark.parametrize("quantize", [False, True])
def test_validate_wire_accepts_honest(quantize):
    ok, reasons = validate_wire(_wire(quantize=quantize))
    assert ok.all() and all(r is None for r in reasons)


@pytest.mark.parametrize("mode,reason", [
    ("nan", "non_finite"), ("index", "index_range"),
    ("negative_index", "index_range"),
])
def test_validate_wire_rejects(mode, reason):
    wire = corrupt_wire(_wire(), [1], mode=mode)
    ok, reasons = validate_wire(wire)
    assert list(ok) == [True, False, True]
    assert reasons[1] == reason


def test_validate_quantized_wire_nan_scale():
    wire = corrupt_wire(_wire(quantize=True), [0], mode="nan")
    ok, reasons = validate_wire(wire)
    assert list(ok) == [False, True, True]
    assert reasons[0] == "non_finite"


def test_validate_wire_over_budget():
    """A payload claiming more entries than its Shannon budget affords is a
    fits violation."""
    wire = _wire(k_cap=8, samples=4)
    from repro.core.channel import bits_per_entry

    d = bits_per_entry(16, 64)
    honest = 8 * 4 * d  # k_cap entries x samples
    ok, reasons = validate_wire(wire, budget_bits=[honest, honest, honest - 1.0])
    assert list(ok) == [True, True, False]
    assert reasons[2] == "over_budget"


def test_quarantine_wire_is_k0_exclusion():
    """Quarantine == all-False transmit mask == the existing k = 0 path:
    the densified stack of a quarantined row is exactly zero."""
    wire = corrupt_wire(_wire(), [1], mode="nan")
    ok, _ = validate_wire(wire)
    q = quarantine_wire(wire, ok)
    dense = np.asarray(wire_densify(q))
    assert not q.mask[1].any()
    assert (dense[1] == 0).all()
    assert q.mask[0].any() and q.mask[2].any()


def test_validate_dense():
    stack = np.zeros((3, 4, 8), np.float32)
    stack[1, 2, 3] = np.nan
    ok, reasons = validate_dense(stack)
    assert list(ok) == [True, False, True]
    assert reasons[1] == "non_finite"
    h = np.zeros((3, 4, 2), np.float32)
    h[2, 0, 0] = np.inf
    ok2, _ = validate_dense(np.zeros((3, 4, 8), np.float32), h)
    assert list(ok2) == [True, True, False]


def test_server_aggregate_sparse_wire_validates():
    from repro.fed.server import Server

    server = Server(SERVER, seed=0, distill_steps=1)
    wire = corrupt_wire(_wire(n=3, samples=4, vocab=SERVER.vocab_size,
                              k_cap=8), [2], mode="nan")
    k_g, _ = server.aggregate_sparse_wire(wire, validate=True)
    assert np.isfinite(np.asarray(k_g)).all()


# ---------------------------------------------------------------------------
# end-to-end contracts on the engine ladder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["sequential", "batched", "fused", "fused_e2e"])
def test_none_preset_bit_identical(engine):
    """faults='none' must be indistinguishable from faults=None on every
    engine path — the disabled machinery costs nothing."""
    ds = _dataset()
    base = run_federated(CLIENT, SERVER, ds, _cfg(engine, rounds=2))
    none = run_federated(CLIENT, SERVER, ds, _cfg(engine, rounds=2, faults="none"))
    assert base.server_acc == none.server_acc
    assert base.client_acc == none.client_acc
    assert base.per_client_k == none.per_client_k
    for ra, rb in zip(base.ledger.rounds, none.ledger.rounds):
        assert ra.uplink_bytes == rb.uplink_bytes
    assert none.num_quarantined is None  # disabled config leaves taps off


def test_fault_parity_across_engines():
    """The same fault realisation must hit every engine identically: same
    quarantine/crash counts, same delivered k, same ledger bytes."""
    ds = _dataset()
    runs = {
        e: run_federated(CLIENT, SERVER, ds, _cfg(e, faults="corruption"))
        for e in ("sequential", "batched", "fused_e2e")
    }
    ref = runs["sequential"]
    assert sum(ref.num_quarantined) > 0, "corruption preset must engage"
    for name, run in runs.items():
        assert run.num_quarantined == ref.num_quarantined, name
        assert run.num_crashed == ref.num_crashed, name
        assert run.per_client_k == ref.per_client_k, name
        assert run.attempted_k == ref.attempted_k, name
        assert run.retrans_bytes == ref.retrans_bytes, name
        for ra, rb in zip(run.ledger.rounds, ref.ledger.rounds):
            assert ra.uplink_bytes == rb.uplink_bytes, name
            assert ra.num_transmitters == rb.num_transmitters, name


def test_corruption_retransmission_in_ledger():
    """Retransmission bytes appear in the ledger's uplink: a faulty run's
    uplink equals the fault-free uplink of the DELIVERED payloads plus the
    tapped retrans_bytes."""
    ds = _dataset()
    run = run_federated(CLIENT, SERVER, ds, _cfg("batched", faults="corruption"))
    assert sum(run.retrans_bytes) > 0
    for stats, retrans in zip(run.ledger.rounds, run.retrans_bytes):
        assert stats.retrans_bytes == retrans
        # the on-air total always covers the retransmitted copies
        assert stats.uplink_bytes >= retrans


def test_crashes_are_not_quarantine():
    """The crash path is observable as num_crashed (attempted > 0, zero
    bytes), distinct from both quarantine and the k = 0 budget path."""
    ds = _dataset()
    run = run_federated(
        CLIENT, SERVER, ds,
        _cfg("batched", rounds=4,
             faults=FaultConfig(name="t", crash_prob=0.5)),
    )
    assert sum(run.num_crashed) > 0
    assert sum(run.num_quarantined) == 0
    for rnd, n_crash in enumerate(run.num_crashed):
        # every crash is a client with attempted k > 0 that delivered k = 0
        lost = sum(
            1 for ak, dk in zip(run.attempted_k[rnd], run.per_client_k[rnd])
            if ak > 0 and dk == 0
        )
        assert lost >= n_crash


def test_faults_require_adaptive_k():
    ds = _dataset()
    with pytest.raises(ValueError, match="adaptive"):
        run_federated(CLIENT, SERVER, ds,
                      _cfg("batched", method="all_logits", faults="corruption"))


def test_summary_nan_safe():
    """FedRun.summary() must survive all-dropped rounds (NaN accuracies):
    max() over a NaN-bearing list is order-dependent."""
    from repro.core.protocol import CommLedger
    from repro.fed.rounds import FedRun

    run = FedRun(ledger=CommLedger(), server_acc=[0.5, float("nan"), 0.3],
                 client_acc=[], mean_k=[])
    assert run.summary()["best_server_acc"] == 0.5
    empty = FedRun(ledger=CommLedger(), server_acc=[float("nan")],
                   client_acc=[], mean_k=[])
    assert np.isnan(empty.summary()["best_server_acc"])


def test_fault_config_in_fingerprint():
    """Changing the fault preset must fail a resume fingerprint check."""
    from repro.fed.rounds import _config_fingerprint

    a = _config_fingerprint(_cfg("batched"))
    b = _config_fingerprint(_cfg("batched", faults="corruption"))
    assert a != b
    assert _config_fingerprint(_cfg("batched", rounds=9)) == a  # rounds excluded


def test_scan_rounds_fault_parity():
    """The multi-round lax.scan driver consumes faults as pure data masks:
    same realisation as the per-round host path."""
    ds = _dataset()
    host = run_federated(CLIENT, SERVER, ds, _cfg("fused_e2e", faults="corruption"))
    scan = run_federated(
        CLIENT, SERVER, ds,
        dataclasses.replace(_cfg("fused_e2e", faults="corruption"), scan_rounds=True),
    )
    assert scan.num_quarantined == host.num_quarantined
    assert scan.per_client_k == host.per_client_k
    assert scan.retrans_bytes == host.retrans_bytes
    for ra, rb in zip(scan.ledger.rounds, host.ledger.rounds):
        assert ra.uplink_bytes == rb.uplink_bytes


# ---------------------------------------------------------------------------
# golden trajectory: host loop vs one-dispatch scan, committed record
# ---------------------------------------------------------------------------

_GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data", "faults_golden.json")
_GOLDEN_PRESETS = ("corruption", "crashes", "lossy")
_GOLDEN_ROUNDS = 3
_GOLDEN_FIELDS = ("ks", "uplink_bytes", "num_quarantined", "num_crashed", "retrans_bytes")


def _golden_record(run) -> dict:
    return {
        "ks": [[int(k) for k in ks] for ks in run.per_client_k],
        "uplink_bytes": [float(r.uplink_bytes) for r in run.ledger.rounds],
        "num_quarantined": [int(n) for n in run.num_quarantined],
        "num_crashed": [int(n) for n in run.num_crashed],
        "retrans_bytes": [float(b) for b in run.retrans_bytes],
    }


def _golden_runs(preset: str):
    """The tiny fault scenario as a per-round host loop and as one scan."""
    ds = _dataset()
    cfg = _cfg("fused_e2e", rounds=_GOLDEN_ROUNDS, faults=preset)
    host = run_federated(CLIENT, SERVER, ds, cfg)
    scan = run_federated(CLIENT, SERVER, ds, dataclasses.replace(cfg, scan_rounds=True))
    return host, scan


@pytest.mark.parametrize("preset", _GOLDEN_PRESETS)
def test_golden_trajectory_host_vs_scan(preset):
    with open(_GOLDEN_PATH) as f:
        golden = json.load(f)[preset]
    host, scan = _golden_runs(preset)
    # host loop == scan == the committed record, exactly
    assert _golden_record(host) == _golden_record(scan) == golden
    assert all(0.0 <= a <= 1.0 for a in host.server_acc + scan.server_acc)


def test_golden_record_is_current():
    """The committed record covers exactly the golden presets, one entry a
    round, and every preset engages its faults (catches a stale file after
    an intentional regeneration)."""
    with open(_GOLDEN_PATH) as f:
        golden = json.load(f)
    assert set(golden) == set(_GOLDEN_PRESETS)
    for rec in golden.values():
        assert set(rec) == set(_GOLDEN_FIELDS)
        assert all(len(rec[field]) == _GOLDEN_ROUNDS for field in _GOLDEN_FIELDS)
    assert sum(golden["corruption"]["num_quarantined"]) > 0
    assert sum(golden["corruption"]["retrans_bytes"]) > 0
    assert sum(golden["crashes"]["num_crashed"]) > 0


if __name__ == "__main__":
    import sys

    from test_scenario import write_golden

    if "--regen" in sys.argv:
        record = {}
        for preset in _GOLDEN_PRESETS:
            host, scan = _golden_runs(preset)
            assert _golden_record(host) == _golden_record(scan), preset
            record[preset] = _golden_record(host)
        write_golden(_GOLDEN_PATH, record)
        print(f"wrote {_GOLDEN_PATH}")
    else:
        print("usage: PYTHONPATH=src python tests/test_faults.py --regen")
