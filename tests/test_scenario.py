"""Scenario engine: time-correlated channel dynamics (PR 7).

Three layers of guarantees:

1. Statistical correctness of the dynamics themselves — the Gauss-Markov
   chain's lag-1 autocorrelation matches ``rho``, its stationary marginal
   stays the i.i.d. Exp(1) Rayleigh-power law (the Gaussian copula's whole
   point), the Gilbert-Elliott burst-length mean matches the closed form
   1/p_bg, and Jakes' Doppler correlation comes out of the J0 Bessel form.
2. Determinism/keying — ``rho=0`` (and every i.i.d.-equivalent spelling:
   ``scenario=None``, ``ScenarioConfig()``, the ``iid`` preset) is
   bit-identical to the legacy per-round draws; realisations are invariant
   to query call-order and cohort permutation (PR-4's re-keying guarantees
   extended to stateful channels).
3. The golden trajectory — a committed tiny-scenario record
   (tests/data/scenario_golden.json: per-round k / payload bytes / outage
   for every correlated preset at fixed seed) asserted
   bit-identical between the host round loop and the one-dispatch
   ``run_rounds`` scan, whose in-scan channel tap must replay the host
   simulator.

Regenerate the golden record (only after an intentional format change):

    PYTHONPATH=src python tests/test_scenario.py --regen
"""

import json
import math
import os

import numpy as np
import pytest

from repro.core.channel import ChannelConfig, ChannelSimulator
from repro.core.scenario import (
    ScenarioConfig,
    bessel_j0,
    exp_to_gauss,
    ge_mean_burst,
    ge_stationary_bad,
    get_scenario,
    jakes_rho,
)

_GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data", "scenario_golden.json")
_GOLDEN_PRESETS = ("gauss_markov", "jakes", "gilbert_elliott", "mobility")
_GOLDEN_SELS = [[0, 1], [2, 3], [1, 2]]
_GOLDEN_CHAN = ChannelConfig(
    bandwidth_hz=2e5, mean_snr_db=2.0, min_k=0, dropout_prob=0.25
)


# ---------------------------------------------------------------------------
# presets / config validation / Jakes
# ---------------------------------------------------------------------------


def test_preset_registry():
    for name in ("iid", "gauss_markov", "jakes", "gilbert_elliott", "mobility"):
        sc = get_scenario(name)
        assert isinstance(sc, ScenarioConfig) and sc.name == name
    assert get_scenario(None) is None
    custom = ScenarioConfig(name="mine", rho=0.5)
    assert get_scenario(custom) is custom
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("definitely_not_a_preset")


def test_config_validation():
    with pytest.raises(ValueError, match="rho"):
        ScenarioConfig(rho=1.0)
    with pytest.raises(ValueError, match="rho"):
        ScenarioConfig(rho=-0.1)
    with pytest.raises(ValueError, match="p_gb"):
        ScenarioConfig(p_gb=0.2)  # p_bg missing
    with pytest.raises(ValueError, match=r"p_gb must be in \[0, 1\]"):
        ScenarioConfig(p_gb=1.5, p_bg=0.5)
    with pytest.raises(ValueError, match="period"):
        ScenarioConfig(snr_period_rounds=0.0)


def test_bessel_j0_reference_values():
    # Abramowitz & Stegun table values (scipy.special.j0 cross-checked to
    # <5e-9 during development; scipy itself is not a dependency).
    for x, want in [(0.0, 1.0), (1.0, 0.7651976866), (2.404825558, 0.0),
                    (5.0, -0.1775967713), (10.0, -0.2459357645)]:
        assert bessel_j0(x) == pytest.approx(want, abs=1e-7)


def test_jakes_rho_physics():
    # rho = J0(2 pi f_d T): zero velocity -> full correlation; faster
    # clients decorrelate; the preset's pedestrian 1 m/s @ 2.6 GHz, 5 ms
    # slot sits near 0.98.
    assert jakes_rho(0.0, 2.6e9, 5e-3) == pytest.approx(1.0, abs=1e-6)
    rhos = [jakes_rho(v, 2.6e9, 5e-3) for v in (0.5, 1.0, 3.0, 10.0)]
    assert rhos == sorted(rhos, reverse=True)
    assert jakes_rho(1.0, 2.6e9, 5e-3) == pytest.approx(0.9815, abs=1e-3)
    sc = get_scenario("jakes")
    assert sc.effective_rho == pytest.approx(jakes_rho(1.0, 2.6e9, 5e-3))


# ---------------------------------------------------------------------------
# statistical properties of the dynamics
# ---------------------------------------------------------------------------


def _realise(sim: ChannelSimulator, rounds: int) -> np.ndarray:
    """(rounds, num_clients) realised SNR dB."""
    ids = list(range(sim.num_clients))
    return np.array(
        [[s.snr_db for s in sim.states(r, ids)] for r in range(rounds)]
    )


def _fade_power(sim: ChannelSimulator, snr: np.ndarray) -> np.ndarray:
    """Invert the realised SNR back to the fading power (Exp(1) marginal)."""
    base = sim.config.mean_snr_db + sim._shadowing_db
    return 10.0 ** ((snr - base[None, :]) / 10.0)


def test_gauss_markov_autocorrelation_matches_rho():
    rho = 0.8
    cfg = ChannelConfig(scenario=ScenarioConfig(name="gm", rho=rho))
    sim = ChannelSimulator(8, cfg, seed=3)
    power = _fade_power(sim, _realise(sim, 300))
    z = exp_to_gauss(power)  # back to the underlying AR(1) Gaussian
    a, b = z[:-1].ravel(), z[1:].ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr == pytest.approx(rho, abs=0.05)


def test_gauss_markov_stationary_marginal_is_exp1():
    # The copula construction keeps the per-round marginal EXACTLY the
    # i.i.d. Exp(1) Rayleigh power regardless of rho — check the first two
    # moments and the median against the closed form.
    cfg = ChannelConfig(scenario=ScenarioConfig(name="gm", rho=0.9))
    sim = ChannelSimulator(8, cfg, seed=5)
    p = _fade_power(sim, _realise(sim, 400)).ravel()
    assert np.mean(p) == pytest.approx(1.0, abs=0.08)
    assert np.median(p) == pytest.approx(math.log(2), abs=0.06)
    assert np.mean(p > 3.0) == pytest.approx(math.exp(-3.0), abs=0.02)


def test_gilbert_elliott_burst_statistics():
    p_gb, p_bg = 0.2, 0.25
    cfg = ChannelConfig(
        fast_fading=False,
        scenario=ScenarioConfig(name="ge", p_gb=p_gb, p_bg=p_bg),
    )
    sim = ChannelSimulator(6, cfg, seed=11)
    bad = ~np.isfinite(_realise(sim, 500))
    # stationary occupancy
    assert ge_stationary_bad(p_gb, p_bg) == pytest.approx(p_gb / (p_gb + p_bg))
    assert np.mean(bad) == pytest.approx(ge_stationary_bad(p_gb, p_bg), abs=0.05)
    # mean burst length == 1/p_bg (geometric dwell in the bad state)
    bursts = []
    for c in range(bad.shape[1]):
        run = 0
        for b in bad[:, c]:
            if b:
                run += 1
            elif run:
                bursts.append(run)
                run = 0
        if run:
            bursts.append(run)
    assert ge_mean_burst(p_bg) == pytest.approx(1.0 / p_bg)
    assert np.mean(bursts) == pytest.approx(ge_mean_burst(p_bg), abs=0.6)


# ---------------------------------------------------------------------------
# rho = 0 bit-identity + keying invariances
# ---------------------------------------------------------------------------

_IID_SPELLINGS = [None, ScenarioConfig(), "iid"]


@pytest.mark.parametrize("dropout", [0.0, 0.3])
@pytest.mark.parametrize("spelling", _IID_SPELLINGS[1:], ids=["default", "iid"])
def test_iid_spellings_bit_identical_to_legacy(dropout, spelling):
    base_cfg = ChannelConfig(bandwidth_hz=2e5, mean_snr_db=2.0, dropout_prob=dropout)
    legacy = ChannelSimulator(5, base_cfg, seed=0)
    scen_cfg = ChannelConfig(
        bandwidth_hz=2e5, mean_snr_db=2.0, dropout_prob=dropout,
        scenario=get_scenario(spelling),
    )
    sim = ChannelSimulator(5, scen_cfg, seed=0)
    ids = list(range(5))
    for r in range(6):
        a = [s.snr_db for s in legacy.states(r, ids)]
        b = [s.snr_db for s in sim.states(r, ids)]
        assert a == b  # exact, including -inf outage positions


def test_rho_zero_hypothesis_sweep():
    """rho=0 must be bit-identical to the legacy i.i.d. draws for ANY
    (seed, dropout, round, cohort) — the property, swept by hypothesis."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        dropout=st.sampled_from([0.0, 0.2, 0.7]),
        rnd=st.integers(0, 12),
        cohort=st.lists(st.integers(0, 5), min_size=1, max_size=6, unique=True),
    )
    def prop(seed, dropout, rnd, cohort):
        cfg = dict(bandwidth_hz=2e5, mean_snr_db=2.0, dropout_prob=dropout)
        legacy = ChannelSimulator(6, ChannelConfig(**cfg), seed=seed)
        sim = ChannelSimulator(
            6, ChannelConfig(**cfg, scenario=ScenarioConfig()), seed=seed
        )
        assert [s.snr_db for s in legacy.states(rnd, cohort)] == [
            s.snr_db for s in sim.states(rnd, cohort)
        ]

    prop()


def test_query_order_and_cohort_permutation_invariance():
    """Stateful channels keep PR-4's guarantee: the realisation is a pure
    function of (seed, round, cid) — query order, cohort composition and
    cohort ordering don't change it."""
    cfg = ChannelConfig(
        bandwidth_hz=2e5, mean_snr_db=2.0, dropout_prob=0.2,
        scenario=get_scenario("gauss_markov"),
    )
    in_order = ChannelSimulator(6, cfg, seed=4)
    want = {r: [s.snr_db for s in in_order.states(r, range(6))] for r in range(5)}

    shuffled = ChannelSimulator(6, cfg, seed=4)
    # later round first, then a permuted subset of an earlier round
    got4 = [s.snr_db for s in shuffled.states(4, [5, 0, 3])]
    assert got4 == [want[4][5], want[4][0], want[4][3]]
    got1 = [s.snr_db for s in shuffled.states(1, [2, 1])]
    assert got1 == [want[1][2], want[1][1]]
    # re-query is stable
    assert [s.snr_db for s in shuffled.states(4, range(6))] == want[4]


def test_step_channel_carry_contract():
    cfg = ChannelConfig(scenario=get_scenario("gauss_markov"))
    sim = ChannelSimulator(4, cfg, seed=0)
    carry = sim.init_channel_carry()
    assert carry.round_index == -1
    carry, snr, bad = sim.step_channel(carry, 0)
    assert carry.round_index == 0 and snr.shape == (4,) and bad.shape == (4,)
    with pytest.raises(ValueError, match="contiguous"):
        sim.step_channel(carry, 5)


def test_scan_channel_inputs_operands():
    sim = ChannelSimulator(
        3, ChannelConfig(scenario=get_scenario("iid")), seed=0
    )
    ops = sim.scan_channel_inputs(4)
    assert ops["w"].shape == (4, 3) and ops["u"].shape == (4, 3)
    assert ops["base_snr_db"].shape == (4, 3)
    assert ops["z0"].shape == (3,) and ops["bad0"].shape == (3,)
    # the iid preset is served by the SAME executable via rho=0 data
    assert float(ops["rho"]) == 0.0
    assert float(ops["fade_scale"]) == 1.0
    gm = ChannelSimulator(
        3, ChannelConfig(scenario=get_scenario("gauss_markov")), seed=0
    )
    assert float(gm.scan_channel_inputs(4)["rho"]) == pytest.approx(0.9)


# ---------------------------------------------------------------------------
# hygiene: query validation
# ---------------------------------------------------------------------------


def test_states_rejects_negative_round_and_duplicates():
    sim = ChannelSimulator(4, ChannelConfig(), seed=0)
    with pytest.raises(ValueError, match="round_index"):
        sim.states(-1, [0, 1])
    with pytest.raises(ValueError, match="duplicate"):
        sim.states(0, [1, 1])
    with pytest.raises(ValueError, match="round_index"):
        sim.topk_for(-3, [0], vocab_size=256, num_samples=16)
    with pytest.raises(ValueError, match="duplicate"):
        sim.topk_for(0, [2, 0, 2], vocab_size=256, num_samples=16)
    # valid queries still work
    assert len(sim.states(0, [0, 1])) == 2


# ---------------------------------------------------------------------------
# golden trajectory: host loop vs one-dispatch scan, committed record
# ---------------------------------------------------------------------------


def _golden_run(preset: str):
    """Host-loop and scan runs of the tiny golden scenario, plus the host
    simulator the scan's in-scan tap must replay."""
    import dataclasses

    import jax.numpy as jnp

    from test_engine import _e2e_engine, _shared_cohort

    ds, c_host = _shared_cohort(4)
    _, c_scan = _shared_cohort(4)
    cfg = dataclasses.replace(_GOLDEN_CHAN, scenario=get_scenario(preset))
    sim = ChannelSimulator(4, cfg, seed=0)
    sels = _GOLDEN_SELS
    pubs = [jnp.asarray(ds.tokens[16 * r:16 * (r + 1)]) for r in range(3)]
    states = [sim.states_batched(r, sels[r]) for r in range(3)]

    host = _e2e_engine(c_host, ds, k_min=0)
    bcast, host_ks, host_bytes = None, [], []
    for r in range(3):
        ph = host.run_round(
            sels[r], pubs[r], bcast, states[r], adaptive_k=True, send_h=True
        )
        bcast = host.broadcast_state(pubs[r])
        host_ks.append(ph.ks)
        host_bytes.append([p.bytes for p in ph.payloads])

    scan = _e2e_engine(c_scan, ds, k_min=0)
    traj = scan.run_rounds(
        sels, pubs, states, adaptive_k=True, send_h=True,
        channel_scan=sim.scan_channel_inputs(3),
    )
    return sim, sels, host_ks, host_bytes, traj


def _golden_record(preset: str) -> dict:
    sim, sels, host_ks, host_bytes, traj = _golden_run(preset)
    assert traj.ks == host_ks
    assert [[p.bytes for p in pl] for pl in traj.payloads] == host_bytes
    return {
        "ks": host_ks,
        "payload_bytes": host_bytes,
        "outage": [[bool(o) for o in row] for row in traj.outage],
        "snr_db": [
            [round(s, 3) if math.isfinite(s) else None for s in row]
            for row in traj.snr_db
        ],
    }


@pytest.mark.parametrize("preset", _GOLDEN_PRESETS)
def test_golden_trajectory_host_vs_scan(preset):
    with open(_GOLDEN_PATH) as f:
        golden = json.load(f)[preset]
    sim, sels, host_ks, host_bytes, traj = _golden_run(preset)

    # host loop == scan == the committed record, bit-for-bit on k and bytes
    assert traj.ks == host_ks == golden["ks"]
    assert [[p.bytes for p in pl] for pl in traj.payloads] \
        == host_bytes == golden["payload_bytes"]

    # the in-scan channel tap replays the host simulator's realisation
    for r in range(3):
        host_states = sim.states(r, sels[r])
        for i, st in enumerate(host_states):
            assert bool(traj.outage[r][i]) == (st.snr_db == -math.inf)
            assert bool(traj.outage[r][i]) == golden["outage"][r][i]
            g = golden["snr_db"][r][i]
            if g is None:
                assert not math.isfinite(traj.snr_db[r][i])
            else:
                assert traj.snr_db[r][i] == pytest.approx(g, abs=5e-3)
                assert st.snr_db == pytest.approx(g, abs=5e-3)


def write_golden(path: str, record: dict) -> None:
    """One line a preset, so a regenerated record diffs by preset."""
    lines = [f" {json.dumps(name)}: {json.dumps(rec)}" for name, rec in record.items()]
    with open(path, "w") as f:
        f.write("{\n" + ",\n".join(lines) + "\n}\n")


def test_golden_record_is_current():
    """The committed record covers exactly the golden presets (catches a
    stale file after an intentional regeneration)."""
    with open(_GOLDEN_PATH) as f:
        golden = json.load(f)
    assert set(golden) == set(_GOLDEN_PRESETS)
    for preset in _GOLDEN_PRESETS:
        rec = golden[preset]
        assert set(rec) == {"ks", "payload_bytes", "outage", "snr_db"}
        assert len(rec["ks"]) == len(_GOLDEN_SELS)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.dirname(__file__))
    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(_GOLDEN_PATH), exist_ok=True)
        write_golden(_GOLDEN_PATH, {p: _golden_record(p) for p in _GOLDEN_PRESETS})
        print(f"wrote {_GOLDEN_PATH}")
    else:
        print("usage: PYTHONPATH=src python tests/test_scenario.py --regen")
