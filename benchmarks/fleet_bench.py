"""Fleet-scale FleetStore benchmark (PR 9) — writes BENCH_fleet[.quick].json.

The claim under test: with ``fleet_store="host"`` the per-round cost of a
federated round is a function of the COHORT, not the fleet — a 100k-client
fleet (cohort 10) runs each round at (within noise of) the 10-client
fleet's latency, with the device-resident fleet footprint independent of N
(the shared frozen backbone only; cohort and prefetch buffers are
transient).  Four readings:

* ``device_n10``    — the default device store at N=10: the pre-PR-9
                      layout, whose device footprint is the whole stacked
                      fleet (the O(N) curve the host store removes).
* ``host_bit_identical`` — a host-store N=10 run replays the same cohort
                      sequence as the device-store run: per-round adaptive
                      k, payload bytes, and the FINAL fleet lora/opt state
                      must match exactly (the streamed rows round-trip
                      host<->device losslessly).
* ``fleet``         — the scale sweep: N in {10, 1k, 10k, 100k} host-store
                      fleets (template-row lazy init past N=10; a pool of
                      10 real client datasets cycles mod 10 — client RNG
                      streams are pool state, fleet trainable state is the
                      store's) at fixed cohort 10, timing run_round with
                      the round driver's prefetch pattern (hint round r+1
                      BEFORE fetching round r).
* ``ratios``        — per-N latency vs the N=10 host run, and the
                      flatness of the device-resident fleet bytes.

benchmarks/check_bench.py gates on this record: bit-identity true, device
bytes flat across N, and every latency ratio <= 1.15.

Run:  PYTHONPATH=src python benchmarks/fleet_bench.py [--quick]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")

COHORT = 10  # the paper's clients_per_round


class _CyclingClients:
    """A pool of ``len(base)`` real clients presented as an N-client
    fleet: dataset shards and RNG streams cycle mod the pool size, while
    the per-client TRAINABLE state stays truly per-client in the store
    (the only state that scales with N)."""

    def __init__(self, base):
        self._base = list(base)

    def __getitem__(self, i):
        return self._base[int(i) % len(self._base)]

    def __len__(self):
        return len(self._base)


def _build():
    from repro.configs.base import LoRAConfig
    from repro.configs.gpt2_paper import REDUCED_CLIENT
    from repro.data import make_banking77_like
    from repro.fed.client import Client
    from repro.models import init as model_init

    lora = LoRAConfig(rank=4, alpha=32.0, dropout=0.0, targets=("q", "v", "head"))
    cfg = REDUCED_CLIENT.with_overrides(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=1024, max_seq_len=32, lora=lora,
    )
    ds = make_banking77_like(
        vocab_size=cfg.vocab_size, seq_len=16, total=60 * COHORT + 100, seed=0
    )
    backbone = model_init(jax.random.PRNGKey(123), cfg)

    def cohort():
        return [
            Client(i, cfg, ds.subset(np.arange(i * 60, (i + 1) * 60)),
                   num_classes=ds.num_classes, seed=i, local_steps=2,
                   distill_steps=1, initial_params=backbone)
            for i in range(COHORT)
        ]

    pub = jnp.asarray(ds.tokens[-64:])
    return cfg, ds, cohort, pub


def _mk_engine(cohort, cfg, num_classes, fleet_store):
    from repro.fed.engine import FusedEngine

    return FusedEngine(
        cohort(), cfg, num_classes=num_classes, local_steps=2,
        distill_steps=1, fleet_store=fleet_store,
    )


def _drive(engine, sels, pub, states_for, *, collect=False):
    """Run one round per sel with the round driver's prefetch pattern
    (hint round r+1 BEFORE running round r).  Returns per-round wall
    times, and (ks, payload bytes) per round when ``collect``."""
    times, rows = [], []
    for r, sel in enumerate(sels):
        if r + 1 < len(sels):
            engine.prefetch_cohort(sels[r + 1])
        states = states_for(r)
        t0 = time.time()
        phase = engine.run_round(sel, pub, None, states,
                                 adaptive_k=True, send_h=True)
        if phase.dense is not None:
            jax.block_until_ready(phase.dense)
        times.append(time.time() - t0)
        if collect:
            rows.append((list(phase.ks),
                         [p.bytes for p in phase.payloads]))
    return times, rows


def _fleet_leaves(store):
    state = store.state_dict()
    return [np.asarray(x)
            for k in ("lora", "opt")
            for x in jax.tree.leaves(state[k])]


def bench_fleet(quick: bool = True, out_json: str | None = None):
    from repro.core import ChannelConfig, ChannelSimulator
    from repro.fed.store import HostFleetStore
    from repro.lora import split_lora

    cfg, ds, cohort, pub = _build()
    sim = ChannelSimulator(
        COHORT, ChannelConfig(bandwidth_hz=5e5, mean_snr_db=5.0), seed=0
    )
    # channel realisations are per cohort POSITION here (the bench fixes
    # the physical link pool, like the client pool)
    states_for = lambda r: sim.states_batched(r % 20, list(range(COHORT)))  # noqa: E731

    rounds = 3 if quick else 5
    warmup = 1
    ns = [10, 1_000, 10_000] if quick else [10, 1_000, 10_000, 100_000]

    # -- bit-identity: device vs host at N=10, same cohort sequence -------
    rng = np.random.default_rng(7)
    id_sels = [[int(x) for x in rng.permutation(COHORT)] for _ in range(4)]
    dev_eng = _mk_engine(cohort, cfg, ds.num_classes, "device")
    host_eng = _mk_engine(cohort, cfg, ds.num_classes, "host")
    _, dev_rows = _drive(dev_eng, id_sels, pub, states_for, collect=True)
    _, host_rows = _drive(host_eng, id_sels, pub, states_for, collect=True)
    bit_identical = dev_rows == host_rows and all(
        np.array_equal(a, b)
        for a, b in zip(_fleet_leaves(dev_eng._store),
                        _fleet_leaves(host_eng._store))
    )
    assert bit_identical, (
        "host-store N=10 run diverged from the device-store run "
        f"(ks/bytes match: {dev_rows == host_rows})"
    )
    dev_bytes_n10 = dev_eng._store.device_bytes()

    # -- scale sweep: host store, fixed cohort, growing fleet -------------
    lora0, frozen0 = split_lora(cohort()[0].params)
    opt0 = cohort()[0].opt
    fleet = {}
    for n in ns:
        eng = _mk_engine(cohort, cfg, ds.num_classes, "host")
        if n > COHORT:
            eng._store = HostFleetStore.from_template(
                lora0, frozen0, opt0, num_clients=n
            )
            eng.clients = _CyclingClients(eng.clients)
        rng = np.random.default_rng(1)
        sels = [sorted(int(x) for x in rng.choice(n, COHORT, replace=False))
                for _ in range(warmup + rounds)]
        times, _ = _drive(eng, sels, pub, states_for)
        fleet[str(n)] = {
            "sec_per_round": round(min(times[warmup:]), 4),
            "fleet_device_bytes": eng._store.device_bytes(),
            "fleet_host_bytes": eng._store.host_bytes(),
        }

    base = fleet[str(COHORT)]["sec_per_round"]
    dev_flat = [fleet[str(n)]["fleet_device_bytes"] for n in ns]
    ratios = {
        "latency_vs_n10": {
            str(n): round(fleet[str(n)]["sec_per_round"] / base, 3) for n in ns
        },
        "host_device_bytes_flat": round(max(dev_flat) / min(dev_flat), 4),
    }
    shape = (f"cohort={COHORT};L2;d64;V{cfg.vocab_size};T16;P64;steps=2+1;"
             f"rank{cfg.lora.rank}")

    if out_json:
        record = {
            "bench": "fleet_store",
            "shape": shape,
            "quick": quick,
            "rounds_timed": rounds,
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "host_bit_identical": bool(bit_identical),
            "device_n10": {
                "fleet_device_bytes": dev_bytes_n10,
                "note": "pre-PR-9 layout: whole fleet stacked on device "
                        "(grows O(N); at N=100k this tree would be "
                        f"~{dev_bytes_n10 // COHORT * 100_000 / 1e9:.1f} GB)",
            },
            "fleet": fleet,
            "ratios": ratios,
            "notes": (
                "Host-store fleets at fixed cohort 10: N>10 fleets use "
                "HostFleetStore.from_template (calloc-backed lazy rows; "
                "resident memory scales with committed rows) over a pool "
                "of 10 real client datasets cycling mod 10 — trainable "
                "state is truly per-client in the store.  Rounds run with "
                "the driver's prefetch pattern (hint r+1 before round r); "
                "min-of-rounds on this noisy CPU container.  "
                "fleet_device_bytes = device-RESIDENT fleet footprint "
                "between rounds (shared frozen backbone only for the host "
                "store — flat in N); fleet_host_bytes is the host stack's "
                "address-space size (calloc: mostly untouched pages at "
                "large N).  host_bit_identical: device- and host-store "
                "N=10 runs produced identical per-round k, payload bytes, "
                "and final fleet lora/opt state."
            ),
        }
        with open(out_json, "w") as f:
            json.dump(record, f, indent=1)

    rows = [("fleet_device_n10_bytes", dev_bytes_n10, shape)]
    for n in ns:
        e = fleet[str(n)]
        rows.append((
            f"fleet_host_n{n}_round",
            e["sec_per_round"] * 1e6,
            f"{shape};dev_bytes={e['fleet_device_bytes']}"
            f";vs_n10={ratios['latency_vs_n10'][str(n)]:.2f}x",
        ))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = "--quick" in sys.argv
    suffix = "quick.json" if quick else "json"
    out = os.path.join(_REPO_ROOT, f"BENCH_fleet.{suffix}")
    for name, us, derived in bench_fleet(quick=quick, out_json=out):
        print(f"{name},{us:.0f},{derived}")
    with open(out) as f:
        rec = json.load(f)
    for n, r in rec["ratios"]["latency_vs_n10"].items():
        print(f"latency N={n} vs N=10: {r:.2f}x")
    print(f"device-bytes flatness: {rec['ratios']['host_device_bytes_flat']}")
    print(f"-> {out}")
