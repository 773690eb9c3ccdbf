"""Federated AdaLD round orchestration (paper Algorithm 1 + §IV setup).

One communication round (Fig. 1's 10 steps):
  1. server broadcasts global knowledge {K_g, h_g} (downlink accounted);
  2. selected clients distill locally against it (lines 5-7);
  3. clients fine-tune on private data (line 8);
  4. clients infer the public set, adaptively Top-k by live channel state
     (lines 9-10) and upload sparse logits + LoRA projections (line 11);
  5. server aggregates (line 15), distills into the LLM (line 16).

Four method presets reproduce the paper's comparison (§IV):
  adald      — adaptive Top-k + adaptive aggregation + LoRA-projection loss
  adaptive   — adaptive Top-k + adaptive aggregation, logits-only
  zeropad    — adaptive Top-k + zero-padding mean aggregation, logits-only
  all_logits — full logits (k = vocab), mean aggregation, logits-only
"""

from __future__ import annotations

import dataclasses
import json
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_io
from repro.configs.base import ModelConfig
from repro.core.channel import ChannelConfig, ChannelSimulator
from repro.core.faults import FaultConfig, FaultSimulator, get_faults, validate_dense
from repro.core.scenario import ScenarioConfig, get_scenario
from repro.core.protocol import CommLedger, RoundStats, downlink_bits
from repro.data.partition import dirichlet_partition, iid_partition, split_public_private
from repro.data.synthetic import IntentDataset
from repro.fed.client import Client, make_upload_payload
from repro.fed.engines import BroadcastState, cohort_budgets, make_engine
from repro.fed.server import Server
from repro.fed.steps import EVAL_BATCH, make_eval_fn

__all__ = ["FedConfig", "FedRun", "run_federated", "METHODS"]

Method = Literal["adald", "adaptive", "zeropad", "all_logits"]
Engine = Literal["sequential", "batched", "fused", "fused_e2e"]

METHODS: dict[str, dict] = {
    "adald": dict(aggregation="adaptive", send_h=True, adaptive_k=True),
    "adaptive": dict(aggregation="adaptive", send_h=False, adaptive_k=True),
    "zeropad": dict(aggregation="zeropad", send_h=False, adaptive_k=True),
    "all_logits": dict(aggregation="zeropad", send_h=False, adaptive_k=False),
}


@dataclasses.dataclass
class FedConfig:
    """Paper Table I defaults (reduced-scale knobs exposed)."""

    method: Method = "adald"
    # Round executor: "batched" stacks the selected cohort along a leading
    # client axis and runs each phase as one vmapped/jitted step; "fused"
    # additionally collapses the whole CLIENT phase into ONE jitted round
    # body (adaptive k as data); "fused_e2e" folds the SERVER phase in too
    # (sparse-wire aggregation + server distillation + broadcast — a whole
    # round is one compiled call); "sequential" is the bit-compatible
    # one-client-at-a-time reference.
    engine: Engine = "batched"
    # Fused engines: place the client axis over jax devices (shard_map).  For
    # "fused_e2e" the placement lives INSIDE the whole-round executable (the
    # server phase stays replicated); odd cohorts are padded with masked
    # k = 0 rows.
    shard_clients: bool = False
    # fused_e2e only: run ALL rounds as ONE compiled lax.scan dispatch
    # (FusedE2EEngine.run_rounds) with the per-round eval tapped inside the
    # scan — the R-round trajectory (accuracies, distill loss, mean_k) comes
    # back as scanned outputs instead of R host round-trips.
    scan_rounds: bool = False
    # Fleet-state residency (repro.fed.store): "device" keeps the whole
    # fleet's LoRA/opt stacked on the accelerator (bit-identical to the
    # pre-store layout); "host" keeps the fleet in host memory and streams
    # only each round's cohort to the device — O(cohort) device memory at
    # any fleet size, with round r+1's cohort transfer prefetched under
    # round r's compute.  scan_rounds requires "device" (the multi-round
    # scan carries the whole fleet as a donated device operand); with
    # "host" the run falls back to the per-round driver.  Checkpoints are
    # layout-compatible across stores ("host" writes per-client-range
    # shards instead of one fleet tree), so the knob is excluded from the
    # resume fingerprint.
    fleet_store: str = "device"
    num_clients: int = 50
    clients_per_round: int = 10
    rounds: int = 20
    public_size: int = 2000
    non_iid: bool = True
    dirichlet_gamma: float = 0.5
    seed: int = 0
    temperature: float = 2.0
    lam: float = 0.03
    lr: float = 1e-3
    distill_lr: float = 3e-3
    local_steps: int = 4
    distill_steps: int = 2       # client-side distill updates per round
    server_distill_steps: int = 12  # server-side (the LLM learns only here)
    public_batch: int = 256  # samples of the public set used per round
    eval_size: int = 512
    use_kernels: bool = False
    restrict_to_support: bool = False
    # Quantize the sparse uplink wire to int8 values + one fp32 scale per
    # (client, sample) row: (value, index) entries are priced at 8 bits, so
    # the same Shannon budget affords a genuinely larger adaptive k at a
    # fixed SNR (the projection h stays at ``channel.value_bits``).  Served
    # by the batched/fused engines; "sequential" rejects it.
    quantize_wire: bool = False
    # Round-body compute dtype for the fused engines ("float32" |
    # "bfloat16"): forward/backward math runs in the given dtype while the
    # LoRA/optimizer master state stays fp32 (the cast lives inside the
    # differentiated loss, so grads accumulate back to fp32 before AdamW).
    compute_dtype: str = "float32"
    channel: ChannelConfig = dataclasses.field(default_factory=ChannelConfig)
    # Channel-dynamics scenario: a repro.core.scenario preset name
    # ("iid" | "gauss_markov" | "jakes" | "gilbert_elliott" | "mobility"),
    # a ScenarioConfig, or None (i.i.d., bit-identical to the pre-scenario
    # simulator).  When set it overrides ``channel.scenario``; with
    # scan_rounds the channel state additionally evolves INSIDE the
    # compiled multi-round scan (one executable for every scenario) and
    # the per-round realised SNR/outage come back in FedRun.
    scenario: "str | ScenarioConfig | None" = None
    # Fault-injection scenario (repro.core.faults): a preset name
    # ("none" | "corruption" | "crashes" | "bursty" | "lossy"), a
    # FaultConfig, or None.  Drawn from (seed, round, cid)-keyed streams on
    # domains disjoint from the channel simulator's, so enabling faults
    # never perturbs a run's channel realisation; the "none" preset is
    # bit-identical to None on every engine path.  Non-delivering clients
    # (crashed mid-upload / quarantined after exhausting HARQ retries) are
    # excluded from aggregation through the existing k = 0 transmit-mask
    # pattern; their on-air bytes stay on the ledger.
    faults: "str | FaultConfig | None" = None
    # Backbone pretraining (simulates the paper's pretrained GPT-2 W'; the
    # pretrain split is disjoint from public/private/eval).  0 disables.
    # Clients: supervised (they fine-tune on labelled shards anyway);
    # server: LM-only by default — generic features, NO class knowledge, so
    # its accuracy trajectory isolates what distillation transfers (the
    # paper's Fig. 2 server curve).
    pretrain_steps: int = 80
    pretrain_frac: float = 0.12
    pretrain_lr: float = 2e-3
    server_pretrain: str = "lm"  # "lm" | "supervised" | "none"
    server_pretrain_steps: int = 60


@dataclasses.dataclass
class FedRun:
    ledger: CommLedger
    server_acc: list[float]
    client_acc: list[float]
    mean_k: list[float]
    # Per-round list of each selected client's adaptive k (0 = dropped
    # straggler that transmitted nothing).
    per_client_k: list[list[int]] = dataclasses.field(default_factory=list)
    # Per-round final server-distill step loss (NaN when the engine does not
    # expose it — only the fused_e2e engine computes it in-program).
    distill_loss: list[float] = dataclasses.field(default_factory=list)
    # Heterogeneous scan runs only: per-round accuracy per family bucket
    # (fleet bucket order) from the in-scan eval tap.
    family_client_acc: list[list[float]] | None = None
    # Scenario scan runs only: per-round cohort realised SNR (dB, -inf in
    # outage) and outage flags from the in-scan channel tap.
    snr_db: list[list[float]] | None = None
    outage: list[list[bool]] | None = None
    # Fault-injection runs only (None when FedConfig.faults is off):
    # per-round counts of quarantined uploads (corruption that exhausted
    # HARQ retries, plus wire-validation rejections) and mid-upload crashes,
    # the per-round retransmission bytes (on-air cost beyond each delivered
    # payload's first copy — included in the ledger's uplink_bytes), and
    # each selected client's ATTEMPTED adaptive k.  per_client_k/mean_k
    # keep reporting the DELIVERED view (0 for a lost upload), so
    # attempted_k is what separates "budget afforded nothing" from "died on
    # the air".
    num_quarantined: list[int] | None = None
    num_crashed: list[int] | None = None
    retrans_bytes: list[float] | None = None
    attempted_k: list[list[int]] | None = None

    def summary(self) -> dict:
        # NaN-safe best: all-dropped rounds contribute NaN accuracies, and
        # max() over a list with NaN entries is ORDER-DEPENDENT (any NaN
        # encountered after the true max poisons the comparison chain).
        finite = [a for a in self.server_acc if np.isfinite(a)]
        return {
            **self.ledger.summary(),
            "best_server_acc": max(finite) if finite else float("nan"),
        }


def _config_fingerprint(fed: FedConfig) -> dict:
    """A JSON-normalised image of the FedConfig for checkpoint/resume
    compatibility checks.  ``rounds`` is excluded: extending the horizon of
    a checkpointed run is exactly what resume is for.  ``fleet_store`` is
    excluded too: residency does not change the trajectory, and both store
    kinds read either checkpoint layout (monolithic fleet tree or
    per-client shards), so a run may resume under the other store."""
    d = dataclasses.asdict(fed)
    d.pop("rounds")
    d.pop("fleet_store", None)
    return json.loads(json.dumps(d, sort_keys=True, default=str))


def run_federated(
    client_cfg: ModelConfig | Sequence[ModelConfig],
    server_cfg: ModelConfig,
    dataset: IntentDataset,
    fed: FedConfig,
    *,
    verbose: bool = False,
    ckpt_dir: str | None = None,
    resume: bool = False,
) -> FedRun:
    """Run the whole federation.  ``client_cfg`` may be ONE config (the
    homogeneous fleet of the paper's §IV setup) or a sequence of FAMILY
    configs — clients then cycle through the families round-robin (client i
    runs ``client_cfg[i % F]``), and the engines serve the mixed fleet
    through the family-bucketed heterogeneous path (`repro.fed.cohort`).
    Families must share a vocabulary and LoRA rank (the paper's §II
    exchange contracts); with pretraining enabled, one backbone is
    pretrained PER family and shared by that family's clients.

    ``ckpt_dir`` enables crash-safe round-granular checkpoints through
    :mod:`repro.checkpoint` (atomic writes; one ``step_{r}`` file after
    every completed round — after every completed BLOCK with
    ``scan_rounds``, where a round is not a host-visible boundary).
    ``resume=True`` restores the newest valid checkpoint in ``ckpt_dir``
    and continues: host RNG draws and per-client batch streams are
    deterministically replayed through the completed rounds, device state
    is restored losslessly from the checkpoint, and channels/faults replay
    for free from their (seed, round, cid) keying — the resumed ``FedRun``
    is bit-identical to an uninterrupted run.  An empty/missing ``ckpt_dir``
    with ``resume=True`` simply starts from round 0 (idempotent restart).
    """
    preset = METHODS[fed.method]
    rng = np.random.default_rng(fed.seed)

    fault_cfg = get_faults(fed.faults)
    if fault_cfg is not None and not fault_cfg.enabled:
        fault_cfg = None  # the "none" preset is literally no fault machinery
    if fault_cfg is not None and not preset["adaptive_k"]:
        raise ValueError(
            "fault injection requires an adaptive-k method (faulted clients "
            "are excluded through the k = 0 transmit-mask path, which "
            f"method {fed.method!r} never takes)"
        )

    if resume and ckpt_dir is None:
        raise ValueError("resume=True requires ckpt_dir")
    completed = 0
    ckpt_meta: dict = {}
    if resume:
        step = ckpt_io.latest_step(ckpt_dir)
        if step is not None:
            completed = int(step)
            ckpt_meta = ckpt_io.step_metadata(ckpt_dir, step) or {}
            stored = ckpt_meta.get("config")
            now = _config_fingerprint(fed)
            if stored is not None and stored != now:
                diff = sorted(
                    k for k in set(stored) | set(now)
                    if stored.get(k) != now.get(k)
                )
                raise ValueError(
                    f"checkpoint in {ckpt_dir} was written by a different "
                    f"FedConfig (differing fields: {diff}); resuming it "
                    "would not reproduce the original trajectory"
                )
            if completed >= fed.rounds:
                raise ValueError(
                    f"checkpoint already holds {completed} completed rounds "
                    f">= fed.rounds={fed.rounds}; raise fed.rounds to extend "
                    "the run"
                )

    families = (
        [client_cfg] if isinstance(client_cfg, ModelConfig) else list(client_cfg)
    )
    if not families:
        raise ValueError("client_cfg must name at least one model config")
    cfgs = [families[i % len(families)] for i in range(fed.num_clients)]

    # carve a disjoint pretraining split first (simulated pretrained W')
    server_init = None
    client_inits: dict[ModelConfig, object] = {}
    if fed.pretrain_steps > 0:
        from repro.fed.pretrain import pretrain_classifier, pretrain_lm

        n_pre = int(len(dataset) * fed.pretrain_frac)
        pre_idx = np.random.default_rng(fed.seed + 31).permutation(len(dataset))
        pretrain_ds = dataset.subset(pre_idx[:n_pre])
        dataset = dataset.subset(pre_idx[n_pre:])
        # Resuming: every pretrained tensor (client backbones, server init)
        # is restored from the checkpoint below, so the pretrain COMPUTE is
        # skipped — only the data split above must still be applied (it
        # shapes the public/private/eval pools the replayed rounds draw
        # from).
        if completed:
            # Topology-only placeholders: the pretrained run hands every
            # client of a family the SAME param arrays, which the batched
            # engines detect (shared_frozen_backbone) and store unstacked.
            # Resume must reproduce that sharing layout before the restore
            # overwrites the values, or the checkpoint tree shapes mismatch.
            from repro.models import init as model_init

            for fi, fam in enumerate(families):
                client_inits[fam] = model_init(
                    jax.random.PRNGKey(fed.seed + 17 * fi), fam
                )
        else:
            # one pretrained backbone per family; family 0 keeps the
            # historical seed so a homogeneous run is bit-identical to the
            # pre-hetero path
            for fi, fam in enumerate(families):
                client_inits[fam] = pretrain_classifier(
                    fam, pretrain_ds, num_classes=dataset.num_classes,
                    steps=fed.pretrain_steps, lr=fed.pretrain_lr,
                    seed=fed.seed + 17 * fi, verbose=verbose,
                )
            if fed.server_pretrain == "supervised":
                server_init = pretrain_classifier(
                    server_cfg, pretrain_ds, num_classes=dataset.num_classes,
                    steps=fed.server_pretrain_steps, lr=fed.pretrain_lr,
                    seed=fed.seed + 999, verbose=verbose,
                )
            elif fed.server_pretrain == "lm":
                server_init = pretrain_lm(
                    server_cfg, pretrain_ds, steps=fed.server_pretrain_steps,
                    lr=fed.pretrain_lr, seed=fed.seed + 999, verbose=verbose,
                )

    public, private = split_public_private(dataset, fed.public_size, seed=fed.seed)
    if fed.non_iid:
        parts = dirichlet_partition(
            private.labels, fed.num_clients, gamma=fed.dirichlet_gamma, seed=fed.seed
        )
    else:
        parts = iid_partition(len(private), fed.num_clients, seed=fed.seed)

    clients = [
        Client(
            i,
            cfgs[i],
            private.subset(parts[i]),
            num_classes=dataset.num_classes,
            seed=fed.seed + i,
            lr=fed.lr,
            distill_lr=fed.distill_lr,
            temperature=fed.temperature,
            lam=fed.lam,
            local_steps=fed.local_steps,
            distill_steps=fed.distill_steps,
            restrict_to_support=fed.restrict_to_support,
            initial_params=client_inits.get(cfgs[i]),
        )
        for i in range(fed.num_clients)
    ]
    server = Server(
        server_cfg,
        seed=fed.seed + 999,
        distill_lr=fed.distill_lr,
        temperature=fed.temperature,
        lam=fed.lam,
        aggregation=preset["aggregation"],
        distill_steps=fed.server_distill_steps,
        use_kernels=fed.use_kernels,
        restrict_to_support=fed.restrict_to_support,
        initial_params=server_init,
    )
    channel_cfg = fed.channel
    if fed.scenario is not None:
        channel_cfg = dataclasses.replace(
            channel_cfg, scenario=get_scenario(fed.scenario)
        )
    chan_sim = ChannelSimulator(fed.num_clients, channel_cfg, seed=fed.seed)
    fault_sim = (
        FaultSimulator(fed.num_clients, fault_cfg, seed=fed.seed)
        if fault_cfg is not None
        else None
    )

    # held-out eval split (from the private pool tail, disjoint from clients'
    # data only in expectation at reduced scale; standard FedD evaluation)
    eval_idx = rng.permutation(len(private))[: fed.eval_size]
    eval_tokens, eval_labels = private.tokens[eval_idx], private.labels[eval_idx]
    evaluate = make_eval_fn(server_cfg, dataset.num_classes)
    # per-family client evaluators (make_eval_fn is lru-cached per config)
    evaluate_client = {fam: make_eval_fn(fam, dataset.num_classes) for fam in families}

    engine = make_engine(
        fed.engine,
        clients,
        cfgs[0],
        num_classes=dataset.num_classes,
        lr=fed.lr,
        distill_lr=fed.distill_lr,
        temperature=fed.temperature,
        lam=fed.lam,
        local_steps=fed.local_steps,
        distill_steps=fed.distill_steps,
        restrict_to_support=fed.restrict_to_support,
        value_bits=fed.channel.value_bits,
        k_min=fed.channel.min_k,
        shard_clients=fed.shard_clients,
        use_kernels=fed.use_kernels,
        quantize_wire=fed.quantize_wire,
        compute_dtype=fed.compute_dtype,
        fleet_store=fed.fleet_store,
        # fused_e2e only: the engine owns the server phase too
        server=server,
        server_distill_steps=fed.server_distill_steps,
        aggregation=preset["aggregation"],
    )
    handles_server = getattr(engine, "handles_server", False)

    ledger = CommLedger()
    run = FedRun(ledger=ledger, server_acc=[], client_acc=[], mean_k=[])
    if fault_sim is not None:
        run.num_quarantined, run.num_crashed = [], []
        run.retrans_bytes, run.attempted_k = [], []

    pub_rng = np.random.default_rng(fed.seed + 7)

    def draw_round(rnd: int):
        """One round's host-rng draws — cohort, public batch, channel
        realisation — in THE canonical order.  The per-round loop and the
        scan_rounds pre-draw both go through here, so the two paths can
        never desynchronize their rng streams."""
        sel = rng.choice(fed.num_clients, size=fed.clients_per_round, replace=False)
        pub_sel = pub_rng.integers(0, len(public), size=fed.public_batch)
        return (
            [int(i) for i in sel],
            jnp.asarray(public.tokens[pub_sel]),
            chan_sim.states_batched(rnd, list(sel)),
        )

    def apply_faults(rnd, sel, states, fault_inputs=None, round_offset=0):
        """Resolve this round's deliveries and force the non-delivering
        clients (crashed mid-upload / HARQ-exhausted corruption) to k = 0
        BEFORE any engine sees the round, by putting their channel entry
        into outage (snr -> -inf, zero bit budget).  Every engine —
        sequential, batched, fused, fused_e2e, hetero, and the multi-round
        scans (where k is already an int32 data operand) — then excludes
        them through the ONE existing transmit-mask path; no fault-specific
        executable exists.  Returns ``(states', attempted_ks, resolution,
        ghost_payloads)`` with the attempted manifests of quarantined
        uploads (their bytes were spent on air) for the ledger.
        """
        n_samples = fed.public_batch
        attempted = cohort_budgets(
            states, cfgs[sel[0]], n_samples, preset["adaptive_k"], len(sel),
            preset["send_h"], value_bits=fed.channel.value_bits,
            k_min=fed.channel.min_k, quantize_wire=fed.quantize_wire,
        )
        specs, payload_bits = [], []
        for i, cid in enumerate(sel):
            if attempted[i] > 0:
                p, _rank = make_upload_payload(
                    cfgs[cid], cid, n_samples, attempted[i],
                    send_h=preset["send_h"], value_bits=fed.channel.value_bits,
                    snr_db=float(states.snr_db[i]), quantize=fed.quantize_wire,
                )
            else:
                p = None
            specs.append(p)
            payload_bits.append(0.0 if p is None else float(p.spec.uplink_bits))
        budget_bits = [float(st.bit_budget) for st in states]
        if fault_inputs is not None:
            res = fault_sim.resolve_from_inputs(
                fault_inputs, round_offset, sel, attempted,
                payload_bits, budget_bits,
            )
        else:
            res = fault_sim.resolve_round(
                rnd, sel, attempted, payload_bits, budget_bits
            )
        failed = [
            i for i, (k, d) in enumerate(zip(attempted, res.delivered))
            if k > 0 and not d
        ]
        if failed:
            snr = np.array(states.snr_db, dtype=np.float64)
            snr[failed] = -np.inf
            states = dataclasses.replace(states, snr_db=snr)
        ghosts = [specs[i] for i in failed if res.reasons[i] == "corrupt"]
        for i in failed:
            if res.reasons[i] == "corrupt":
                specs[i].attempts = res.attempts[i]
                specs[i].delivered = False
        return states, attempted, res, ghosts

    def fault_ledger(sel, res, ghosts, payloads):
        """Price HARQ retries onto the delivered manifests (in place, so the
        engine-reported uplink bytes already include them) and account the
        quarantined attempts.  Returns ``(extra_bytes, retrans_bytes,
        stats_kw)``: bytes to ADD to the engine-reported uplink (the ghost
        manifests' spent attempts), the total on-air cost beyond each
        delivered payload's first copy, and the RoundStats fault taps."""
        by_cid = {p.client_id: p for p in payloads}
        retrans = 0.0
        for i, cid in enumerate(sel):
            if res.delivered[i] and res.attempts[i] > 1:
                p = by_cid.get(cid)
                if p is not None:
                    p.attempts = res.attempts[i]
                    retrans += (res.attempts[i] - 1) * p.spec.uplink_bytes
        extra = float(sum(g.bytes for g in ghosts))
        retrans += extra
        counts: dict[str, int] = {}
        for r in res.reasons:
            if r is not None:
                counts[r] = counts.get(r, 0) + 1
        stats_kw = dict(
            num_quarantined=res.num_quarantined,
            num_crashed=res.num_crashed,
            fault_counts=counts or None,
            retrans_bytes=retrans,
        )
        return extra, retrans, stats_kw

    def record_fault_taps(attempted, res, retrans):
        run.num_quarantined.append(res.num_quarantined)
        run.num_crashed.append(res.num_crashed)
        run.retrans_bytes.append(retrans)
        run.attempted_k.append(list(attempted))

    # -- crash-safe checkpointing ---------------------------------------
    # A host-store fleet checkpoints as per-client-range SHARDS next to the
    # main step npz (never materialised as one tree — the whole point of
    # out-of-core residency); the shards are written FIRST and the main
    # npz LAST, so a valid step file implies complete shards (ckpt.py's
    # ordering contract) and a crash mid-shard-write resumes from the
    # previous step.
    fleet_sharded = (
        getattr(engine, "store_kind", "device") == "host"
        and hasattr(engine, "save_fleet_shards")
    )

    def ckpt_tree(like: bool = False, include_fleet: bool = True):
        """The full federation state as one checkpointable pytree: fleet
        LoRA/opt (+ backbone), server state, and — for server-owning
        engines — the broadcast carry.  ``like=True`` builds the restore
        skeleton on a freshly-constructed engine, where the broadcast carry
        does not exist yet and is shaped from the config instead.  Round
        index and histories ride the JSON metadata sidecar; channel and
        fault trajectories replay for free from (seed, round, cid) keying.
        ``include_fleet=False`` leaves the fleet out (it rides in shards).
        """
        tree = {}
        if include_fleet:
            tree["fleet"] = engine.fleet_state()
        if handles_server:
            tree["server"] = engine.server_state()
            if like:
                bc = {
                    "b_logits": np.zeros(
                        (fed.public_batch, server_cfg.vocab_size), np.float32
                    )
                }
                if server_cfg.lora is not None:
                    bc["b_h"] = np.zeros(
                        (fed.public_batch, server_cfg.lora.rank), np.float32
                    )
            else:
                bc = {"b_logits": engine._b_logits}
                if engine._b_h is not None:
                    bc["b_h"] = engine._b_h
            tree["bcast"] = bc
        else:
            tree["server"] = {"s_params": server.params, "s_opt": server.opt}
        return tree

    def save_ckpt(step: int) -> None:
        meta = dict(
            config=_config_fingerprint(fed),
            server_acc=run.server_acc, client_acc=run.client_acc,
            mean_k=run.mean_k, per_client_k=run.per_client_k,
            distill_loss=run.distill_loss,
            ledger=[dataclasses.asdict(r) for r in ledger.rounds],
        )
        for tap in ("num_quarantined", "num_crashed", "retrans_bytes",
                    "attempted_k", "family_client_acc", "snr_db", "outage"):
            v = getattr(run, tap)
            if v is not None:
                meta[tap] = v
        if fleet_sharded:
            # shards FIRST, main npz LAST: the main file is the atomic
            # completion marker for the whole sharded checkpoint
            engine.save_fleet_shards(ckpt_io.fleet_shard_dir(ckpt_dir, step))
            meta["fleet_sharded"] = True
        ckpt_io.save_step(
            ckpt_dir, step, ckpt_tree(include_fleet=not fleet_sharded), **meta
        )

    resume_bcast: BroadcastState | None = None
    if completed:
        was_sharded = bool(ckpt_meta.get("fleet_sharded"))
        tree, _step = ckpt_io.restore_step(
            ckpt_dir, ckpt_tree(like=True, include_fleet=not was_sharded),
            completed,
        )
        if was_sharded:
            engine.load_fleet_shards(
                ckpt_io.fleet_shard_dir(ckpt_dir, completed)
            )
        else:
            engine.load_fleet_state(tree["fleet"])
        if handles_server:
            engine.load_server_state(tree["server"])
        else:
            server.params = jax.tree.map(jnp.asarray, tree["server"]["s_params"])
            server.opt = jax.tree.map(jnp.asarray, tree["server"]["s_opt"])
        # Deterministic replay of the host-rng chain through the completed
        # rounds: the cohort/public/channel draws and each selected client's
        # private-batch stream advance exactly as the original rounds did,
        # so round `completed` sees the same draws it would have seen
        # uninterrupted.  Device state is restored, not recomputed.
        last_pub = None
        for rnd in range(completed):
            sel, pub_tokens, _states = draw_round(rnd)
            for cid in sel:
                clients[cid].next_train_batches(fed.local_steps)
            last_pub = pub_tokens
        if handles_server:
            engine.load_broadcast(
                last_pub, tree["bcast"]["b_logits"], tree["bcast"].get("b_h")
            )
            resume_bcast = engine.broadcast_state(last_pub)
        else:
            # the broadcast is a pure function of (restored server params,
            # replayed public batch) — recompute it bit-identically
            g_logits, g_h, g_bits = server.broadcast(last_pub)
            resume_bcast = BroadcastState(
                tokens=last_pub, logits=g_logits, h=g_h, bits=g_bits
            )
        # restore the recorded history so the resumed FedRun is the FULL
        # run's record, not just the tail's
        run.server_acc[:] = [float(x) for x in ckpt_meta.get("server_acc", [])]
        run.client_acc[:] = [float(x) for x in ckpt_meta.get("client_acc", [])]
        run.mean_k[:] = [float(x) for x in ckpt_meta.get("mean_k", [])]
        run.per_client_k[:] = [
            [int(k) for k in ks] for ks in ckpt_meta.get("per_client_k", [])
        ]
        run.distill_loss[:] = [
            float(x) for x in ckpt_meta.get("distill_loss", [])
        ]
        for tap in ("num_quarantined", "num_crashed", "retrans_bytes",
                    "attempted_k", "family_client_acc", "snr_db", "outage"):
            if tap in ckpt_meta:
                setattr(run, tap, ckpt_meta[tap])
        for entry in ckpt_meta.get("ledger", []):
            ledger.record(RoundStats(**entry))

    store_kind = getattr(engine, "store_kind", "device")
    if fed.scan_rounds:
        if not handles_server:
            raise ValueError(
                "FedConfig.scan_rounds requires engine='fused_e2e' "
                f"(got {fed.engine!r})"
            )
        if store_kind != "device" and verbose:
            # the scan carries the WHOLE fleet as a donated device operand,
            # which defeats the host store's O(cohort) residency — trade
            # the amortised dispatch for streaming and drive per round
            print(
                "[rounds] scan_rounds needs the device fleet store; "
                f"fleet_store={store_kind!r} falls back to the per-round "
                "driver with cohort prefetch"
            )
    if fed.scan_rounds and store_kind == "device":
        # Pre-draw every remaining round in the same order the per-round
        # loop uses, then run the block as one compiled multi-round dispatch
        # with the eval tap inside the scan.  A resumed run scans only the
        # rounds after the checkpoint (the restored broadcast carry warm-
        # starts it); checkpoint granularity on this path is the BLOCK
        # boundary — a round inside the scan is not a host-visible state.
        start = completed
        n_block = fed.rounds - start
        fault_inputs = (
            # the scan path consumes the fault trajectory through its DATA
            # operands (scan_fault_inputs) — resolved host-side into the
            # int32 k masks the compiled scan already takes, bit-identical
            # to the per-round stream path
            fault_sim.scan_fault_inputs(n_block, start_round=start)
            if fault_sim is not None
            else None
        )
        sels, pubs, states_list, fault_rows = [], [], [], []
        for j, rnd in enumerate(range(start, fed.rounds)):
            sel, pub_tokens, states = draw_round(rnd)
            if fault_sim is not None:
                states, attempted, res, ghosts = apply_faults(
                    rnd, sel, states, fault_inputs, j
                )
                fault_rows.append((attempted, res, ghosts))
            sels.append(sel)
            pubs.append(pub_tokens)
            states_list.append(states)
        # the in-scan tap reads the same samples the host-side batched eval
        # walks (whole eval batches; the remainder is dropped there too)
        seen = (len(eval_tokens) // EVAL_BATCH) * EVAL_BATCH
        eval_kw = {}
        if seen:
            eval_kw = dict(
                eval_tokens=jnp.asarray(eval_tokens[:seen]),
                eval_labels=jnp.asarray(eval_labels[:seen]),
            )
        chan_kw = {}
        if chan_sim.scenario is not None:
            # scenario channel state evolves inside the same compiled scan;
            # budgets above were priced from the identical host chain
            chan_kw = dict(
                channel_scan=chan_sim.scan_channel_inputs(
                    n_block, start_round=start
                )
            )
        traj = engine.run_rounds(
            sels, pubs, states_list,
            adaptive_k=preset["adaptive_k"], send_h=preset["send_h"],
            **eval_kw, **chan_kw,
        )
        engine.sync_server()
        # extend (never clobber) the taps a resumed run restored
        if traj.family_client_acc is not None:
            run.family_client_acc = (
                (run.family_client_acc or []) + traj.family_client_acc
            )
        if traj.snr_db is not None:
            run.snr_db = (run.snr_db or []) + traj.snr_db
            run.outage = (run.outage or []) + traj.outage
        b_rank = server_cfg.lora.rank if server_cfg.lora is not None else None
        b_bits = downlink_bits(fed.public_batch, server_cfg.vocab_size, b_rank)
        for j, rnd in enumerate(range(start, fed.rounds)):
            # an eval split smaller than one batch degenerates to 0.0 on the
            # host path (no whole batch to walk) — mirror it, not NaN
            s_acc = traj.server_acc[j] if traj.server_acc else 0.0
            c_acc = traj.client_acc[j] if traj.client_acc else 0.0
            downlink = b_bits * len(sels[j]) if rnd > 0 else 0
            stats_kw: dict = {}
            extra = 0.0
            if fault_rows:
                attempted, res, ghosts = fault_rows[j]
                extra, retrans, stats_kw = fault_ledger(
                    sels[j], res, ghosts, traj.payloads[j]
                )
                record_fault_taps(attempted, res, retrans)
            # after fault_ledger: delivered manifests carry their HARQ
            # attempts, so p.bytes already prices the retries
            uplink = float(sum(p.bytes for p in traj.payloads[j])) + extra
            run.server_acc.append(s_acc)
            run.client_acc.append(c_acc)
            run.mean_k.append(traj.mean_k[j])
            run.per_client_k.append(list(traj.ks[j]))
            run.distill_loss.append(traj.distill_loss[j])
            ledger.record(
                RoundStats(
                    round_index=rnd,
                    uplink_bytes=uplink,
                    downlink_bytes=downlink / 8.0,
                    server_accuracy=s_acc,
                    client_accuracy=c_acc,
                    distill_loss=traj.distill_loss[j],
                    mean_k=traj.mean_k[j],
                    num_selected=len(sels[j]),
                    num_transmitters=len(traj.payloads[j]),
                    **stats_kw,
                )
            )
            if verbose:
                print(
                    f"[{fed.method}/{fed.engine}+scan] round {rnd:3d}  "
                    f"server_acc={s_acc:.3f} client_acc={c_acc:.3f}  "
                    f"mean_k={traj.mean_k[j]:7.1f}  uplink={uplink/1e6:.2f}MB  "
                    f"tx={len(traj.payloads[j])}/{len(sels[j])}"
                )
        if ckpt_dir is not None:
            save_ckpt(fed.rounds)
        return run

    # Broadcast knowledge carried across rounds: None until the server has
    # distilled once (cold server at round 0 -> no downlink that round); a
    # resumed run re-enters with the checkpointed broadcast.
    bcast: BroadcastState | None = resume_bcast
    # Rounds are pre-drawn ONE round ahead so the store can stage round
    # r+1's cohort (host->device prefetch) under round r's compute.  The
    # draw order is unchanged — draw_round(r) still fires in increasing r,
    # keeping the host-rng chain identical to the non-prefetching loop —
    # and the channel/fault draws are (seed, round, cid)-keyed, so drawing
    # round r+1 before round r's faults resolve changes nothing.
    pending = draw_round(completed) if fed.rounds > completed else None
    for rnd in range(completed, fed.rounds):
        sel, pub_tokens, states = pending
        pending = draw_round(rnd + 1) if rnd + 1 < fed.rounds else None
        if pending is not None:
            engine.prefetch_cohort(pending[0])
        fault_row = None
        if fault_sim is not None:
            states, attempted, res, ghosts = apply_faults(rnd, sel, states)
            fault_row = (attempted, res, ghosts)

        # one broadcast of last round's knowledge per selected client
        downlink = bcast.bits * len(sel) if bcast is not None else 0

        phase = engine.run_round(
            sel, pub_tokens, bcast, states,
            adaptive_k=preset["adaptive_k"], send_h=preset["send_h"],
        )

        stats_kw: dict = {}
        extra = 0.0
        if fault_row is not None:
            attempted, res, ghosts = fault_row
            extra, retrans, stats_kw = fault_ledger(
                sel, res, ghosts, phase.payloads
            )
            record_fault_taps(attempted, res, retrans)

        if handles_server:
            # fused_e2e: aggregation + server distillation + broadcast all
            # happened inside the engine's single compiled round call.
            bcast = engine.broadcast_state(pub_tokens)
            engine.sync_server()
        else:
            dense, h_stack = phase.dense, phase.h
            if fault_sim is not None and dense is not None:
                # server-side integrity gate on the received stack: a
                # transmitter whose payload decodes to non-finite values is
                # quarantined instead of poisoning the eq. 6-7 aggregation
                ok, _reasons = validate_dense(dense, h_stack)
                if not ok.all():
                    n_bad = int((~ok).sum())
                    for i in np.flatnonzero(~ok):
                        phase.payloads[int(i)].delivered = False
                    keep = np.flatnonzero(ok)
                    dense = dense[jnp.asarray(keep)] if len(keep) else None
                    if h_stack is not None:
                        h_stack = (
                            h_stack[jnp.asarray(keep)] if len(keep) else None
                        )
                    stats_kw["num_quarantined"] = (
                        stats_kw.get("num_quarantined") or 0
                    ) + n_bad
                    counts = stats_kw.get("fault_counts") or {}
                    counts["invalid_wire"] = counts.get("invalid_wire", 0) + n_bad
                    stats_kw["fault_counts"] = counts
            if dense is not None:
                k_g, h_g = server.aggregate_dense(dense, h_stack)
                server.distill(pub_tokens, k_g, h_g)
            # else: every selected client dropped this round -> no
            # aggregation, the server's knowledge simply carries over.
            g_logits, g_h, g_bits = server.broadcast(pub_tokens)
            bcast = BroadcastState(tokens=pub_tokens, logits=g_logits, h=g_h, bits=g_bits)

        s_acc = evaluate(server.params, jnp.asarray(eval_tokens), jnp.asarray(eval_labels))
        c_acc = evaluate_client[cfgs[sel[0]]](
            engine.client_params(sel[0]), jnp.asarray(eval_tokens), jnp.asarray(eval_labels)
        )
        uplink = phase.uplink_bytes + extra
        d_loss = (
            engine.last_distill_loss if handles_server else float("nan")
        )
        run.server_acc.append(s_acc)
        run.client_acc.append(c_acc)
        run.mean_k.append(float(np.mean(phase.ks)))
        run.per_client_k.append(list(phase.ks))
        run.distill_loss.append(d_loss)
        ledger.record(
            RoundStats(
                round_index=rnd,
                uplink_bytes=uplink,
                downlink_bytes=downlink / 8.0,
                server_accuracy=s_acc,
                client_accuracy=c_acc,
                distill_loss=d_loss,
                mean_k=float(np.mean(phase.ks)),
                num_selected=len(sel),
                num_transmitters=phase.num_transmitters,
                **stats_kw,
            )
        )
        if verbose:
            print(
                f"[{fed.method}/{fed.engine}] round {rnd:3d}  server_acc={s_acc:.3f} "
                f"client_acc={c_acc:.3f}  mean_k={np.mean(phase.ks):7.1f}  "
                f"uplink={uplink/1e6:.2f}MB  tx={phase.num_transmitters}/{len(sel)}"
            )
        if ckpt_dir is not None:
            save_ckpt(rnd + 1)
    return run
