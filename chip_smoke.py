"""Bring-up smoke test: the federation and adapter serving on a TPU chip at
the paper's published widths (GPT-2 small clients, GPT-2 large server,
V=50,257, LoRA r=8 on q/v), through the entry points a user calls.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: the sharded cohort only

Phases on one chip:
  (a) the device JAX reports; anything but a TPU exits non-zero;
  (b) ``run_federated`` with ``engine="fused_e2e"``, 2 rounds, 10 clients,
      Table I's public batch of 256, under the default LTE-like uplink
      (1 MHz, 10 dB mean SNR), where the adaptive k stays far below V;
  (c) the same run with ``scan_rounds=True``: identical k and bytes;
  (d) the same per-round run with ``use_kernels=True``: the Pallas kernels
      run compiled (no interpret mode) with identical k and bytes, and the
      wire-scatter kernel matches the jnp scatter at full width;
  (e) ``ServeSession`` on GPT-2 small: 8 tenants through an 8-slot
      ``AdapterCache``, a 32-token prompt, 16 decoded tokens, one decode
      executable, stacked decode against per-request single-adapter decode
      and a float32 reference.

With ``--chips 4`` it runs one fused_e2e round with ``shard_clients=True``
on the 4-device cohort mesh and the same round unsharded, and compares them.

Weights and data come from ``--seed``.  Every cut from the paper's setting
is printed before the phases run; the timings printed are smoke timings
(compiles included), not benchmark numbers.  The last line of standard
output is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import io
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ROUNDS, CLIENTS, PUBLIC_BATCH, SEQ_LEN = 2, 10, 256, 24
PRETRAIN_STEPS = 4
PROMPT, GEN, TENANTS = 32, 16, 8

# Tolerances between DIFFERENT executables (never bit equality).  On the
# chip, float32 matmuls with more than a few rows run on the MXU at the
# default precision (one bf16 pass), so an operand that differs from the
# other executable's at float32 rounding level can round to a different
# bf16 value (4e-3 apart); over a round's Adam steps that moves the model,
# not just the last bits.
# * FLIP_SAMPLES — per-round vs scanned rounds, jnp vs Pallas aggregation:
#   an argmax over near-tied class logits can flip; accuracies may differ
#   by that many eval samples.
# * LOSS_RTOL — the last server-distill loss of a round; the jnp and Pallas
#   aggregations, equal to 1e-5, moved it by 9.5e-3 on a v5e.  A wrong
#   aggregation (a client dropped, an index misplaced) fails the kernel
#   parity check below at O(1), long before this bound matters.
# * SCATTER_RTOL — the Pallas wire scatter sums up to N terms per output
#   in another order than XLA's scatter-add (one-hot matmul at highest
#   precision): float32 rounding of the largest sum.
# * UPDATE_RTOL — sharded vs unsharded round (both at highest matmul
#   precision, so the comparison sees placement, not bf16 rounding), each
#   model's update compared in norm.  Within a precision-equal pair the
#   remaining gap is float32 reduction order, amplified where Adam divides
#   a rounding-level gradient by its own sqrt(v), plus a near-tie in some
#   client's top-k that swaps one wire index (k and bytes still equal) and
#   moves the server's teacher at that entry.  A client's update landing
#   on another client is a gap of O(1) of the update's norm.
# * DECODE_RTOL — stacked decode (batch 8, MXU at default precision) and
#   single-adapter decode against a highest-precision float32 reference;
#   a wrong tenant's adapter moves the logits by O(1) of their scale.
FLIP_SAMPLES = 4
LOSS_RTOL = 5e-2
SCATTER_RTOL = 1e-5
UPDATE_RTOL = 5e-2
DECODE_RTOL = 5e-2


def _check(ok, detail=""):
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise SystemExit(f"[smoke] FAIL: {detail}")


class _Stamped(io.TextIOBase):
    """stdout proxy that prefixes each line with the seconds since start."""

    def __init__(self, out, t0):
        self._out, self._t0, self._bol = out, t0, True

    def write(self, s):
        for part in s.splitlines(keepends=True):
            if self._bol:
                self._out.write(f"[smoke +{time.perf_counter() - self._t0:7.1f}s] ")
            self._out.write(part)
            self._bol = part.endswith("\n")
        return len(s)

    def flush(self):
        self._out.flush()


def _round_arg_shapes(ccfg, scfg, cohort):
    """ShapeDtypeStructs of one fused_e2e round's operands (see
    ``make_fused_e2e_round_fn``) — to lower the round without arrays."""
    import jax
    import jax.numpy as jnp

    from repro.lora import split_lora
    from repro.models import init as model_init
    from repro.optim import adamw_init

    def state(cfg, lead):
        p = jax.eval_shape(lambda k: model_init(k, cfg), jax.random.PRNGKey(0))
        lora, frozen = split_lora(p)
        opt = jax.eval_shape(
            lambda x: adamw_init(x, state_dtype=cfg.optimizer_state_dtype), lora
        )
        stack = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype), t
        )
        return stack(lora), frozen, stack(opt)

    sds = jax.ShapeDtypeStruct
    lora, frozen, opt = state(ccfg, (cohort,))
    s_lora, s_frozen, s_opt = state(scfg, ())
    steps, batch = 4, 32  # FedConfig.local_steps, Client.batch_size
    return (
        lora, frozen, opt, s_lora, s_frozen, s_opt,
        sds((PUBLIC_BATCH, SEQ_LEN), jnp.int32),
        sds((PUBLIC_BATCH, scfg.vocab_size), jnp.float32),
        sds((PUBLIC_BATCH, scfg.lora.rank), jnp.float32),
        sds((), jnp.bool_),
        {"tokens": sds((cohort, steps, batch, SEQ_LEN), jnp.int32),
         "labels": sds((cohort, steps, batch), jnp.int32)},
        sds((PUBLIC_BATCH, SEQ_LEN), jnp.int32),
        sds((cohort,), jnp.int32),
    )


def federation(ccfg, scfg, ds, fed, t0):
    """Phases (b)-(d) on one chip."""
    import jax
    import numpy as np

    from repro.fed import run_federated
    from repro.fed import steps as fed_steps
    from repro.fed.engines.base import k_cap_bucket
    from repro.kernels import ops as kops

    def run(label, **over):
        cfg = dataclasses.replace(fed, **over)
        start = time.perf_counter()
        with contextlib.redirect_stdout(_Stamped(sys.stdout, t0)):
            print(f"[smoke] ({label}) run_federated {over or ''}")
            r = run_federated(ccfg, scfg, ds, cfg, verbose=True)
        wall = time.perf_counter() - start
        ks = r.per_client_k
        byts = [x.uplink_bytes for x in r.ledger.rounds]
        print(f"[smoke] ({label}) smoke timing: {wall:.1f}s for set-up + "
              f"{fed.rounds} rounds (compiles included); k_cap per round "
              f"{[k_cap_bucket(k, ccfg.vocab_size) for k in ks]}")
        print(f"[smoke] ({label}) server_acc={r.server_acc} "
              f"client_acc={r.client_acc} distill_loss={r.distill_loss} "
              f"mean_k={r.mean_k} uplink_bytes={byts}")
        _check(np.all(np.isfinite(r.server_acc)) and np.all(np.isfinite(r.client_acc)))
        _check(np.all(np.isfinite(r.distill_loss)), r.distill_loss)
        _check(all(b > 0 for b in byts), byts)
        _check(any(0 < k < ccfg.vocab_size for row in ks for k in row), ks)
        return r, ks, byts

    def agree(label, a, b):
        n_eval = (fed.eval_size // fed_steps.EVAL_BATCH) * fed_steps.EVAL_BATCH
        _check(a[1] == b[1], f"{label}: k differs {a[1]} vs {b[1]}")
        _check(a[2] == b[2], f"{label}: bytes differ {a[2]} vs {b[2]}")
        for name in ("server_acc", "client_acc"):
            gap = np.abs(np.subtract(getattr(a[0], name), getattr(b[0], name)))
            print(f"[smoke] {label}: max |{name} gap| = {gap.max()} "
                  f"({gap.max() * n_eval:.0f} of {n_eval} eval samples)")
            _check(gap.max() <= FLIP_SAMPLES / n_eval, (name, gap))
        np.testing.assert_allclose(a[0].distill_loss, b[0].distill_loss, rtol=LOSS_RTOL)
        gap = np.abs(np.subtract(a[0].distill_loss, b[0].distill_loss))
        print(f"[smoke] {label}: k and bytes identical; max |distill_loss gap| = {gap.max()}")

    base = run("b")
    gc.collect()
    scan = run("c", scan_rounds=True)
    agree("(c) scan vs per-round", scan, base)
    gc.collect()

    _check(not kops.interpret_mode(), "Pallas kernels would run interpreted")
    k_cap = max(k_cap_bucket(k, ccfg.vocab_size) for k in base[1])
    round_fn = fed_steps.make_fused_e2e_round_fn(
        ccfg, scfg, ds.num_classes, k_cap=k_cap, use_kernels=True,
        local_steps=fed.local_steps, distill_steps=fed.distill_steps,
        server_distill_steps=fed.server_distill_steps,
    )
    text = jax.jit(round_fn).lower(
        *_round_arg_shapes(ccfg, scfg, fed.clients_per_round)
    ).as_text()
    _check("tpu_custom_call" in text, "no Pallas kernel in the lowered round")
    print(f"[smoke] (d) lowered round (k_cap={k_cap}) holds "
          f"{text.count('tpu_custom_call')} tpu_custom_call site(s); "
          f"interpret_mode()={kops.interpret_mode()}")
    for k in sorted({k_cap, 512}):
        scatter_parity(fed.clients_per_round, k, ccfg.vocab_size, fed.seed)
    kern = run("d", use_kernels=True)
    agree("(d) use_kernels vs jnp", kern, base)


def scatter_parity(n, k_cap, vocab, seed):
    """The wire-scatter kernel against the jnp scatter-add at full width."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.aggregation import scatter_wire_sums
    from repro.kernels import ops as kops

    key = jax.random.PRNGKey(seed)
    shape = (n, PUBLIC_BATCH, k_cap)
    # logit-scale values; the clients' top-k sets overlap heavily (one
    # shared backbone), so indices come from a pool of 2·k_cap ids per row
    v = 10.0 * jax.random.normal(jax.random.fold_in(key, 1), shape)
    a, b = jnp.abs(v) * v, jnp.abs(v)
    pool = jax.random.randint(jax.random.fold_in(key, 2), (PUBLIC_BATCH, 2 * k_cap), 0, vocab)
    pick = jax.random.randint(jax.random.fold_in(key, 3), shape, 0, 2 * k_cap)
    idx = jnp.take_along_axis(jnp.broadcast_to(pool, (n,) + pool.shape), pick, axis=-1)
    worst = 0.0
    for got, want in zip(kops.scatter_wire_sums(a, b, idx, vocab),
                         scatter_wire_sums(a, b, idx, vocab)):
        got, want = np.asarray(got), np.asarray(want)
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=SCATTER_RTOL, atol=SCATTER_RTOL * scale)
        worst = max(worst, float(np.abs(got - want).max()) / scale)
    print(f"[smoke] (d) Pallas wire scatter == jnp scatter-add at N={n}, "
          f"P={PUBLIC_BATCH}, k_cap={k_cap}, V={vocab}: max |gap| / scale = {worst}")


def serving(cfg, seed):
    """Phase (e): multi-tenant decode on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import _RandomAdapters
    from repro.lora import lora_template, merge_lora, split_lora
    from repro.models import forward, init as model_init
    from repro.serve import AdapterCache, ServeConfig, ServeSession

    params = model_init(jax.random.PRNGKey(seed), cfg)
    source = _RandomAdapters(params, TENANTS, seed)
    cache = AdapterCache(source, like=lora_template(params), slots=TENANTS)
    scfg = ServeConfig(model=cfg, batch=TENANTS, cache_len=PROMPT + GEN, seed=seed)
    sess = ServeSession(scfg, params, adapters=cache)
    sess.attach(list(range(TENANTS)))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (TENANTS, PROMPT)).astype(np.int32)

    start = time.perf_counter()
    logits = [np.asarray(sess.prefill(prompts))]
    toks = []
    decode_start = time.perf_counter()
    for _ in range(GEN):  # greedy decode, keeping every step's logits
        toks.append(logits[-1].argmax(-1).astype(np.int32))
        logits.append(np.asarray(sess.step(toks[-1])))
    steady = (time.perf_counter() - decode_start) / GEN  # each step ends in a read-back
    toks, logits = np.stack(toks, 1), np.stack(logits[:-1], 1)  # (B, GEN[, V])
    stats = sess.stats()
    print(f"[smoke] (e) smoke timing: {time.perf_counter() - start:.1f}s for "
          f"{PROMPT}-token prefill + {GEN} decode steps at batch {TENANTS}; "
          f"first step {stats['first_step_s']}, steady {steady:.4f}s/step; "
          f"executables {stats['executables']}; cache {stats['adapter_cache']}")
    _check(stats["executables"]["stacked"] == 1, stats["executables"])
    _check(np.isfinite(logits).all())

    _, frozen = split_lora(params)
    single = ServeSession(dataclasses.replace(scfg, batch=1), params)
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t})[0])
    worst = {"stacked": 0.0, "single": 0.0}
    for b in range(TENANTS):
        # tenant b's adapter merged the classic single-adapter way, run at
        # batch 1 and teacher-forced on the stacked decode's tokens
        single.params = merge_lora(source.lora_row(b), frozen)
        one = [np.asarray(single.prefill(prompts[b:b + 1]))[0]]
        for t in range(GEN - 1):
            one.append(np.asarray(single.step(toks[b:b + 1, t]))[0])
        seq = jnp.asarray(np.concatenate([prompts[b], toks[b, :-1]])[None])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference(single.params, seq)[0, PROMPT - 1:], np.float32)
        scale = float(np.abs(ref).max())
        for name, got in (("stacked", logits[b]), ("single", np.stack(one))):
            gap = float(np.abs(got - ref).max()) / scale
            worst[name] = max(worst[name], gap)
            _check(gap <= DECODE_RTOL, (name, b, gap))
    print(f"[smoke] (e) max |logit - float32 reference| / scale: {worst} "
          f"(bound {DECODE_RTOL})")


def sharded_round(ccfg, scfg, ds, seed):
    """--chips 4: one fused_e2e round on the 4-device cohort mesh against
    the same round unsharded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ChannelConfig, ChannelSimulator
    from repro.fed.client import Client
    from repro.fed.engines import FusedE2EEngine
    from repro.fed.engines.base import k_cap_bucket
    from repro.fed.server import Server
    from repro.lora import split_lora
    from repro.models import init as model_init
    from repro.sharding import COHORT_AXIS

    backbone = model_init(jax.random.PRNGKey(seed + 7), ccfg)
    per = len(ds) // CLIENTS
    sim = ChannelSimulator(CLIENTS, ChannelConfig(), seed=seed)
    sel = list(range(CLIENTS))
    states = sim.states_batched(0, sel)
    pub = jnp.asarray(ds.tokens[:PUBLIC_BATCH])

    def one_round(shard):
        """One round; returns (engine, ks, bytes, [(new, old) state pairs])."""
        clients = [Client(i, ccfg, ds.subset(np.arange(i * per, (i + 1) * per)),
                          num_classes=ds.num_classes, seed=seed + i,
                          initial_params=backbone) for i in sel]
        eng = FusedE2EEngine(clients, ccfg, server=Server(scfg, seed=seed + 999),
                             num_classes=ds.num_classes, shard_clients=shard)
        def adapters():  # every trained tree, on the host
            trees = [split_lora(eng.client_params(i))[0] for i in sel] + [eng._s_lora]
            return jax.tree.map(np.asarray, trees)

        old = adapters()
        start = time.perf_counter()
        phase = eng.run_round(sel, pub, None, states, adaptive_k=True, send_h=True)
        new = adapters()
        print(f"[smoke] shard_clients={shard}: smoke timing "
              f"{time.perf_counter() - start:.1f}s for one round (compile included); "
              f"ks={phase.ks}")
        return eng, phase.ks, [p.bytes for p in phase.payloads], list(zip(new, old))

    with jax.default_matmul_precision("highest"):
        _, ks_a, bytes_a, plain = one_round(False)
        gc.collect()
        eng, ks_b, bytes_b, shard = one_round(True)
    _check(ks_a == ks_b, (ks_a, ks_b))
    _check(bytes_a == bytes_b, (bytes_a, bytes_b))
    # each model's update, in norm, against its own size (UPDATE_RTOL)
    names = [f"client {i}" for i in sel] + ["server"]
    worst = {}
    for name, (new_a, old), (new_b, _) in zip(names, plain, shard):
        for a, b, o in zip(*(jax.tree.leaves(t) for t in (new_a, new_b, old))):
            step, gap = np.linalg.norm(a - o), np.linalg.norm(a - b)
            _check(gap <= UPDATE_RTOL * step, (name, gap, step))
            worst[name] = max(worst.get(name, 0.0), float(gap / step) if step else 0.0)
    print(f"[smoke] sharded vs unsharded at highest precision: k and bytes "
          f"identical; max |update gap| / |update| per model: {worst} "
          f"(bound {UPDATE_RTOL})")

    # the client phase really spans the 4 devices: the sharded round's own
    # executable leaves its wire split over the cohort axis of all of them
    k_cap = k_cap_bucket(ks_b, ccfg.vocab_size)
    pad = (-CLIENTS) % jax.device_count()
    with jax.default_matmul_precision("highest"):
        compiled = eng._e2e_step(k_cap, True).lower(
            *_round_arg_shapes(ccfg, scfg, CLIENTS + pad)).compile()
    wire = compiled.output_shardings[4]
    print(f"[smoke] sharded round wire output sharding: {wire}")
    _check(len(wire.device_set) == jax.device_count() == 4, wire)
    _check(not wire.is_fully_replicated and COHORT_AXIS in str(wire.spec), wire)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    # seed 1: under the default channel both rounds' adaptive k fall in one
    # k_cap bucket, so round 1 reuses round 0's executable (a steady round)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"[smoke] (a) device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("[smoke] FAIL: no TPU; this smoke test has no CPU fallback",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but {device['count']} device(s)",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[smoke] FAIL: the repro package is not next to this script: {e}",
              file=sys.stderr)
        return 1
    print(f"[smoke] compile cache: {enable_compile_cache()}")

    from repro.configs.gpt2_paper import GPT2_LARGE, GPT2_SMALL
    from repro.data import make_banking77_like
    from repro.fed import FedConfig

    # remat: without it the round's saved activations need ~65 GiB (compile
    # rehearsal against a described v5e); widths are never changed
    ccfg = GPT2_SMALL.with_overrides(remat=True)
    scfg = GPT2_LARGE.with_overrides(remat=True)
    ds = make_banking77_like(vocab_size=ccfg.vocab_size, seq_len=SEQ_LEN, seed=args.seed)
    print(f"[smoke] widths: clients {ccfg.num_layers}L/d{ccfg.d_model}, server "
          f"{scfg.num_layers}L/d{scfg.d_model}, V={ccfg.vocab_size}, LoRA "
          f"r={ccfg.lora.rank} on {ccfg.lora.targets}; data seq_len={SEQ_LEN}")
    if args.chips == 4:
        print("[smoke] cuts: remat on; one round per placement; random "
              "shared backbone (no pretraining); fleet = cohort of "
              f"{CLIENTS} clients; both rounds at highest matmul precision")
        sharded_round(ccfg, scfg, ds, args.seed)
    else:
        fed = FedConfig(
            engine="fused_e2e", num_clients=CLIENTS, clients_per_round=CLIENTS,
            rounds=ROUNDS, public_batch=PUBLIC_BATCH, seed=args.seed,
            pretrain_steps=PRETRAIN_STEPS, server_pretrain="none",
        )
        print(f"[smoke] cuts: remat on; rounds {ROUNDS} (Table I: 20); fleet "
              f"{CLIENTS} clients, all selected (Table I: 50, 10 per round); "
              f"client pretraining {PRETRAIN_STEPS} steps (default 80); "
              "server pretraining off")
        federation(ccfg, scfg, ds, fed, t0)
        gc.collect()
        serving(GPT2_SMALL, args.seed)
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
