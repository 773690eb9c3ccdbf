"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

For each combination this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod);
  2. builds ShapeDtypeStruct stand-ins for every model input (input_specs);
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile()``;
  4. prints ``compiled.memory_analysis()`` (HBM fit proof) and
     ``compiled.cost_analysis()`` (FLOPs/bytes for the roofline);
  5. parses the post-SPMD HLO for collective ops and sums their payload
     bytes (cost_analysis does not report collectives);
  6. writes one JSON record to experiments/dryrun/ for benchmarks/roofline.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs 4]
"""

import os
import sys

if __name__ == "__main__":
    # Run as a program, the dry-run compiles against 512 forced HOST
    # devices and never needs an accelerator: pin the CPU backend so no
    # process of it (the --all parent included) ever holds a chip.  Set
    # only here, so importing this module leaks nothing into the importer.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    if "--cost-mode" in sys.argv:
        # python-unroll inner chunk loops BEFORE model modules import, so
        # HLO cost analysis sees every op (XLA counts while bodies once).
        os.environ["REPRO_UNROLL"] = "1"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHITECTURES, INPUT_SHAPES  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.launch.mesh import V5E, make_production_mesh  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.serve import make_decode_step as make_serve_step, make_prefill_step  # noqa: E402
from repro import sharding as sh  # noqa: E402

DEFAULT_OUT = "experiments/dryrun"

from repro.launch.policy import arch_shape_config, input_specs, window_for  # noqa: E402


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|u32|s8|u8|pred|s64|u64)\[([\d,]*)\]")
_DTYPE_BYTES = {
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
}
COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute",
)


def _bytes_of_shape_str(text: str) -> int:
    """Sum byte sizes of every typed buffer in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind result-payload bytes from post-SPMD HLO."""
    out: dict[str, int] = {c: 0 for c in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # result-defining lines look like:  %name = TYPE op-name(...)
        m = re.match(r"%?[\w\.\-]+ = (\([^)]*\)|[^ ]+) ([\w\-]+)\(", stripped)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        if op.rstrip("-start") in COLLECTIVES or op in COLLECTIVES:
            kind = op[: -len("-start")] if op.endswith("-start") else op
            if kind not in out:
                continue
            out[kind] += _bytes_of_shape_str(result_type)
            out["count"] += 1
    return out


# ---------------------------------------------------------------------------
# lowering per step kind
# ---------------------------------------------------------------------------


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool, cfg_override=None):
    shape = INPUT_SHAPES[shape_name]
    cfg = cfg_override if cfg_override is not None else arch_shape_config(arch, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    w = window_for(cfg, shape)

    from repro.models import init as model_init

    params_shape = jax.eval_shape(lambda k: model_init(k, cfg), jax.random.key(0))
    pspecs = sh.param_specs(params_shape, mesh)
    p_sh = sh.tree_named(mesh, pspecs)

    batch_shardable = shape.global_batch % int(np.prod([mesh.shape[a] for a in sh.batch_axes(mesh)])) == 0

    sh.set_activation_sharding(mesh)
    with mesh:
        if shape.kind == "train":
            from repro.optim import adamw_init

            opt_shape = jax.eval_shape(
                lambda p: adamw_init(p, state_dtype=cfg.optimizer_state_dtype), params_shape
            )
            ospecs = sh.opt_state_specs(pspecs)
            o_sh = sh.tree_named(mesh, ospecs)
            bspecs = sh.batch_specs(
                mesh, batch_shardable=batch_shardable,
                with_frontend=cfg.frontend != "none", with_labels=False,
            )
            b_sh = sh.tree_named(mesh, bspecs)
            step = make_train_step(cfg)
            specs = input_specs(cfg, shape, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shape, opt_shape, specs["batch"])
        elif shape.kind == "prefill":
            bspecs = sh.batch_specs(
                mesh, batch_shardable=batch_shardable,
                with_frontend=cfg.frontend != "none", with_labels=False,
            )
            b_sh = sh.tree_named(mesh, bspecs)
            step = make_prefill_step(cfg, window=w)
            specs = input_specs(cfg, shape, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh), out_shardings=None)
            lowered = jitted.lower(params_shape, specs["batch"])
        else:  # decode
            specs = input_specs(cfg, shape, mesh)
            cspecs = sh.cache_specs(specs["cache"], mesh, batch_shardable=batch_shardable)
            c_sh = sh.tree_named(mesh, cspecs)
            t_sh = sh.tree_named(
                mesh, sh.batch_specs(mesh, batch_shardable=batch_shardable, with_labels=False)
            )["tokens"]
            # token is (B,): 1-D spec
            from jax.sharding import NamedSharding, PartitionSpec as P

            t_sh = NamedSharding(mesh, P(sh.batch_axes(mesh) if batch_shardable else None))
            step = make_serve_step(cfg, window=w)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, t_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shape, specs["cache"], specs["token"])
    sh.set_activation_sharding(None)
    return cfg, shape, mesh, lowered


def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str) -> dict:
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_combo(arch, shape_name, multi_pod=multi_pod)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {}
    if mem is not None:
        for field in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            mem_rec[field] = int(getattr(mem, field, 0) or 0)
        print("memory_analysis:", mem_rec)

    cost = compiled.cost_analysis() or {}
    cost_rec = {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}
    print("cost_analysis flops=%.4g bytes=%.4g" % (
        cost_rec.get("flops", -1), cost_rec.get("bytes accessed", -1)))

    coll = collective_bytes(compiled.as_text())
    print("collectives:", {k: f"{v/1e6:.1f}MB" for k, v in coll.items() if k != "count" and v},
          "count:", coll["count"])

    n_chips = int(np.prod(list(mesh.shape.values())))
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "chips": n_chips,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory": mem_rec,
        "cost": cost_rec,
        "collectives": coll,
        "model_params": cfg.param_count(),
        "model_params_active": cfg.param_count(active_only=True),
        "microbatches": cfg.microbatches,
        "hw": V5E,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{record['mesh']}.json"
    )
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"[dryrun] OK {arch} x {shape_name} x {record['mesh']} "
          f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s) -> {path}")
    return record


def _depth_reduced(cfg: ModelConfig, n: int) -> ModelConfig:
    """Config with n periods of layers (and n encoder layers), microbatch 1."""
    from repro.models.transformer import period_of

    p = period_of(cfg)
    kw = dict(num_layers=n * p, microbatches=1)
    if cfg.encoder_layers:
        kw["encoder_layers"] = n
    return cfg.with_overrides(**kw)


def run_cost(arch: str, shape_name: str, *, out_dir: str) -> dict:
    """Loop-corrected HLO cost estimation (roofline numerators).

    XLA's cost analysis counts while-loop bodies ONCE, so a scan-over-layers
    program under-reports FLOPs by ~num_layers x.  We lower the SAME step at
    depths of 1 and 2 layer-periods with inner chunk loops python-unrolled
    (REPRO_UNROLL=1), isolate the per-period body cost as the difference, and
    extrapolate:  total = f(P) + (R-1) * (f(2P) - f(P)).
    """
    assert os.environ.get("REPRO_UNROLL") == "1", "run via --cost-mode CLI"
    from repro.models.transformer import period_of

    shape = INPUT_SHAPES[shape_name]
    base_cfg = arch_shape_config(arch, shape)
    repeats = base_cfg.num_layers // period_of(base_cfg)
    if base_cfg.encoder_layers:
        assert base_cfg.encoder_layers // 1 == repeats, (
            "body extrapolation assumes equal encoder/decoder repeat counts"
        )

    results = []
    for n in (1, 2):
        cfg_n = _depth_reduced(base_cfg, n)
        _, _, mesh, lowered = lower_combo(
            arch, shape_name, multi_pod=False, cfg_override=cfg_n
        )
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        results.append(
            {
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "collectives": coll,
            }
        )
        print(f"[cost] {arch} x {shape_name} depth n={n}: "
              f"flops={results[-1]['flops']:.4g} coll={coll['count']}")

    f1, f2 = results

    def extrap(a, b):
        return a + (repeats - 1) * (b - a)

    est = {
        "flops": extrap(f1["flops"], f2["flops"]),
        "bytes": extrap(f1["bytes"], f2["bytes"]),
        "collectives": {
            k: extrap(f1["collectives"][k], f2["collectives"][k])
            for k in f1["collectives"]
        },
    }
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "single_pod",
        "kind": shape.kind,
        "repeats": repeats,
        "depth1": f1,
        "depth2": f2,
        "estimate": est,
        "model_params": base_cfg.param_count(),
        "model_params_active": base_cfg.param_count(active_only=True),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__cost.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"[cost] OK {arch} x {shape_name}: est flops/device "
          f"{est['flops']:.4g} -> {path}")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHITECTURES), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--cost-mode", action="store_true",
                    help="loop-corrected HLO cost estimation (single-pod)")
    ap.add_argument("--all", action="store_true", help="run the full matrix via subprocesses")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    # `python -m` imports the repro.launch package (and jax) before the
    # environment above is set, so pin the backend through the config too
    jax.config.update("jax_platforms", "cpu")

    if args.all:
        combos = [
            (a, s)
            for a in ARCHITECTURES
            if a != "gpt2-paper"
            for s in INPUT_SHAPES
        ]
        procs: list[tuple[tuple, subprocess.Popen]] = []
        failures = []
        pending = list(combos)
        while pending or procs:
            while pending and len(procs) < args.jobs:
                a, s = pending.pop(0)
                cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
                       "--shape", s, "--out", args.out]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                if args.cost_mode:
                    cmd.append("--cost-mode")
                # the children compile against forced host devices: they
                # pin the CPU backend (and the 512-device flag) themselves
                procs.append(((a, s), subprocess.Popen(cmd)))
            done = [(c, p) for c, p in procs if p.poll() is not None]
            procs = [(c, p) for c, p in procs if p.poll() is None]
            for c, p in done:
                if p.returncode != 0:
                    failures.append(c)
                    print(f"[dryrun] FAIL {c}")
            time.sleep(1.0)
        print(f"[dryrun] matrix done, {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    if args.cost_mode:
        run_cost(args.arch, args.shape, out_dir=args.out)
    else:
        run_one(args.arch, args.shape, multi_pod=args.multi_pod, out_dir=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
