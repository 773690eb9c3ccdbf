"""Serving CLI: a thin driver over :class:`repro.serve.ServeSession`.

Single-adapter smoke (the pre-redesign behaviour, honest timing):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --tokens 32

Multi-tenant: give every request its own tenant adapter, paged through the
AdapterCache — from a federation checkpoint (``fed_train --ckpt-dir``) or
from synthetic random adapters when no checkpoint is given:

  PYTHONPATH=src python -m repro.launch.serve --adapters 8 --slots 8
  PYTHONPATH=src python -m repro.launch.serve --adapters 8 --from-ckpt runs/fed

Timing is split: the first decode step (jit compile + run) is reported
separately, throughput is STEADY-STATE decode tokens/sec after that warmup
— the pre-redesign script started its clock before the first jitted call
and folded ~seconds of XLA compile into tok/s.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHITECTURES, get_smoke_config
from repro.lora import lora_template, map_lora, split_lora
from repro.serve import (
    AdapterCache,
    ServeConfig,
    ServeSession,
    export_adapters,
    serving_params,
)


class _RandomAdapters:
    """Synthetic tenant population: tenant cid = adapter with randomized
    A AND B (fresh-init B is zero — the delta would vanish)."""

    def __init__(self, params, num_adapters: int, seed: int):
        self._lora, _ = split_lora(params)
        self.num_adapters = int(num_adapters)
        self._seed = seed

    def lora_row(self, cid: int):
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), int(cid))
        counter = [0]

        def rnd(x):
            counter[0] += 1
            k = jax.random.fold_in(key, counter[0])
            return 0.05 * jax.random.normal(k, x.shape).astype(x.dtype)

        return map_lora(rnd, self._lora)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHITECTURES), default="gpt2-paper")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--adapters", type=int, default=0,
                    help="serve this many distinct tenants (0 = single-adapter)")
    ap.add_argument("--slots", type=int, default=8,
                    help="device adapter-cache slots")
    ap.add_argument("--from-ckpt", default=None,
                    help="page tenant adapters from this fed_train --ckpt-dir "
                         "(default: synthetic random adapters)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.from_ckpt is not None and args.arch == "gpt2-paper":
        # fed_train trains REDUCED_CLIENT by default — the smoke config's
        # shapes (2 layers) would not match the checkpointed backbone
        from repro.configs.gpt2_paper import REDUCED_CLIENT as cfg
    else:
        cfg = get_smoke_config(args.arch)
    from repro.models import init as model_init

    params = model_init(jax.random.PRNGKey(args.seed), cfg)
    scfg = ServeConfig(
        model=cfg, batch=args.batch, cache_len=args.prompt_len + args.tokens,
        temperature=args.temperature, seed=args.seed,
    )

    adapters = None
    if args.adapters > 0:
        if cfg.lora is None:
            raise SystemExit(f"--adapters needs a LoRA-enabled arch; "
                             f"{args.arch} smoke config has none")
        if args.from_ckpt is not None:
            source = export_adapters(args.from_ckpt)
            params = serving_params(source, params)
        else:
            source = _RandomAdapters(params, args.adapters, args.seed)
        adapters = AdapterCache(
            source, like=lora_template(params), slots=args.slots
        )

    sess = ServeSession(scfg, params, adapters=adapters)
    if adapters is not None:
        tenant_ids = [i % source.num_adapters for i in range(args.batch)]
        slots = sess.attach(tenant_ids)
        print(f"[serve] tenants {tenant_ids} -> slots {slots.tolist()}")

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)
    ).astype(np.int32)

    sess.prefill(prompts)  # also warms up + compiles the decode step
    t0 = time.perf_counter()
    gen, logits = sess.decode(args.tokens)
    logits = np.asarray(logits)  # the read-back ends the timed decode
    steady = (time.perf_counter() - t0) / max(args.tokens, 1)
    assert np.isfinite(logits).all()

    s = sess.stats()
    mode = "stacked" if sess.attached else "single"
    tok_s = args.batch / steady if steady > 0 else float("inf")
    print(f"[serve] {args.arch} ({mode}): compile+first step "
          f"{s['first_step_s'].get(mode, 0.0):.2f}s, steady decode "
          f"{steady * 1e3:.1f} ms/step = {tok_s:.1f} tok/s "
          f"({args.batch}x{args.tokens} tokens)")
    if adapters is not None:
        print(f"[serve] adapter cache: {s['adapter_cache']} "
              f"(slots={s['adapter_slots']})")
    print(f"[serve] executables: {s['executables']}")
    print("[serve] sample:", gen[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
