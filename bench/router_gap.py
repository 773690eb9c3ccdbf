"""The widest difference between the program's and the reference's biased
router scores, which the Moonlight cell's near-tie margin (``tie_margin``
in its limits file) has to exceed ten times over.

    python3 bench/router_gap.py --workload serve.moonlight.slora128 \
        --seeds <n,n,...> [--rows 8]

For each seed the cell is set up as the benchmark sets it up, with the
program's router made to hand every decode step's biased scores (``sigmoid
(logits) + score_bias``, per MoE layer, each row of the batch) to the host;
one batch of requests is served, and the plain reference, its router
likewise observed, runs over the first ``rows`` requests.  The difference
is taken over every position before a request's first near-tie at a
generous margin (1e-5, ten times the limit's own at most): a tie broken the
other way sends the two sides down different experts, and everything after
it differs by far more than rounding.  Nothing here runs in the timed path.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GENEROUS = 1e-5


@contextlib.contextmanager
def _observed(module, name, biased_of, sink):
    """Within the block, ``module.name`` hands the biased scores that
    ``biased_of(*args)`` computes from its arguments to ``sink``, in order."""
    import jax

    orig = getattr(module, name)

    def observed(*args):
        jax.debug.callback(lambda b: sink.append(np.asarray(b)), biased_of(*args), ordered=True)
        return orig(*args)

    setattr(module, name, observed)
    try:
        yield
    finally:
        setattr(module, name, orig)


def _program_biased(params, tokens, moe, compute_dtype):
    import jax
    import jax.numpy as jnp

    logits = jnp.einsum("...d,de->...e", tokens.astype(compute_dtype),
                        params["router"]["w"].astype(compute_dtype)).astype(jnp.float32)
    return jax.nn.sigmoid(logits) + params["score_bias"].astype(jnp.float32)


def _reference_biased(h, m, s, mm, *_):
    import jax
    import jax.numpy as jnp

    logits = mm("td,de->te", h, m["router"]["w"]).astype(jnp.float32)
    return jax.nn.sigmoid(logits) + m["score_bias"]


def reference_scores(frozen, lora, tokens, spec, matmul="highest"):
    """The plain reference's biased router scores of ``tokens (B, T)``, in
    order of request and MoE layer, ``(B * layers, T, E)``, from its own
    pass with its router observed."""
    import jax

    from bench import reference_mla

    seen = []
    # a function of its own, so that jit traces it afresh with the router observed
    served = jax.jit(lambda *a, **k: reference_mla.served_logits.__wrapped__(*a, **k),
                     static_argnames=("spec", "matmul"))
    with _observed(reference_mla, "_route", _reference_biased, seen):
        served(frozen, lora, tokens, tokens[:, :1] * 0, spec=spec, matmul=matmul)
        jax.effects_barrier()
    return np.stack(seen)


def measure(root: str, name: str, seed: int, rows: int = 8) -> dict:
    """``{"widest": ..., "positions": ...}`` for one seed (module docstring)."""
    import jax
    import jax.numpy as jnp

    from bench import reference_mla
    from bench import run as bench_run
    from repro.models import moe

    files = bench_run.cell_files(root, name)
    driver = bench_run.load_module(
        os.path.join(root, "bench", "traffic", files["mix"]["driver"] + ".py"))
    program = []
    with _observed(moe, "route", _program_biased, program):
        cell = driver.Cell(files["config"], files["mix"], seed, bench_run.Recorder(), 0.0)
        jax.effects_barrier()
        program.clear()
        tenants, prompts, outputs = cell.requests.next()
        cell._batch(tenants, prompts, outputs)
        jax.effects_barrier()
    _, _, _, toks, _ = cell.served[-1]
    cell.free()
    layers = cell.spec["num_hidden_layers"] - cell.spec["first_k_dense_replace"]
    steps = len(program) // layers
    prog = np.stack(program).reshape(steps, layers, cell.batch, -1)  # (T, layers, B, E)
    seq = np.concatenate([prompts, toks], axis=1)[:rows, :steps]
    frozen, _ = cell._weights()
    lora = {s: {t: {ab: jnp.asarray(cell.leaves[s][t][ab][tenants[:rows]]) for ab in ("A", "B")}
                for t in cell.leaves[s]} for s in cell.leaves}
    ref = reference_scores(frozen, lora, jnp.asarray(seq), reference_mla.spec_of(cell.spec),
                           cell.matmul).reshape(rows, layers, steps, -1)
    top = -np.sort(-ref, axis=-1)
    k = cell.spec["num_experts_per_tok"]
    margin = (top[..., k - 1] - top[..., k]).min(axis=1)  # (rows, steps)
    widest, positions = 0.0, 0
    for b in range(rows):
        ties = np.flatnonzero(margin[b] < GENEROUS)
        upto = ties[0] if ties.size else steps
        diff = np.abs(prog[:upto, :, b] - ref[b].transpose(1, 0, 2)[:upto])
        widest = max(widest, float(diff.max(initial=0.0)))
        positions += int(upto)
    return {"cell": name, "seed": seed, "widest": widest, "positions": positions,
            "rows": rows, "steps": steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(measure(ROOT, args.workload, seed, args.rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
