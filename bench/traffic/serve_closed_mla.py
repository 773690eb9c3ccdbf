"""Serving traffic on the Moonlight block: the closed loop of
``serve_closed.py`` (its ``Requests`` and its batch of one attach, a
prefill teacher-forced through the decode step and greedy decoding to the
longest output), through the program's ``ServeSession`` and
``AdapterCache``, with the Moonlight configuration built from its own
file (``model_config``: the published keys, cut to one chip's share).

Prompt ids are drawn from the vocabulary slice; logits and the argmax are
over it.  At set-up the driver reads the compiled decode step's HLO into
a map from instruction to part (``bench/scopes.py``), which the per-layer
metrics of the ``mla`` and ``moe`` parts read from the trace.  The
program's routing counters (``ServeSession.stats``) are read at the
window's start and end.

The check runs the plain reference (``bench/reference_mla.py``) over a
sample of the finished requests, with the longest among them, and compares
the logit the program gave each served token, as ``serve_closed.py``
does.  With random weights two experts' biased scores can lie within
rounding of each other, and the two sides may then choose different
experts, both rightly.  So the reference is handed the experts the program
routed each token to (``ServeSession.routes``, kept after each batch) and
follows them wherever its own scores allow that choice within
``tie_margin``; elsewhere a differing choice is a ``route_mismatches``.
``dropped_pairs`` counts the pairs the program's routing dropped in the
whole run.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_mla, reference_mla, scopes, weights_mla
from bench.traffic import serve_closed
from bench.traffic.serve_closed import CONTROLS, Requests

# the cell's limits: besides the limits of the check, the near-tie margin
# and why it is what it is, which the check reads wherever it runs
LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "limits",
                      "serve.moonlight.slora128.json")
NOT_CHECKS = ("tie_margin", "tie_margin_why")


def model_config(cfg: dict):
    """The program's ModelConfig for a Moonlight configuration file (the
    program always renormalises the chosen gates: ``norm_topk_prob``)."""
    from repro.configs.base import LoRAConfig, MLAConfig, ModelConfig, MoEConfig

    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k gates (norm_topk_prob true)")
    lora = cfg["lora"]
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=cfg["num_hidden_layers"],
        first_dense_layers=cfg["first_k_dense_replace"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mla=MLAConfig(kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
                      rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
                      d_ff=cfg["moe_intermediate_size"], scoring=cfg["scoring_func"],
                      routed_scale=cfg["routed_scaling_factor"],
                      shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                      experts_held=(cfg["n_routed_experts"], cfg["first_held_expert"])),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        lora=LoRAConfig(rank=lora["rank"], alpha=lora["alpha"], dropout=0.0,
                        targets=tuple(lora["targets"])),
    )


class HostAdapters:
    """The tenants' adapters, resident in host memory (``AdapterSource``)."""

    def __init__(self, leaves: dict, count: int):
        self.leaves, self.num_adapters = leaves, count

    def lora_row(self, cid: int):
        return {stack: {"pos0": {"lora": {
            t: {ab: self.leaves[stack][t][ab][cid] for ab in ("A", "B")}
            for t in self.leaves[stack]}}} for stack in self.leaves}


class Cell(serve_closed.Cell):
    def __init__(self, config: dict, mix: dict, seed: int, rec, seconds: float):
        from repro.lora import lora_template
        from repro.serve import AdapterCache, ServeConfig, ServeSession

        del seconds  # the loop is closed: no rate or horizon to plan for
        self.mix, self.seed, self.rec = mix, seed, rec
        self.spec = config
        self.matmul = config["matmul_precision"]
        jax.config.update("jax_default_matmul_precision", self.matmul)
        cfg = model_config(config)
        self.batch = config["batch"]
        self.vocab = config["vocab_size"]
        params, leaves = self._weights()
        self.leaves = jax.tree.map(np.asarray, leaves)
        del leaves
        cache = AdapterCache(HostAdapters(self.leaves, mix["tenants"]),
                             like=lora_template(params), slots=config["slots"])
        self.session = ServeSession(
            ServeConfig(model=cfg, batch=self.batch, cache_len=config["cache_len"], seed=seed),
            params, adapters=cache)
        self.requests = Requests(mix, self.batch, self.vocab, seed + 5)
        self.served, self.routes = [], []
        warm = Requests(mix, self.batch, self.vocab, seed + 6)
        for _ in range(mix["warm_batches"]):
            self._batch(*warm.next(mix["min_prompt"], mix["min_output"]), keep=False)
        cache.reset_stats()
        self.scopes = scopes.scope_map(self._step_hlo())
        self.dropped = 0
        with open(LIMITS) as f:
            self.tie = json.load(f)["tie_margin"]

    def _batch(self, tenants, prompts, outputs, keep=True):
        """``serve_closed``'s batch; a kept batch also keeps the experts the
        program routed its tokens to (device arrays, read at the check)."""
        got = super()._batch(tenants, prompts, outputs, keep)
        if keep:
            self.routes.append(self.session.routes())
        return got

    def _weights(self):
        return weights_mla.serving(weights_mla.seed_key(self.seed, 0),
                                   weights_mla.sizes(self.spec), self.mix["tenants"])

    def _step_hlo(self) -> str:
        """The compiled decode step's HLO text (from the compile cache)."""
        s = self.session
        args = (s._frozen, s.adapters.slab, s._slot_idx, s._cache,
                jnp.zeros((self.batch,), jnp.int32))
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        return s._stacked.lower(*shapes).compile().as_text()

    def window(self, seconds: float) -> dict:
        ttfts, gaps = [], []
        first = len(self.served)
        before = self.session.stats()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ttft, g = self._batch(*self.requests.next())
            ttfts += [ttft] * self.batch
            gaps += g
        elapsed = time.perf_counter() - t0
        after = self.session.stats()
        batches = self.served[first:]
        requests = len(batches) * self.batch
        tokens = sum(int(b[2].sum()) for b in batches)
        stats = self.session.adapters.stats
        m = self.spec
        routed = sum(after["moe_routed_pairs"]) - sum(before["moe_routed_pairs"])
        used = sum(after["moe_experts_used"]) - sum(before["moe_experts_used"])
        self.dropped = after["moe_dropped_pairs"]
        flops = fixed = latent = n_steps = live_rows = 0
        for tenants, prompts, outputs, _, _ in batches:
            length = prompts.shape[1]
            for pos in range(length + int(outputs.max()) - 1):
                live = np.ones(self.batch, bool) if pos < length else outputs > pos - length + 1
                filled = [pos] * int(live.sum())
                flops += len(filled) * flops_mla.token_flops(m, pos)
                fixed += (flops_mla.step_fixed_bytes(m, self.batch)
                          + len(set(tenants[live].tolist())) * 4
                          * flops_mla.adapter_weights(m))
                latent += flops_mla.latent_bytes(m, filled)
                live_rows += len(filled)
                n_steps += 1
        live_share = live_rows / max(1, n_steps * self.batch)
        flops += int(routed * live_share) * flops_mla.pair_flops(m)
        experts = used * 4 * flops_mla.expert_weights(m)
        _, moe_layers = flops_mla.layers(m)
        return {
            "window_s": elapsed, "attempted": requests, "failed": 0,
            "metrics": {
                "serve_tokens_per_s": tokens / elapsed,
                "ttft_p95_ms": 1e3 * float(np.percentile(ttfts, 95)),
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            },
            "counters": {
                "requests": requests, "batches": len(batches), "steps": n_steps,
                "step_flops": flops, "step_bytes": fixed + latent + experts,
                "mla_bytes": n_steps * 4 * m["num_hidden_layers"] * flops_mla.mla_weights(m)
                + latent,
                "moe_bytes": n_steps * 4 * moe_layers * flops_mla.moe_fixed_weights(m) + experts,
                "routed_pairs": routed, "experts_used": used, "dropped_pairs": self.dropped,
                "hits": stats.hits, "misses": stats.misses,
                "step_module": "jit_stacked_decode_step",
                "session_modules": ["jit_stacked_decode_step", "jit_slab_set_row"],
                "scopes": self.scopes,
            },
        }

    def readings(self, prec=None) -> dict:
        """``serve_closed``'s readings, ``route_mismatches`` (positions and
        layers where the program chose experts the reference could not have
        chosen within the tie margin), ``followed_share`` (the share of
        positions and layers where it followed the program's other choice
        at a near-tie) and ``dropped_pairs``.  With ``prec`` the reference
        computed at that lower precision takes the program's place, its
        own routes with it."""
        return dict(served_gaps(self, prec), dropped_pairs=self.dropped)

    def check(self, limits: dict) -> dict:
        got = self.readings()
        return {k: (got[k], lim) for k, lim in limits.items() if k not in NOT_CHECKS}


def _sample(cell: Cell) -> list[tuple]:
    """``serve_closed.sample``'s requests (the same seeded choice, with the
    longest), each with the experts the program routed its positions to,
    ``(layers, C, k)``."""
    rng = np.random.default_rng(cell.seed + 7)
    flat = [(b, r) for b in range(len(cell.served)) for r in range(cell.batch)]

    def size(br):
        return cell.served[br[0]][1].shape[1] + cell.served[br[0]][2][br[1]]

    picks = {max(flat, key=size)}
    for i in rng.permutation(len(flat)):
        if len(picks) >= cell.mix["check_requests"]:
            break
        picks.add(flat[i])
    out = []
    for b, r in sorted(picks):
        tenants, prompts, outputs, toks, tops = cell.served[b]
        n = int(outputs[r])
        routes = np.concatenate([np.asarray(a[:, r]) for a in cell.routes[b].values()])
        out.append((int(tenants[r]), prompts[r], toks[r, :n], tops[r, :n],
                    routes.transpose(0, 2, 1)))
    return out


@functools.partial(jax.jit, static_argnames=("spec", "matmul", "control"))
def _block_gaps(frozen, lora, toks, pos, valid, length, chosen, top, routes, tie, *, spec,
                matmul, control):
    if control:
        values, products = CONTROLS[control]
        low, routes, _, _ = reference_mla.served_logits(frozen, lora, toks, pos, spec=spec,
                                                        prec=values, matmul=products)
        chosen, top = jnp.argmax(low, -1), jnp.max(low, -1)
    ref, _, mismatch, followed = reference_mla.served_logits(
        frozen, lora, toks, pos, routes, tie, spec=spec, matmul=matmul)
    # the positions and layers of the real requests' own tokens
    real = jnp.broadcast_to(
        (jnp.arange(toks.shape[1]) < length[:, None])[:, None] & valid.any(-1)[:, None, None],
        mismatch.shape)
    at = jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    gap = jnp.where(valid, jnp.abs(top - at), 0.0)
    greedy_gap = jnp.where(valid, ref.max(-1) - at, 0.0).max()
    return (gap.max(), gap.sum(), valid.sum(), greedy_gap, jnp.sum(mismatch & real),
            jnp.sum(followed & real), jnp.sum(real))


def served_gaps(cell: Cell, prec=None) -> dict:
    """The check's numbers, in blocks of ``check_block`` requests of one
    shape each, as ``serve_closed.served_gaps``."""
    if prec is not None and prec not in CONTROLS:
        raise ValueError(f"no control at precision {prec!r}")
    frozen, _ = cell._weights()
    mix = cell.mix
    spec = reference_mla.spec_of(cell.spec)
    width, n_out = mix["max_prompt"] + mix["max_output"], mix["max_output"]
    worst = {"logit_gap": 0.0, "greedy_gap": 0.0}
    total = count = mismatches = followed = routed = 0.0
    picks = _sample(cell)
    size = mix["check_block"]
    for lo in range(0, len(picks), size):
        block = picks[lo:lo + size]
        real = len(block)
        block += block[:1] * (size - real)  # one shape for every call
        toks = np.zeros((size, width), np.int32)
        pos = np.zeros((size, n_out), np.int32)
        valid = np.zeros((size, n_out), bool)
        length = np.zeros(size, np.int32)
        chosen = np.zeros((size, n_out), np.int32)
        top = np.zeros((size, n_out), np.float32)
        layers, _, k = block[0][4].shape
        routes = np.zeros((size, layers, width, k), np.int32)
        for i, (_, prompt, out, logit, route) in enumerate(block):
            seq = np.concatenate([prompt, out[:-1]])
            toks[i, :seq.size] = seq
            length[i] = seq.size
            pos[i, :out.size] = np.arange(prompt.size - 1, seq.size)
            valid[i, :out.size] = i < real
            chosen[i, :out.size], top[i, :out.size] = out, logit
            routes[i, :, :seq.size] = route[:, :seq.size]
        lora = {s: {t: {ab: jnp.asarray(np.stack([cell.leaves[s][t][ab][c] for c, *_ in block]))
                        for ab in ("A", "B")} for t in cell.leaves[s]} for s in cell.leaves}
        got = _block_gaps(frozen, lora, toks, pos, valid, length, chosen, top, routes,
                          float(cell.tie), spec=spec, matmul=cell.matmul, control=prec)
        widest, summed, n_valid, greedy, n_mis, n_fol, n_routed = (float(x) for x in got)
        worst["logit_gap"] = max(worst["logit_gap"], widest)
        worst["greedy_gap"] = max(worst["greedy_gap"], greedy)
        total, count = total + summed, count + n_valid
        mismatches, followed, routed = mismatches + n_mis, followed + n_fol, routed + n_routed
    return dict(worst, logit_gap_mean=total / count, route_mismatches=int(mismatches),
                followed_share=followed / routed)
