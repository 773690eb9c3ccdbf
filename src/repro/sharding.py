"""Logical-axis sharding rules (DESIGN §4).

Physical mesh axes:
  single-pod: ("data", "model") = (16, 16)
  multi-pod:  ("pod", "data", "model") = (2, 16, 16)

Logical roles:
  BATCH  — activation batch; shards over ("pod","data")
  FSDP   — weight-shard axis (ZeRO-3 style); shards over ("pod","data") so
           optimizer state for 398B-param configs fits HBM
  TENSOR — heads / d_ff / experts / vocab; shards over ("model",)
  SEQ    — decode KV-cache sequence axis; shards over ("model",)
           (flash-decoding layout, DESIGN §4)

Parameter specs are derived from pytree *paths* (the zoo's naming is the
contract; tested in tests/test_sharding.py).  A leading stacked-layer axis
(from scan-over-layers) is automatically skipped.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "COHORT_AXIS",
    "axis_names",
    "batch_axes",
    "cohort_mesh",
    "fsdp_axes",
    "param_specs",
    "batch_specs",
    "cache_specs",
    "opt_state_specs",
    "named",
    "tree_named",
]

# ---------------------------------------------------------------------------
# federated cohort axis
#
# The FL round engines' leading client axis is embarrassingly parallel
# (Algorithm 1 runs each selected client independently), so its device
# placement is a plain 1-D mesh — orthogonal to the production data/model
# mesh above.  One shared name + constructor keeps the fused client-phase
# shard_map and the fused-e2e in-body shard_map on the same axis contract.
# ---------------------------------------------------------------------------

COHORT_AXIS = "clients"


def cohort_mesh() -> Mesh:
    """1-D mesh over every addressable device, axis :data:`COHORT_AXIS` —
    where the round engines place the selected cohort (``shard_clients``).
    On CPU, exercised via ``XLA_FLAGS=--xla_force_host_platform_device_count``.
    """
    import numpy as np

    return Mesh(np.array(jax.devices()), (COHORT_AXIS,))


def axis_names(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh: Mesh):
    return batch_axes(mesh)


def _param_spec_for(path: tuple[str, ...], shape: tuple[int, ...], mesh: Mesh) -> P:
    """Spec for one parameter leaf given its path and shape."""
    ndim = len(shape)
    model_size = mesh.shape["model"]
    fsdp = fsdp_axes(mesh)
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    gparent = path[-3] if len(path) >= 3 else ""

    # ---- stacked-layer leading axes (posJ dicts under "stack"/"encoder") ----
    lead: tuple = ()
    core_ndim = ndim
    if any(p.startswith("pos") and p[3:].isdigit() for p in path):
        lead = (None,)
        core_ndim = ndim - 1

    def spec(*axes):
        assert len(axes) == core_ndim, (path, ndim, axes)
        return P(*(lead + axes))

    # ---- embeddings / heads ----
    if name in ("embed", "lm_head", "pos_embed"):
        return P("model", fsdp)  # (V, D): vocab tensor-sharded, D fsdp

    # ---- norms / scalars / vectors ----
    if parent in ("norm1", "norm2", "norm_x", "final_norm", "enc_norm") or name in (
        "scale",
        "bias",
    ) and core_ndim == 1 and parent not in ("gate_norm",):
        return spec(*([None] * core_ndim))
    if parent == "gate_norm":  # (d_inner,) — model-sharded like its activations
        return spec("model")

    # ---- attention projections ----
    if gparent in ("attn", "cross") or parent in ("attn", "cross"):
        if name == "b":
            return spec("model") if parent != "wo" else spec(None)
        if parent in ("wq", "wk", "wv"):
            return spec(fsdp, "model")
        if parent == "wo":
            return spec("model", fsdp)
        # MLA: the latent projection is shared by all heads; kv_b_proj's
        # halves are head-sharded like wq
        if parent == "wkv_a":
            return spec(fsdp, None)
        if parent in ("wk_b", "wv_b"):
            return spec(None, "model")

    # ---- LoRA ----
    if name == "A":
        return spec(fsdp, None)
    if name == "B" and core_ndim == 2 and parent not in ("in_proj", "out_proj"):
        return spec(None, "model")

    # ---- MoE ----
    if parent == "router":
        return spec(fsdp, None) if core_ndim == 2 else spec(None)
    if name == "score_bias":
        return spec(None)
    if name in ("up", "gate", "down") and core_ndim == 3:
        # 2D weight-stationary sharding (§Perf iteration 7): experts over
        # model, per-expert F over fsdp.  The expert einsums then need NO
        # weight all-gathers (the contraction dims are unsharded or match),
        # only an activation-sized all-reduce after `down` — replacing the
        # GB-scale gathered-weight buffers the scan held live.
        return spec("model", None, fsdp) if name != "down" else spec("model", fsdp, None)

    # ---- dense MLP ----
    if parent in ("up", "gate"):
        if name == "w":
            return spec(fsdp, "model")
        return spec("model")
    if parent == "down":
        if name == "w":
            return spec("model", fsdp)
        return spec(None)

    # ---- SSM (Mamba2) ----
    if parent in ("w_z", "w_x"):
        # (D, d_inner): inner dim tensor-sharded
        return spec(fsdp, "model") if name == "w" else spec("model")
    if parent == "w_bc":
        # B/C (2N wide): replicated — O(N) small
        return spec(fsdp, None) if name == "w" else spec(None)
    if parent == "w_dt":
        # dt heads: shard over model when divisible (jamba H=256), else
        # replicate (mamba2-130m H=24) — keeping dt/a head-sharded keeps the
        # (B,H,nc,Q,Q) SSD decay tensors head-sharded (§Perf iteration 4)
        div = shape[-1] % model_size == 0
        if name == "w":
            return spec(fsdp, "model") if div else spec(fsdp, None)
        return spec("model") if div else spec(None)
    if parent == "out_proj":
        if name == "w":
            return spec("model", fsdp)
        return spec(None)
    if name == "conv_x_w":
        return spec(None, "model")
    if name == "conv_x_b":
        return spec("model")
    if name in ("conv_bc_w",):
        return spec(None, None)
    if name in ("dt_bias", "a_log", "d_skip"):
        return spec("model") if shape[-1] % model_size == 0 else spec(None)
    if name == "conv_bc_b":
        return spec(None)

    # fallback: replicate
    return spec(*([None] * core_ndim))


def _path_strings(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        elif hasattr(p, "idx"):
            out.append(f"idx{p.idx}")
        else:
            out.append(str(p))
    return tuple(out)


def param_specs(params_shape: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching a params (shape) pytree."""

    def one(path, leaf):
        return _param_spec_for(_path_strings(path), tuple(leaf.shape), mesh)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_state_specs(params_specs: Any, count_spec: P | None = None) -> Any:
    """AdamW state: moments shard exactly like params; count replicated."""
    from repro.optim.adamw import AdamWState

    return AdamWState(
        m=params_specs,
        v=params_specs,
        count=count_spec if count_spec is not None else P(),
    )


def batch_specs(mesh: Mesh, *, batch_shardable: bool = True, with_frontend: bool = False,
                with_labels: bool = True) -> dict:
    """Input batch: tokens/labels (B, S) batch-sharded (unless B=1)."""
    b = batch_axes(mesh) if batch_shardable else None
    out = {"tokens": P(b, None)}
    if with_labels:
        out["labels"] = P(b, None)
    if with_frontend:
        out["frontend"] = P(b, None, None)
    return out


def cache_specs(cache_shape: Any, mesh: Mesh, *, batch_shardable: bool = True) -> Any:
    """Decode cache: KV k/v (B, Kv, Dh, C) and MLA latent (B, W, C) -> seq
    (C) sharded over model;
    SSM conv (B, W-1, ch) -> ch over model; state (B,H,P,N) -> H over model.
    All have a leading stacked-repeats axis from scan-over-layers."""
    b = batch_axes(mesh) if batch_shardable else None

    def one(path, leaf):
        names = _path_strings(path)
        nd = len(leaf.shape)
        last = names[-1]
        if last in ("k", "v") and nd == 5:  # (R, B, Kv, Dh, C)
            return P(None, b, None, None, "model")
        if last == "latent" and nd == 4:  # (R, B, kv_rank + rope_dim, C)
            return P(None, b, None, "model")
        if last == "pos":  # (R, C)
            return P(None, "model")
        if last == "length":
            return P() if nd == 0 else P(None)
        if last == "conv_x":  # (R, B, W-1, d_inner)
            return P(None, b, None, "model")
        if last == "conv_bc":  # (R, B, W-1, 2N)
            return P(None, b, None, None)
        if last == "state":  # (R, B, H, P, N): H is not mesh-divisible for
            # every arch (mamba2-130m has 24 heads); N=128 always divides.
            return P(None, b, None, None, "model")
        if last == "enc_out":  # (B, F, D)
            return P(b, None, None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# ---------------------------------------------------------------------------
# activation sharding constraints (perf: §Perf iteration 1)
#
# XLA's sharding propagation loses the head axis through the GQA reshapes,
# replicating (B, H, S, T) attention scores on every device.  The launcher
# installs logical->mesh rules here; model code calls ``constrain`` at the
# few places propagation needs anchoring.  Default None = no-op (single-
# device tests, FL runtime).
# ---------------------------------------------------------------------------

_ACTIVATION_RULES: dict | None = None


def set_activation_sharding(mesh: Mesh | None) -> None:
    """Install (or clear, with None) activation-constraint rules."""
    global _ACTIVATION_RULES
    if mesh is None:
        _ACTIVATION_RULES = None
        return
    _ACTIVATION_RULES = {
        "batch": batch_axes(mesh),
        "heads": "model",
        "dff": "model",
        "vocab": "model",
        "kv": None,
    }


def rules_installed() -> bool:
    return _ACTIVATION_RULES is not None


def constrain(x, *logical: str | None):
    """with_sharding_constraint by logical axis names; no-op when rules are
    uninstalled.  Must run under the mesh context (the launcher's ``with
    mesh:``)."""
    if _ACTIVATION_RULES is None:
        return x
    spec = P(*[(_ACTIVATION_RULES.get(l) if l else None) for l in logical])
    return jax.lax.with_sharding_constraint(x, spec)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
