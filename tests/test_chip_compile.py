"""The main path's Pallas kernels compile for a TPU v5e chip at published
widths (GPT-2's V=50,257, Table I's public batch P=256, a cohort of N=10),
and so does the serving decode step, which reads each layer's KV cache
where it lies: its temporary memory stays below one layer's K slice; the
same holds for Moonlight's latent cache, whose held experts run as grouped
products.

Nothing runs here: each kernel is lowered against a v5e:2x2 topology that is
DESCRIBED, not attached, and compiled by the chip's own compiler, which
refuses what interpret mode accepts (unaligned blocks, primitives Mosaic
cannot lower, VMEM overflows).  The topology is described inside a
module-scoped fixture — only the test process that runs this file loads the
TPU compiler — and the persistent compilation cache is off around these
compiles (a cached TPU executable cannot be read back without a chip).
"""

import pytest

import jax
import jax.numpy as jnp

from repro.configs.gpt2_paper import GPT2_SMALL
from repro.configs.moonlight_16b_a3b import CONFIG as MOONLIGHT
from repro.kernels.distill_kl import distill_kl_pallas
from repro.kernels.sparse_agg import (
    scatter_wire_sums_dequant_pallas,
    scatter_wire_sums_pallas,
)
from repro.kernels.topk_select import topk_mask_dynamic_pallas, topk_mask_pallas
from repro.lora import split_lora
from repro.models import init, init_cache
from repro.serve import make_decode_step
from repro.serve.steps import make_stacked_decode_step

V, P, N = 50_257, 256, 10
K_CAPS = (512, 4096)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return fn.lower(*args, interpret=False, **static).compile().as_text()


def test_topk_mask_dynamic_compiles(one_chip):
    # the fused engine's uplink sparsifier: one budget per (client, sample) row
    text = _compiled_text(
        topk_mask_dynamic_pallas, one_chip,
        ((N * P, V), jnp.float32), ((N * P,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k_cap", K_CAPS)
def test_topk_mask_compiles(one_chip, k_cap):
    text = _compiled_text(
        topk_mask_pallas, one_chip, ((N * P, V), jnp.float32), k=k_cap
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k_cap", K_CAPS)
def test_scatter_wire_sums_compiles(one_chip, k_cap):
    # the server's wire aggregation in the fused_e2e round (use_kernels)
    wire = (N, P, k_cap)
    text = _compiled_text(
        scatter_wire_sums_pallas, one_chip,
        (wire, jnp.float32), (wire, jnp.float32), (wire, jnp.int32), vocab=V,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k_cap", K_CAPS)
def test_scatter_wire_sums_dequant_compiles(one_chip, k_cap):
    wire = (N, P, k_cap)
    text = _compiled_text(
        scatter_wire_sums_dequant_pallas, one_chip,
        (wire, jnp.int8), ((N, P), jnp.float32), (wire, jnp.int8),
        (wire, jnp.int32), vocab=V, mode="adaptive",
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", (P, N * P))
def test_distill_kl_compiles(one_chip, rows):
    # server-side (P rows) and cohort-side (N·P rows) distillation KL
    text = _compiled_text(
        distill_kl_pallas, one_chip,
        ((rows, V), jnp.float32), ((rows, V), jnp.float32), temperature=2.0,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["stacked", "single"])
def test_decode_step_moves_no_layer_slice(one_chip, mode):
    # GPT-2 small's widths and the served cell's batch 8 and 1,024-slot
    # cache, in two layers: no whole layer slice of the cache is copied out,
    # relaid or written back, and the donated cache comes back in its own
    # buffers.  (The CPU compiler materialises every per-layer operand of a
    # dot, so only the chip's compiler can show this.)
    cfg, b, c = GPT2_SMALL.with_overrides(num_layers=2), 8, 1024

    def spec(tree, lead=()):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((*lead, *a.shape), a.dtype, sharding=one_chip), tree
        )

    params = spec(jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg)))
    cache = spec(jax.eval_shape(lambda: init_cache(cfg, b, c)))
    token = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    if mode == "single":
        step = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        compiled = step.lower(params, cache, token).compile()
    else:
        lora, frozen = split_lora(params)
        step = jax.jit(make_stacked_decode_step(cfg), donate_argnums=(3,))
        slots = token  # (B,) int32 like a token: each request's adapter slot
        compiled = step.lower(frozen, spec(lora, (4,)), slots, cache, token).compile()
    mem = compiled.memory_analysis()
    layer_k_bytes = b * c * cfg.num_kv_heads * cfg.head_dim * 4
    assert mem.temp_size_in_bytes < layer_k_bytes, (mem.temp_size_in_bytes, layer_k_bytes)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes, (mem.alias_size_in_bytes, cache_bytes)


def test_latent_decode_step_reads_the_cache_in_place(one_chip):
    # Moonlight's published widths, the cell's batch 128 and 1,024 slots,
    # 8 of 64 experts held, in one dense and one MoE layer: the latent stack
    # is read where it lies by both of its reads (scores, weighted sum), and
    # the held experts' grouped products compile to the chip's ragged dot
    import dataclasses

    cfg = MOONLIGHT.with_overrides(
        num_layers=2, vocab_size=20_480, param_dtype="float32", compute_dtype="float32",
        moe=dataclasses.replace(MOONLIGHT.moe, experts_held=(8, 24)))
    b, c = 128, 1024

    def spec(tree, lead=()):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((*lead, *a.shape), a.dtype, sharding=one_chip), tree
        )

    params = spec(jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg)))
    cache = spec(jax.eval_shape(lambda: init_cache(cfg, b, c)))
    token = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    lora, frozen = split_lora(params)
    step = jax.jit(make_stacked_decode_step(cfg), donate_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        compiled = step.lower(frozen, spec(lora, (4,)), token, cache, token).compile()
    mem = compiled.memory_analysis()
    layer_latent_bytes = b * c * (cfg.mla.kv_rank + cfg.mla.rope_dim) * 4
    assert mem.temp_size_in_bytes < layer_latent_bytes / 2, (
        mem.temp_size_in_bytes, layer_latent_bytes)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes, (mem.alias_size_in_bytes, cache_bytes)
    assert "ragged-dot" in compiled.as_text()
