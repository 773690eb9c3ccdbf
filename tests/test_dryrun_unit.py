"""Dry-run machinery unit tests (parser + policy; no 512-device compile)."""

import jax
import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.launch.policy import arch_shape_config, input_specs, window_for

# collective parser is defined inside dryrun; test it on a synthetic HLO
# snippet (importing the module sets no XLA_FLAGS — only its CLI does).
HLO = """
ENTRY %main {
  %ag = bf16[16,512]{1,0} all-gather(bf16[1,512]{1,0} %p0), replica_groups={}
  %ar.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add
  %a2a = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all(f32[8,4]{1,0} %y, f32[8,4]{1,0} %z)
  %cp = u32[2]{0} collective-permute(u32[2]{0} %c), source_target_pairs={{0,1}}
  %rs = bf16[64]{0} reduce-scatter(bf16[1024]{0} %w), to_apply=%add
  %dot = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b)
}
"""


def _parser():
    from repro.launch import dryrun

    return dryrun.collective_bytes


def test_collective_parser_counts_and_bytes():
    collective_bytes = _parser()
    out = collective_bytes(HLO)
    assert out["all-gather"] == 16 * 512 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["all-to-all"] == 8 * 4 * 4 * 2  # tuple of two buffers
    assert out["collective-permute"] == 2 * 4
    assert out["reduce-scatter"] == 64 * 2
    assert out["count"] == 5


def test_window_policy():
    shapes = INPUT_SHAPES
    assert window_for(get_config("command-r-35b"), shapes["long_500k"]) == 4096
    assert window_for(get_config("mamba2-130m"), shapes["long_500k"]) is None
    assert window_for(get_config("jamba-1.5-large-398b"), shapes["long_500k"]) is None
    assert window_for(get_config("command-r-35b"), shapes["decode_32k"]) is None


@pytest.mark.parametrize("arch", ["yi-9b", "internvl2-76b", "seamless-m4t-large-v2"])
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_shapes(arch, shape_name):
    shape = INPUT_SHAPES[shape_name]
    cfg = arch_shape_config(arch, shape)
    specs = input_specs(cfg, shape)
    if shape.kind in ("train", "prefill"):
        tok = specs["batch"]["tokens"]
        assert tok.shape[0] == shape.global_batch
        if cfg.family == "vlm":
            # patches + text tokens together occupy the assigned seq_len
            assert tok.shape[1] + cfg.frontend_len == shape.seq_len
        else:
            assert tok.shape[1] == shape.seq_len
        if cfg.frontend != "none":
            fe = specs["batch"]["frontend"]
            assert fe.shape == (shape.global_batch, cfg.frontend_len, cfg.d_model)
    else:
        assert specs["token"].shape == (shape.global_batch,)
        layers = specs["cache"]["layers"]
        assert layers  # per-layer caches exist
        # no allocation happened: these are ShapeDtypeStructs
        leaf = jax.tree.leaves(specs["cache"])[0]
        assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_decode_cache_ring_bounded_by_window():
    shape = INPUT_SHAPES["long_500k"]
    cfg = arch_shape_config("command-r-35b", shape)
    specs = input_specs(cfg, shape)
    k = specs["cache"]["layers"]["pos0"].k
    assert k.shape[2] == 4096  # ring buffer, not 524288
    cfg_j = arch_shape_config("jamba-1.5-large-398b", shape)
    specs_j = input_specs(cfg_j, shape)
    # jamba attention position carries the full-length cache
    attn_pos = f"pos{cfg_j.attn_offset}"
    assert specs_j["cache"]["layers"][attn_pos].k.shape[2] == 524288
