"""Sharding-spec machinery + roofline parsers (unit level, 1 device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro import configs
from repro.core.params import ParamDef, spec_tree, stack_defs
from repro.launch import roofline
from repro.sharding.context import spec_for


RULES = {"heads": "model", "ffn": "model", "embed": None,
         "batch": ("pod", "data"),
         "__sizes__": {"model": 16, "data": 16, "pod": 2}}


def test_spec_tree_divisibility_fallback():
    defs = {
        "ok": ParamDef((64, 32), axes=("embed", "heads")),     # 32 % 16 == 0
        "bad": ParamDef((64, 24), axes=("embed", "heads")),    # 24 % 16 != 0
    }
    specs = spec_tree(defs, RULES)
    assert specs["ok"] == PartitionSpec(None, "model")
    assert specs["bad"] == PartitionSpec(None, None)


def test_spec_tree_axis_used_once():
    defs = {"w": ParamDef((32, 32), axes=("heads", "ffn"))}
    spec = spec_tree(defs, RULES)["w"]
    # both logical axes map to "model"; only the first dim may take it
    assert spec == PartitionSpec("model", None)


def test_stacked_defs_get_layer_axis():
    defs = stack_defs({"w": ParamDef((8, 32), axes=(None, "ffn"))}, 4)
    assert defs["w"].shape == (4, 8, 32)
    assert spec_tree(defs, RULES)["w"] == PartitionSpec(None, None, "model")


def test_spec_for_batch_multi_axis():
    spec = spec_for((64, 128), ("batch", None), RULES)
    assert spec == PartitionSpec(("pod", "data"), None)
    # batch not divisible by pod*data => replicated
    assert spec_for((7, 128), ("batch", None), RULES) == \
        PartitionSpec(None, None)


# ------------------------------------------------------------- roofline
def test_shape_bytes():
    assert roofline.shape_bytes("bf16[16,4096,128]{2,1,0}") == \
        16 * 4096 * 128 * 2
    assert roofline.shape_bytes("(f32[8]{0}, s32[4]{0})") == 8 * 4 + 4 * 4
    assert roofline.shape_bytes("pred[]") == 1


def test_collective_bytes_parser():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), replica_groups={{0,1}}
  %ar.1 = bf16[256]{0} all-reduce-start(%y), to_apply=%add
  %ar.d = bf16[256]{0} all-reduce-done(%ar.1)
  %rs = (f32[32]{0}, f32[32]{0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = u8[1024]{0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    out = roofline.collective_bytes(hlo)
    assert out["all-gather"] == 64 * 128 * 4
    assert out["all-reduce"] == 256 * 2          # -start counted, -done not
    assert out["reduce-scatter"] == 32 * 4 * 2
    assert out["collective-permute"] == 1024


def test_hbm_traffic_counts_major_ops_only():
    hlo = """
ENTRY %main (p0: f32[128,64], p1: f32[64,32]) -> f32[128,32] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %p1 = f32[64,32]{1,0} parameter(1)
  %t = f32[128,64]{1,0} tanh(%p0)
  ROOT %d = f32[128,32]{1,0} dot(%t, %p1), lhs_contracting_dims={1}
}
"""
    got = roofline.hbm_traffic(hlo)
    want = (128 * 64 + 64 * 32 + 128 * 32) * 4   # dot operands + result
    assert got == want


def test_roofline_terms_and_bottleneck():
    rl = roofline.Roofline(flops=197e12, hbm_bytes=819e9 * 2,
                           coll_bytes=50e9 * 0.5, coll_by_kind={},
                           device_kind="TPU v5 lite")
    assert abs(rl.t_compute - 1.0) < 1e-9
    assert abs(rl.t_memory - 2.0) < 1e-9
    assert abs(rl.t_collective - 0.5) < 1e-9
    assert rl.bottleneck == "memory"
    assert rl.t_bound == 2.0


def test_peaks_keyed_by_device_kind_and_unknown_kind_raises():
    assert roofline.peaks("TPU v5 lite")["flops"] == 197e12
    rl = roofline.Roofline(flops=1.0, hbm_bytes=1.0, coll_bytes=0.0,
                           coll_by_kind={}, device_kind="cpu")
    with pytest.raises(KeyError, match="cpu"):
        rl.t_compute


def test_model_flops_moe_counts_active_only():
    dense = configs.get_config("qwen3-0.6b")
    moe = configs.get_config("mixtral-8x22b")
    n_active = roofline.active_params(moe)
    # 8 experts top-2: active far below total
    from repro.core.params import count_params
    from repro.train.state import model_defs
    assert n_active < 0.5 * count_params(model_defs(moe))
    assert roofline.model_flops(dense, 1000) == \
        6.0 * roofline.active_params(dense) * 1000


def test_cell_supported_matrix():
    ok, _ = configs.cell_supported("mamba2-780m", "long_500k")
    assert ok
    ok, why = configs.cell_supported("gemma-7b", "long_500k")
    assert not ok and "full-attention" in why
    for arch in configs.ARCH_NAMES:
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            assert configs.cell_supported(arch, shape)[0]
