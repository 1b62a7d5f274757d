"""Roofline machinery unit tests (HLO collective parser + term math)."""
import numpy as np
import pytest

from repro.launch import roofline as rl

_HLO = """
ENTRY %main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={}
  %ag = bf16[4,256]{1,0} all-gather(%p0), dimensions={0}
  %a2a = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%p0, %p0)
  %cps = u8[1024]{0} collective-permute-start(%p0)
  %cpd = u8[1024]{0} collective-permute-done(%cps)
  %rs = f32[2,64]{1,0} reduce-scatter(%p0), dimensions={0}
  %ars = f32[32]{0} all-reduce-start(%p0)
  %ard = f32[32]{0} all-reduce-done(%ars)
}
"""


def test_parse_collectives_counts_and_bytes():
    out = rl.parse_collectives(_HLO)
    # all-reduce: 16*128*4 = 8192 B (x2 ring factor) + async 32*4=128 (x2)
    assert out["all-reduce"]["count"] == 2
    assert out["all-reduce"]["bytes"] == 2 * (16 * 128 * 4) + 2 * (32 * 4)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 4 * 256 * 2
    # tuple result: both elements counted
    assert out["all-to-all"]["bytes"] == 2 * (8 * 8 * 4)
    # -start counted once, -done skipped
    assert out["collective-permute"]["count"] == 1
    assert out["collective-permute"]["bytes"] == 1024
    assert out["reduce-scatter"]["bytes"] == 2 * 64 * 4


def test_roofline_terms_and_dominance():
    t = rl.roofline_terms(flops_per_chip=197e12, bytes_per_chip=819e9 / 2,
                          coll_bytes_per_chip=0)
    np.testing.assert_allclose(t["compute_s"], 1.0)
    np.testing.assert_allclose(t["memory_s"], 0.5)
    assert t["dominant"] == "compute"
    np.testing.assert_allclose(t["roofline_fraction"], 1.0)
    t2 = rl.roofline_terms(1e12, 1e12, 1e12)
    assert t2["dominant"] == "collective"  # 20s > 1.2s > 5ms


def test_roofline_peaks_refuse_unknown_device_kind():
    assert rl.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        rl.roofline_terms(1e12, 1e12, 0, device_kind="cpu")


def test_model_flops_convention():
    assert rl.model_flops("train", 10, 7) == 6 * 10 * 7
    assert rl.model_flops("prefill", 10, 7) == 2 * 10 * 7
    assert rl.model_flops("decode", 10, 7) == 2 * 10 * 7


def test_shape_bytes_dtypes():
    assert rl._shape_bytes("bf16[2,3]{1,0}") == 12
    assert rl._shape_bytes("u8[10]{0}") == 10
    assert rl._shape_bytes("(f32[4]{0}, s32[2]{0})") == 24
