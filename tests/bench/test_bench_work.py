"""The yardstick's peaks and the work counted from shapes, pinned."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import peaks, work  # noqa: E402


def test_peaks_table_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["peak_flops_bf16"] == 197e12 and p["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")


def test_fused_rollout_controller_fleet():
    w = work.fused_rollout([16, 128, 8], slots=1024, k=8)
    # weights (16*128 + 128*8 float32 per session) read and written once
    assert w["weight_bytes"] == 25_165_824
    # + membranes (136) and traces (152) in and out, rule once, K drive
    # and readout rows
    assert w["bytes"] == 25_165_824 + 2 * 1024 * 288 * 4 + 4 * 3072 * 4 \
        + 8 * 1024 * 24 * 4
    assert w["flops"] == 8 * 1024 * 10 * 3072
    least, bound = peaks.least_time_s(w["flops"], w["bytes"], "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(w["bytes"] / 819e9)


def test_fused_rollout_scales_with_slots_not_k_in_weights():
    a = work.fused_rollout([16, 128, 8], slots=8, k=8)
    b = work.fused_rollout([16, 128, 8], slots=8, k=16)
    assert a["weight_bytes"] == b["weight_bytes"] == 196_608
    assert b["flops"] == 2 * a["flops"]


def test_decode_step_qwen3_4b():
    d = work.decode_step(layers=36, d_model=2560, heads=32, kv_heads=8,
                         head_dim=128, d_ff=9728, vocab=151936, slots=8,
                         cached_positions=8 * 1024, adapter_neurons=128)
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    weights = 36 * per_layer + 151936 * 2560
    assert weights == 4_022_272_000
    assert d["weight_bytes"] == 2 * weights
    kv = 2 * 36 * 8 * 128 * 8 * 1024 * 2
    adapter = 2 * 8 * 128 * 128 * 4 + 2 * 2560 * 128 * 2 + 4 * 128 * 128 * 4
    assert d["bytes"] == 2 * weights + kv + adapter
    least, bound = peaks.least_time_s(d["flops"], d["bytes"], "TPU v5 lite")
    assert bound == "bytes" and 0.0112 < least < 0.0114
