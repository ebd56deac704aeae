"""`chip_smoke.py`'s phases, run on CPU at smoke sizes.

The script fixes the published widths and ``impl="pallas"`` in `main()`;
its phase functions take config, sizes and impl, so the same schedules
(admission with eviction, fused windows and per-event steps, evict ->
re-admit into another slot, zero compiles after warm-up, the parity checks
against the xla oracle) run here through the Pallas interpreter.
"""
import json
import os
import sys

import numpy as np

from repro.configs import get_smoke
from repro.core import snn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

CONTROLLER = snn.SNNConfig(layer_sizes=(16, 128, 8))
SNN = dict(slots=8, n_users=12, k=4)
LM = dict(slots=8, max_len=48, prompt_len=8, steps=4, k=4)


def test_snn_fleet_phase_matches_oracle():
    rows = chip_smoke.snn_fleet_phase(CONTROLLER, impl="pallas-interpret",
                                      **SNN)
    assert [r["datapath"] for r in rows] == ["float32", "int8"]
    for r in rows:
        assert r["recompiles_after_warmup"] == 0
        assert r["evictions"] >= SNN["n_users"] - SNN["slots"]
        assert r["max_abs_diff"] <= chip_smoke.FLOAT_TOL
    assert rows[1]["bitwise"]
    json.dumps(rows)                     # each row is one printable line


def test_snn_mesh_phase_on_one_device():
    rows = chip_smoke.snn_mesh_phase(CONTROLLER, impl="pallas-interpret",
                                     devices=1, **SNN)
    for r in rows:
        assert r["bitwise"] and r["slot_devices"] == [0]


def test_lm_pool_phase_matches_oracle():
    row = chip_smoke.lm_pool_phase(get_smoke("qwen3-4b"),
                                   impl="pallas-interpret", **LM)
    assert row["tokens_equal"] and row["w_fast_nonzero"]
    assert row["w_fast_max_abs_diff"] <= chip_smoke.FLOAT_TOL
    assert row["recompiles_after_warmup"] == 0
    assert row["compiled_programs"]["decode_step"] == 1
    json.dumps(row)


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU found" in out.err


def test_first_window_reports_near_tie_flips():
    la = np.array([[[1.0, 0.98, 0.0], [0.0, 2.0, 1.0]]], np.float32)
    lb = np.array([[[0.97, 0.99, 0.0], [0.0, 2.0, 1.0]]], np.float32)
    r = chip_smoke._first_window({"logits": [la]}, {"logits": [lb]})
    assert (r["flips"], r["positions"]) == (1, 2)
    assert np.isclose(r["flip_max_margin"], 0.02)
    assert np.isclose(r["logits_max_abs_diff"], 0.03)
