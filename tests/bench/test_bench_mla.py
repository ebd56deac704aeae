"""The DeepSeek-V2 decode-pool load loop (`bench/loops/lm_pool_mla.py`) at
smoke size on the CPU: the traffic loop runs through `LMScheduler` with
latent attention, a held share of the experts and the adapter under
pallas-interpret, and comes out correct; the control and each fault come
out not correct; the same seed serves the same requests.

The cell is cut to 3 layers of width 64 (MLA ranks 32/16/8/16, YaRN over
32 positions), 8 held of a 16-wide router (top 4, 2 shared), a 512-token
vocabulary, and a pool of 2 slots of 128 positions.  Limits as in
`test_bench_lm.py`, from readings on the CPU: in bfloat16 the program's
served tokens lie a mean 3.1e-4 to 1.8e-3 below the reference's best
(which requests finish in the 2-s window depends on the machine's load;
limit 5e-3, where the faults read 0.12 to 3.1), its W_fast within 0.10 of
the reference's (limit 0.5; an adapter that never learned reads 1).  At
this size bfloat16 rounding moves tokens about as far as the int8 control
does (1.8e-3), so the control is tested in float32: there the program
reads 0.0 and 4.2e-7, the control 4.6e-3 and 0.15."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

M = harness.load_manifest(ROOT)
CELL = "lm_dsv2_lite_reason"
SMOKE_LIMITS = {"logit_gap_mean": 5e-3, "wfast_gap": 0.5}
F32_LIMITS = {"logit_gap_mean": 1e-5, "wfast_gap": 1e-5}


def _cell(dtype="bfloat16"):
    cell = harness.resolve(CELL, ROOT, M)
    cell.config.update(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, router_experts=16, first_held_expert=4,
        num_experts_per_tok=4, vocab_size=512,
        adapter_impl="pallas-interpret", torch_dtype=dtype)
    cell.config["rope_scaling"] = dict(
        cell.config["rope_scaling"], original_max_position_embeddings=32)
    limits = SMOKE_LIMITS if dtype == "bfloat16" else F32_LIMITS
    cell.traffic.update(slots=2, max_len=128, prompt_lens=[8, 16],
                        prompt_shares=[0.5, 0.5], out_min=16, requests=8,
                        check_requests=4, limits=dict(limits))
    return cell


def _run(cell, plant=None, seed=2**31 + 77, seconds=2.0):
    return cell.loop().run(cell, seed=seed, seconds=seconds,
                           trace_dir=None, t_start=time.perf_counter(),
                           plant=plant)


def test_traffic_loop_runs_and_is_correct():
    run = _run(_cell())
    assert run["correct"], run["checks"]
    assert run["notes"]["compiles_in_window"] == 0
    assert run["notes"]["requests_compared"] >= 2
    e2e = run["e2e"]
    assert e2e["tokens_per_s"] > 0 and e2e["itl_p95_ms"] > 0
    assert e2e["setup_s"] > 0
    assert list(run["checks"]) == ["logit_gap_mean", "wfast_gap"]
    assert 0 < run["checks"]["wfast_gap"]["value"] < 0.3
    # the program's counters: 2 streams x top 4 over 16 experts, 8 held,
    # read 0.5 assignments per held expert, MoE layer and step on average
    li = run["layer_inputs"]
    assert li["moe_steps"] == run["notes"]["steps"]
    reader = harness.load_module(
        harness.reader_path("held_expert_load.dsv2", ROOT))
    assert 0.25 < reader.read(li) < 1.0


def test_same_seed_same_requests():
    loop = _cell().loop()
    tr = harness.resolve(CELL, ROOT, M).traffic
    a = loop.requests(2**33 + 5, tr, 102400)
    b = loop.requests(2**33 + 5, tr, 102400)
    c = loop.requests(2**33 + 6, tr, 102400)
    assert all((x["prompt"] == y["prompt"]).all() and x["out"] == y["out"]
               for x, y in zip(a, b))
    assert [(len(x["prompt"]), x["out"]) for x in a] == \
        [(len(x["prompt"]), x["out"]) for x in c]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, c))
    assert all(tr["out_min"] <= x["out"] <= tr["max_len"] - len(x["prompt"])
               for x in a)


@pytest.mark.parametrize("what", ["program", "control"])
def test_control_is_not_correct_where_the_program_is(what):
    cell = _cell(dtype="float32")
    plant = cell.loop().plant_control if what == "control" else None
    run = _run(cell, plant=plant)
    assert run["notes"]["requests_compared"] >= 2
    if what == "program":
        assert run["correct"], run["checks"]
    else:
        assert not run["correct"], run["checks"]
        gap = run["checks"]["logit_gap_mean"]
        assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(fault):
    cell = _cell()
    run = _run(cell, plant=cell.loop().plant_fault(fault))
    assert run["notes"]["requests_compared"] >= 1
    assert not run["correct"], (fault, run["checks"])
    if fault == "unchanged":            # an adapter that never learned
        assert run["checks"]["wfast_gap"]["value"] == pytest.approx(1.0)
