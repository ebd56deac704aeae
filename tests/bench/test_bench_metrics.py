"""Each per-layer metric's reader, on a synthetic trace: the number it
reads, and nothing (None) where the trace holds nothing for it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402

M = harness.load_manifest(ROOT)
NAMES = [m["name"] for m in M["per_layer"]]


def _reader(name):
    return harness.load_module(harness.reader_path(name, ROOT))


def _run(ops, spans, least=1e-9):
    tr = Trace({"/device:TPU:0": sorted(ops, key=lambda e: e.start)},
               sorted(spans, key=lambda e: e.start))
    return {"trace": tr, "window": (0.0, 1000.0), "least_time_s": least,
            "calls": 2}


FLEET = _run(
    [Event("jit__pool_rollout:_pool_rollout.1 [kernel]", 100, 110),
     Event("jit__pool_rollout:_pool_rollout.1 [kernel]", 600, 610),
     Event("jit_squeeze:copy.1", 200, 300)],
    [Event("bench.window", 0, 1000), Event("bench.call", 50, 450),
     Event("pool.rollout", 60, 80), Event("bench.call", 500, 900),
     Event("pool.rollout", 510, 530)])
LM = _run(
    [Event("jit__pool_step:while.1", 10, 150),
     Event("jit__pool_step:fusion.2", 20, 100),
     Event("jit__pool_step:while.1", 510, 650),
     Event("jit__pool_step:fusion.2", 520, 600),
     Event("jit__prefill_session:fusion", 300, 400)],
    [Event("bench.window", 0, 1000), Event("bench.step", 8, 250),
     Event("lm.decode_step", 9, 12), Event("bench.step", 508, 900),
     Event("lm.decode_step", 509, 512)])
EXPECTED = {
    "sched_host_ms.fleet": (FLEET, 380e-6),        # 400 - 20 ns per call
    "sched_host_ms.loop": (FLEET, 380e-6),
    "device_idle_share.fleet": (FLEET, 88.0),       # 120 of 1000 ns busy
    "device_idle_share.loop": (FLEET, 88.0),
    "rollout_roofline.fleet": (FLEET, 10.0),        # 1 ns least / 10 ns
    "step_mfu.fleet": (FLEET, 0.2),                 # 2 x 1 ns / 1000 ns
    "sched_host_ms.lm": (LM, 250e-6),    # idle 150 + 350 ns in 2 steps
    "decode_step_ms.lm": (LM, 140e-6),   # the program busy 280 ns
    "step_mfu.lm": (LM, 0.2),
    "device_idle_share.lm": (LM, 62.0),  # 380 of 1000 ns busy
}


def test_every_per_layer_metric_has_a_pinned_reading():
    assert sorted(EXPECTED) == sorted(NAMES)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_its_number(name):
    run, want = EXPECTED[name]
    assert _reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_empty_trace(name):
    empty = {"trace": Trace({}, []), "window": (0.0, 1000.0),
             "least_time_s": 1e-9, "calls": 0}
    assert _reader(name).read(empty) is None
