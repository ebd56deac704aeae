"""The controller-fleet load loop at smoke size on the CPU: every traffic loop
runs through `FleetScheduler` under pallas-interpret and comes out correct,
and the control and each fault that the cell can have come out not correct.

The harness's look for a chip is skipped: the loop's `run` is called
directly, on the cell as `bench/harness.py` resolves it, with the sessions
cut to a smoke size."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

M = harness.load_manifest(ROOT)
CELLS = [w["name"] for w in M["workloads"]
         if harness.resolve(w["name"], ROOT, M).config["kind"] == "snn_fleet"]


def _cell(name, sessions=8):
    cell = harness.resolve(name, ROOT, M)
    cell.config["impl"] = "pallas-interpret"
    cell.traffic.update(sessions=sessions, slots=sessions, devices=1)
    return cell


def _run(cell, plant=None, seed=2**31 + 99):
    return cell.loop().run(cell, seed=seed, seconds=0.3, trace_dir=None,
                             t_start=time.perf_counter(), plant=plant)


@pytest.mark.parametrize("name", CELLS)
def test_traffic_loop_runs_and_is_correct(name):
    run = _run(_cell(name))
    assert run["correct"], run["checks"]
    assert run["failed"] == 0 and run["attempted"] >= 8
    assert run["notes"]["compiles_in_window"] == 0
    e2e = run["e2e"]
    assert e2e["controller_steps_per_s"] > 0 and e2e["control_p95_ms"] > 0
    assert 0 < e2e["setup_s"]
    assert list(run["checks"]) == ["flip_share", "gap"]


def test_same_seed_same_drives():
    cell = _cell(CELLS[0])
    drv = cell.loop()
    a = drv.Drives(2**33 + 5, 8, 16, 0.1)
    b = drv.Drives(2**33 + 5, 8, 16, 0.1)
    assert (a.advance() == b.advance()).all()
    assert abs(a.x).max() <= 1.0


def test_control_is_not_correct():
    cell = _cell("ctrl_loop_8")         # 8 sessions: the cell's own size
    run = _run(cell, plant=cell.loop().plant_control)
    assert not run["correct"], run["checks"]
    assert run["checks"]["gap"]["value"] > run["checks"]["gap"]["limit"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(fault):
    cell = _cell(CELLS[0])
    run = _run(cell, plant=cell.loop().plant_fault(fault))
    assert not run["correct"], (fault, run["checks"])


def test_compared_numbers_are_the_flip_share_and_the_gap_of_the_rest():
    import numpy as np
    numbers = _cell(CELLS[0]).loop().compared
    gaps = np.array([1e-6, 2e-6, 0.5, 0.3] + [1e-6] * 96)
    flipped = np.zeros(100, bool)
    flipped[[2, 3]] = True
    got = numbers(flipped, gaps, {"flip_share": 0.01, "gap": 1e-5})
    assert got == {"flip_share": {"value": 0.02, "limit": 0.01},
                   "gap": {"value": 2e-6, "limit": 1e-5}}
    got = numbers(np.zeros(100, bool), gaps, {"flip_share": 0.0, "gap": 0.0})
    assert got["flip_share"]["value"] == 0.0 and got["gap"]["value"] == 0.5


SHARDED = """
import json, sys, time
sys.path.insert(0, {root!r})
from bench import harness
cell = harness.resolve({name!r}, {root!r})
cell.config["impl"] = "pallas-interpret"
cell.traffic.update(sessions=32, slots=32, devices=4)
loop = cell.loop()
out = {{}}
for what, plant in (("program", None),
                    ("unchanged", loop.plant_fault("unchanged"))):
    run = loop.run(cell, seed=2**31 + 98, seconds=0.3, trace_dir=None,
                   t_start=time.perf_counter(), plant=plant)
    out[what] = [run["correct"], run["notes"]["compiles_in_window"]]
print(json.dumps(out))
"""


def test_a_pool_sharded_over_four_devices_is_correct_and_a_fault_not():
    """The traffic's ``devices`` key: the pool sharded over `fleet_mesh(4)`
    on four host devices, in a process of its own since the device count
    is fixed when JAX starts."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=ROOT, name=CELLS[0])],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"program": [True, 0], "unchanged": [False, 0]}, got
