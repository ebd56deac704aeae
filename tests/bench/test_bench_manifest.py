"""BENCHMARK.json and the discovery of what it names."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
M = harness.load_manifest(ROOT)


def _metrics():
    return M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    for p in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert M["command"][0] == "python3"
    assert all(not w.startswith("/") for w in M["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["configs", "workloads", "metrics"])
def test_names_and_units_use_allowed_characters(kind):
    items = _metrics() if kind == "metrics" else M[kind]
    names = [x["name"] for x in items]
    assert len(names) == len(set(names))
    for x in items:
        assert NAME.match(x["name"]), x["name"]
        if kind == "metrics":
            assert UNIT.match(x["unit"]), x["unit"]
            assert x["better"] in ("lower", "higher")
        if kind == "workloads":
            assert NAME.match(x["config"]) and NAME.match(x["traffic"])
            assert x["chips"] in (1, 4)
            assert 1 <= len(x["why"]) <= 200
        if kind == "configs":
            assert all(NAME.match(k) for k in x["reduced"])


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in M["workloads"]:
        cell = harness.resolve(w["name"], ROOT, M)
        names = [x["name"] for x in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_per_layer_moves_is_reported_by_each_of_its_cells():
    e2e = {x["name"]: x for x in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            cell = harness.resolve(w, ROOT, M)
            assert m["moves"] in [x["name"] for x in cell.end_to_end], \
                (m["name"], w)


def test_every_named_file_is_found():
    for w in M["workloads"]:
        cell = harness.resolve(w["name"], ROOT, M)
        assert callable(cell.loop().run)
        assert cell.reference().__doc__
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    with pytest.raises(KeyError):
        harness.resolve("no_such_cell", ROOT, M)


def test_an_added_cell_traffic_and_metric_need_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    manifest = json.loads(json.dumps(M))
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "fleet_closed_8.json")))
    traffic.update(sessions=16, slots=16)
    (root / "bench" / "traffic" / "fleet_closed_16.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "calls.small.py").write_text(
        "def read(run):\n    return float(run['calls'])\n")
    manifest["workloads"].append(
        {"name": "ctrl_small", "config": M["configs"][0]["name"],
         "traffic": "fleet_closed_16", "chips": 1, "why": "test"})
    manifest["end_to_end"][0].setdefault("workloads", []).append(
        "ctrl_small")
    manifest["per_layer"].append(
        {"name": "calls.small", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": manifest["end_to_end"][0]["name"],
         "workloads": ["ctrl_small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    pins = root / "tests" / "bench" / "pins"
    shutil.copytree(os.path.join(ROOT, "tests", "bench", "pins"), pins)
    (pins / "calls.small.json").write_text(json.dumps(
        {"trace": "traces/fleet.json", "calls": 3, "want": 3.0,
         "why": "the run's 3 calls"}))
    cell = harness.resolve("ctrl_small", str(root))
    assert cell.traffic["sessions"] == 16
    assert [m["name"] for m in cell.per_layer] == ["calls.small"]
    metrics = harness.load_module(
        os.path.join(ROOT, "tests", "bench", "test_bench_metrics.py"))
    assert metrics.unpinned(str(root)) == ([], [])
    run, want = metrics.pinned("calls.small", str(root))
    assert cell.reader("calls.small").read(run) == want
    (pins / "calls.small.json").unlink()
    assert metrics.unpinned(str(root)) == (["calls.small"], [])


def test_a_metric_without_workloads_follows_what_it_moves():
    moves = {"name": "x", "moves": "tokens_per_s"}
    assert harness._applies(moves, "lm", ["setup_s", "tokens_per_s"])
    assert not harness._applies(moves, "ctrl", ["setup_s", "control_p95_ms"])
    assert harness._applies({"name": "setup_s"}, "any", [])
    assert not harness._applies(dict(moves, workloads=["ctrl"]), "lm",
                                ["tokens_per_s"])


def test_a_suffixed_metric_reads_with_its_base_reader_unless_it_has_its_own():
    shared = harness.reader_path("device_idle_share.some_new_cell", ROOT)
    assert os.path.basename(shared) == "device_idle_share.py"
    own = harness.reader_path("sched_host_ms.lm", ROOT)
    assert os.path.basename(own) == "sched_host_ms.lm.py"


def _run_bench(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ctrl_loop_8",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_bench(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    for p in M["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_bench(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
