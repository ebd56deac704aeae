"""The benchmark's data-driven core: the manifest and what it names.

`BENCHMARK.json` at the checkout's root names every cell, configuration,
traffic mix and metric.  Each of these is found by its name:

  * a configuration: ``bench/configs/<file>`` (JSON; the manifest's
    ``file``), whose ``kind`` picks the load loop ``bench/loops/<kind>.py``
    and the plain reference ``bench/reference/<kind>.py``;
  * a traffic mix: ``bench/traffic/<traffic>.json``, parameters that the
    loop's one general generator reads;
  * a per-layer metric: ``bench/metrics/<name>.py``, a reader with
    ``read(run) -> float | None`` over the traced run; where that file is
    missing, the reader of its base name ``bench/metrics/<base>.py``
    (``device_idle_share.lm`` reads with ``device_idle_share.py``).

Adding a cell, a mix, a configuration of a known kind or a metric adds
files; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a Python file by path (its name may hold dots)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + os.path.basename(path)[:-3].replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def key_from_seed(seed: int):
    """A PRNG key for any whole number (the seed may exceed 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with everything it names, resolved."""
    name: str
    chips: int
    config: dict            # the configuration file, plus its "name"
    traffic: dict           # the traffic file, plus its "name"
    end_to_end: List[dict]  # the manifest's end-to-end metrics of this cell
    per_layer: List[dict]   # the manifest's per-layer metrics of this cell
    root: str

    def loop(self):
        return load_module(os.path.join(self.root, "bench", "loops",
                                        self.config["kind"] + ".py"))

    def reference(self):
        return load_module(os.path.join(self.root, "bench", "reference",
                                        self.config["kind"] + ".py"))

    def reader(self, metric: str):
        return load_module(reader_path(metric, self.root))


def reader_path(metric: str, root: str = ROOT) -> str:
    """The reader file of per-layer metric `metric`: its own, else the one
    of its base name (the part before the first dot)."""
    own = os.path.join(root, "bench", "metrics", metric + ".py")
    if os.path.isfile(own):
        return own
    return os.path.join(root, "bench", "metrics",
                        metric.split(".", 1)[0] + ".py")


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    # without a `workloads` list a per-layer metric is read in every cell
    # that reports the end-to-end metric it `moves`
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", None) in e2e_names if "moves" in metric \
        else True


def resolve(workload: str, root: str = ROOT,
            manifest: Optional[dict] = None) -> Cell:
    """The cell called `workload`, with its configuration, traffic and
    metrics loaded.  Raises KeyError for a name the manifest lacks."""
    m = load_manifest(root) if manifest is None else manifest
    cells = {w["name"]: w for w in m["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in m["configs"]}
    centry = configs[w["config"]]
    config = dict(_json(os.path.join(root, centry["file"])),
                  name=centry["name"])
    traffic = dict(_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json")),
                   name=w["traffic"])
    e2e = [x for x in m["end_to_end"] if _applies(x, workload, [])]
    names = [x["name"] for x in e2e]
    layer = [x for x in m["per_layer"] if _applies(x, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer, root=root)


# ---- the result line -------------------------------------------------------


def result_line(cell: Cell, run: Dict[str, Any], layer: Dict[str, float],
                trace: bool) -> dict:
    """The contract's last line: with --trace 0 the cell's end-to-end
    metrics, with --trace 1 its per-layer ones; `checks` comes last."""
    if trace:
        units = {x["name"]: x["unit"] for x in cell.per_layer}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in layer.items()}
    else:
        metrics = {x["name"]: {"value": run["e2e"][x["name"]],
                               "unit": x["unit"]}
                   for x in cell.end_to_end}
    out = {"correct": bool(run["correct"]), "attempted": run["attempted"],
           "failed": run["failed"], "metrics": metrics,
           "device": run["device"]}
    if trace and run.get("breakdown") is not None:
        out["breakdown"] = run["breakdown"]
    out["checks"] = run["checks"]
    return out
