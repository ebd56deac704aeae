#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Sets up the cell named in `BENCHMARK.json` from the seed (weights, state,
traffic), warms up every shape the window uses, measures for `--seconds`
through the product entry points, checks what the window produced against
the plain reference, and prints one JSON line as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and ``checks`` (each compared number with its limit), which
also end standard error.

Exits nonzero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives at
``$JAX_COMPILATION_CACHE_DIR`` or else at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import time


def _process_age_s() -> float:
    """Seconds since this process started (0 where /proc is missing)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age_s()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, the many small host-side ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = _args(argv)
    from bench import harness
    cell = harness.resolve(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform "
              f"{devices[0].platform!r}); no result", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, "
              f"{len(devices)} found; no result", file=sys.stderr)
        return 2
    _enable_compile_cache()

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_traces", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = cell.loop().run(cell, seed=args.seed, seconds=args.seconds,
                            trace_dir=trace_dir, t_start=T_START)
    layer = {}
    if trace_dir is not None:
        from bench import trace as tr
        reduced = tr.load(tr.find_xplane(trace_dir))
        lo, hi = tr.window(reduced)
        run["device"]["busy_s"] = tr.busy_seconds(reduced, lo, hi)
        run["device"]["window_s"] = (hi - lo) * 1e-9
        run["breakdown"] = {
            "device_ops": tr.top(tr.op_seconds(reduced, lo, hi)),
            "idle_gaps": tr.top(tr.idle_by_span(reduced, lo, hi))}
        run["layer_inputs"]["trace"] = reduced
        run["layer_inputs"]["window"] = (lo, hi)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(run["layer_inputs"])
            if value is not None:
                layer[m["name"]] = value
        shutil.rmtree(trace_dir, ignore_errors=True)

    line = harness.result_line(cell, run, layer, bool(args.trace))
    print("bench: " + json.dumps(run.get("notes", {})), file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
