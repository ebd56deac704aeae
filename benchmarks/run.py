"""Benchmark entry point: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick | --smoke] [--check]

  adaptation        Fig. 3   plasticity vs weight-trained generalization
  engine_breakdown  Table I  per-engine FLOPs/bytes/roofline latency
  mnist_throughput  Table II pipelined fwd+learn FPS methodology
  latency           8 us     controller end-to-end latency analogue
  fleet_throughput  serving  native batched-weights launch vs vmap recipe
  serving_churn     serving  session churn into a fixed slot pool (pinned
                             zero recompiles + evict/restore bit-equality)
  serving_lm        serving  plastic LM decode pool under churn: layout x
                             backend x adapter datapath, tokens/s +
                             windowed rollout path (pinned zero recompiles,
                             mid-generation evict/re-admit bit-identity,
                             vacant-slot freeze)
  quant_parity      fixed-pt float-vs-quant control parity + int8 pool bytes
                             (asserted bounds; bit-equal across backends)
  rollout_fused     perf     time-fused rollout megakernel vs per-step
                             launches, K-sweep x float/int8 datapaths
                             (parity gate: quant bitwise, float <= 1e-6)
  robustness        scenario  closed-loop adaptation sweep: scenario x
                             backend x datapath, plastic vs frozen (gate
                             scenarios asserted: recovery >= 0.5 plastic,
                             <= 0.25 frozen, one compile per cell)
  obs_overhead      obs      fleet telemetry cost gate: <= 5% throughput
                             overhead at B=256, exactly one extra program
                             per entry point, watchdog-silent churn
  obs_health        obs      session-health gate: every detector catches
                             its injected fault, zero false positives on
                             clean churn (fleet + LM), recorder overhead
                             <= 5% at B=256, one program per record variant
  roofline          Roofline table from the dry-run artifacts (if present)

``--check`` is the bench DRIFT GATE (CI): after the run, every checked-in
``benchmarks/results/<name>.json`` must have a freshly-written counterpart
(``<name>_smoke.json`` under --smoke, or an overwritten canonical file)
whose SCHEMA covers the checked-in one — recursive key paths plus backend
(``impl``/``impls``) coverage, never timings.  A bench that silently stops
producing a cell (a dropped key, a lost backend, a bench that stopped
writing at all) fails CI instead of rotting unnoticed.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")


# ---- drift gate ------------------------------------------------------------

def _schema_paths(obj, prefix=""):
    """Recursive key paths of a JSON document; list elements merge under
    '[]' so a sweep's schema is the union of its rows' keys."""
    paths = set()
    if isinstance(obj, dict):
        for k, v in obj.items():
            p = f"{prefix}.{k}" if prefix else str(k)
            paths.add(p)
            paths |= _schema_paths(v, p)
    elif isinstance(obj, list):
        for el in obj:
            paths |= _schema_paths(el, prefix + "[]")
    return paths


def _coverage_values(obj, keys):
    """Coverage cells: every scalar value reachable under one of `keys`
    (e.g. backend names under 'impl'/'impls', scenario names under
    'scenario'/'scenarios').  Non-scalar values under those keys are
    recursed into like any other node."""
    found = set()
    if isinstance(obj, dict):
        for k, v in obj.items():
            vals = v if isinstance(v, list) else [v]
            if k in keys and all(isinstance(x, (str, int, float))
                                 for x in vals):
                found |= {str(x) for x in vals}
            else:
                found |= _coverage_values(v, keys)
    elif isinstance(obj, list):
        for el in obj:
            found |= _coverage_values(el, keys)
    return found


# Coverage dimensions: every sweep axis the gate protects, by NAME, so a
# failure says which dimension lost cells (not just that "something" did).
# Each entry: dimension -> the JSON keys whose scalar values enumerate its
# cells.  Adding a protected axis = adding one row here.
_DIMENSIONS = {
    # engine backend: xla oracle / pallas / pallas-interpret
    "impl": ("impl", "impls"),
    # scenario sweeps (robustness/adaptation): a sweep that silently loses
    # a scenario row fails the gate like a lost backend
    "scenario": ("scenario", "scenarios", "gate_scenarios"),
    # numeric datapath: float32 vs int8 — both cells must keep appearing
    "datapath": ("datapath", "datapaths", "mode"),
    # LM backbone family: dense GQA, Mamba2 SSM, MoE, hybrids
    "layout": ("layout", "layouts"),
    # sharded-pool device counts (fleet_throughput's device sweep): a
    # sweep that silently drops a D cell fails like a lost backend — the
    # smoke sweep must force the same counts the checked-in artifact has
    "devices": ("devices", "device_counts"),
    # session-health detectors (obs_health's detection table): a detector
    # whose injected-fault row silently disappears fails the gate
    "detector": ("detector", "detectors"),
}


def check_drift(reference: dict, started_at: float) -> list:
    """Compare fresh smoke outputs against the checked-in result schemas.

    `reference` maps canonical stem -> parsed checked-in JSON (snapshotted
    BEFORE the benches ran — quick-mode benches overwrite their canonical
    files in place).  Returns a list of human-readable failures: each
    names the exact key paths that went missing and, per `_DIMENSIONS`
    axis, exactly which coverage cells were lost.  EXTRA fresh paths
    (cells the checked-in artifact has never seen) are reported too — as
    a notice, not a failure — so a bench growing new cells is visible in
    the gate output before the canonical result is re-checked in.
    """
    failures = []
    for stem, ref in sorted(reference.items()):
        fresh = None
        # smoke runs write <stem>_smoke.json, capped full runs (the
        # harness's --max-batch/--steps bounds) write <stem>_capped.json,
        # quick-mode benches overwrite the canonical file in place
        for cand in (os.path.join(RESULTS, f"{stem}_smoke.json"),
                     os.path.join(RESULTS, f"{stem}_capped.json"),
                     os.path.join(RESULTS, f"{stem}.json")):
            if (os.path.exists(cand)
                    and os.path.getmtime(cand) >= started_at):
                with open(cand) as f:
                    fresh = json.load(f)
                break
        if fresh is None:
            failures.append(
                f"{stem}: no fresh output (expected {stem}_smoke.json or an "
                f"overwritten {stem}.json) — the bench stopped writing "
                "results")
            continue
        ref_paths, fresh_paths = _schema_paths(ref), _schema_paths(fresh)
        missing = ref_paths - fresh_paths
        if missing:
            failures.append(
                f"{stem}: schema key paths missing from the fresh output: "
                f"{sorted(missing)}")
        extra = fresh_paths - ref_paths
        if extra:
            print(f"NOTE: {stem}: fresh output has key paths not in the "
                  f"checked-in result (re-check it in to protect them): "
                  f"{sorted(extra)}")
        for dim, keys in _DIMENSIONS.items():
            lost = _coverage_values(ref, keys) - _coverage_values(fresh, keys)
            if lost:
                failures.append(
                    f"{stem}: coverage dimension {dim!r} lost cells: "
                    f"{sorted(lost)}")
    return failures


def _reference_results() -> dict:
    ref = {}
    for path in glob.glob(os.path.join(RESULTS, "*.json")):
        stem = os.path.splitext(os.path.basename(path))[0]
        if stem.endswith("_smoke") or "_smoke_" in stem or \
                stem.endswith("_capped"):
            continue
        with open(path) as f:
            ref[stem] = json.load(f)
    return ref


# ---- harness ---------------------------------------------------------------

def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    quick = "--quick" in argv or "--smoke" in argv
    check = "--check" in argv
    reference = _reference_results() if check else {}
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    failures = []

    from benchmarks import (adaptation, engine_breakdown, fleet_throughput,
                            latency, mnist_throughput, obs_health,
                            obs_overhead, quant_parity, robustness,
                            rollout_fused, roofline, serving_churn,
                            serving_lm)

    for name, fn in (
        ("engine_breakdown", lambda: engine_breakdown.main(quick=quick)),
        # latency's checked-in artifact validates the TPU program (the
        # canonical results/latency.json is impl=pallas-interpret); the
        # harness runs the same backend so the drift gate's coverage check
        # compares like with like.
        ("latency",
         lambda: latency.main(quick=quick, impl="pallas-interpret")),
        ("mnist_throughput", lambda: mnist_throughput.main(quick=quick)),
        ("adaptation", lambda: adaptation.main(quick=quick)),
        ("fleet_throughput",
         lambda: fleet_throughput.main(
             ["--smoke"] if quick else ["--max-batch", "256"])),
        ("serving_churn",
         lambda: serving_churn.main(
             ["--smoke"] if quick else ["--steps", "100"])),
        ("serving_lm",
         lambda: serving_lm.main(["--smoke"] if quick else [])),
        ("quant_parity",
         lambda: quant_parity.main(["--smoke"] if quick else [])),
        ("rollout_fused",
         lambda: rollout_fused.main(["--smoke"] if quick else [])),
        ("robustness",
         lambda: robustness.main(["--smoke"] if quick else [])),
        ("obs_overhead",
         lambda: obs_overhead.main(["--smoke"] if quick else [])),
        ("obs_health",
         lambda: obs_health.main(["--smoke"] if quick else [])),
        ("roofline_single", lambda: roofline.main(["--mesh", "single"])),
        ("roofline_multi", lambda: roofline.main(["--mesh", "multi"])),
    ):
        print(f"\n===== {name} =====")
        try:
            rc = fn()
            # benches with asserted bounds return an int exit code; the
            # older harnesses return their results dict (not a failure)
            if isinstance(rc, int) and rc:
                failures.append((name, f"exit code {rc}"))
        except Exception as e:  # keep the harness running; report at end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))

    if check:
        print("\n===== drift gate =====")
        drift = check_drift(reference, t0)
        for msg in drift:
            print("DRIFT:", msg)
        if not drift:
            print(f"all {len(reference)} checked-in result schemas covered "
                  "by fresh outputs")
        failures += [("drift-gate", m) for m in drift]

    print(f"\nbenchmarks done in {time.time() - t0:.0f}s; "
          f"{len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
