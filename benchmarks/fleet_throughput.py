"""Fleet-mode throughput: native batched-weights launch vs the vmap recipe.

The many-user serving path steps B independent plastic memories — one per
request stream — every decode/control step.  Historically that was
``jax.vmap(engine.layer_step)``: vmapping a `pallas_call` stamps out B
logical kernel instances and broadcasts the shared rule theta to
``(B, 4, N, M)``.  Fleet mode instead gives the kernel first-class
per-request weights ``(B, N, M)`` and launches ONE program over a
``(cdiv(M, bm), B)`` grid with theta fetched once per tile.

This benchmark sweeps the fleet size B and times both paths on the SAME
fused dual-engine step (weights evolve under the rule across iterations,
as in production).  ``--impl pallas-interpret`` (default) validates the
TPU program on CPU; on TPU pass ``--impl pallas``.

Baseline honesty notes:

  * On the Pallas backends the vmap baseline materializes theta per
    stream (``in_axes theta=0``): the pallas_call batching rule cannot
    carry an unmapped operand — ``in_axes=None`` fails to lower, i.e. the
    historical recipe was never runnable on pallas/pallas-interpret at
    all, and the broadcast is what its batching rule attempts internally
    anyway.
  * On ``--impl xla`` the two paths are the SAME lowering by construction
    (the fleet oracle in kernels/plasticity/ref.py is defined as the vmap
    of the unbatched step), so expect parity there — the kernel-launch and
    theta-broadcast win this benchmark measures is a Pallas-path property.

The sweep also times the TIME-FUSED path (`engine.rollout`, the rollout
megakernel of kernels/plasticity/fused): K timesteps of the same layer in
ONE launch, with state resident across the window.  Per-step launches are
exactly what makes the per-step rows collapse super-linearly with B on the
interpret backend; fusing K steps and blocking ``block_b`` streams per
grid program divides that overhead by K * block_b.

The DEVICE sweep (``--device-counts``, on by default) measures the sharded
session pool: for each D in the sweep it runs, in this process, a meshed
`serving.scheduler.FleetScheduler` on the first D visible devices
(`distributed.sharding.fleet_mesh`), and reports the fused pool-step rate,
scaling efficiency vs D=1, and the device-loss drain latency
(`fail_device` -> `drain_failed`; D=1 has no surviving shard, so its drain
cells are null).  Zero recompiles across the timed section AND the drain
are asserted in every cell.  The counts default to every power of two up
to the visible device count; one process drives every device, so on a TPU
host the sweep runs over the real chips.  CPU CI gets several devices from
the job's environment (``XLA_FLAGS=--xla_force_host_platform_device_count``);
there all D shards share one physical CPU, so efficiency ~1/D is expected —
the sweep pins the mechanism and the drain path, not a speedup.

    PYTHONPATH=src python benchmarks/fleet_throughput.py [--smoke] [--impl ...]

Writes benchmarks/results/fleet_throughput.json:
    {"sweep": [{"batch": B, "native_steps_per_s": ..., "vmap_steps_per_s":
    ..., "native_speedup": ..., "native_controller_steps_per_s": ...,
    "vmap_controller_steps_per_s": ..., "collapse_ratio": ...,
    "fused_steps_per_s": ..., "fused_controller_steps_per_s": ...,
    "fused_speedup_vs_per_step": ...}, ...], "fused_k": K, ...,
    "device_counts": [1, 2, 4, 8],
    "device_sweep": [{"devices": D, "slots": B, "resident": ...,
    "pool_steps_per_s": ..., "controller_steps_per_s": ...,
    "speedup_vs_1dev": ..., "scaling_efficiency": ..., "drain_ms": ...,
    "drained": ..., "steps_lost": ..., "recompiles": 0}, ...]}

``collapse_ratio`` is (B * steps/s at B) / (steps/s at B=1) — the
aggregate-throughput scaling a flat per-launch cost would hold at B; a
value far below B is the launch-overhead collapse this benchmark exposes
(and the fused rows repair).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import engine

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def make_fleet(b: int, n: int, m: int, key: jax.Array):
    """B request streams: per-stream weights/membranes/traces, shared rule."""
    ks = jax.random.split(key, 5)
    x = (jax.random.uniform(ks[0], (b, n)) > 0.5).astype(jnp.float32)
    state = engine.LayerState(
        w=jnp.zeros((b, n, m), jnp.float32),           # zero-start (Phase 2)
        v=0.1 * jax.random.normal(ks[1], (b, m)),
        trace_pre=jax.random.uniform(ks[2], (b, n)),
        trace_post=jax.random.uniform(ks[3], (b, m)),
        theta=0.05 * jax.random.normal(ks[4], (4, n, m)))
    return state, x


def _native_step(state, x, params, impl):
    return engine.layer_step(state, x, params=params, impl=impl)


def _vmap_step(state, x, params, impl):
    # The historical recipe.  theta is materialized per stream because the
    # pallas_call batching rule rejects unmapped operands outright in this
    # JAX version (see module docstring) — and broadcasting is what the
    # rule attempts for mapped operands anyway; that B-fold coefficient
    # traffic is exactly what fleet mode eliminates.
    b = x.shape[0]
    vstate = engine.LayerState(
        w=state.w, v=state.v, trace_pre=state.trace_pre,
        trace_post=state.trace_post,
        theta=jnp.broadcast_to(state.theta, (b, *state.theta.shape)))
    new_state, out = jax.vmap(
        lambda l, xx: engine.layer_step(l, xx, params=params, impl=impl),
        in_axes=(engine.LayerState(w=0, v=0, trace_pre=0, trace_post=0,
                                   theta=0), 0))(vstate, x)
    # Hand back the shared rule so iterations don't re-broadcast a broadcast.
    return dataclasses.replace(new_state, theta=state.theta), out


def bench_steps_per_s(step_fn, state, x, iters: int) -> float:
    """Steady-state fused-step rate; weights thread through (plasticity on)."""
    fn = jax.jit(step_fn)
    state, out = fn(state, x)                  # compile + warm-up
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, out = fn(state, x)
    jax.block_until_ready(out)
    return iters / (time.perf_counter() - t0)


def bench_fused_steps_per_s(layer, x, params, impl: str, k: int,
                            block_b: int, iters: int) -> float:
    """Per-TIMESTEP rate of the time-fused rollout (K steps per launch)."""
    b, n = x.shape
    m = layer.v.shape[-1]
    net = engine.NetworkState(
        w=(layer.w,), v=(layer.v,),
        trace=(layer.trace_pre, layer.trace_post),
        t=jnp.zeros((), jnp.int32))
    drives = jnp.broadcast_to(x[None], (k, b, n)).astype(jnp.float32)
    fn = jax.jit(functools.partial(
        engine.rollout, params=[params], impl=impl, block_b=block_b))
    theta = [layer.theta]
    net2, out = fn(net, theta, drives)         # compile + warm-up
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        net2, out = fn(net2, theta, drives)
    jax.block_until_ready(out)
    return iters * k / (time.perf_counter() - t0)


# ---- the sharded-pool device sweep -----------------------------------------


def _device_cell(args, d: int) -> dict:
    """One device-sweep cell on the first `d` visible devices: meshed
    pool-step throughput + device-loss drain latency, with zero recompiles
    asserted across both."""
    import numpy as np

    from repro.core import snn
    from repro.distributed import sharding as dsh
    from repro.serving.scheduler import FleetScheduler

    slots = args.slots if args.slots else (8 if args.smoke else 16)
    cfg = snn.SNNConfig(layer_sizes=(args.n, args.m), impl=args.impl,
                        block_m=args.block_m)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0))
    sched = FleetScheduler(cfg, theta, slots=slots, mesh=dsh.fleet_mesh(d))
    # half-occupied: drain needs free healthy slots on the survivors
    users = [f"u{i}" for i in range(slots // 2)]
    for u in users:
        sched.admit(u)
    rng = np.random.RandomState(0)
    drives = {u: rng.rand(args.n).astype(np.float32) for u in users}
    k = args.k
    # warm-up: the step program, then one churn cycle so every slot
    # program the drain reuses is compiled before the recompile gate arms
    jax.block_until_ready(sched.pool_step(dict(drives), timesteps=k))
    sched.evict(users[0])
    sched.admit(users[0])
    jax.block_until_ready(sched.pool_step(dict(drives), timesteps=k))
    warm = sched.compile_count()

    iters = 3 if args.smoke else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = sched.pool_step(dict(drives), timesteps=k)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    cell = {"devices": d, "impl": args.impl, "slots": slots,
            "resident": len(users),
            "pool_steps_per_s": iters * k / dt,
            "controller_steps_per_s": iters * k * len(users) / dt}

    if d > 1:
        # drain latency: snapshot, kill device 0's shard (poisoned, so the
        # drain provably never reads it), re-home onto survivors
        sched.persist_resident()
        t0 = time.perf_counter()
        stranded = sched.fail_device(0, poison=True)
        report = sched.drain_failed()
        drain_s = time.perf_counter() - t0
        assert {r["uid"] for r in report} == set(stranded)
        assert all(r["to_device"] != 0 for r in report), report
        # the drained pool must still serve
        jax.block_until_ready(sched.pool_step(dict(drives), timesteps=k))
        # reconcile the metrics registry against the drain's own event log:
        # the counters are the externally scraped record of this incident,
        # so they must agree with what the benchmark just observed
        snap = sched.metrics.snapshot()
        failures = snap["pool_device_failures_total"]["value"]
        drained = snap["pool_drained_sessions_total"]["value"]
        assert failures == 1.0, snap
        assert drained == float(len(report)), (drained, len(report))
        cell.update(drain_ms=drain_s * 1e3, drained=len(report),
                    steps_lost=int(sum(r["steps_lost"] for r in report)),
                    device_failures_total=failures,
                    drained_sessions_total=drained)
    else:
        # a 1-device pool has no surviving shard to drain onto
        cell.update(drain_ms=None, drained=0, steps_lost=0)

    cell["recompiles"] = sched.compile_count() - warm
    assert cell["recompiles"] == 0, sched.compiled_programs()
    return cell


def _run_device_sweep(args):
    """Run one in-process cell per device count and aggregate scaling
    efficiency vs the first count (1 by default)."""
    if args.device_counts:
        counts = [int(c) for c in str(args.device_counts).split(",") if c]
    else:
        n = len(jax.devices())
        counts = [1 << i for i in range(n.bit_length()) if 1 << i <= n]
    print("devices,pool_steps_per_s,scaling_efficiency,drain_ms,recompiles")
    cells = [_device_cell(args, d) for d in counts]
    base = cells[0]["pool_steps_per_s"]
    for c in cells:
        c["speedup_vs_1dev"] = c["pool_steps_per_s"] / base
        c["scaling_efficiency"] = c["speedup_vs_1dev"] / c["devices"]
        drain = ("" if c["drain_ms"] is None else f'{c["drain_ms"]:.1f}')
        print(f'{c["devices"]},{c["pool_steps_per_s"]:.2f},'
              f'{c["scaling_efficiency"]:.3f},{drain},{c["recompiles"]}')
    return counts, cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (seconds, not minutes)")
    ap.add_argument("--impl", default="pallas-interpret",
                    choices=["xla", "pallas", "pallas-interpret"])
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--block-m", type=int, default=64)
    ap.add_argument("--k", type=int, default=8,
                    help="fused-rollout window length (timesteps per launch)")
    ap.add_argument("--block-b", type=int, default=8,
                    help="fused-rollout streams per grid program")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="cap the B sweep (the aggregate benchmarks/run.py "
                         "harness uses 256 to bound interpret-mode wall "
                         "time; the B=1024 point is minutes on CPU)")
    ap.add_argument("--out", default=None,
                    help="results path; defaults to results/"
                         "fleet_throughput.json, or a separate _smoke file "
                         "under --smoke so CI/quick runs never clobber the "
                         "checked-in full-sweep artifact")
    ap.add_argument("--device-counts", default=None,
                    help="comma-separated device counts for the sharded-"
                         "pool sweep, each at most the visible device count "
                         "(default: every power of two up to it); the first "
                         "count is the scaling-efficiency baseline")
    ap.add_argument("--devices-only", action="store_true",
                    help="run ONLY the device sweep and merge it into the "
                         "--out artifact, preserving an existing B sweep "
                         "(CI regenerates device cells without re-running "
                         "the minutes-long B=1024 rows)")
    ap.add_argument("--slots", type=int, default=None,
                    help="device-sweep pool size (default 8 smoke / 16)")
    args = ap.parse_args(argv)
    if args.out is None:
        capped = args.max_batch is not None and args.max_batch < 1024
        name = ("fleet_throughput_smoke.json" if args.smoke else
                "fleet_throughput_capped.json" if capped else
                "fleet_throughput.json")
        args.out = os.path.join(RESULTS, name)

    batches = [1, 16] if args.smoke else [1, 16, 64, 256, 1024]
    if args.max_batch is not None:
        batches = [b for b in batches if b <= args.max_batch]
    params = engine.EngineParams(block_m=args.block_m)
    sweep = []
    if args.devices_only:
        batches = []
    else:
        print("batch,native_steps_per_s,vmap_steps_per_s,native_speedup,"
              "fused_steps_per_s,fused_speedup_vs_per_step")
    native_b1 = None
    for b in batches:
        state, x = make_fleet(b, args.n, args.m, jax.random.PRNGKey(b))
        iters = max(2, min(30, 4096 // b)) if not args.smoke else 2
        native = bench_steps_per_s(
            functools.partial(_native_step, params=params, impl=args.impl),
            state, x, iters)
        vmapped = bench_steps_per_s(
            functools.partial(_vmap_step, params=params, impl=args.impl),
            state, x, iters)
        # time-fused path: same workload, K timesteps per launch.  Window
        # iters scale by K since each launch does K steps of work.
        fused_iters = max(2, iters // 2) if not args.smoke else 2
        fused = bench_fused_steps_per_s(state, x, params, args.impl,
                                        args.k, args.block_b, fused_iters)
        if native_b1 is None:
            native_b1 = native                 # batches always start at B=1
        row = {"batch": b, "native_steps_per_s": native,
               "vmap_steps_per_s": vmapped,
               "native_speedup": native / vmapped,
               "native_controller_steps_per_s": native * b,
               # satellite bugfix: the baseline's per-controller number and
               # the aggregate-scaling ratio were missing from the schema,
               # hiding the collapse this PR's fused path repairs
               "vmap_controller_steps_per_s": vmapped * b,
               "collapse_ratio": (native * b) / native_b1,
               "fused_k": args.k,
               "fused_steps_per_s": fused,
               "fused_controller_steps_per_s": fused * b,
               "fused_collapse_ratio": None,   # filled after the sweep
               "fused_speedup_vs_per_step": fused / native}
        sweep.append(row)
        print(f"{b},{native:.2f},{vmapped:.2f},{native / vmapped:.2f},"
              f"{fused:.2f},{fused / native:.2f}")
    if sweep:
        fused_b1 = sweep[0]["fused_steps_per_s"]
        for row in sweep:
            row["fused_collapse_ratio"] = (
                row["fused_controller_steps_per_s"] / fused_b1)

    counts, dev_cells = _run_device_sweep(args)

    payload = {"impl": args.impl, "n": args.n, "m": args.m,
               "block_m": args.block_m, "fused_k": args.k,
               "block_b": args.block_b, "smoke": bool(args.smoke),
               "sweep": sweep,
               "device_counts": counts, "device_sweep": dev_cells}
    if args.devices_only and os.path.exists(args.out):
        # refresh ONLY the device cells; keep the existing B sweep rows
        with open(args.out) as f:
            payload = json.load(f)
        payload["device_counts"] = counts
        payload["device_sweep"] = dev_cells

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
