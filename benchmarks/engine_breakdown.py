"""Paper Table I analogue: per-engine resource/latency breakdown.

The FPGA report counts LUTs/REGs/BRAM/DSP per engine; the TPU-native
equivalent is FLOPs / HBM bytes / roofline-latency per engine stage of the
fused dual-engine step, derived from the kernel's actual shapes at the
paper's controller scale (L1: obs->128, L2: 128->act) and at MNIST scale
(784-1024-10).

Also measures CPU wall time of the PRODUCT layer step —
`core.engine.layer_step`, the same entry point `snn.timestep` and serving
run — under the "xla" backend (and "pallas-interpret" with --interpret),
and — the paper's architectural claim — FUSED dual-engine vs SEQUENTIAL
forward-then-plasticity HBM traffic.
"""
from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import engine
from repro.launch.mesh import V5E, peaks

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def stage_model(b: int, n: int, m: int, plastic: bool = True) -> dict:
    """Analytic FLOPs/bytes for one fused dual-engine invocation."""
    d = 2  # bf16 storage on TPU (paper: fp16)
    fwd_flops = 2 * b * n * m                 # psum matmul
    lif_flops = 4 * b * m                     # V update + compare + select
    trace_flops = 2 * b * m
    plast_flops = (2 * b * n * m             # Hebbian outer product (MXU)
                   + 4 * n * m               # four-term combine
                   + 2 * n * m)              # w += clip
    fwd_bytes = d * (b * n + n * m + 3 * b * m)
    plast_bytes = d * (4 * n * m + n * m + b * n + b * m)  # theta+w+traces
    seq_bytes = fwd_bytes + plast_bytes + d * n * m  # re-fetch w if unfused
    fused_bytes = fwd_bytes + d * 4 * n * m          # w/traces already resident
    out = {
        "forward": {"flops": fwd_flops + lif_flops + trace_flops,
                    "bytes": fwd_bytes},
        "plasticity": {"flops": plast_flops if plastic else 0,
                       "bytes": plast_bytes if plastic else 0},
        "fused_bytes": fused_bytes,
        "sequential_bytes": seq_bytes,
    }
    hw = peaks(V5E)
    for stage in ("forward", "plasticity"):
        s = out[stage]
        s["compute_us"] = s["flops"] / hw["peak_flops_bf16"] * 1e6
        s["memory_us"] = s["bytes"] / hw["hbm_bw"] * 1e6
        s["roofline_us"] = max(s["compute_us"], s["memory_us"])
    return out


def measure_wall(b, n, m, iters=5, impls=("xla",)) -> dict:
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    x = (jax.random.uniform(ks[0], (b, n)) > 0.5).astype(jnp.float32)
    layer = engine.LayerState(
        w=0.1 * jax.random.normal(ks[1], (n, m)),
        v=jnp.zeros((b, m)),
        trace_pre=jax.random.uniform(ks[4], (b, n)),
        trace_post=jax.random.uniform(ks[5], (b, m)),
        theta=0.01 * jax.random.normal(ks[2], (4, n, m)))
    step = jax.jit(functools.partial(engine.layer_step,
                                     params=engine.EngineParams()),
                   static_argnames=("impl",))

    res = {}
    for impl in impls:
        out = step(layer, x, impl=impl)                # warm up / compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(layer, x, impl=impl)
            jax.block_until_ready(out)
        res[f"{impl}_us"] = (time.perf_counter() - t0) / iters * 1e6
    return res


def measure_launch_overhead(iters: int = 20) -> dict:
    """Per-launch floor: a NO-OP `pallas_call` vs the real step kernel.

    The no-op kernel copies one (8, 128) tile — everything it costs is
    launch/dispatch overhead, not compute.  Its share of the control-scale
    dual-engine step is the fraction a per-step schedule burns on launches
    alone, and exactly what the time-fused rollout (`engine.rollout`, one
    launch per K * num_layers steps) amortizes away.
    """
    def _noop(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    x = jnp.zeros((8, 128), jnp.float32)
    fn = jax.jit(pl.pallas_call(
        _noop, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True))
    out = fn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
        jax.block_until_ready(out)
    noop_us = (time.perf_counter() - t0) / iters * 1e6
    wall = measure_wall(1, 8, 128, iters=iters,
                        impls=("pallas-interpret",))
    step_us = wall["pallas-interpret_us"]
    return {"impl": "pallas-interpret",
            "noop_pallas_call_us": noop_us,
            "step_kernel_us": step_us,
            "launch_overhead_fraction": min(1.0, noop_us / step_us)}


def measure_fused_k_sweep(ks=(1, 2, 4, 8), b: int = 16, n: int = 64,
                          m: int = 64, iters: int = 3,
                          impl: str = "pallas-interpret") -> dict:
    """Fused-vs-per-step window timing: K steps per launch vs K launches.

    Both sides run the SAME fleet workload (B per-stream weight sets, one
    plastic layer) jitted; the per-step side issues one `layer_step`
    pallas_call per timestep, the fused side one `engine.rollout` launch
    for the whole window.  Reported per-TIMESTEP so rows are comparable
    across K.
    """
    key = jax.random.PRNGKey(0)
    ks_r = jax.random.split(key, 5)
    x = (jax.random.uniform(ks_r[0], (b, n)) > 0.5).astype(jnp.float32)
    layer = engine.LayerState(
        w=jnp.zeros((b, n, m), jnp.float32),
        v=0.1 * jax.random.normal(ks_r[1], (b, m)),
        trace_pre=jax.random.uniform(ks_r[2], (b, n)),
        trace_post=jax.random.uniform(ks_r[3], (b, m)),
        theta=0.05 * jax.random.normal(ks_r[4], (4, n, m)))
    params = engine.EngineParams(block_m=m)
    net = engine.NetworkState(
        w=(layer.w,), v=(layer.v,),
        trace=(layer.trace_pre, layer.trace_post),
        t=jnp.zeros((), jnp.int32))
    theta = [layer.theta]

    def time_fn(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6

    rows = []
    for k in ks:
        def per_step(l, xx):
            for _ in range(k):
                l, _o = engine.layer_step(l, xx, params=params, impl=impl)
            return l
        drives = jnp.broadcast_to(x[None], (k, b, n)).astype(jnp.float32)
        step_us = time_fn(jax.jit(per_step), layer, x)
        fused_us = time_fn(
            jax.jit(functools.partial(engine.rollout, params=[params],
                                      impl=impl)),
            net, theta, drives)
        rows.append({"k": k,
                     "per_step_us_per_step": step_us / k,
                     "fused_us_per_step": fused_us / k,
                     "fused_speedup": step_us / fused_us})
    return {"impl": impl, "batch": b, "n": n, "m": m, "sweep": rows}


def main(quick: bool = False, interpret: bool = False):
    os.makedirs(RESULTS, exist_ok=True)
    # paper scales: control (8-128-8 @ batch 1), MNIST (784-1024-10)
    layers = {
        "control_L1": (1, 8, 128), "control_L2": (1, 128, 8),
        "mnist_L1": (1, 784, 1024), "mnist_L2": (1, 1024, 10),
    }
    impls = ("xla", "pallas-interpret") if interpret else ("xla",)
    rows = {}
    print("layer,engine,flops,bytes,roofline_us,cpu_xla_us")
    for name, (b, n, m) in layers.items():
        sm = stage_model(b, n, m)
        wall = measure_wall(b, n, m, iters=2 if quick else 5, impls=impls)
        rows[name] = {"model": sm, "wall": wall}
        for eng in ("forward", "plasticity"):
            s = sm[eng]
            print(f"{name},{eng},{s['flops']},{s['bytes']},"
                  f"{s['roofline_us']:.3f},{wall['xla_us']:.1f}")
        fused_save = 1 - sm["fused_bytes"] / sm["sequential_bytes"]
        rows[name]["fusion_traffic_saving"] = fused_save
        print(f"{name},fusion_saving,,,{100*fused_save:.1f}%,")
    # end-to-end latency analogue of the paper's 8 us (two layers, roofline)
    total_us = sum(
        max(rows[f"control_L{i}"]["model"][e]["roofline_us"]
            for e in ("forward", "plasticity")) for i in (1, 2))
    rows["control_e2e_roofline_us"] = total_us
    print(f"control_e2e,roofline_total,,,{total_us:.3f},  (paper FPGA: 8 us)")
    # per-launch overhead floor (the cost the fused rollout amortizes)
    lo = measure_launch_overhead(iters=5 if quick else 20)
    rows["launch_overhead"] = lo
    print(f"launch_overhead,noop_vs_step,,,"
          f"{lo['noop_pallas_call_us']:.1f}us/"
          f"{lo['step_kernel_us']:.1f}us,"
          f"{100 * lo['launch_overhead_fraction']:.0f}%")
    # fused-vs-per-step window: K timesteps per launch vs K launches
    sweep = measure_fused_k_sweep(ks=(1, 4) if quick else (1, 2, 4, 8),
                                  iters=2 if quick else 3)
    rows["fused_k_sweep"] = sweep
    for r in sweep["sweep"]:
        print(f"fused_k_sweep,k={r['k']},,,"
              f"{r['per_step_us_per_step']:.0f}us->"
              f"{r['fused_us_per_step']:.0f}us,"
              f"{r['fused_speedup']:.2f}x")
    with open(os.path.join(RESULTS, "engine_breakdown.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    import sys
    main(quick="--quick" in sys.argv, interpret="--interpret" in sys.argv)
