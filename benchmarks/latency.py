"""Paper 8 µs end-to-end latency analogue.

The FPGA completes one control step (inference + plasticity, both layers,
all timesteps pipelined) in 8 µs at 0.713 W.  On TPU v5e the same
controller is minuscule; the honest comparison is the ROOFLINE latency of
the fused dual-engine program at controller scale plus measured CPU wall
time of the PRODUCT path — `snn.controller_step`, every layer routed
through the PlasticEngine (--impl selects the backend; "xla" default, an
upper bound — CPU is not the target).

Since the time-fused rollout landed, `snn.controller_step` executes its
whole ``timesteps x layers`` window as ONE `engine.rollout` launch (a
single `pallas_call` on the Pallas backends) — the measured wall time here
is the fused path, the software analogue of the paper's single-pipeline
8 µs datapath.

Prints a CSV: scale,roofline_us,cpu_wall_us,paper_fpga_us.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import snn
from repro.launch.mesh import V5E, peaks

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def controller_roofline_us(obs: int, hidden: int, act: int,
                           timesteps: int) -> float:
    """Roofline latency of one control step on one v5e core."""
    d = 2
    hw = peaks(V5E)
    total = 0.0
    for (n, m) in ((obs, hidden), (hidden, act)):
        flops = 2 * n * m + 2 * n * m + 10 * m          # fwd + hebb + pointwise
        byts = d * (5 * n * m + 2 * n + 4 * m)          # w + theta(4) + traces
        total += max(flops / hw["peak_flops_bf16"],
                     byts / hw["hbm_bw"]) * 1e6
    return total * timesteps


def measured_wall_us(cfg: snn.SNNConfig, iters: int = 20) -> float:
    state = snn.init_state(cfg)
    theta = snn.init_theta(cfg, jax.random.PRNGKey(0), scale=0.05)
    obs = jnp.linspace(-1, 1, cfg.layer_sizes[0])
    step = jax.jit(lambda s, o: snn.controller_step(cfg, s, theta, o))
    s, a = step(state, obs)
    jax.block_until_ready(a)
    t0 = time.perf_counter()
    for _ in range(iters):
        s, a = step(s, obs)
        jax.block_until_ready(a)
    return (time.perf_counter() - t0) / iters * 1e6


def main(quick: bool = False, impl: str = "xla"):
    os.makedirs(RESULTS, exist_ok=True)
    rows = {"impl": impl, "fused_rollout": True}
    print("scale,roofline_us,cpu_wall_us,paper_fpga_us")
    for name, (o, h, a, t) in {
        "control_8_128_8": (8, 128, 8, 4),
        "mnist_784_1024_10": (784, 1024, 10, 8),
    }.items():
        cfg = snn.SNNConfig(layer_sizes=(o, h, a), timesteps=t, impl=impl)
        roof = controller_roofline_us(o, h, a, t)
        wall = measured_wall_us(cfg, iters=5 if quick else 20)
        rows[name] = {"roofline_us": roof, "cpu_wall_us": wall,
                      "paper_fpga_us": 8.0}
        print(f"{name},{roof:.3f},{wall:.1f},8.0")
    with open(os.path.join(RESULTS, "latency.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--impl", default="xla",
                    choices=["xla", "pallas", "pallas-interpret"])
    args = ap.parse_args()
    main(quick=args.quick, impl=args.impl)
