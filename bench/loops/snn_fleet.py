"""The load loop for plastic SNN controller fleets served by `FleetScheduler`.

One general generator and loop for every traffic mix of this kind.  The
traffic file gives:

  sessions, slots   resident sessions (all admitted in set-up) and slots
  devices           chips the pool is sharded over (`fleet_mesh`), 1 or 4
  drive_step        std of the per-call step of each session's drive, a
                    random walk clipped to [-1, 1] (16-dim for 16-128-8)
  warmup_calls      calls made in set-up, which compile every program
  check_share       share of the window's calls compared with the
                    reference (drawn from the seed; the last always is)
  limits            the limits of `correct`'s numbers (`compared`):
                    `flip_share` and `gap`, per cell, since the widest
                    gap grows with the answers compared

The loop is closed: the next `pool_step` starts only after every session's
action window of the previous one is on the host.  The window runs until
`seconds` have passed; the last call counts whole.

`correct`: for every compared call, the plain reference
(`bench/reference/snn_fleet.py`) runs the same K steps from the pool's
state before the call, with the call's drives, and is compared with the
action windows the client received and the pool's state after the call:
the share of those session-calls whose spikes differ, and the widest gap
of the rest, each against the traffic's limit.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np


def program_config(cfg: dict):
    """The configuration file as the program's `snn.SNNConfig`."""
    from repro.core import snn
    if cfg["dtype"] != "float32" or cfg["readout"] != "leaky":
        raise ValueError("this loop runs float32 fleets with a leaky "
                         "readout")
    return snn.SNNConfig(
        layer_sizes=tuple(cfg["layer_sizes"]), timesteps=cfg["window_k"],
        trace_decay=cfg["trace_decay"],
        lif=snn.LIFConfig(tau_m=cfg["tau_m"], v_threshold=cfg["v_th"],
                          v_reset=cfg["v_reset"]),
        w_clip=cfg["w_clip"], plastic=True, spiking_readout=False,
        impl=cfg["impl"])


class Drives:
    """Each session's drive: a seeded random walk clipped to [-1, 1]."""

    def __init__(self, seed: int, sessions: int, dim: int, step: float):
        self._rng = np.random.default_rng([seed, 7])
        self._step = step
        self.x = self._rng.uniform(-1, 1, (sessions, dim)).astype(np.float32)

    def advance(self) -> np.ndarray:
        self.x = np.clip(
            self.x + self._step * self._rng.standard_normal(
                self.x.shape, dtype=np.float32), -1.0, 1.0)
        return self.x


def compared(flipped: np.ndarray, gaps: np.ndarray, limits: dict) -> dict:
    """The numbers `correct` compares, each with its limit: the share of
    compared session-calls whose hidden spike trains differ from the
    reference's (a membrane within rounding of threshold spikes on one
    side only, or a fault), and the largest gap of the others."""
    return {"flip_share": {"value": float(flipped.mean()),
                           "limit": limits["flip_share"]},
            "gap": {"value": float(gaps[~flipped].max(initial=0.0)),
                    "limit": limits["gap"]}}


def _ref_state(fleet):
    return tuple(fleet.w), tuple(fleet.v), tuple(fleet.trace)


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, plant: Optional[Callable] = None) -> dict:
    """Set up, measure and check one run of `cell`.

    `plant(sched, ref, cfg)` (tests and calibration only) replaces the pool's
    rollout after set-up is built, to run a control or a fault in the
    program's place; `bench/run.py` never passes it.
    """
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.distributed.sharding import fleet_mesh
    from repro.obs.watchdog import watchdog
    from repro.serving import FleetScheduler

    from bench import harness, peaks, work
    from bench import trace as bench_trace

    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    sessions, slots, k = tr["sessions"], tr["slots"], cfg["window_k"]
    n_in = cfg["layer_sizes"][0]
    devices = int(tr.get("devices", 1))

    # ---- set-up: rule, pool, sessions, warm-up --------------------------
    # The rule is the deployment's, fixed by the configuration: the pool
    # compiles it into its programs, so a rule drawn per seed would
    # compile every run.  The seed draws the traffic.
    theta = jax.jit(lambda key: ref.make_theta(cfg, key))(
        harness.key_from_seed(cfg["rule_seed"]))
    mesh = fleet_mesh(devices) if devices > 1 else None
    sched = FleetScheduler(program_config(cfg), theta, slots=slots, mesh=mesh)
    uids = [f"s{i}" for i in range(sessions)]
    for u in uids:
        sched.admit(u)
    slot_of = np.array([sched.user_slot[u] for u in uids])
    if plant is not None:
        plant(sched, ref, cfg)
    walk = Drives(seed, sessions, n_in, tr["drive_step"])
    pick = np.random.default_rng([seed, 11])
    kept = []                  # (pre, drives, host outs, post) per check

    def call(x):
        drives = {u: x[i] for i, u in enumerate(uids)}
        pre = sched.pool
        t0 = time.perf_counter()
        with TraceAnnotation("bench.call"):
            outs = sched.pool_step(drives, timesteps=k)
            with TraceAnnotation("bench.readback"):
                host = jax.device_get(outs)
        return time.perf_counter() - t0, (pre, x, host, sched.pool)

    x = walk.x
    for _ in range(tr["warmup_calls"]):
        kept.append(call(x)[1])
        x = walk.advance()
    jax.block_until_ready(sched.pool)

    # ---- the window -----------------------------------------------------
    watchdog.install()
    watchdog.reset()
    if trace_dir is not None:
        bench_trace.start(trace_dir)
    watchdog.arm()
    setup_s = time.perf_counter() - t_start
    lat, answered = [], 0
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            dt, last = call(x)
            lat.append(dt)
            answered += len(last[2])
            if pick.random() < tr["check_share"]:
                kept.append(last)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            with TraceAnnotation("bench.drives"):
                x = walk.advance()
    watchdog.disarm()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    if kept[-1] is not last:
        kept.append(last)                  # the state after the window
    calls = len(lat)
    used = jax.devices()[:devices]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    # ---- correctness: every kept call against the reference -------------
    # hidden traces move by ~1e-6 under rounding; a spike added, dropped
    # or moved by one step anywhere in the window moves them by >= 0.05
    flip_at = 1e-3
    flipped, gaps = [], []
    for pre, xs, host, post in kept:
        drv = np.zeros((slots, n_in), np.float32)
        drv[slot_of] = xs
        window = jnp.broadcast_to(jnp.asarray(drv)[None], (k, slots, n_in))
        r = ref.rollout(cfg, theta, *_ref_state(pre), window)
        outs = np.zeros((k, slots, cfg["layer_sizes"][-1]), np.float32)
        for i, u in enumerate(uids):
            outs[:, slot_of[i]] = host[u]
        p = _ref_state(post) + (jnp.asarray(outs),)
        f, g = (np.asarray(a)[slot_of] for a in ref.compare(r, p, flip_at))
        flipped.append(f)
        gaps.append(g)
    flipped, gaps = np.concatenate(flipped), np.concatenate(gaps)
    checks = compared(flipped, gaps, tr["limits"])
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and answered == calls * sessions)

    dev = jax.devices()[0]
    # per chip: a sharded pool runs slots / devices sessions on each
    cost = work.fused_rollout(cfg["layer_sizes"], slots // devices, k)
    least, bound = (peaks.least_time_s(cost["flops"], cost["bytes"],
                                       dev.device_kind)
                    if dev.platform == "tpu" else (None, None))
    return {
        "e2e": {"setup_s": setup_s,
                "controller_steps_per_s": sessions * k * calls / elapsed,
                "control_p95_ms": 1e3 * float(np.percentile(lat, 95))},
        "correct": correct, "attempted": calls * sessions,
        "failed": calls * sessions - answered, "checks": checks,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
        "layer_inputs": {"least_time_s": least, "bound": bound,
                         "calls": calls, "window_s": elapsed},
        "notes": {"compiles_in_window": watchdog.violations,
                  "calls": calls,
                  "first_call_ms": [1e3 * t for t in lat[:3]],
                  "median_call_ms": 1e3 * float(np.median(lat)),
                  "max_call_ms": 1e3 * max(lat),
                  "session_calls_compared": int(gaps.size),
                  "session_calls_flipped": int(flipped.sum()),
                  "gap_median": float(np.median(gaps[~flipped]))
                  if (~flipped).any() else None,
                  "gap_p99": float(np.percentile(gaps[~flipped], 99))
                  if (~flipped).any() else None,
                  "least_time_bound": bound},
    }


# ---- controls and faults: stand-ins for the pool's rollout -----------------
# Used by `bench/calibrate.py` on the chip and by the tests under
# tests/bench; a benchmark run never plants one.


def plant_control(sched, ref, cfg) -> None:
    """The reference in the program's place, its psum one precision step
    down (`high`, three bfloat16 passes, for float32 at `highest`)."""

    def rollout(fleet, window, active, teach, seeds):
        w, v, tr, outs = ref.rollout(cfg, sched.theta, fleet.w, fleet.v,
                                     fleet.trace, window, precision="high")
        return fleet.__class__(w=w, v=v, trace=tr, t=fleet.t + window.shape[0],
                               w_scale=fleet.w_scale), outs

    sched._rollout = rollout


def plant_fault(kind: str):
    """A plant that breaks the pool's rollout: ``"unchanged"`` returns the
    state it was given; ``"half"`` steps only the first half of the slots
    (the rest keep their state and answer zeros); ``"altered"`` hands slot
    0 the action window of slot 1."""
    import jax.numpy as jnp

    def plant(sched, ref, cfg):
        real = sched._rollout

        def rollout(fleet, window, active, teach, seeds):
            new, outs = real(fleet, window, active, teach, seeds)
            if kind == "unchanged":
                return fleet, outs
            if kind == "half":
                b = outs.shape[1]
                keep = jnp.arange(b) < b // 2

                def pick(n, o):
                    m = keep.reshape((b,) + (1,) * (n.ndim - 1))
                    return jnp.where(m, n, o)
                new = new.__class__(
                    w=tuple(map(pick, new.w, fleet.w)),
                    v=tuple(map(pick, new.v, fleet.v)),
                    trace=tuple(map(pick, new.trace, fleet.trace)),
                    t=new.t, w_scale=new.w_scale)
                return new, jnp.where(keep[None, :, None], outs, 0.0)
            if kind == "altered":
                return new, outs.at[:, 0].set(outs[:, 1])
            raise ValueError(f"unknown fault {kind!r}")

        sched._rollout = rollout
    return plant
