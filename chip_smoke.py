#!/usr/bin/env python3
"""Chip smoke: the two product paths, once, on a TPU, at published widths.

    python chip_smoke.py             # one chip: phases snn_fleet and lm_pool
    python chip_smoke.py --chips 4   # four chips: the meshed pools only

Phase ``snn_fleet`` serves the paper's controller (16-128-8) from a
`serving.FleetScheduler` of 1024 slots: 1536 users (so admissions evict),
fused ``pool_step`` windows of K=8 and per-event ``step`` calls, and one
evict -> re-admit into a different slot.  It runs in float32 and in int8
(`snn.quant_config`): a pool on ``impl="pallas"`` and one on the xla oracle
take the same schedule in lockstep.  int8 must match bitwise over the
whole run.  float32 is compared per call from the oracle's state: within
1e-5 on every slot whose hidden spike train agrees, with at most 1% of
slot-calls flipped (two float32 implementations that differ in rounding
alone part ways where a membrane sits within an ulp of threshold).

Phase ``lm_pool`` serves qwen3-4b at its published widths (bf16, plastic
adapter of 128 neurons) from a `serving.LMScheduler` of 8 slots x 1024
tokens: eight 128-token prompts through ``admit_prompt``, 16 ``step``
calls, one ``decode_window`` of K=8 and evict -> re-admit, on
``adapter_impl="pallas"`` and then, after the first pool is freed, on the
xla adapter with the same params: greedy tokens equal, adapter ``W_fast``
rows within 1e-5, logits finite.

``--chips 4`` runs both schedules on a 4-device `fleet_mesh` against the
same pool unmeshed on one chip (references first, kept on the host), and
checks that slot blocks land on four devices.  The LM pool's check (equal
greedy tokens) does not hold on four v5e chips today: the partitioned
bf16 backbone rounds differently and near-tie tokens flip (ROADMAP D9);
its failure message reports the first window's logit difference and the
margins of the flipped tokens.

Every phase asserts zero compiles after its warm-up (`obs.watchdog`).  A
failed check raises; the script exits nonzero, and exits nonzero at once
when JAX finds no TPU.  Earlier lines are one JSON object per phase; the
last line is ``{"ok": true, "device": {...}}``.  The phase functions take
their config, sizes and impl, so tests run them on CPU at smoke sizes with
``impl="pallas-interpret"``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import snn  # noqa: E402
from repro.distributed.sharding import fleet_mesh  # noqa: E402
from repro.models import factory  # noqa: E402
from repro.obs.watchdog import watchdog  # noqa: E402
from repro.serving import FleetScheduler, LMScheduler  # noqa: E402

FLOAT_TOL = 1e-5      # README "Backend parity": float32 paths agree to 1e-5


# ---- helpers ------------------------------------------------------------------


def _host(tree):
    return [np.asarray(a) for a in jax.tree.leaves(jax.device_get(tree))]


def _max_diff(a, b) -> float:
    """Max |a - b| over two lists of host arrays of equal structure."""
    assert len(a) == len(b), (len(a), len(b))
    d = 0.0
    for x, y in zip(a, b):
        assert x.shape == y.shape and x.dtype == y.dtype, (x.shape, y.shape)
        if x.size:
            d = max(d, float(np.max(np.abs(x.astype(np.float64)
                                           - y.astype(np.float64)))))
    return d


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _slot_devices(sched) -> list:
    """Device ids holding the pool's first leaf, one per addressable shard,
    and the slot -> device map of every resident slot."""
    leaf = jax.tree.leaves(sched.pool)[0]
    shard_devs = sorted({s.device.id for s in leaf.addressable_shards})
    slot_devs = sorted({sched.slot_device(s)
                        for s in sched.user_slot.values()})
    return shard_devs, slot_devs


# ---- phase snn_fleet ----------------------------------------------------------


def snn_lockstep(pools, *, slots: int, n_users: int, k: int, rounds: int,
                 seed: int = 0, resync: bool = False) -> dict:
    """Drive two `FleetScheduler`s, ``pools = [(cfg, mesh), (cfg, mesh)]``
    (reference first), through the fleet schedule in lockstep and compare
    every call's outputs and the pools after it.

    Schedule: fill the pool; per round, one ``pool_step`` window of K, one
    per-event ``step`` and ``(n_users - slots) / (rounds + 1)`` new
    admissions with LRU eviction; then evict two residents and re-admit
    them so each lands in the other's slot, and run one more window and
    step.  Round 0, with one such slot trade, is the warm-up; the watchdog
    is armed after it.

    Differences are taken per slot.  A slot whose hidden spike train
    differs in a call (a hidden trace apart by at least half of one
    spike's decayed contribution) is counted as flipped and left out of
    the max: two float32 implementations that differ only in rounding
    part ways wherever a membrane sits within an ulp of threshold.

    ``resync`` copies the reference pool into the candidate after every
    call, so each call is compared from a common state: free-running, the
    rounding differences grow through plasticity.  Integer datapaths
    never need it.
    """
    if n_users <= slots:
        raise ValueError("n_users must exceed slots so admissions evict")
    scheds = [FleetScheduler(cfg, snn.init_theta(cfg, jax.random.PRNGKey(seed)),
                             slots=slots, mesh=mesh) for cfg, mesh in pools]
    ref, cand = scheds
    rng = np.random.default_rng(seed)
    n_in = pools[0][0].layer_sizes[0]
    uids = [f"u{i}" for i in range(n_users)]
    for sch in scheds:
        for u in uids[:slots]:
            sch.admit(u)
    nxt = slots
    per_round = (n_users - slots) // (rounds + 1)
    diffs, flips, busy = [], [], [0.0, 0.0]
    decay = pools[0][0].trace_decay

    def call(op, steps):
        table = rng.random((n_users, n_in), dtype=np.float32)
        drives = {u: table[int(u[1:])] for u in ref.active_users}
        outs = []
        for i, sch in enumerate(scheds):
            t0 = time.perf_counter()
            outs.append(op(sch, drives))
            jax.block_until_ready(sch.pool)
            busy[i] += time.perf_counter() - t0
        d = np.zeros(slots)                      # per-slot max |diff|
        for u, slot in ref.user_slot.items():
            d[slot] = _max_diff([np.asarray(outs[0][u])],
                                [np.asarray(outs[1][u])])
        for a, b in zip(_host(ref.pool), _host(cand.pool)):
            if a.ndim:                           # slot-major leaves
                d = np.maximum(d, np.abs(a.astype(np.float64) - b).reshape(
                    slots, -1).max(1))
        hidden = zip(_host(ref.pool.trace[1:-1]), _host(cand.pool.trace[1:-1]))
        flipped = np.zeros(slots, bool)
        for a, b in hidden:
            flipped |= np.abs(a.astype(np.float64) - b).max(1) \
                >= 0.5 * decay ** steps
        diffs.append(float(d[~flipped].max(initial=0.0)))
        flips.append(int(flipped.sum()))
        if resync:
            cand.pool = jax.tree.map(jnp.copy, ref.pool)

    def trade():
        """Evict -> re-admit into a DIFFERENT slot: two residents trade."""
        for sch in scheds:
            ua, ub = sorted(sch.user_slot, key=sch.user_slot.get)[:2]
            sa, sb = sch.user_slot[ua], sch.user_slot[ub]
            sch.evict(ua)
            sch.evict(ub)
            assert sch.admit(ub) == sa and sch.admit(ua) == sb

    window = lambda sch, d: sch.pool_step(d, timesteps=k)
    step = lambda sch, d: sch.step(d)
    watchdog.install()
    steps = 0
    for r in range(rounds + 1):
        if r == 1:
            watchdog.reset()
            watchdog.arm()
            busy[:], steps = [0.0, 0.0], 0
        call(window, k)
        call(step, 1)
        steps += k + 1
        for sch in scheds:
            for u in uids[nxt:nxt + per_round]:
                sch.admit(u, evict_lru=True)
        nxt += per_round
        if r == 0:
            trade()
    trade()
    call(window, k)
    call(step, 1)
    steps += k + 1
    watchdog.disarm()
    shard_devs, slot_devs = _slot_devices(cand)
    return {"per_call_max_abs_diff": diffs, "max_abs_diff": max(diffs),
            "per_call_flipped_slots": flips,
            "flipped_slot_calls": sum(flips), "slot_calls": slots * len(flips),
            "evictions": cand.evictions,
            "recompiles_after_warmup": watchdog.violations,
            "recompile_signatures": list(watchdog.violation_signatures),
            "compiled_programs": cand.compiled_programs(),
            "smoke_controller_steps_per_s": steps * slots / busy[1],
            "shard_devices": shard_devs, "slot_devices": slot_devs}


FLIP_SHARE = 0.01     # float32: at most 1% of slot-calls with a flipped spike


def _check_snn(run: dict, exact: bool, what: str) -> float:
    assert run["recompiles_after_warmup"] == 0, run["recompile_signatures"]
    diff, flipped = run["max_abs_diff"], run["flipped_slot_calls"]
    if exact:
        assert diff == 0.0 and flipped == 0, \
            f"{what}: not bitwise equal (max |diff| {diff}, {flipped} flips)"
    else:
        assert diff <= FLOAT_TOL, f"{what}: max |diff| {diff} > {FLOAT_TOL}"
        assert flipped <= FLIP_SHARE * run["slot_calls"], \
            f"{what}: {flipped} of {run['slot_calls']} slot-calls flipped"
    return diff


def _snn_row(run, **fields):
    keep = ("max_abs_diff", "per_call_max_abs_diff", "flipped_slot_calls",
            "per_call_flipped_slots", "slot_calls", "evictions",
            "recompiles_after_warmup", "compiled_programs",
            "smoke_controller_steps_per_s")
    return {**fields, **{key: run[key] for key in keep},
            "bitwise": run["max_abs_diff"] == 0.0
            and run["flipped_slot_calls"] == 0}


def snn_fleet_phase(cfg, *, slots: int, n_users: int, k: int, impl: str,
                    rounds: int = 3, seed: int = 0) -> list:
    """The fleet schedule on `impl` against the xla oracle, in float32
    (per call from the oracle's state: within 1e-5 wherever the hidden
    spike trains agree, at most 1% of slot-calls flipped) and int8 (over
    the whole run, bitwise)."""
    rows = []
    for datapath in ("float32", "int8"):
        c = dataclasses.replace(cfg, impl=impl)
        if datapath == "int8":
            c = snn.quant_config(c)
        quant = datapath == "int8"
        run = snn_lockstep([(dataclasses.replace(c, impl="xla"), None),
                            (c, None)], slots=slots, n_users=n_users, k=k,
                           rounds=rounds, seed=seed, resync=not quant)
        _check_snn(run, quant, f"snn_fleet {datapath} {impl} vs xla")
        rows.append(_snn_row(
            run, phase="snn_fleet", datapath=datapath, impl=impl, vs="xla",
            compared="whole run" if quant else "per call from the oracle",
            layer_sizes=list(c.layer_sizes), slots=slots, users=n_users,
            window_k=k))
    return rows


def snn_mesh_phase(cfg, *, slots: int, n_users: int, k: int, impl: str,
                   devices: int, rounds: int = 3, seed: int = 0) -> list:
    """The fleet schedule on a `devices`-way `fleet_mesh` against the same
    pool unmeshed on one device: bitwise over the whole run, float32 and
    int8."""
    rows = []
    for datapath in ("float32", "int8"):
        c = dataclasses.replace(cfg, impl=impl)
        if datapath == "int8":
            c = snn.quant_config(c)
        run = snn_lockstep([(c, None), (c, fleet_mesh(devices))],
                           slots=slots, n_users=n_users, k=k, rounds=rounds,
                           seed=seed)
        _check_snn(run, True, f"snn_fleet {datapath} meshed vs unmeshed")
        assert len(run["shard_devices"]) == devices, run["shard_devices"]
        assert len(run["slot_devices"]) == devices, run["slot_devices"]
        rows.append(_snn_row(
            run, phase="snn_fleet_mesh", datapath=datapath, impl=impl,
            devices=devices, vs="unmeshed", compared="whole run",
            slots=slots, users=n_users, shard_devices=run["shard_devices"],
            slot_devices=run["slot_devices"]))
    return rows


# ---- phase lm_pool --------------------------------------------------------------


def lm_schedule(model, params, *, slots: int, max_len: int, prompt_len: int,
                steps: int, k: int, seed: int = 0, mesh=None) -> dict:
    """Drive one `LMScheduler` through the LM schedule; host results only.

    Admit ``slots`` prompts; warm up with one ``step``, one
    ``decode_window`` of K and one evict -> admit a new prompt -> re-admit
    the evicted stream into another slot; arm the watchdog; then
    ``steps - 1`` more ``step`` calls, one more window, and a second
    evict -> re-admit into a different slot.
    """
    vocab = model.cfg.vocab
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, (slots + 1, prompt_len), dtype=np.int32)
    sched = LMScheduler(model, params, slots=slots, max_len=max_len,
                        mesh=mesh)
    uids = [f"s{i}" for i in range(slots)]
    for i, u in enumerate(uids):
        sched.admit_prompt(u, prompts[i])
    toks, logits = [], []

    def step():
        t = sched.step()
        toks.append(np.array([t[u] for u in sorted(t)], np.int32))

    def window():
        tail = rng.integers(0, vocab, (slots, k - 1), dtype=np.int32)
        ws = {u: np.concatenate([[sched.pending(u)], tail[i]]).astype(
            np.int32) for i, u in enumerate(sorted(sched.user_slot))}
        out = sched.decode_window(ws)
        lg = np.stack([np.asarray(out[u], np.float32) for u in sorted(out)])
        assert np.isfinite(lg).all(), "non-finite window logits"
        logits.append(lg)
        toks.append(lg.argmax(-1).astype(np.int32))

    def swap(evictee, newcomer):
        """Evict `evictee`, admit `newcomer` into its slot, then evict a
        second resident and re-admit `evictee` into THAT slot."""
        slot = sched.user_slot[evictee]
        sched.evict(evictee)
        if newcomer in sched.store:
            sched.admit_prompt(newcomer, prompts[0])     # restored
        else:
            sched.admit_prompt(newcomer, prompts[slots])  # fresh prefill
        assert sched.user_slot[newcomer] == slot
        other = sorted(u for u in sched.user_slot if u != newcomer)[0]
        other_slot = sched.user_slot[other]
        sched.evict(other)
        assert sched.admit_prompt(evictee, prompts[0]) == other_slot != slot
        return other

    watchdog.install()
    step()
    window()
    parked = swap(uids[3], "s_new")
    jax.block_until_ready(sched.pool)
    watchdog.reset()
    watchdog.arm()
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        step()
    window()
    swap(uids[5], parked)
    jax.block_until_ready(sched.pool)
    dt = time.perf_counter() - t0
    watchdog.disarm()
    pend = np.array([sched.pending(u) for u in sorted(sched.user_slot)],
                    np.int32)
    shard_devs, slot_devs = _slot_devices(sched)
    return {"tokens": toks + [pend], "logits": logits,
            "w_fast": np.asarray(sched.pool["cache"]["adapter"]["w_fast"]),
            "recompiles_after_warmup": watchdog.violations,
            "recompile_signatures": list(watchdog.violation_signatures),
            "compiled_programs": sched.compiled_programs(),
            "smoke_tokens_per_s": slots * (steps - 1 + k) / dt,
            "shard_devices": shard_devs, "slot_devices": slot_devs}


def _first_window(a: dict, b: dict) -> dict:
    """The first decode window of two runs (fed the same tokens while the
    step before it agreed): its logits' max |diff|, the positions whose
    greedy token differs, and the largest margin, in `a`'s logits, between
    the two tokens chosen there.  A flip whose margin is within the logit
    difference is a near-tie that rounding alone can turn."""
    la, lb = a["logits"][0], b["logits"][0]
    ta, tb = la.argmax(-1), lb.argmax(-1)
    flip = ta != tb
    margin = (np.take_along_axis(la, ta[..., None], -1)
              - np.take_along_axis(la, tb[..., None], -1))[..., 0]
    return {"logits_max_abs_diff": float(np.abs(la - lb).max()),
            "flips": int(flip.sum()), "positions": int(flip.size),
            "flip_max_margin": float(margin[flip].max(initial=0.0))}


def _check_lm(a: dict, b: dict, what: str) -> dict:
    for run in (a, b):
        assert run["recompiles_after_warmup"] == 0, run["recompile_signatures"]
    for i, (x, y) in enumerate(zip(a["tokens"], b["tokens"])):
        assert np.array_equal(x, y), (
            f"{what}: greedy tokens differ at call {i} ({int((x != y).sum())}"
            f" of {x.size}); first window {_first_window(a, b)}")
    w_diff = float(np.max(np.abs(a["w_fast"].astype(np.float64)
                                 - b["w_fast"].astype(np.float64))))
    assert w_diff <= FLOAT_TOL, \
        f"{what}: W_fast max |diff| {w_diff} > {FLOAT_TOL}"
    logit_diff = _max_diff(a["logits"], b["logits"])
    return {"tokens_equal": True, "w_fast_max_abs_diff": w_diff,
            "window_logits_max_abs_diff": logit_diff,
            "w_fast_nonzero": bool(np.any(a["w_fast"]))}


def _lm_model(cfg, impl):
    return factory.build(cfg, plastic_adapter=True, adapter_neurons=128,
                         adapter_impl=impl)


def lm_pool_phase(cfg, *, slots: int, max_len: int, prompt_len: int,
                  steps: int, k: int, impl: str, seed: int = 0) -> dict:
    """The LM schedule with the adapter on `impl`, then on xla, same
    params: tokens equal, W_fast within 1e-5, logits finite."""
    model = _lm_model(cfg, impl)
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    kw = dict(slots=slots, max_len=max_len, prompt_len=prompt_len,
              steps=steps, k=k, seed=seed)
    t0 = time.perf_counter()
    run = lm_schedule(model, params, **kw)
    wall = time.perf_counter() - t0
    gc.collect()                        # the first pool is gone: free it
    ref = lm_schedule(_lm_model(cfg, "xla"), params, **kw)
    cmp = _check_lm(run, ref, f"lm_pool {impl} vs xla")
    return {"phase": "lm_pool", "arch": cfg.name, "impl": impl, "vs": "xla",
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "adapter_neurons": 128, "dtype": cfg.dtype, "slots": slots,
            "max_len": max_len, "prompt_len": prompt_len, "steps": steps,
            "window_k": k, **cmp,
            "recompiles_after_warmup": run["recompiles_after_warmup"],
            "compiled_programs": run["compiled_programs"],
            "compiled_programs_xla": ref["compiled_programs"],
            "param_init_s": init_s, "wall_s": wall,
            "smoke_tokens_per_s": run["smoke_tokens_per_s"]}


def lm_mesh_phase(cfg, *, slots: int, max_len: int, prompt_len: int,
                  steps: int, k: int, impl: str, devices: int,
                  seed: int = 0) -> dict:
    """The LM schedule on a `devices`-way `fleet_mesh` vs unmeshed: equal
    tokens.  The unmeshed reference runs first and is freed (params are
    replicated on every chip for the meshed run)."""
    model = _lm_model(cfg, impl)
    params = model.init(jax.random.PRNGKey(seed))
    kw = dict(slots=slots, max_len=max_len, prompt_len=prompt_len,
              steps=steps, k=k, seed=seed)
    ref = lm_schedule(model, params, **kw)
    # replicate through host memory: a device-to-device copy would hold
    # two copies of the params on the first chip at once
    host = jax.device_get(params)
    del params
    gc.collect()
    mesh = fleet_mesh(devices)
    params = jax.device_put(
        host, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    del host
    run = lm_schedule(model, params, mesh=mesh, **kw)
    cmp = _check_lm(run, ref, "lm_pool meshed vs unmeshed")
    assert len(run["shard_devices"]) == devices, run["shard_devices"]
    assert len(run["slot_devices"]) == devices, run["slot_devices"]
    return {"phase": "lm_pool_mesh", "arch": cfg.name, "impl": impl,
            "devices": devices, "vs": "unmeshed", "slots": slots, **cmp,
            "shard_devices": run["shard_devices"],
            "slot_devices": run["slot_devices"],
            "recompiles_after_warmup": run["recompiles_after_warmup"],
            "compiled_programs": run["compiled_programs"],
            "smoke_tokens_per_s": run["smoke_tokens_per_s"]}


# ---- main ---------------------------------------------------------------------


class _CompileClock:
    """Sums backend compile seconds (jax.monitoring) for per-phase lines."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the meshed-pool comparison")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; {len(jax.devices())} found", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    controller = snn.SNNConfig(layer_sizes=(16, 128, 8))
    qwen = factory.build("qwen3-4b").cfg           # published widths, bf16
    snn_sizes = dict(slots=1024, n_users=1536, k=8)
    lm_sizes = dict(slots=8, max_len=1024, prompt_len=128, steps=16, k=8)
    if args.chips == 1:
        phases = [
            lambda: snn_fleet_phase(controller, impl="pallas", **snn_sizes),
            lambda: [lm_pool_phase(qwen, impl="pallas", **lm_sizes)]]
    else:
        phases = [
            lambda: snn_mesh_phase(controller, impl="pallas",
                                   devices=args.chips, **snn_sizes),
            lambda: [lm_mesh_phase(qwen, impl="pallas", devices=args.chips,
                                   **lm_sizes)]]
    for phase in phases:
        c0, t0 = clock.seconds, time.perf_counter()
        rows = phase()
        for row in rows:
            row.update(device_kind=dev.device_kind,
                       phase_compile_s=clock.seconds - c0,
                       phase_s=time.perf_counter() - t0,
                       peak_bytes_in_use=_peak_bytes(),
                       compile_cache=cache_dir)
            print(json.dumps(row), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
