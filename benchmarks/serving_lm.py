"""Plastic LM serving under churn: tokens/s through the LMScheduler pool.

Sweeps layout (dense GQA / Mamba2 SSM / MoE) x engine backend (xla /
pallas-interpret) x adapter datapath (float32 / int8) and, per cell, drives
Poisson admissions + geometric departures against a fixed `LMScheduler`
slot pool while every resident stream decodes greedily AND learns online
(its own W_fast rewritten by the fused plastic engine every token).  The
windowed path is exercised in the same loop: periodically each stream
advances K teacher-forced tokens through `decode_window` (the backbone
scans, the adapter runs K plasticity steps as ONE `plastic.decode_rollout`
launch).

Per cell, measured AND asserted:

  * tokens/s under churn (sequential) and through the windowed path,
  * recompiles after warm-up — PINNED AT ZERO: pool shapes are fixed, slot
    indices traced, occupancy a runtime ``active`` mask; admissions,
    evictions, and mixed occupancy never retrace anything,
  * evict -> persist -> re-admit bit-identity MID-GENERATION: a probe
    stream's greedy tokens and final session pytree (backbone cache,
    adapter W_fast/traces, step counter, pending token) are bit-equal
    whether or not the stream was evicted at token 3, displaced by a rival,
    and re-admitted into a DIFFERENT slot — probed inside the SAME
    scheduler instance, so it also proves zero probe-induced recompiles,
  * vacant-slot freeze: an evicted slot's entire session row is
    bit-unchanged after further pool steps.

The MoE cells pin the capacity no-op contract: expert capacity is raised
so no token ever drops, making cross-row capacity coupling inert — the one
place a neighbour could legitimately alter an active stream's output.

    PYTHONPATH=src python benchmarks/serving_lm.py [--smoke] [--impl ...]

Writes benchmarks/results/serving_lm.json (or _smoke.json under --smoke so
CI never clobbers the checked-in artifact; the run.py drift gate requires
the smoke sweep to keep covering every layout/impl/datapath cell of the
checked-in one).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.models import factory
from repro.serving import LMScheduler, SessionStore

RESULTS = os.path.join(os.path.dirname(__file__), "results")

LAYOUT_ARCH = {"dense": "qwen3-4b", "ssm": "mamba2-1.3b",
               "moe": "deepseek-moe-16b"}


def build_model(layout: str, impl: str, datapath: str, neurons: int):
    cfg = factory.build(LAYOUT_ARCH[layout], smoke=True).cfg
    cfg = cfg.with_(plastic_adapter=True, adapter_neurons=neurons,
                    adapter_impl=impl, adapter_quant=(datapath == "int8"))
    model = factory.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["adapter"]["scale"] = jax.numpy.float32(0.5)
    return model, params


def prompt_for(uid: str, length: int, vocab: int) -> np.ndarray:
    rng = np.random.RandomState(abs(hash(uid)) % (2 ** 31))
    return rng.randint(0, vocab, size=length).astype(np.int32)


def _probe_trajectory(sched, uid, prompt, n_tokens, interrupt_at=None,
                      rival_prompt=None):
    """Greedy-decode `uid` for `n_tokens` inside the CURRENT scheduler;
    optionally evict mid-generation, let a rival displace the slot, and
    re-admit (store restore) into a DIFFERENT slot."""
    sched.admit_prompt(uid, prompt)
    toks = [sched.pending(uid)]
    for t in range(n_tokens):
        if interrupt_at is not None and t == interrupt_at:
            sched.evict(uid)                     # persist mid-generation
            sched.store._warm.pop(uid, None)     # force the archive path
            sched.admit_prompt("rival", rival_prompt)  # takes the old slot
            sched.step()
            slot = sched.admit_prompt(uid, prompt)     # restored, NEW slot
            assert sched.user_slot["rival"] != slot
            sched.evict("rival")
        toks.append(sched.step()[uid])
    sess = jax.tree.map(np.asarray, sched.session_view(uid))
    sched.evict(uid)
    return toks, sess


def bench_cell(layout: str, impl: str, datapath: str, *, slots: int,
               steps: int, window: int, prompt_len: int, neurons: int,
               arrival: float = 0.4, depart: float = 0.1,
               seed: int = 0) -> dict:
    model, params = build_model(layout, impl, datapath, neurons)
    vocab = model.cfg.vocab
    max_len = prompt_len + steps + 4 * window + 8
    sched = LMScheduler(model, params, slots=slots, max_len=max_len,
                        store=SessionStore())

    # ---- warm-up: touch every jitted program once ------------------------
    sched.admit_prompt("warm", prompt_for("warm", prompt_len, vocab))
    sched.step()
    sched.decode_window({"warm": np.full((window,), sched.pending("warm"),
                                         np.int32)})
    sched.evict("warm")
    sched.admit_prompt("warm", prompt_for("warm", prompt_len, vocab))
    sched.step()
    sched.evict("warm")
    warm_compiles = sched.compile_count()

    # ---- churn loop ------------------------------------------------------
    rng = np.random.default_rng(seed)
    user_pool = [f"u{i:02d}" for i in range(3 * slots)]
    next_uid = 0
    seq_tokens = win_tokens = 0
    seq_wall = win_wall = 0.0
    for t in range(steps):
        for _ in range(int(rng.poisson(arrival))):
            uid = user_pool[next_uid % len(user_pool)]
            next_uid += 1
            if uid in sched.user_slot:
                continue
            sched.admit_prompt(uid, prompt_for(uid, prompt_len, vocab),
                               evict_lru=True)
        for uid in list(sched.active_users):
            if rng.random() < depart:
                sched.evict(uid)
        occ = len(sched.user_slot)
        if occ == 0:
            continue
        if window > 1 and t % 4 == 3:
            # windowed path: each stream advances `window` teacher-forced
            # tokens (its pending token + forced continuations) in ONE
            # fused launch
            wins = {u: np.concatenate(
                [[sched.pending(u)],
                 rng.integers(0, vocab, window - 1)]).astype(np.int32)
                for u in sched.active_users}
            t0 = time.perf_counter()
            out = sched.decode_window(wins)
            jax.tree.leaves(out)[0].block_until_ready()
            win_wall += time.perf_counter() - t0
            win_tokens += occ * window
        else:
            t0 = time.perf_counter()
            out = sched.step()
            seq_wall += time.perf_counter() - t0
            seq_tokens += occ

    recompiles = sched.compile_count() - warm_compiles
    assert recompiles == 0, (
        f"{layout}/{impl}/{datapath}: churn caused {recompiles} recompiles "
        "— the fixed-shape contract is broken")

    # ---- vacant-slot freeze ---------------------------------------------
    for uid in list(sched.active_users):
        sched.evict(uid)
    sched.admit_prompt("holder", prompt_for("holder", prompt_len, vocab))
    vacant = sched.slot_user.index(None)
    import jax.numpy as jnp
    before = jax.tree.map(np.asarray,
                          sched._take(sched.pool, jnp.int32(vacant)))
    for _ in range(5):
        sched.step()
    after = jax.tree.map(np.asarray,
                         sched._take(sched.pool, jnp.int32(vacant)))
    idle_frozen = all(np.array_equal(a, b) for a, b in
                      zip(jax.tree.leaves(before), jax.tree.leaves(after)))
    assert idle_frozen, (f"{layout}/{impl}/{datapath}: vacant slot drifted "
                         "— the active-mask no-op contract is broken")
    sched.evict("holder")

    # ---- evict -> persist -> re-admit bit-identity mid-generation --------
    probe_prompt = prompt_for("probe", prompt_len, vocab)
    rival_prompt = prompt_for("rival", prompt_len, vocab)
    ref_toks, ref_sess = _probe_trajectory(sched, "probe_ref", probe_prompt,
                                           8)
    int_toks, int_sess = _probe_trajectory(sched, "probe_int", probe_prompt,
                                           8, interrupt_at=3,
                                           rival_prompt=rival_prompt)
    bit_identical = ref_toks == int_toks and all(
        np.array_equal(a, b) for a, b in
        zip(jax.tree.leaves(ref_sess), jax.tree.leaves(int_sess)))
    assert bit_identical, (
        f"{layout}/{impl}/{datapath}: evict -> persist -> re-admit diverged "
        f"mid-generation ({ref_toks} vs {int_toks})")
    probe_recompiles = sched.compile_count() - warm_compiles
    assert probe_recompiles == 0, (
        f"{layout}/{impl}/{datapath}: the probe retraced "
        f"{probe_recompiles} programs")

    return {
        "layout": layout, "arch": model.cfg.name, "impl": impl,
        "datapath": datapath, "slots": slots, "steps": steps,
        "window": window, "adapter_neurons": neurons,
        "tokens_per_s": seq_tokens / seq_wall if seq_wall else 0.0,
        "window_tokens_per_s": win_tokens / win_wall if win_wall else 0.0,
        "seq_tokens": seq_tokens, "window_tokens": win_tokens,
        "evictions": sched.evictions,
        "pool_mbytes": sched.pool_nbytes() / 1e6,
        "compiled_programs": warm_compiles,
        "recompiles_after_warmup": int(recompiles),
        "idle_slot_frozen": bool(idle_frozen),
        "evict_readmit_bit_identical": bool(bit_identical),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cells for CI (seconds per cell)")
    ap.add_argument("--impl", default=None,
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="restrict to one backend (default: xla and "
                         "pallas-interpret)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.out is None:
        name = "serving_lm_smoke.json" if args.smoke else "serving_lm.json"
        args.out = os.path.join(RESULTS, name)

    impls = [args.impl] if args.impl else ["xla", "pallas-interpret"]
    layouts = ["dense", "ssm", "moe"]
    datapaths = ["float32", "int8"]
    knobs = (dict(slots=3, steps=12, window=3, prompt_len=4, neurons=8)
             if args.smoke else
             dict(slots=8, steps=48, window=4, prompt_len=8, neurons=32))

    sweep = []
    print("layout,impl,datapath,tokens_per_s,window_tokens_per_s,"
          "recompiles,bit_identical")
    for layout in layouts:
        for impl in impls:
            for dp in datapaths:
                row = bench_cell(layout, impl, dp, **knobs)
                sweep.append(row)
                print(f"{layout},{impl},{dp},{row['tokens_per_s']:.1f},"
                      f"{row['window_tokens_per_s']:.1f},"
                      f"{row['recompiles_after_warmup']},"
                      f"{row['evict_readmit_bit_identical']}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"impls": impls, "layouts": layouts,
                   "datapaths": datapaths, "smoke": bool(args.smoke),
                   "sweep": sweep}, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
