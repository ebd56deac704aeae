"""The load loop for plastic LM decode pools served by `LMScheduler`.

One general generator and loop for every traffic mix of this kind.  The
traffic file gives:

  slots, max_len     the pool: decode slots and the cache length of each
  prompt_lens        prompt lengths, with their shares `prompt_shares`
  out_min            the shortest output; outputs are log-uniform in
                     [out_min, max_len - prompt]
  requests           requests per run, more than a window finishes
  order_seed         the fixed order of their lengths
  check_requests     finished requests compared with the reference (the
                     one with the most served tokens always among them)
  limits             the limits of the compared numbers (`logit_gap_mean`,
                     `wfast_gap`)

Every seed serves the same (prompt, output) lengths in the same order:
the shares are met exactly, the outputs sit at evenly spaced quantiles of
the log-uniform law, and `order_seed` shuffles them once.  A window
finishes only the first twenty or so, so an order drawn per seed would
change the work from seed to seed.  The seed draws the prompt tokens and
the weights.  The loop is closed: every slot holds a stream, greedy decoding
runs through `LMScheduler.step`, and a stream that has served its output
is evicted and replaced by the next request at once.

`correct`: once the window has closed and the pool is freed, the plain
reference (`bench/reference/lm_pool.py`) runs each sampled request's
prompt with its served tokens.  `logit_gap_mean` is the mean, over every
served token compared, of the gap by which its reference logit lies below
the reference's best (0 where it is the reference's argmax).
`wfast_gap` is the largest ||W_prog - W_ref|| / ||W_ref|| of the sampled
requests' adapter W_fast after their last step: an adapter that does not
learn reads 1.  Sound runs read up to about a fifth, since the adapter's
spikes sit on a threshold that bfloat16 and float32 hidden states cross
differently; `wfast_bf16h_gap` (reported, not compared) is how far the
reference's own W_fast moves when its hidden states are rounded to
bfloat16.  Also reported, not compared: the widest single logit gap,
which swings from seed to seed as much as the control's does.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Callable, Optional

import numpy as np


def program_model(cfg: dict):
    """The configuration as the program's model (`factory.build`)."""
    from repro.models import factory
    from repro.models.config import ModelConfig
    mc = ModelConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qk_norm=True,
        qkv_bias=cfg["attention_bias"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], layout="dense",
        dtype=cfg["torch_dtype"], remat=False)
    return factory.build(mc, plastic_adapter=True,
                         adapter_neurons=cfg["adapter_neurons"],
                         adapter_impl=cfg["adapter_impl"])


def requests(seed: int, tr: dict, vocab: int) -> list:
    """The run's requests, in order: dicts with ``prompt`` (int32 array)
    and ``out`` (tokens to serve, the prefill's first one included).  The
    lengths and their order are the traffic's (`order_seed`); the seed
    draws the prompt tokens."""
    rng = np.random.default_rng([seed, 5])
    n = tr["requests"]
    counts = [round(s * n) for s in tr["prompt_shares"]]
    counts[0] += n - sum(counts)
    sizes = []
    for p, c in zip(tr["prompt_lens"], counts):
        lo, hi = math.log(tr["out_min"]), math.log(tr["max_len"] - p)
        sizes += [(p, int(math.exp(lo + (hi - lo) * (i + 0.5) / c)))
                  for i in range(c)]
    order = np.random.default_rng(tr["order_seed"]).permutation(len(sizes))
    return [{"prompt": rng.integers(0, vocab, sizes[i][0], dtype=np.int32),
             "out": sizes[i][1]} for i in order]


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, plant: Optional[Callable] = None) -> dict:
    """Set up, measure and check one run of `cell`.

    `plant(sched)` (tests and calibration only) breaks the pool's decode
    step after set-up; `plant_control` instead reads the control's
    number.  `bench/run.py` never passes either.
    """
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.obs.watchdog import watchdog
    from repro.serving import LMScheduler
    from repro.serving.sessions import SessionStore

    from bench import harness, peaks, work
    from bench import trace as bench_trace

    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    slots, max_len = tr["slots"], tr["max_len"]
    dims = ref.dims(cfg)

    # ---- set-up: weights, pool, warm-up, the first streams ---------------
    weights = ref.make_weights(cfg, harness.key_from_seed(seed))
    model = program_model(cfg)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.key(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != got:
        raise ValueError("the benchmark's weights do not fit the program's "
                         "parameter layout")
    # a finished stream is archived on the host only: no device copy kept
    sched = LMScheduler(model, weights, slots=slots, max_len=max_len,
                        store=SessionStore(capacity=0))
    warm_rng = np.random.default_rng([seed, 9])
    for i, p in enumerate(tr["prompt_lens"]):      # every shape once
        uid = f"warm{i}"
        sched.admit_prompt(uid, warm_rng.integers(0, cfg["vocab_size"], p,
                                                  dtype=np.int32))
        sched.pending(uid)
        sched.step()
        sched.evict(uid)
    queue = requests(seed, tr, cfg["vocab_size"])
    live, nxt = {}, 0           # uid -> request; the next request to admit

    def admit():
        nonlocal nxt
        req = dict(queue[nxt % len(queue)], uid=f"r{nxt}")
        nxt += 1
        with TraceAnnotation("bench.admit"):
            sched.admit_prompt(req["uid"], req["prompt"])
            req["served"] = [sched.pending(req["uid"])]
        req["t_last"] = time.perf_counter()
        live[req["uid"]] = req

    for _ in range(slots):
        admit()
    template = jax.eval_shape(lambda: sched.session_view("r0"))
    if plant is not None:
        plant(sched)
    jax.block_until_ready(sched.pool)

    # ---- the window ------------------------------------------------------
    watchdog.install()
    watchdog.reset()
    if trace_dir is not None:
        bench_trace.start(trace_dir)
    watchdog.arm()
    setup_s = time.perf_counter() - t_start
    itl, finished, steps, generated, positions = [], [], 0, 0, 0
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        for req in live.values():
            req["t_last"] = t0
        while True:
            with TraceAnnotation("bench.step"):
                toks = sched.step()
            t = time.perf_counter()
            steps += 1
            for uid, tok in toks.items():
                req = live[uid]
                req["served"].append(tok)
                itl.append(t - req["t_last"])
                req["t_last"] = t
                positions += len(req["prompt"]) + len(req["served"]) - 1
            generated += len(toks)
            elapsed = t - t0
            if elapsed >= seconds:
                break
            for uid in [u for u, r in live.items()
                        if len(r["served"]) >= r["out"]]:
                with TraceAnnotation("bench.replace"):
                    sched.evict(uid)
                    finished.append(live.pop(uid))
                    admit()
    watchdog.disarm()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use", 0)

    # ---- correctness: sampled finished requests against the reference ----
    pick = np.random.default_rng([seed, 11])
    sample = []
    if finished:
        longest = max(range(len(finished)),
                      key=lambda i: len(finished[i]["served"]))
        rest = [i for i in pick.permutation(len(finished)) if i != longest]
        sample = [finished[i] for i in
                  [longest] + rest[:tr["check_requests"] - 1]]
    w_prog = {}
    for req in sample:
        state, _ = sched.store.checkout(req["uid"], lambda: None,
                                        template=template)
        w_prog[req["uid"]] = np.asarray(state["cache"]["adapter"]["w_fast"])
    del sched
    gc.collect()

    control = getattr(plant, "control", False)
    gaps, wfast_gap, bf16h_gap = [], 0.0, 0.0
    for req in sample:
        p, served = len(req["prompt"]), np.asarray(req["served"], np.int32)
        seq = np.zeros(max_len, np.int32)
        seq[:p] = req["prompt"]
        seq[p:p + len(served) - 1] = served[:-1]
        # logits at positions p-1 .. p+len(served)-2 chose the served tokens
        chosen = np.zeros(max_len, np.int32)
        chosen[p - 1:p - 1 + len(served)] = served
        mask = np.zeros(max_len, bool)
        mask[p - 1:p - 1 + len(served)] = True
        # the adapter ran on the hidden states of the len(served) - 1
        # decode steps: positions p .. p+len(served)-2
        steps_on = np.zeros(max_len, bool)
        steps_on[:len(served) - 1] = True
        h = ref.hidden(weights, jnp.asarray(seq), dims_=dims)
        lg = ref.logits(weights, h, dims_=dims)
        w_ref = np.asarray(ref.adapter_rollout(
            weights, jnp.roll(h, -p, axis=0), jnp.asarray(steps_on),
            dims_=dims))
        w_got = w_prog[req["uid"]]
        w_bf16h = np.asarray(ref.adapter_rollout(
            weights, jnp.roll(h.astype(jnp.bfloat16).astype(jnp.float32),
                              -p, axis=0),
            jnp.asarray(steps_on), dims_=dims))
        if control:
            hq = ref.hidden(weights, jnp.asarray(seq), dims_=dims, quant=True)
            chosen = jnp.argmax(ref.logits(weights, hq, dims_=dims,
                                           quant=True), -1)
            w_got = np.asarray(ref.adapter_rollout(
                weights, jnp.roll(hq, -p, axis=0), jnp.asarray(steps_on),
                dims_=dims, low=True))
            del hq
        gap = ref.served_gap(lg, jnp.asarray(chosen), jnp.asarray(mask))
        gaps.append(np.asarray(gap)[mask])
        ref_norm = max(float(np.linalg.norm(w_ref)), 1e-30)
        wfast_gap = max(wfast_gap,
                        float(np.linalg.norm(w_got - w_ref)) / ref_norm)
        bf16h_gap = max(bf16h_gap,
                        float(np.linalg.norm(w_bf16h - w_ref)) / ref_norm)
        del h, lg, gap
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    checks = {"logit_gap_mean": {
        "value": float(gaps.mean()) if gaps.size else 0.0,
        "limit": tr["limits"]["logit_gap_mean"]},
        "wfast_gap": {"value": wfast_gap,
                      "limit": tr["limits"]["wfast_gap"]}}
    correct = bool(sample) and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    mean_pos = positions / max(steps, 1)
    cost = work.decode_step(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], slots=slots, cached_positions=mean_pos,
        adapter_neurons=cfg["adapter_neurons"])
    least, bound = (peaks.least_time_s(cost["flops"], cost["bytes"],
                                       dev.device_kind)
                    if dev.platform == "tpu" else (None, None))
    return {
        "e2e": {"setup_s": setup_s, "tokens_per_s": generated / elapsed,
                "itl_p95_ms": 1e3 * float(np.percentile(itl, 95))},
        "correct": correct,
        "attempted": len(finished) + len(live), "failed": 0,
        "checks": checks,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
        "layer_inputs": {"least_time_s": least, "bound": bound,
                         "steps": steps, "window_s": elapsed},
        "notes": {"compiles_in_window": watchdog.violations,
                  "steps": steps, "tokens": generated,
                  "finished": len(finished), "admitted": nxt,
                  "median_itl_ms": 1e3 * float(np.median(itl)),
                  "max_itl_ms": 1e3 * max(itl),
                  "mean_cached_positions": mean_pos,
                  "served_compared": int(gaps.size),
                  "logit_gap_max": float(gaps.max(initial=0.0)),
                  "wfast_bf16h_gap": bf16h_gap,
                  "median_itl_ms_halves": [
                      1e3 * float(np.median(itl[:len(itl) // 2])),
                      1e3 * float(np.median(itl[len(itl) // 2:]))],
                  "memory_at_close": {
                      k: v for k, v in mem.items()
                      if k in ("bytes_in_use", "bytes_limit",
                               "largest_free_block_bytes",
                               "num_allocs")},
                  "served_off_argmax": int((gaps > 0).sum()),
                  "requests_compared": len(sample),
                  "least_time_ms": None if least is None else 1e3 * least,
                  "least_time_bound": bound},
    }


# ---- the control and faults -----------------------------------------------
# Used by `bench/calibrate.py` on the chip and by the tests under
# tests/bench; a benchmark run never plants one.


def plant_control(sched) -> None:
    """The program runs as it is; the numbers read are the control's: the
    reference with int8 weights picks the token at each position, and its
    adapter, W_fast held in bfloat16, runs on its hidden states."""


plant_control.control = True


def plant_fault(kind: str):
    """A plant that breaks the pool's decode step: ``"unchanged"`` returns
    the pool it was given (no cache write, no adapter step); ``"half"``
    serves the second half of the slots their previous token again, as if
    they were left out of the step; ``"altered"`` serves every slot a
    neighbour of the token it chose."""
    import jax.numpy as jnp

    def plant(sched):
        real = sched._step_fn

        def step(params, pool, active):
            new, nxt = real(params, pool, active)
            if kind == "unchanged":
                return pool, nxt
            if kind == "half":
                keep = jnp.arange(nxt.shape[0]) < nxt.shape[0] // 2
                nxt = jnp.where(keep, nxt, pool["tok"])
                return dict(new, tok=nxt), nxt
            if kind == "altered":                # a neighbouring id
                return new, jnp.where(nxt > 0, nxt - 1, nxt + 1)
            raise ValueError(f"unknown fault {kind!r}")

        sched._step_fn = step
    return plant
