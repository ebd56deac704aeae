"""The load loop for plastic decode pools of a latent-attention MoE model
(DeepSeek-V2) served by `LMScheduler`, on one chip's share of the experts.

The traffic, its generator and the closed loop are `lm_pool`'s
(`bench/loops/lm_pool.py`: `requests`, `plant_control` and `plant_fault`
are imported from it, and the traffic file has its format): every slot
holds a stream, greedy decoding runs through `LMScheduler.step`, and a
stream that has served its output is evicted and replaced at once.

The model is built from the configuration's own keys: MLA widths, YaRN
rotary scaling, a dense first layer, then MoE layers whose router keeps
the published width (`router_experts`) while this chip holds
`n_routed_experts` of its experts from `first_held_expert`.  The least
time of a step is `bench/work_mla_moe.py`'s.  The program's counters
`lm_moe_held_assignments` and `lm_moe_steps`, read before the scheduler is
freed, give the held experts' load over the window.

`correct` is `lm_pool`'s comparison against this kind's reference
(`bench/reference/lm_pool_mla.py`, given the same share of the experts):
`logit_gap_mean` over every served token of the sampled finished
requests, and `wfast_gap` of their adapters' W_fast.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Callable, Optional

import numpy as np

from bench.harness import load_module

_LM = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "lm_pool.py"), "bench_loops_lm_pool")
requests = _LM.requests
plant_control = _LM.plant_control
plant_fault = _LM.plant_fault


def program_model(cfg: dict):
    """The configuration as the program's model (`factory.build`)."""
    from repro.models import factory
    from repro.models.config import ModelConfig, MoEConfig, YarnScaling
    ys = cfg["rope_scaling"]
    mc = ModelConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(ys["factor"]),
            original_max_position=int(ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])),
        qkv_bias=cfg["attention_bias"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], layout="moe",
        moe=MoEConfig(num_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      first_dense=cfg["first_k_dense_replace"],
                      first_dense_ff=cfg["intermediate_size"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      router_experts=cfg["router_experts"],
                      first_held=cfg["first_held_expert"]),
        dtype=cfg["torch_dtype"], remat=False)
    return factory.build(mc, plastic_adapter=True,
                         adapter_neurons=cfg["adapter_neurons"],
                         adapter_impl=cfg["adapter_impl"])


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, plant: Optional[Callable] = None) -> dict:
    """Set up, measure and check one run of `cell`; `plant` as in
    `lm_pool.run` (tests and calibration only)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.obs.watchdog import watchdog
    from repro.serving import LMScheduler
    from repro.serving.sessions import SessionStore

    from bench import harness, peaks
    from bench import trace as bench_trace

    work = load_module(os.path.join(cell.root, "bench", "work_mla_moe.py"))
    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    slots, max_len = tr["slots"], tr["max_len"]
    dims = ref.dims(cfg)

    # ---- set-up: weights, pool, warm-up, the first streams ---------------
    model = program_model(cfg)
    weights = ref.make_weights(cfg, harness.key_from_seed(seed))
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
    held_c = sched.metrics.counter("lm_moe_held_assignments")
    steps_c = sched.metrics.counter("lm_moe_steps")

    # ---- the window ------------------------------------------------------
    watchdog.install()
    watchdog.reset()
    if trace_dir is not None:
        bench_trace.start(trace_dir)
    watchdog.arm()
    setup_s = time.perf_counter() - t_start
    held0, steps0 = held_c.value, steps_c.value
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
    held_n, moe_steps = held_c.value - held0, steps_c.value - steps0
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
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
        router=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"], vocab=cfg["vocab_size"],
        slots=slots, cached_positions=mean_pos,
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
                         "steps": steps, "window_s": elapsed,
                         "moe_held_assignments": held_n,
                         "moe_steps": moe_steps,
                         "held_experts": cfg["n_routed_experts"]},
        "notes": {"compiles_in_window": watchdog.violations,
                  "steps": steps, "tokens": generated,
                  "finished": len(finished), "admitted": nxt,
                  "median_itl_ms": 1e3 * float(np.median(itl)),
                  "max_itl_ms": 1e3 * max(itl),
                  "mean_cached_positions": mean_pos,
                  "served_compared": int(gaps.size),
                  "logit_gap_max": float(gaps.max(initial=0.0)),
                  "wfast_bf16h_gap": bf16h_gap,
                  "held_assignments_per_step": held_n / max(moe_steps, 1),
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
