"""Batched serving driver: prefill + decode with KV/SSM caches, optional
FireFly-P plastic adapter (the paper's Phase-2 online adaptation running
inside an LM serving stack).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --batch 4 --prompt-len 32 --gen 16 --plastic [--plastic-impl pallas]

With --plastic every decode step runs the fused dual-engine program
(core.engine.layer_step) once per request stream; --plastic-impl picks the
backend ("xla" oracle, "pallas" TPU kernel, "pallas-interpret" validation).

With --session-dir the adapter's per-stream fast weights become SESSIONS
(repro.serving): each batch row is a named user admitted into a
`serving.AdapterPool` before decode and evicted (persisted) after —
re-running the driver with the same --session-dir resumes every user's
plastic memory bit-identically instead of re-zeroing it.  --adapter-quant
makes the pool FPGA-faithful fixed-point: int8 W_fast rows with per-user
scales and deterministic stochastic rounding keyed on each user's own step
counter.

The model lowers through `models.factory`, so any registered arch — dense
GQA, MoE, Mamba2 SSM, zamba hybrid — serves through the same driver.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.distributed import sharding as shd
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_decode_step, make_prefill
from repro.models import factory
from repro.obs import (AdapterFlightRecorder, HealthConfig, MetricsRegistry,
                       phase, serve_metrics)
from repro.obs import watchdog as _watchdog
from repro.serving import AdapterPool, SessionStore


def generate(cfg, params, prompts, max_len: int, gen: int,
             temperature: float = 0.0, seed: int = 0, adapters=None,
             registry=None, watch=None, metrics_json=None,
             metrics_interval: int = 0, flight=None):
    """Greedy/temperature sampling loop.  prompts (B, S) int32.

    Returns (tokens (B, gen), per-step latencies, final cache).  The decode
    step is AOT-compiled BEFORE the timed loop — historically the first
    iteration absorbed the jit compile, skewing decode_ms_p50/mean and
    tokens_per_s; all reported latencies are now steady-state.

    `adapters`: optional `serving.AdapterPool` whose admitted users are the
    batch rows (user b in slot b).  Its pool pytree REPLACES the fresh
    prefill cache's adapter entry, so each stream resumes its user's
    learned fast weights instead of starting from zero; after the loop the
    learned state flows back into the pool (the caller evicts to persist).

    `registry`: optional `obs.MetricsRegistry` — per-step decode latencies
    go into the ``serve_decode_seconds`` histogram and throughput into the
    ``serve_tokens_per_s`` gauge.  `watch`: optional `RecompileWatchdog`,
    ARMED only after loop iteration 0 (the decode step is AOT-compiled
    up-front, but the sampling helpers — argmax/categorical/fold_in — are
    tiny jitted programs that legitimately compile on first use inside the
    loop); from iteration 1 on, any backend compile is a violation.
    `metrics_json` + ``metrics_interval > 0``: dump a registry snapshot to
    that path every `metrics_interval` decode steps (and the caller dumps
    once more at exit).

    `flight`: optional `obs.AdapterFlightRecorder` (requires a plastic
    adapter in the cache).  Each decode step feeds the adapter state into
    the device-side ring + detectors.  The decode jit DONATES the cache,
    so the "before" view is a jitted materialized copy (`a + 0`) taken
    each step — aliasing the donated buffers would read freed memory.
    """
    prefill = jax.jit(make_prefill(cfg, max_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    m_decode = (registry.histogram("serve_decode_seconds",
                                   "per-token decode step latency")
                if registry is not None else None)
    with phase("serve.prefill"):
        logits, cache = prefill(params, prompts)
    if adapters is not None:
        # the pool IS the adapter state: one scheduler-admitted row per
        # batch stream (restored or fresh), installed wholesale — no
        # per-row scatter loop
        cache["adapter"] = adapters.pool
    key = jax.random.PRNGKey(seed)
    if flight is not None and "adapter" not in cache:
        raise ValueError("flight recording needs a plastic adapter in the "
                         "cache (cfg.plastic_adapter=True)")
    # decode donates `cache`, so the recorder's before-view must be a real
    # copy; `a + 0` materializes fresh buffers (compiles once, pre-arm)
    snap = jax.jit(lambda t: jax.tree.map(lambda a: a + jnp.zeros_like(a), t))
    outs, lats = [], []
    tok = _sample(logits, key, temperature)
    # Warm-up: compile against the real avals without consuming the (donated)
    # cache buffers or advancing the generation state; the loop calls the
    # compiled executable, so no iteration pays trace+compile.
    decode_c = decode.lower(params, cache, tok[:, None]).compile()
    if flight is not None and adapters is not None:
        # align the restored pool's layout with the decode step's OUTPUT
        # adapter: from iteration 1 on the loop feeds decode outputs back
        # in, so without this the flight snapshot's input shardings change
        # once after the first step and snap/_update re-lower post-arm
        _, out_cache_sh = decode_c.output_shardings
        cache["adapter"] = jax.device_put(cache["adapter"],
                                          out_cache_sh["adapter"])
    armed = False
    try:
        for i in range(gen):
            if i == 1 and watch is not None:
                watch.arm()
                armed = True
            outs.append(tok)
            before = snap(cache["adapter"]) if flight is not None else None
            t0 = time.perf_counter()
            with phase("serve.decode_step"):
                logits, cache = decode_c(params, cache, tok[:, None])
                logits.block_until_ready()
            dt = time.perf_counter() - t0
            lats.append(dt)
            if flight is not None:
                flight.observe(before, cache["adapter"])
            if m_decode is not None:
                m_decode.observe(dt)
            key = jax.random.fold_in(key, i)
            tok = _sample(logits, key, temperature)
            if (metrics_json and metrics_interval > 0 and registry is not None
                    and (i + 1) % metrics_interval == 0):
                registry.to_json(metrics_json)
    finally:
        if armed:
            watch.disarm()
    if registry is not None and lats:
        registry.gauge("serve_tokens_per_s",
                       "steady-state decode throughput (whole batch)"
                       ).set(prompts.shape[0] * len(lats) / sum(lats))
    if adapters is not None:
        # hand the learned rows back (the loop's donation consumed the
        # buffers the pool was holding)
        adapters.pool = cache["adapter"]
        adapters.advance_steps(gen)
    return jnp.stack(outs, axis=1), lats, cache


def _sample(logits, key, temperature):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--plastic", action="store_true",
                    help="attach the FireFly-P plastic adapter at decode")
    ap.add_argument("--plastic-impl", default="xla",
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="PlasticEngine backend for the adapter's fused "
                         "dual-engine step (pallas on TPU)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (2.3x decode memory-roofline win)")
    ap.add_argument("--adapter-quant", action="store_true",
                    help="with --plastic: fixed-point adapter pool (int8 "
                         "W_fast, per-user scales, int32 membranes/traces)")
    ap.add_argument("--session-dir", default=None,
                    help="with --plastic: durable per-user session store "
                         "for the adapter fast weights; each batch row is a "
                         "user whose learned W_fast persists across runs")
    ap.add_argument("--users", default=None,
                    help="comma-separated user ids for the batch rows "
                         "(default user0..user{B-1}); needs --session-dir")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-json", default=None,
                    help="write a metrics-registry JSON snapshot here "
                         "(final, plus periodic with --metrics-interval)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="with --metrics-json: also dump every N decode "
                         "steps (0 = final snapshot only)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the metrics registry over HTTP on this "
                         "port for the run's duration (/metrics Prometheus "
                         "text, /metrics.json snapshot; 0 = ephemeral)")
    ap.add_argument("--flight-dir", default=None,
                    help="with --plastic: run the adapter flight recorder "
                         "over the decode loop and write one incident "
                         "bundle (JSON + NPZ ring dump) per flagged "
                         "stream into this directory")
    args = ap.parse_args(argv)
    if (args.session_dir or args.users) and not args.plastic:
        ap.error("--session-dir/--users require --plastic (sessions are "
                 "the adapter's fast-weight state)")
    if args.users and not args.session_dir:
        ap.error("--users names the rows of a durable session store; "
                 "pass --session-dir too")
    if args.adapter_quant and not args.plastic:
        ap.error("--adapter-quant quantizes the plastic adapter pool; "
                 "pass --plastic too")
    if args.flight_dir and not args.plastic:
        ap.error("--flight-dir records the plastic adapter's health "
                 "channels; pass --plastic too")
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.plastic:
        cfg = cfg.with_(plastic_adapter=True,
                        adapter_neurons=min(128, cfg.d_model),
                        adapter_impl=args.plastic_impl,
                        adapter_quant=args.adapter_quant)
    if args.kv_quant:
        cfg = cfg.with_(kv_quant=True)
    model = factory.build(cfg)
    mesh = make_local_mesh()
    max_len = args.prompt_len + args.gen

    with shd.use_mesh(mesh), mesh:
        params = model.init(jax.random.PRNGKey(args.seed))
        prompts = jax.random.randint(
            jax.random.PRNGKey(args.seed + 1),
            (args.batch, args.prompt_len), 0, cfg.vocab)
        if cfg.input_mode == "embeddings":
            prompts_in = jax.nn.one_hot(prompts % cfg.d_model, cfg.d_model,
                                        dtype=cfg.adtype)
        else:
            prompts_in = prompts

        registry = MetricsRegistry()
        watch = _watchdog.install(registry)
        watch.reset()
        metrics_server = None
        if args.metrics_port is not None:
            metrics_server = serve_metrics(registry, port=args.metrics_port)
        flight = None
        if args.flight_dir is not None:
            from repro.models import plastic as _plastic
            flight = AdapterFlightRecorder(
                HealthConfig(), slots=args.batch,
                qcfg=_plastic.QUANT if args.adapter_quant else None,
                mesh=mesh)
        store = users = pool = None
        if args.session_dir is not None:
            store = SessionStore(root=args.session_dir, capacity=args.batch,
                                 registry=registry)
            users = (args.users.split(",") if args.users
                     else [f"user{b}" for b in range(args.batch)])
            if len(users) != args.batch:
                raise SystemExit(f"--users needs exactly {args.batch} ids, "
                                 f"got {len(users)}")
            if len(set(users)) != len(users):
                raise SystemExit(
                    "--users ids must be unique: two rows sharing a session "
                    "would silently overwrite each other's learned state")
            # scheduler-admit path: user b lands in pool slot b (admission
            # fills free slots in order), restoring persisted fast weights
            # through the SessionStore's validated checkout
            pool = AdapterPool(cfg, slots=args.batch, store=store,
                               registry=registry)
            for u in users:
                pool.admit(u)

        toks, lats, cache = generate(cfg, params, prompts_in, max_len,
                                     args.gen, args.temperature, args.seed,
                                     adapters=pool, registry=registry,
                                     watch=watch,
                                     metrics_json=args.metrics_json,
                                     metrics_interval=args.metrics_interval,
                                     flight=flight)
        tokens_learned = None
        if pool is not None:
            tokens_learned = [int(pool._steps[pool.user_slot[u]])
                              for u in users]
            for u in users:         # evict = gather + write-through persist
                pool.evict(u)

    out = {
        "arch": cfg.name, "plastic": bool(cfg.plastic_adapter),
        "batch": args.batch, "generated": int(toks.shape[1]),
        "decode_ms_p50": sorted(lats)[len(lats) // 2] * 1e3,
        "decode_ms_mean": sum(lats) / len(lats) * 1e3,
        "tokens_per_s": args.batch * len(lats) / sum(lats),
        "recompiles_after_warmup": watch.violations,
    }
    if watch.violations:
        out["recompile_signatures"] = watch.violation_signatures
    if store is not None:
        out["sessions"] = {
            "users": users, "resumed": store.restores,
            "created": store.creates,
            "tokens_learned": tokens_learned}
    if flight is not None:
        uid_by_slot = dict(enumerate(users)) if users else None
        incidents = flight.dump(args.flight_dir, uid_by_slot=uid_by_slot,
                                registry=registry, watchdog=watch)
        out["flight"] = {
            "dir": args.flight_dir, "steps_recorded": flight.pos,
            "flagged_slots": flight.flagged_slots(),
            "incidents": incidents}
    if args.metrics_json:
        registry.to_json(args.metrics_json)
        out["metrics_json"] = args.metrics_json
    if metrics_server is not None:
        out["metrics_port"] = metrics_server.server_address[1]
        metrics_server.shutdown()
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
