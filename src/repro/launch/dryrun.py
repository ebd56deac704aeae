import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers AND
compiles under the production meshes, and extract the roofline inputs.

The two lines above run before any other import — jax locks the device
count at first init, and the dry-run needs 512 placeholder CPU devices so
jax.make_mesh can build (16,16) and (2,16,16).

Per cell this driver:
  1. builds abstract inputs (specs.input_specs — ShapeDtypeStructs, no
     allocation),
  2. jit(...).lower(...).compile() under the mesh,
  3. records compiled.memory_analysis() (the fits-in-HBM proof),
     compiled.cost_analysis() (XLA's own counters, loop bodies counted
     once — kept for reference), and hlo_analysis.analyze() (trip-count-
     attributed FLOPs / bytes / per-kind collective wire bytes: the
     numbers §Roofline uses),
  4. writes benchmarks/results/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    python -m repro.launch.dryrun --all --mesh single --jobs 8
    python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import json
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

LM_ARCHS = [
    "qwen2-72b", "internlm2-20b", "qwen3-4b", "qwen1.5-32b",
    "zamba2-7b", "deepseek-moe-16b", "grok-1-314b",
    "musicgen-medium", "pixtral-12b", "mamba2-1.3b",
]
SHAPE_NAMES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plastic: bool = False, fsdp: bool = True,
             save: bool = True, overrides: dict | None = None) -> dict:
    import jax

    from repro.distributed import sharding as shd
    from repro.launch import hlo_analysis, steps
    from repro.launch.mesh import V5E, make_production_mesh, peaks
    from repro.launch.specs import input_specs
    from repro.optim import adamw, warmup_cosine

    mesh_kind = "multi" if multi_pod else "single"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()

    if overrides and "fsdp" in overrides:
        fsdp = overrides.pop("fsdp")
    cfg_overrides = {}
    if overrides:
        from repro.configs import get_config as _gc
        probe = _gc(arch)
        cfg_overrides = {k: v for k, v in overrides.items()
                         if hasattr(probe, k)}
    with shd.use_mesh(mesh), mesh:
        spec = input_specs(arch, shape_name, mesh, plastic=plastic,
                           fsdp=fsdp, cfg_overrides=cfg_overrides)
        cfg = spec["cfg"]
        if overrides:
            spec["setup"].update({k: v for k, v in overrides.items()
                                  if not hasattr(cfg, k)})
        out = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "chips": int(n_chips), "plastic": plastic,
            "kind": spec["kind"], "setup": spec.get("setup", {}),
        }
        if spec["kind"] == "skip":
            out["skipped"] = spec["why"]
            if save:
                _save(out, mesh_kind, arch, shape_name, plastic)
            return out

        setup = spec["setup"]
        if spec["kind"] == "train":
            opt = adamw(lr=warmup_cosine(3e-4, 100, 10_000),
                        moment_dtype=setup.get("moment_dtype", "float32"))
            fn = steps.make_train_step(
                cfg, opt, microbatches=setup.get("microbatches", 1),
                accum_dtype=setup.get("accum_dtype", "float32"),
                remat_policy=setup.get("remat_policy", "nothing"))
            donate = (0, 1)
        elif spec["kind"] == "prefill":
            fn = steps.make_prefill(cfg, spec["shape"].seq_len)
            donate = ()
        else:
            fn = steps.make_decode_step(cfg)
            donate = (1,)

        lowered = jax.jit(fn, donate_argnums=donate).lower(*spec["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_rec = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_rec[attr] = int(v)
        # arguments are donated (params/opt/cache buffers are reused), so
        # live bytes per device = args + temps (outputs alias args)
        live = (mem_rec.get("argument_size_in_bytes", 0)
                + mem_rec.get("temp_size_in_bytes", 0))
        mem_rec["live_bytes_per_device"] = live
        est = estimate_tpu_memory(spec, mesh)
        mem_rec.update(est)
        hw = peaks(V5E)
        mem_rec["hbm_frac"] = est["tpu_live_bytes"] / hw["hbm_bytes"]
        mem_rec["hbm_frac_cpu_compiled"] = live / hw["hbm_bytes"]
        print(f"[{mesh_kind}] {arch} x {shape_name}: "
              f"tpu-est {est['tpu_live_bytes']/2**30:.2f} GiB/chip "
              f"({100*mem_rec['hbm_frac']:.0f}% of HBM); "
              f"cpu-compiled live {live/2**30:.2f} GiB/chip")
        print(mem)

        cost = compiled.cost_analysis()
        cost_rec = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float))
                    and k in ("flops", "bytes accessed", "transcendentals")}
        print({k: f"{v:.3e}" for k, v in cost_rec.items()})

        hlo = hlo_analysis.analyze(compiled.as_text())
        out.update({
            "lower_s": t_lower, "compile_s": t_compile,
            "memory": mem_rec, "cost_analysis": cost_rec, "hlo": hlo,
            "model_flops": steps.model_flops(
                cfg, spec["kind"], spec["shape"].global_batch,
                spec["shape"].seq_len),
            "n_params": _n_params(cfg),
            "n_active_params": steps.n_active_params(cfg),
        })
        terms = hlo_analysis.roofline_terms(hlo, hw)
        out["roofline"] = terms
        print({k: (f"{v:.3e}" if isinstance(v, float) else v)
               for k, v in terms.items()})

    if save:
        _save(out, mesh_kind, arch, shape_name, plastic)
    return out


def _n_params(cfg) -> int:
    from repro.models import transformer as T
    return T.n_params(cfg)


def _tree_device_bytes(tree) -> int:
    """Exact per-device bytes of a ShapeDtypeStruct pytree with shardings."""
    import jax
    import math
    total = 0
    for l in jax.tree.leaves(tree):
        shape = l.shape
        if getattr(l, "sharding", None) is not None:
            shape = l.sharding.shard_shape(l.shape)
        total += math.prod(shape, start=1) * l.dtype.itemsize
    return total


def estimate_tpu_memory(spec, mesh) -> dict:
    """Analytic TPU-native live-bytes estimate per device.

    XLA:CPU's float-normalization pass legalizes bf16 dots by materializing
    fp32 copies of their operands (including multi-GiB KV caches), which
    inflates compiled ``memory_analysis`` temps ~2-3x relative to a TPU
    compilation where bf16 is native.  This estimate is the TPU-side
    number: exact sharded argument/output bytes + an activation/workspace
    model (documented in EXPERIMENTS.md §Dry-run).
    """
    cfg, kind, setup = spec["cfg"], spec["kind"], spec.get("setup", {})
    args_b = sum(_tree_device_bytes(a) for a in spec["args"])
    act_b = 0
    ws_b = 256 * 2**20        # flat transient allowance (tiles, psums)
    data_ax = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    model_ax = mesh.shape.get("model", 1)
    if kind == "train":
        sh = spec["shape"]
        mb = setup.get("microbatches", 1)
        b_loc = max(sh.global_batch // (data_ax * mb), 1)
        seq_div = model_ax if cfg.act_shard == "sp" else 1
        # saved residual per remat'd block + one block's live working set
        act_b = (cfg.n_layers * b_loc * sh.seq_len * cfg.d_model * 2
                 // seq_div)
        # fp32 grad-accumulator tree (params-shaped, 2D-sharded)
        accum_itemsize = 2 if setup.get("accum_dtype") == "bfloat16" else 4
        act_b += _n_params(cfg) * accum_itemsize // (data_ax * model_ax)
    elif kind == "prefill":
        from repro.models import transformer as T
        from repro.models.layers import abstract_from_plan
        cache_abs = abstract_from_plan(
            T.cache_plan(cfg, spec["shape"].global_batch,
                         spec["shape"].seq_len), mesh)
        act_b = _tree_device_bytes(cache_abs)
    # double-buffered fsdp gather working set: one layer's weights, still
    # tensor-sharded over the model axis after the data-axis gather
    ws_b += 2 * (_n_params(cfg) // max(cfg.n_layers, 1)) * 2 // model_ax
    return {"args_bytes": args_b, "activation_bytes": act_b,
            "workspace_bytes": ws_b,
            "tpu_live_bytes": args_b + act_b + ws_b}


def _save(out: dict, mesh_kind: str, arch: str, shape_name: str,
          plastic: bool) -> None:
    d = os.path.join(RESULTS_DIR, mesh_kind)
    os.makedirs(d, exist_ok=True)
    suffix = "__plastic" if plastic else ""
    path = os.path.join(d, f"{arch}__{shape_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def _cell_entry(job):
    """Subprocess entry (one fresh jax per cell keeps compiles independent)."""
    arch, shape_name, multi_pod, force = job
    mesh_kind = "multi" if multi_pod else "single"
    path = os.path.join(RESULTS_DIR, mesh_kind, f"{arch}__{shape_name}.json")
    if os.path.exists(path) and not force:
        return (arch, shape_name, mesh_kind, "cached")
    try:
        run_cell(arch, shape_name, multi_pod)
        return (arch, shape_name, mesh_kind, "ok")
    except Exception:
        err = traceback.format_exc()
        _save({"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "error": err.splitlines()[-1], "traceback": err},
              mesh_kind, arch, shape_name, False)
        return (arch, shape_name, mesh_kind, "FAIL: " + err.splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--plastic", action="store_true",
                    help="enable the FireFly-P plastic adapter (serve cells)")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    multi = args.mesh == "multi"
    if not args.all:
        assert args.arch and args.shape, "--arch/--shape or --all"
        run_cell(args.arch, args.shape, multi, plastic=args.plastic)
        return 0

    jobs = [(a, s, multi, args.force) for a in LM_ARCHS for s in SHAPE_NAMES]
    if args.jobs <= 1:
        results = [_cell_entry(j) for j in jobs]
    else:
        import multiprocessing as mp
        ctx = mp.get_context("spawn")
        with ctx.Pool(args.jobs) as pool:
            results = pool.map(_cell_entry, jobs)
    bad = [r for r in results if r[3].startswith("FAIL")]
    for r in results:
        print(r)
    print(f"{len(results) - len(bad)}/{len(results)} cells ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
