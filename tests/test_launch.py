"""Launch-layer regression tests: specs/steps stay eval_shape-clean for the
FULL configs (no allocation), and the end-to-end drivers run at smoke scale.
The 512-device lowering itself is exercised by launch/dryrun.py (its own
process owns the XLA device-count flag)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro.launch import steps
from repro.launch.mesh import V5E, make_local_mesh, peaks
from repro.launch.specs import TRAIN_SETUP, input_specs
from repro.models.config import SHAPES
from repro.optim import adamw

LM_ARCHS = [a for a in ARCHS if a != "firefly-snn"]


def test_train_setup_covers_every_arch():
    for a in LM_ARCHS:
        assert a in TRAIN_SETUP, a
        mb = TRAIN_SETUP[a].get("microbatches", 1)
        assert SHAPES["train_4k"].global_batch % mb == 0


def test_hw_constants():
    hw = peaks(V5E)
    assert hw["peak_flops_bf16"] == 197e12
    assert hw["hbm_bw"] == 819e9
    assert hw["hbm_bytes"] == 16 * 2**30
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The env var wins and nothing else is set; unset, the fixed
    <repo>/.jax_cache."""
    import os

    from repro.launch import cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.enable_compile_cache()
        assert path == os.path.join(cache.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isfile(os.path.join(cache.REPO_ROOT, "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_local_mesh_is_auto_and_refuses_missing_devices():
    from jax.sharding import AxisType
    mesh = make_local_mesh()
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(data=len(jax.devices()) + 1)


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-moe-16b",
                                  "mamba2-1.3b", "zamba2-7b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_full_config_eval_shape(arch, shape):
    """FULL configs trace through the step functions abstractly on a
    1-device mesh — catches shape bugs without the 512-device compile."""
    mesh = make_local_mesh()
    with shd.use_mesh(mesh), mesh:
        spec = input_specs(arch, shape, mesh)
        assert spec["kind"] != "skip"
        if spec["kind"] == "train":
            opt = adamw(lr=1e-4)
            fn = steps.make_train_step(
                cfg=spec["cfg"], opt=opt,
                microbatches=spec["setup"].get("microbatches", 1))
        else:
            fn = steps.make_decode_step(spec["cfg"])
        out = jax.eval_shape(fn, *spec["args"])
        assert out is not None


def test_skip_cells_marked():
    mesh = make_local_mesh()
    with shd.use_mesh(mesh), mesh:
        spec = input_specs("qwen2-72b", "long_500k", mesh)
        assert spec["kind"] == "skip" and "quadratic" in spec["why"]
        spec2 = input_specs("mamba2-1.3b", "long_500k", mesh)
        assert spec2["kind"] == "decode"


@pytest.mark.slow
def test_train_driver_end_to_end(tmp_path):
    """The real CLI: 6 steps of a smoke model with checkpointing."""
    import os
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-4b",
         "--smoke", "--steps", "6", "--global-batch", "4",
         "--seq-len", "32", "--ckpt", str(tmp_path), "--save-every", "3"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"steps": 6' in r.stdout
