"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Each case lowers and compiles one kernel for a described (not attached)
``v5e:2x2`` topology with the TPU compiler, from shapes alone, and checks
that the Mosaic kernel is in the program (``tpu_custom_call``).  This is
what interpret mode cannot show: block shapes off the (8, 128) tiling,
contractions Mosaic does not lower, casts and MXU operand types the chip
lacks.  Nothing runs, so results and times are out of scope here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.plasticity import fused, kernel
from repro.kernels.plasticity import quant as Q

QC = Q.QuantConfig()
F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _fleet_step(b, n, m, quant, telemetry=False):
    shapes = [((b, n), I32 if quant else F32), ((b, n, m), I8 if quant else F32)]
    if quant:
        shapes.append(((b,), F32))                       # per-slot scale
    shapes += [((4, n, m), F32), ((b, m), I32 if quant else F32),
               ((b, n), I32 if quant else F32), ((b, m), I32 if quant else F32),
               ((b,), F32)]                              # active
    if quant:
        shapes.append(((b,), I32))                       # per-session seed

        def fn(x, w, sc, th, v, tp, tq, a, sd):
            return kernel.dual_engine_fleet_step_q_pallas(
                x, w, sc, th, v, tp, tq, qcfg=QC, seed=sd, active=a,
                telemetry=telemetry)
    else:
        def fn(x, w, th, v, tp, tq, a):
            return kernel.dual_engine_fleet_step_pallas(
                x, w, th, v, tp, tq, active=a, telemetry=telemetry)
    return fn, shapes


def _shared_step(b, n, m):
    def fn(x, w, th, v, tp, tq):
        return kernel.dual_engine_step_pallas(x, w, th, v, tp, tq)
    return fn, [((b, n), F32), ((n, m), F32), ((4, n, m), F32),
                ((b, m), F32), ((b, n), F32), ((b, m), F32)]


def _fused_fleet(b, sizes, k, quant, telemetry=False):
    n_layers = len(sizes) - 1
    s_dt, w_dt = (I32, I8) if quant else (F32, F32)

    def fn(drives, active, *flat):
        ws = flat[:n_layers]
        ths = flat[n_layers:2 * n_layers]
        vs = flat[2 * n_layers:3 * n_layers]
        trs = flat[3 * n_layers:4 * n_layers + 1]
        rest = flat[4 * n_layers + 1:]
        return fused.rollout_pallas(
            drives, ws, ths, vs, trs,
            spiking=(True,) * (n_layers - 1) + (False,),
            plastic=(True,) * n_layers, qcfg=QC if quant else None,
            scales=rest[:n_layers] if quant else None,
            seed=rest[n_layers] if quant else None, active=active,
            telemetry=telemetry, block_b=8)
    shapes = [((k, b, sizes[0]), s_dt), ((b,), F32)]
    shapes += [((b, sizes[i], sizes[i + 1]), w_dt) for i in range(n_layers)]
    shapes += [((4, sizes[i], sizes[i + 1]), F32) for i in range(n_layers)]
    shapes += [((b, sizes[i + 1]), s_dt) for i in range(n_layers)]
    shapes += [((b, s), s_dt) for s in sizes]
    if quant:
        shapes += [((b,), F32)] * n_layers + [((b,), I32)]
    return fn, shapes


CASES = {
    "fleet-step-f32-B1024-16x128": lambda: _fleet_step(1024, 16, 128, False),
    "fleet-step-int8-B1024-16x128": lambda: _fleet_step(1024, 16, 128, True),
    "adapter-step-f32-B8-128x128": lambda: _fleet_step(8, 128, 128, False),
    "adapter-step-int8-B8-128x128": lambda: _fleet_step(8, 128, 128, True),
    "fused-fleet-f32-16-128-8-K8": lambda: _fused_fleet(
        1024, (16, 128, 8), 8, False),
    "fused-fleet-int8-16-128-8-K8": lambda: _fused_fleet(
        1024, (16, 128, 8), 8, True),
    "fleet-step-int8-telemetry-B1024-16x128": lambda: _fleet_step(
        1024, 16, 128, True, telemetry=True),
    "fused-fleet-f32-telemetry-16-128-8-K8": lambda: _fused_fleet(
        1024, (16, 128, 8), 8, False, telemetry=True),
    "shared-step-f32-784x1024": lambda: _shared_step(8, 784, 1024),
}


def _kernel_name(case):
    """The stable name the case's `pallas_call` gives its kernel: the HLO
    instruction's name, and so the op's name in a chip trace."""
    if case.startswith("fused-"):
        return "plasticity_fused_rollout"
    if case.startswith("shared-"):
        return "plasticity_step"
    return "plasticity_fleet_step" + ("_q" if "-int8-" in case else "")


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{_kernel_name(case)}." in text
