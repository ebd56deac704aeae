"""Device meshes and published chip peaks (functions and tables, not module
state, so the import never touches jax device state).

  single-pod:  (16, 16)      axes (data, model)   — 256 chips (one v5e pod)
  multi-pod:   (2, 16, 16)   axes (pod, data, model) — 512 chips

Model code names only LOGICAL axes ("data"/"model"/"seq");
distributed/sharding.py maps "data" to ("pod","data") when a pod axis
exists, so the same program lowers on either mesh unchanged.  Every mesh is
built with Auto axes: the model code places arrays with
`with_sharding_constraint` and lets GSPMD propagate, which Explicit axes
(jax.make_mesh's default since jax 0.8) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first ``data * model`` local devices.

    Raises when more devices are asked for than exist."""
    n = len(jax.devices())
    if data < 1 or model < 1 or data * model > n:
        raise ValueError(f"make_local_mesh(data={data}, model={model}) needs "
                         f"{data * model} devices; {n} visible")
    return _auto_mesh((data, model), ("data", "model"))


# Published per-chip peaks, keyed by `jax.Device.device_kind`.
#   "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" —
#   197 TFLOP/s bf16, 16 GiB HBM2 at 819 GB/s, 1,600 Gbit/s of
#   inter-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "hbm_bytes": 16 * 1024**3,   # B
        "ici_bw": 1600e9 / 8,        # B/s, all links of one chip
    },
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"sourced row to launch.mesh.PEAKS (known: {sorted(PEAKS)})"
        ) from None
