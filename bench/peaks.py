"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

The benchmark's own copy: the yardstick may not move with the program.

  "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" —
  197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s,
  1,600 Gbit/s of inter-chip interconnect per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "peak_ops_int8": 393e12,     # OP/s
        "hbm_bw": 819e9,             # B/s
        "hbm_bytes": 16 * 1024**3,   # B
        "ici_bw": 1600e9 / 8,        # B/s, all links of one chip
    },
}


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"sourced row to bench/peaks.py (known: {sorted(PEAKS)})"
        ) from None


def least_time_s(flops: float, nbytes: float, device_kind: str
                 ) -> tuple[float, str]:
    """The roofline's least time for `flops` operations moving `nbytes`
    bytes, and which of the two bounds it ("flops" or "bytes")."""
    p = peaks(device_kind)
    t_flops = flops / p["peak_flops_bf16"]
    t_bytes = nbytes / p["hbm_bw"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
