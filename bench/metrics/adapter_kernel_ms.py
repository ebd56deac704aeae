"""Device time per decode step of the plastic adapter's kernel, ms: the own
time inside the window of the `plasticity_fleet_step` Mosaic kernel in the
pool's decode program (`jit__pool_step`), over the `bench.step` spans that
end there.  The kernel's launches from another program are not counted."""
from bench import trace


def _is_adapter_kernel(name):
    return (name.startswith("jit__pool_step:plasticity_fleet_step")
            and name.endswith("[kernel]"))


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    kernel_s = sum(s for name, s in trace.op_seconds(tr, lo, hi).items()
                   if _is_adapter_kernel(name))
    steps = trace.count_spans(tr, "bench.step", lo, hi)
    if kernel_s <= 0 or steps == 0:
        return None
    return 1e3 * kernel_s / steps
