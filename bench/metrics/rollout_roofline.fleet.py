"""The fused rollout kernel's share of its roofline, in %: the least time
of one call (`bench/work.py` over `bench/peaks.py`; bytes bind at these
shapes) over the kernel's device time per call in the trace."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    kernel_s = sum(s for name, s in trace.op_seconds(tr, lo, hi).items()
                   if trace.is_rollout_kernel(name))
    calls = trace.count_spans(tr, "bench.call", lo, hi)
    if kernel_s <= 0 or calls == 0 or run["least_time_s"] is None:
        return None
    return 100.0 * run["least_time_s"] / (kernel_s / calls)
