"""Device time per decode-step program, ms: the time in the traced window
in which an op of the pool's decode program (`LMScheduler`'s jitted
`_pool_step`) ran, over the steps completed there."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    device_s = trace.busy_seconds(tr, lo, hi, prefix="jit__pool_step:")
    steps = trace.count_spans(tr, "bench.step", lo, hi)
    if device_s <= 0 or steps == 0:
        return None
    return 1e3 * device_s / steps
