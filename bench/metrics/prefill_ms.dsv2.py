"""Device time per admission of the prompt's prefill, ms: the time in the
traced window in which an op of the pool's prefill program (`LMScheduler`'s
jitted `_prefill_session`, the plain form of latent attention over the
prompt) ran, over the `bench.admit` spans that end there."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    device_s = trace.busy_seconds(tr, lo, hi, prefix="jit__prefill_session:")
    admits = trace.count_spans(tr, "bench.admit", lo, hi)
    if device_s <= 0 or admits == 0:
        return None
    return 1e3 * device_s / admits
