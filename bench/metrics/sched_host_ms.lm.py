"""Host time per decode step in the scheduler, ms: the device's idle time
inside the benchmark's `bench.step` spans (`LMScheduler.step`: the
dispatch, the read-back of the step's tokens and their per-stream dict),
over the steps.  The span itself also holds the wait for the device, so
its length is not this number."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    steps = trace.count_spans(tr, "bench.step", lo, hi)
    if not tr.devices or steps == 0:
        return None
    idle = trace.idle_by_span(tr, lo, hi)
    return 1e3 * (idle.get("bench.step", 0.0)
                  + idle.get("lm.decode_step", 0.0)) / steps
