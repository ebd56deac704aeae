"""Host time per call in the scheduler's pack, ms: the mean length of the
program's `pool.pack` spans (`FleetScheduler`: the drives gathered into
slot order and the call's window built) that end inside the window.

`mean_span_ms` is the helper of every reader of a span's mean length."""
from bench import trace


def mean_span_ms(run, name):
    """Mean length, in ms, of the host spans called `name` that end inside
    the window; None where none does."""
    lo, hi = run["window"]
    spans = [s for s in trace.spans_named(run["trace"], name)
             if lo <= s.end <= hi]
    if not spans:
        return None
    return 1e-6 * sum(s.end - s.start for s in spans) / len(spans)


def read(run):
    return mean_span_ms(run, "pool.pack")
