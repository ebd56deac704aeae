"""The whole decode step's share of the chip's peak, in %: the least time
of one step's work (`bench/work.py` over `bench/peaks.py`: the weights and
the cached keys and values read once, which bind) times the steps
completed in the traced window, over the window's length."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    steps = trace.count_spans(tr, "bench.step", lo, hi)
    if steps == 0 or run["least_time_s"] is None:
        return None
    return 100.0 * run["least_time_s"] * steps / ((hi - lo) * 1e-9)
