"""The whole pool step's share of the chip's peak, in %: the least time of
one `pool_step` call's work (`bench/work.py` over `bench/peaks.py`) times
the calls completed in the traced window, over the window's length."""
from bench import trace


def read(run):
    tr = run["trace"]
    lo, hi = run["window"]
    calls = trace.count_spans(tr, "bench.call", lo, hi)
    if calls == 0 or run["least_time_s"] is None:
        return None
    return 100.0 * run["least_time_s"] * calls / ((hi - lo) * 1e-9)
