"""Share of the measured window, in %, in which no operation ran on the
device (mean over the chips used)."""
from bench import trace


def read(run):
    tr = run["trace"]
    if not tr.devices:
        return None
    lo, hi = run["window"]
    return 100.0 * (1.0 - trace.busy_seconds(tr, lo, hi) / ((hi - lo) * 1e-9))
