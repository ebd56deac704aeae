"""Host time per call in the scheduler's unpack, ms: the mean length of the
program's `pool.unpack` spans (`FleetScheduler._unpack`: the one launch that
splits the call's outputs per slot, and the per-uid dict) that end inside
the window."""
from bench.metrics.pack_host_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "pool.unpack")
