"""Host time per eviction in the session store's check-in, ms: the mean
length of the program's `pool.persist` spans (`SessionPool.evict`: the
finished stream's state copied to host memory) that end inside the window.
The warm-up's evictions, before it, are not counted."""
from bench.metrics.pack_host_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "pool.persist")
