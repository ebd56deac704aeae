"""Host time per call in the scheduler, ms: the benchmark's call span (the
client's `pool_step` and the read-back of every action window) less the
program's `pool.rollout` span (the dispatch of the pool program).  What is
left is the per-session packing of drives, the per-session slicing of the
outputs and the wait for the read-back."""
from bench import trace


def read(run):
    return trace.self_ms(run["trace"], "bench.call", "pool.rollout")
