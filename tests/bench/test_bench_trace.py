"""The trace reduction (bench/trace.py): on synthetic events, and on a
trace recorded here on the CPU with a gap inside a named span."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402


def _trace():
    ops = [Event("a", 10, 20), Event("b", 15, 30), Event("a", 50, 60)]
    spans = sorted([Event("bench.window", 0, 100), Event("bench.call", 5, 45),
                    Event("pool.rollout", 8, 12), Event("bench.call", 48, 95),
                    Event("pool.rollout", 49, 51)], key=lambda e: e.start)
    return Trace({"/device:TPU:0": ops}, spans)


def test_union_busy_and_gaps():
    tr = _trace()
    ops = tr.devices["/device:TPU:0"]
    assert trace.union([(o.start, o.end) for o in ops], 0, 100) == \
        [(10, 30), (50, 60)]
    assert trace.busy_ns(ops, 0, 100) == 30
    assert trace.busy_ns(ops, 12, 55) == 23
    assert trace.gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert trace.busy_seconds(tr, 0, 100) == pytest.approx(30e-9)


def test_op_seconds_and_idle_named_by_innermost_span():
    tr = _trace()
    # b starts inside a's first run, which keeps only the 5 ns b leaves
    assert trace.op_seconds(tr, 0, 100) == {"a": pytest.approx(15e-9),
                                            "b": pytest.approx(15e-9)}
    idle = trace.idle_by_span(tr, 0, 100)
    # (0,10) mid 5 -> bench.call; (30,50) mid 40 -> bench.call;
    # (60,100) mid 80 -> bench.call
    assert idle == {"bench.call": pytest.approx(70e-9)}
    assert trace.name_points(tr, [2.0, 10.0, 96.0, 200.0]) == \
        ["bench.window", "pool.rollout", "bench.window", "(none)"]


def test_op_seconds_charges_a_nested_op_only_its_own_time():
    ops = [Event("m:while.1", 10, 150), Event("m:fusion.2", 20, 100),
           Event("m:fusion.3", 100, 140), Event("m:copy", 200, 210)]
    tr = Trace({"/device:TPU:0": ops}, [])
    got = trace.op_seconds(tr, 0, 1000)
    assert got == {"m:while.1": pytest.approx(20e-9),
                   "m:fusion.2": pytest.approx(80e-9),
                   "m:fusion.3": pytest.approx(40e-9),
                   "m:copy": pytest.approx(10e-9)}
    assert sum(got.values()) == pytest.approx(
        trace.busy_seconds(tr, 0, 1000))
    assert trace.busy_seconds(tr, 0, 1000, prefix="m:fusion") == \
        pytest.approx(120e-9)


def test_self_ms_subtracts_the_child_span():
    tr = _trace()
    # calls of 40 and 47 ns less rollouts of 4 and 2 ns: mean 40.5 ns
    assert trace.self_ms(tr, "bench.call", "pool.rollout") == \
        pytest.approx(40.5e-6)
    assert trace.self_ms(tr, "lm.decode_step", "pool.rollout") is None
    assert trace.count_spans(tr, "bench.call", 0, 100) == 2
    assert trace.window(tr) == (0, 100)
    assert trace.top({"x": 1.0, "y": 3.0, "z": 2.0}, 2) == \
        [["y", 3.0], ["z", 2.0]]


def test_recorded_cpu_trace_with_a_gap_inside_a_named_span(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.call"):
            f(x).block_until_ready()
        with TraceAnnotation("bench.gap"):
            time.sleep(0.05)
        with TraceAnnotation("bench.call"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    assert tr.devices, "no device (or CPU stand-in) ops in the trace"
    names = {s.name for s in tr.spans}
    assert {"bench.window", "bench.call", "bench.gap"} <= names
    lo, hi = trace.window(tr)
    assert hi - lo >= 0.05e9
    busy = trace.busy_seconds(tr, lo, hi)
    assert 0 < busy < (hi - lo) * 1e-9
    idle = trace.idle_by_span(tr, lo, hi)
    assert idle["bench.gap"] == pytest.approx(0.05, rel=0.5)
    assert trace.count_spans(tr, "bench.call", lo, hi) == 2


def test_op_names_and_the_rollout_kernel():
    hlo = ('%_pool_rollout.1 = (f32[8,1024,8]) custom-call(f32[8,1024,16] '
           '%copy.10), custom_call_target="tpu_custom_call"')
    name = trace.op_name("jit__pool_rollout(9157543988504358124)", hlo)
    assert name == "jit__pool_rollout:_pool_rollout.1 [kernel]"
    assert trace.is_rollout_kernel(name)
    other = trace.op_name("jit_dynamic_slice(35)",
                          "%copy.1 = f32[8,8] copy(f32[8,8] %x)")
    assert other == "jit_dynamic_slice:copy.1"
    assert not trace.is_rollout_kernel(other)
