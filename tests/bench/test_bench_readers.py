"""The readers of a span's mean length and of the adapter's kernel: only
what ends inside the measured window counts."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402

SPAN_READERS = [("pack_host_ms", "pool.pack"),
                ("unpack_host_ms", "pool.unpack"),
                ("evict_persist_ms", "pool.persist")]


def _read(metric, ops, spans):
    tr = Trace({"/device:TPU:0": sorted(ops, key=lambda e: e.start)},
               sorted(spans, key=lambda e: e.start))
    reader = harness.load_module(harness.reader_path(metric, ROOT))
    return reader.read({"trace": tr, "window": (1000.0, 2000.0)})


@pytest.mark.parametrize("metric,span", SPAN_READERS)
def test_span_reader_counts_only_spans_that_end_in_the_window(metric, span):
    spans = [Event(span, 100, 900),         # before: a warm-up's
             Event(span, 990, 1010),        # ends inside, starts before
             Event(span, 1500, 1530),
             Event(span, 1990, 2500),       # ends after
             Event("pool.other", 1200, 1800)]
    assert _read(metric, [], spans) == pytest.approx(25e-6)


@pytest.mark.parametrize("metric,span", SPAN_READERS)
def test_span_reader_reads_none_in_a_window_without_the_span(metric, span):
    spans = [Event(span, 100, 900), Event(span, 1990, 2500),
             Event("pool.other", 1200, 1800)]
    assert _read(metric, [], spans) is None


def test_adapter_kernel_counts_its_own_time_in_the_window_per_step():
    kernel = "jit__pool_step:plasticity_fleet_step.1 [kernel]"
    ops = [Event(kernel, 500, 600),                     # before the window
           Event("jit__pool_step:while.1", 1100, 1300),
           Event(kernel, 1200, 1210),                   # inside the while
           Event(kernel, 1995, 2005),                   # half inside
           Event("jit__pool_step:other_kernel.1 [kernel]", 1400, 1450),
           Event("jit__pool_window:plasticity_fleet_step.1 [kernel]",
                 1500, 1550)]
    steps = [Event("bench.step", 1050, 1400), Event("bench.step", 1900, 1999),
             Event("bench.step", 1990, 2100)]           # ends after
    assert _read("adapter_kernel_ms.lm", ops, steps) == pytest.approx(7.5e-6)
    assert _read("adapter_kernel_ms.lm", ops[:1] + ops[4:], steps) is None
    assert _read("adapter_kernel_ms.lm", ops, steps[2:]) is None
