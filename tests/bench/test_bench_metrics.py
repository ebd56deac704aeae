"""Each per-layer metric's reader, on a synthetic trace: the number it
reads, and nothing (None) where the trace holds nothing for it.

A metric's synthetic run and the reading it pins live in
``tests/bench/pins/<name>.json``, found by the metric's full name: a trace
(``devices``: device plane -> ops as ``[name, start_ns, end_ns]``;
``spans``: host spans in the same form; ``window``; ``least_time_s``;
``calls``), or ``trace``: a trace file of that directory that the pin's own
keys override; ``want``, the reading; and ``why``, its arithmetic.  Adding
a metric adds its reader, its pin and its manifest entry; no edit here."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402

NAMES = [m["name"] for m in harness.load_manifest(ROOT)["per_layer"]]
PIN_KEYS = ("trace", "want", "why")


def pin_dir(root=ROOT):
    return os.path.join(root, "tests", "bench", "pins")


def unpinned(root=ROOT):
    """(per-layer metrics of `root`'s manifest without a pin, pins without
    a metric)."""
    names = {m["name"] for m in harness.load_manifest(root)["per_layer"]}
    pins = {f[:-len(".json")] for f in os.listdir(pin_dir(root))
            if f.endswith(".json")}
    return sorted(names - pins), sorted(pins - names)


def _events(rows):
    return sorted((Event(n, float(s), float(e)) for n, s, e in rows),
                  key=lambda e: e.start)


def pinned(name, root=ROOT):
    """The synthetic run of metric `name` and the reading pinned for it."""
    with open(os.path.join(pin_dir(root), name + ".json")) as f:
        pin = json.load(f)
    run = {}
    if "trace" in pin:
        with open(os.path.join(pin_dir(root), pin["trace"])) as f:
            run = json.load(f)
    run.update((k, v) for k, v in pin.items() if k not in PIN_KEYS)
    trace = Trace({plane: _events(ops)
                   for plane, ops in run["devices"].items()},
                  _events(run["spans"]))
    return (dict(run, trace=trace, window=tuple(map(float, run["window"]))),
            pin["want"])


def _reader(name):
    return harness.load_module(harness.reader_path(name, ROOT))


def test_every_per_layer_metric_has_a_pinned_reading():
    assert unpinned() == ([], [])


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_its_number(name):
    run, want = pinned(name)
    assert _reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_empty_trace(name):
    empty = {"trace": Trace({}, []), "window": (0.0, 1000.0),
             "least_time_s": 1e-9, "calls": 0}
    assert _reader(name).read(empty) is None
