"""Reduce a profiler trace (xplane) to what the per-layer metrics read.

A trace holds, on one clock:

  * device operations: per device, the events of its "XLA Ops" line
    (TPU planes ``/device:TPU:<n>``), each named by the program ("XLA
    Modules" line) it ran in.  On a CPU-only trace, which the
    tests record, the XLA client's worker threads stand in for a device;
  * host spans: the host events named by the benchmark's ``bench.*``
    annotations and the program's ``pool.*`` and ``lm.*`` phases.

From these: busy time (the union of a device's op intervals inside a
window), device time per op name, idle gaps named by the innermost host
span they fall in, and a span's self time net of a child span.
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


class Event(NamedTuple):
    name: str
    start: float                        # ns
    end: float                          # ns


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]     # device plane -> its ops, by start
    spans: List[Event]                  # host spans, by start


SPAN_PREFIXES = ("bench.", "pool.", "lm.")
_CPU_NOISE = ("ThreadpoolListener", "SlinkyThreadPool", "end: ")


def start(trace_dir: str) -> None:
    """Start the profiler for a window: device ops and host annotations,
    with Python's function tracer off (it would slow every call)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under `trace_dir`."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(module: str, hlo: str) -> str:
    """A device op's short name: its module (the jitted program, hash
    dropped), its HLO instruction name, and ``[kernel]`` for a Mosaic
    kernel (a ``tpu_custom_call``)."""
    short = hlo.split(" = ", 1)[0].lstrip("%")
    kernel = " [kernel]" if "tpu_custom_call" in hlo else ""
    return f"{module.split('(', 1)[0]}:{short}{kernel}"


def _device_ops(plane) -> List[Event]:
    """The ops of one device plane, named by `op_name`."""
    lines = {line.name: sorted(_events(line), key=lambda e: e.start)
             for line in plane.lines}
    modules = lines.get("XLA Modules", [])
    starts = [m.start for m in modules]
    ops = []
    for o in lines.get("XLA Ops", []):
        k = bisect.bisect_right(starts, o.start) - 1
        module = modules[k].name if k >= 0 and o.start <= modules[k].end \
            else "?"
        ops.append(Event(op_name(module, o.name), o.start, o.end))
    return ops


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    """Read one xplane file into device ops and host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = _device_ops(plane)
            if ops:
                devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if "XLAPjRtCpuClient" in line.name or "XLAEigen" in line.name:
                    cpu_ops += [e for e in _events(line) if e.end > e.start
                                and not e.name.startswith(_CPU_NOISE)]
                else:
                    spans += [Event(e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns))
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIXES)]
    if not devices and cpu_ops:
        devices["/host:CPU"] = sorted(cpu_ops, key=lambda e: e.start)
    return Trace(devices, sorted(spans, key=lambda e: e.start))


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Iterable[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one op ran."""
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops),
                                       lo, hi))


def gaps(ops: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi]: where no op ran."""
    out, t = [], lo
    for s, e in union(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def spans_named(trace: Trace, name: str) -> List[Event]:
    return [s for s in trace.spans if s.name == name]


def count_spans(trace: Trace, name: str, lo: float, hi: float) -> int:
    """Host spans called `name` that end inside [lo, hi]."""
    return sum(1 for s in trace.spans if s.name == name and lo <= s.end <= hi)


def is_rollout_kernel(name: str) -> bool:
    """Whether a device op (named by `op_name`) is the fused fleet rollout
    kernel: the Mosaic kernel of a pool rollout program."""
    return name.startswith("jit__pool_rollout") and name.endswith("[kernel]")


def window(trace: Trace, name: str = "bench.window") -> Interval:
    """The measured window: the first host span called `name`."""
    for s in trace.spans:
        if s.name == name:
            return s.start, s.end
    raise ValueError(f"trace has no {name!r} span")


def name_points(trace: Trace, points: List[float]) -> List[str]:
    """For each time in `points`, the name of the shortest host span that
    covers it ("(none)" where none does).  One sweep over the spans."""
    order = sorted(range(len(points)), key=points.__getitem__)
    names = ["(none)"] * len(points)
    active: list = []                   # heap of (duration, end, name)
    i = 0
    for j in order:
        t = points[j]
        while i < len(trace.spans) and trace.spans[i].start <= t:
            s = trace.spans[i]
            heapq.heappush(active, (s.end - s.start, s.end, s.name))
            i += 1
        while active and active[0][1] < t:
            heapq.heappop(active)
        if active:
            names[j] = active[0][2]
    return names


def op_seconds(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per op name inside [lo, hi], mean over devices.

    An op's own time: where ops nest on a device's line (a `while` and
    the ops of its body), the outer op is charged only for the time no
    op inside it covers."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices.values():
        open_: list = []                # enclosing ops: (end, name)
        for o in sorted(ops, key=lambda e: (e.start, -e.end)):
            while open_ and open_[-1][0] <= o.start:
                open_.pop()
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                tot[o.name] += d * 1e-9
                if open_:
                    inner = min(o.end, open_[-1][0], hi) - max(o.start, lo)
                    tot[open_[-1][1]] -= max(inner, 0.0) * 1e-9
            open_.append((o.end, o.name))
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in tot.items()}


def idle_by_span(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Idle device seconds inside [lo, hi], mean over devices, each gap
    named by the innermost host span covering its midpoint."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices.values():
        idle = gaps(ops, lo, hi)
        names = name_points(trace, [0.5 * (s + e) for s, e in idle])
        for (s, e), name in zip(idle, names):
            tot[name] += (e - s) * 1e-9
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in tot.items()}


def busy_seconds(trace: Trace, lo: float, hi: float,
                 prefix: str = "") -> float:
    """Busy device seconds inside [lo, hi], mean over devices; with
    `prefix`, only of the ops whose name starts with it (one program's)."""
    if not trace.devices:
        return 0.0
    return sum(busy_ns([o for o in ops if o.name.startswith(prefix)], lo, hi)
               for ops in trace.devices.values()) * 1e-9 / len(trace.devices)


def self_ms(trace: Trace, outer: str, inner: str) -> Optional[float]:
    """Mean over `outer` spans of their duration less that of the `inner`
    spans that start inside them, in ms; None where there is no `outer`
    span.  Spans of one thread nest, so an inner span that starts inside
    an outer one ends inside it too."""
    outs = spans_named(trace, outer)
    if not outs:
        return None
    starts = [o.start for o in outs]
    total = sum(o.end - o.start for o in outs)
    for s in spans_named(trace, inner):
        k = bisect.bisect_right(starts, s.start) - 1
        if k >= 0 and s.start < outs[k].end:
            total -= min(s.end, outs[k].end) - s.start
    return total / len(outs) * 1e-6


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
