"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
the seconds of every device op and the run's ``breakdown``.

Device work is the events of the ``XLA Ops`` line of each ``/device:TPU:<i>``
plane.  Busy time is the union of their intervals inside the traced window,
which is the benchmark's own ``bench.window`` span on the host; the idle
share is 1 − busy / window.  Each idle gap is labelled with the innermost
``bench.*`` host span that covers its middle, so a gap reads as what the
benchmark was doing around the program at the time (a solve, a wave, a
step of ``serve()``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the profiler's clock."""

    #: device index → [(start, end, op name)]
    device_ops: dict
    #: [(start, end, span name)] of the benchmark's host spans
    spans: list


def find_xplane(directory: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler.trace`` wrote."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {len(found)}")
    return found[0]


def op_name(text: str) -> str:
    """``%fusion`` of an event named by its whole HLO instruction,
    ``%fusion.3 = f32[...] fusion(...), ...``: the instruction's name
    without its number, so that the instances of one op add up."""
    name = text.split(" = ", 1)[0]
    base, dot, num = name.rpartition(".")
    return base if dot and num.isdigit() else name


def leaves(ops: list) -> list:
    """The events that hold no other event: a loop's own event spans its
    body's, and counting both would count that time twice."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] >= o[1] or nxt[1] > o[1]]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            ops = device_ops.setdefault(idx, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops, spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged [(start, end)] of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, lo: float, hi: float) -> list:
    """[(start, end)] inside [lo, hi] that ``busy`` does not cover."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label(t: float, spans: list) -> str:
    """Name of the innermost span covering ``t``, or "none"."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers of a traced run read, all of it within
    the ``bench.window`` span."""

    #: seconds in which some operation ran, the mean over the traced devices
    busy_s: float
    #: seconds of the window
    window_s: float
    #: 1 − busy / window, in [0, 1]
    idle_share: float
    #: {op name: seconds} of every leaf event, summed over the traced
    #: devices, most first
    op_seconds: dict
    #: the first ``top`` of ``op_seconds`` as [[op name, seconds]]: the
    #: result line's ``breakdown``
    device_ops: list
    #: [[span name, seconds]] of the idle gaps of every traced device,
    #: longest first, the first ``top``
    idle_gaps: list
    #: {``gw.*`` scope: seconds} of every leaf event, summed over the traced
    #: devices (`bench.xplane.scope_seconds`); None until read from the
    #: trace's file
    scope_seconds: dict | None = None


def window(trace: Trace) -> tuple:
    """(start, end) of the one ``bench.window`` span."""
    windows = [(s, e) for s, e, n in trace.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    return windows[0]


def reduce(trace: Trace, top: int = TOP) -> Reduced:
    lo, hi = window(trace)
    if not any(trace.device_ops.values()):
        raise ValueError("the trace holds no device operation")
    busy_ns, op_time, idle = [], defaultdict(float), []
    for ops in trace.device_ops.values():
        busy = union(ops, lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, name in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] += d
        idle.extend(gaps(busy, lo, hi))
    window_ns = hi - lo
    busy = sum(busy_ns) / len(busy_ns)
    ops = {n: t * 1e-9
           for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])}
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return Reduced(
        busy_s=busy * 1e-9, window_s=window_ns * 1e-9,
        idle_share=1.0 - busy / window_ns,
        op_seconds=ops,
        device_ops=[[n, t] for n, t in list(ops.items())[:top]],
        idle_gaps=[[label((s + e) / 2, trace.spans), (e - s) * 1e-9]
                   for s, e in longest])
