"""From a profiler trace (``*.xplane.pb``) to numbers, with JAX alone.

``jax.profiler.ProfileData`` gives planes, their lines and events with a
start and a duration in nanoseconds. On a TPU the plane of a chip is
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation, named by the operation's text (``%name = type
op(...)``); the host's plane ``/host:CPU`` holds one line per thread
with the program's ``TraceAnnotation`` spans on it (``tpunet/...``).

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices; the idle share is one minus busy
over the traced window. A gap is a stretch of the window with no
operation running; each long gap is attributed to the host span that
covers most (at least half) of it, else to ``host:other``. Checked on a small recorded trace in
``tests/benchmark``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    """Events in seconds. ``device_ops``: per device a list of
    ``(name, start, duration)``; ``host_spans``: ``(name, start,
    duration)`` of every host event whose name starts with one of the
    wanted prefixes."""

    device_ops: dict = field(default_factory=dict)
    host_spans: list = field(default_factory=list)


def short_name(op_text: str) -> str:
    """``%fusion.1 = bf16[..] fusion(...)`` -> ``fusion.1``."""
    return op_text.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str, host_prefixes=("tpunet/", "train")) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = trace.device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend((ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9) for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        trace.host_spans.append(
                            (ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9))
    return trace


def merge_intervals(intervals):
    """Sorted disjoint ``[(start, end)]`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(ops) -> float:
    return sum(e - s for s, e in merge_intervals(
        (s, s + d) for _, s, d in ops))


def window(trace: Trace):
    """(start, end) of the traced device activity over all devices."""
    starts = [s for ops in trace.device_ops.values() for _, s, _ in ops]
    ends = [s + d for ops in trace.device_ops.values() for _, s, d in ops]
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def busy_and_window(trace: Trace, window_s: float | None = None):
    """``(busy_s, window_s)``: busy averaged over the devices; the
    window is the caller's traced seconds, else first op to last."""
    if not trace.device_ops:
        return 0.0, float(window_s or 0.0)
    busy = sum(busy_seconds(ops) for ops in trace.device_ops.values()) \
        / len(trace.device_ops)
    if window_s is None:
        w0, w1 = window(trace)
        window_s = w1 - w0
    return busy, float(window_s)


def idle_pct(trace: Trace, window_s: float | None = None):
    busy, win = busy_and_window(trace, window_s)
    if win <= 0 or busy <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - busy / win)


def pattern_share_pct(trace: Trace, patterns) -> float | None:
    """Share of the devices' operation time in operations whose text
    matches one of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    hit = total = 0.0
    for ops in trace.device_ops.values():
        for name, _, d in ops:
            total += d
            if any(r.search(name) for r in rx):
                hit += d
    return 100.0 * hit / total if total > 0 else None


def span_durations(trace: Trace, name: str):
    return [d for n, _, d in trace.host_spans if n == name]


def top_ops(trace: Trace, n: int = 10):
    """``[[short name, seconds]]``: operations summed by name over the
    devices, the largest first."""
    total: dict = {}
    for ops in trace.device_ops.values():
        for name, _, d in ops:
            key = short_name(name)
            total[key] = total.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 5):
    """``[[covering host span, seconds]]`` of the ``n`` longest gaps
    between operations on the first device: the host span that overlaps
    most of the gap, or ``host:other``."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    merged = merge_intervals((s, s + d) for _, s, d in ops)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)[:n]
    out = []
    for length, g0, g1 in gaps:
        best, cover = "host:other", 0.5 * length    # has to cover half of it
        for name, s, d in trace.host_spans:
            ov = min(g1, s + d) - max(g0, s)
            if ov > cover:
                best, cover = name, ov
        out.append([best, length])
    return out


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace, 10), "idle_gaps": idle_gaps(trace, 5)}
