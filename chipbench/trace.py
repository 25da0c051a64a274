"""From a profiler trace to device intervals, and from those to numbers.

A trace is reduced to plain data first: for each TPU, the operations
that ran on it as ``(instruction text, start_ns, end_ns)`` (loops and
conditionals left out, since their intervals cover their bodies' own
operations), and the benchmark's own host spans (``jax.profiler.TraceAnnotation``) on the same clock.  Every
reduction below works on that data, so tests can hand it small
synthesized traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Op = Tuple[str, float, float]  # (name, start_ns, end_ns)

HOST_SPANS = ("call", "window")
# operations that contain others: their intervals cover their bodies' ops
CONTROL_FLOW = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """The HLO instruction's name (``%fused_round.12 = ...`` -> ``fused_round.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """The HLO opcode of a trace event's instruction text, or ``""``."""
    rest = text.split(" = ", 1)[-1]
    m = _OPCODE.search(rest)
    return m.group(1) if m else ""


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]      # device plane -> its operations
    spans: List[Op]                   # the benchmark's host spans
    window: Interval                  # what the benchmark traced, in ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(logdir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    prof = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Op]] = {}
    spans: List[Op] = []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events
                               if opcode(e.name) not in CONTROL_FLOW)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in HOST_SPANS)
    windows = [s for s in spans if s[0] == "window"]
    if windows:
        window = (windows[0][1], windows[0][2])
    else:
        calls = [s for s in spans if s[0] == "call"]
        window = (min(s[1] for s in calls), max(s[2] for s in calls))
    return Trace(devices, [s for s in spans if s[0] != "window"], window)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def busy(ops: Sequence[Op], window: Interval) -> List[Interval]:
    return clip(union((s, e) for _, s, e in ops), window)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    total = sum(length(busy(ops, trace.window)) for ops in trace.devices.values())
    return total / len(trace.devices) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.devices or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def op_time_s(trace: Trace, match) -> Tuple[float, int]:
    """Summed device seconds and count of the operations ``match(name)``
    accepts, over all devices, inside the window."""
    total, count = 0.0, 0
    for ops in trace.devices.values():
        for name, s, e in ops:
            if match(name):
                for a, b in clip([(s, e)], trace.window):
                    total += b - a
                count += 1
    return total * 1e-9, count


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time, averaged over devices,
    each as ``"<instruction> <opcode>"``."""
    tot: Dict[str, float] = {}
    for ops in trace.devices.values():
        for text, s, e in ops:
            name = f"{op_name(text)} {opcode(text)}"
            for a, b in clip([(s, e)], trace.window):
                tot[name] = tot.get(name, 0.0) + (b - a)
    nd = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v / nd * 1e-9] for name, v in best]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of the first device, each named by the host
    span that covers its middle (``between_calls`` where none does)."""
    if not trace.devices:
        return []
    first = sorted(trace.devices)[0]
    gaps = subtract([trace.window], busy(trace.devices[first], trace.window))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        names = [s[0] for s in trace.spans if s[1] <= mid <= s[2]]
        out.append([names[-1] if names else "between_calls", (b - a) * 1e-9])
    return out
