"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, time per device op and per program, and idle
gaps labelled by the host span open across them.

The window is the host event named ``window`` (the harness opens a
``TraceAnnotation`` of that name around the measured window).  Device
planes are the ``/device:<kind>:<n>`` planes; on each, busy time is the
union of the op intervals on its ``XLA Ops`` line (its ``XLA Modules``
line where a plane has no op line), clipped to the window.  A gap is an
interval of the window in which no op ran; it is labelled with the
innermost host event open across its midpoint whose name is one of
``labels``, or ``(no span)``.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
NO_SPAN = "(no span)"


@dataclasses.dataclass(frozen=True)
class Reduced:
    window_s: float
    n_devices: int
    busy_s: float  # mean over device planes
    op_s: dict  # op name -> self seconds (nested ops excluded), per device
    program_s: dict  # program name -> seconds, per device
    idle_s: dict  # label -> idle seconds, per device

    def top(self, table: dict, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def _op_name(name: str) -> str:
    """``%fusion.11 = f32[...] fusion(...)`` -> ``fusion.11``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_seconds(events, lo, hi) -> collections.Counter:
    """Per op name, the time of its events in [lo, hi) not covered by
    events nested inside them (a while loop holds its body's ops)."""
    out = collections.Counter()
    stack: list = []  # [end, name] of the enclosing events
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= (e - s) / 1e9 if lo <= s < hi else 0.0
        if lo <= s < hi:
            out[_op_name(n)] += (e - s) / 1e9
        stack.append([e, _op_name(n)])
    return out


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label_gaps(gaps, host, labels):
    """Label each gap by the innermost ``labels`` event (latest start) open
    across its midpoint, over all host threads: one sweep over the spans
    in start order, keeping those still open in a heap by end."""
    spans = sorted((s, e, n) for s, e, n in host if n in labels)
    out = collections.Counter()
    open_by_end: list = []
    i = 0
    for lo, hi in sorted(gaps):
        mid = (lo + hi) / 2
        while i < len(spans) and spans[i][0] <= mid:
            s, e, n = spans[i]
            heapq.heappush(open_by_end, (e, s, n))
            i += 1
        while open_by_end and open_by_end[0][0] < mid:
            heapq.heappop(open_by_end)
        label = max(open_by_end, key=lambda x: x[1])[2] if open_by_end else NO_SPAN
        out[label] += (hi - lo) / 1e9
    return out


def reduce(path: str, *, window: str = "bench.window",
           labels: frozenset = frozenset()) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices.append(lines)
    marks = [(s, e) for s, e, n in host if n == window]
    if len(marks) != 1:
        raise ValueError(f"{path}: {len(marks)} host events named {window!r}, want 1")
    lo, hi = marks[0]
    busy = 0.0
    op_s, program_s, idle_s = (collections.Counter() for _ in range(3))
    for lines in devices:
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        merged = _union(ops, lo, hi)
        busy += sum(e - s for s, e in merged) / 1e9
        op_s.update(_self_seconds(ops, lo, hi))
        for s, e, n in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                program_s[_MODULE_SUFFIX.sub("", n)] += (e - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        idle_s.update(_label_gaps(gaps, host, labels | {window}))
    n = max(len(devices), 1)

    def per_device(table):
        return {k: v / n for k, v in table.items()}

    return Reduced(window_s=(hi - lo) / 1e9, n_devices=len(devices), busy_s=busy / n,
                   op_s=per_device(op_s), program_s=per_device(program_s),
                   idle_s=per_device(idle_s))
