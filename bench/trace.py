"""Reduce a profiler trace of the measured window to device numbers.

The trace is JAX's ``.xplane.pb``.  Device planes are ``/device:TPU:<n>``;
their operations are the events of the ``XLA Ops`` line (``XLA Modules``
where a plane has no op line).  The benchmark's own host spans are the
host events named ``bench.<name>``; the window is ``bench.window``.

- ``busy_s``: the union of the device operations' intervals inside the
  window, averaged over the device planes; ``idle`` is the rest.
- ``device_ops``: operations by total time inside the window, each named
  by XLA's instruction name and opcode (``while.91 while``,
  ``fusion.237 fusion kCustom``).
- ``idle_gaps``: the longest stretches of the window in which no operation
  ran, each named by the benchmark span it falls in and by the most
  specific host event running at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # ns, the trace's own clock
    end: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s


def op_name(text: str) -> str:
    """XLA's instruction name and opcode, from an op event's HLO text."""
    if " = " not in text:
        return text[:120]
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):                # a tuple-shaped result
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    kind = re.search(r"kind=(k\w+)", rest)
    out = f"{name.lstrip('%')} {rest.split('(', 1)[0]}"
    return out + (f" {kind.group(1)}" if kind else "")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> List[Event]:
    """Every event of every plane, with its plane and line names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.end_ns)))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def device_ops(events: List[Event]) -> Dict[str, List[Event]]:
    """Per device plane, its operation events."""
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line in OP_LINES:
            planes.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
    return {p: next(lines[n] for n in OP_LINES if n in lines)
            for p, lines in planes.items()}


def _label(events: List[Event], t: float) -> str:
    """The benchmark span and the most specific host event at time ``t``."""
    span, host = None, None
    for e in events:
        if DEVICE_PLANE.match(e.plane) or not e.start <= t <= e.end:
            continue
        if e.name.startswith(SPAN_PREFIX):
            if e.name != WINDOW and (span is None or e.start > span.start):
                span = e
        elif host is None or e.end - e.start < host.end - host.start:
            host = e
    name = span.name[len(SPAN_PREFIX):] if span else "window"
    return f"{name}:{host.name}"[:120] if host else name


def summarize(events: List[Event], top: int = 10) -> Optional[Summary]:
    """The window's device numbers; None where the trace holds no window
    or no device operation."""
    win = next((e for e in events if e.name == WINDOW
                and not DEVICE_PLANE.match(e.plane)), None)
    ops = device_ops(events)
    if win is None or not ops:
        return None
    w0, w1 = win.start, win.end
    busy, by_name = 0.0, {}
    gaps: List[Tuple[float, float]] = []
    for plane_ops in ops.values():
        clipped = [(max(e.start, w0), min(e.end, w1)) for e in plane_ops
                   if e.end > w0 and e.start < w1]
        for e in plane_ops:
            d = min(e.end, w1) - max(e.start, w0)
            if d > 0:
                key = op_name(e.name)
                by_name[key] = by_name.get(key, 0.0) + d
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(ops)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9, n_devices=n,
        device_ops=[(k, v / n / 1e9) for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(_label(events, (a + b) / 2), (b - a) / 1e9)
                   for a, b in gaps[:top]])
