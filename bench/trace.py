"""Reduction of a ``jax.profiler`` trace to device busy time, idle share,
per-kernel time and the longest idle gaps.

Device operations are the events on the ``XLA Ops`` line of each
``/device:`` plane.  Busy time is the union of their intervals inside the
traced window, so nested or overlapping events count once.  The window is
the harness's own ``bench.window`` span on the host plane, which lies on the
same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Event", "Reduced", "merge", "op_name", "reduce_events", "load",
           "window_of", "WINDOW_SPAN", "HOST_SPANS"]

WINDOW_SPAN = "bench.window"
# Host spans the harness and its step wrapper write; an idle gap is named
# by the innermost of them that covers its middle.
HOST_SPANS = ("bench.window", "bench.tick", "engine.chunk", "engine.decode")
_OPS_LINE = "XLA Ops"


# Control-flow ops contain the ops they run; they count toward busy time
# but not toward any op's own time.
_CONTAINERS = ("while", "cond", "conditional", "call")


def op_name(raw: str) -> str:
    """``%_paged_pallas.9 = f32[...] custom-call(...)`` -> ``_paged_pallas``:
    the HLO instruction's name without its ``%`` and numeric suffixes."""
    name = raw.split(" = ", 1)[0].lstrip("%")
    parts = name.split(".")
    while len(parts) > 1 and (parts[-1].isdigit() or parts[-1] == "clone"):
        parts.pop()
    return ".".join(parts)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds
    end: float


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_time: Dict[str, float]           # device seconds by op_name()
    op_count: Dict[str, int]
    gaps: List[Tuple[str, float]]       # longest idle gaps, by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_time(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and event count of ops whose name holds
        ``pattern``."""
        t = sum(v for k, v in self.op_time.items() if pattern in k)
        n = sum(v for k, v in self.op_count.items() if pattern in k)
        return t, n


def _clip(ev: Sequence[Event], lo: float, hi: float):
    for e in ev:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield e.name, s, t


def reduce_events(device: Sequence[Event], host: Sequence[Event],
                  window: Tuple[float, float], top_gaps: int = 10) -> Reduced:
    """Reduce device op events and host spans over ``window``."""
    lo, hi = window
    if hi <= lo:
        raise ValueError("empty trace window")
    clipped = list(_clip(device, lo, hi))
    busy = merge((s, t) for _, s, t in clipped)
    op_time: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    for raw, s, t in clipped:
        name = op_name(raw)
        if name.split(".")[0] in _CONTAINERS:
            continue
        op_time[name] = op_time.get(name, 0.0) + (t - s)
        op_count[name] = op_count.get(name, 0) + 1
    idle, cur = [], lo
    for s, t in busy:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        idle.append((cur, hi))
    spans = [e for e in host if e.name in HOST_SPANS]
    gaps = []
    for s, t in sorted(idle, key=lambda g: g[0] - g[1])[:top_gaps]:
        mid = (s + t) / 2
        inner = [e for e in spans if e.start <= mid <= e.end]
        label = min(inner, key=lambda e: e.end - e.start).name \
            if inner else "outside the window's spans"
        gaps.append((label, t - s))
    return Reduced(window_s=hi - lo, busy_s=sum(t - s for s, t in busy),
                   op_time=op_time, op_count=op_count, gaps=gaps)


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                  * 1e-9) for e in line.events]


def load(trace_dir: str) -> Tuple[List[Event], List[Event], int]:
    """Device op events, host events, and the number of device planes of
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    device: List[Event] = []
    host: List[Event] = []
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [ln for ln in plane.lines if ln.name == _OPS_LINE]
            if ops:
                n_dev += 1
            for ln in ops:
                device.extend(_events(ln))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e.name in HOST_SPANS)
    return device, host, n_dev


def window_of(host: Sequence[Event]) -> Optional[Tuple[float, float]]:
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        return None
    return min(e.start for e in spans), max(e.end for e in spans)
