#!/usr/bin/env python3
"""The serving engine's own spans and programs in a ``jax.profiler`` trace.

``PagedServingEngine`` writes ``serving.*`` host spans with stats (see
``repro.serving.engine``) and runs its steps as the named programs
``jit_paged_decode_step``, ``jit_paged_prefill_chunk`` and
``jit_paged_verify_step``, whose executions the device plane lists on its
``XLA Modules`` line.  This module reads both, beside the device ops that
``bench.trace`` reads, and reduces them over a window:

- idle device seconds summed by the span the host was in: the innermost
  ``serving.*`` span, or, outside them, the innermost harness span
  (``bench.tick``, ``engine.*``);
- device seconds and executions by program;
- mean device milliseconds per decode step and per prefill chunk;
- the 90th percentile of admission -> first token over requests admitted
  early in the window;
- host milliseconds per tick: ``serving.step`` less its ``serving.sync``
  children.

    python3 bench/spans.py <trace dir>

prints the reduction of the newest ``.xplane.pb`` under the directory as
one JSON object.  The window is the harness's ``bench.window`` span where
the trace has one, else the extent of the ``serving.step`` spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import sys
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import trace as tr  # noqa: E402

__all__ = ["Span", "Trace", "Spans", "load", "reduce", "module_name",
           "step_ms", "prefill_latency_p90_ms", "host_ms_per_tick",
           "DECODE", "CHUNK", "VERIFY"]

SERVING = "serving."
DECODE = "jit_paged_decode_step"
CHUNK = "jit_paged_prefill_chunk"
VERIFY = "jit_paged_verify_step"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
# Requests admitted this long after the window opens are left out of the
# admission -> first token percentile: their first tokens may lie past it.
ADMIT_S = 40.0


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float            # seconds
    end: float
    stats: Mapping = dataclasses.field(default_factory=dict, compare=False)


def module_name(raw: str) -> str:
    """``jit_paged_decode_step(1234)`` -> ``jit_paged_decode_step``."""
    return raw.split("(", 1)[0]


@dataclasses.dataclass
class Trace:
    ops: List[tr.Event]         # device ops (``XLA Ops``)
    modules: List[tr.Event]     # program executions (``XLA Modules``)
    host: List[tr.Event]        # the harness's spans (``trace.HOST_SPANS``)
    spans: List[Span]           # the engine's ``serving.*`` spans

    def window(self) -> Optional[Tuple[float, float]]:
        w = tr.window_of(self.host)
        if w is not None:
            return w
        steps = [s for s in self.spans if s.name == "serving.step"]
        if not steps:
            return None
        return min(s.start for s in steps), max(s.end for s in steps)


def load(trace_dir: str) -> Trace:
    """The events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = Trace([], [], [], [])
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                t = (e.start_ns + e.duration_ns) * 1e-9
                if device and line.name == _MODULES_LINE:
                    out.modules.append(tr.Event(module_name(e.name), s, t))
                elif device:
                    out.ops.append(tr.Event(e.name, s, t))
                elif e.name.startswith(SERVING):
                    out.spans.append(Span(e.name, s, t, dict(e.stats)))
                elif e.name in tr.HOST_SPANS:
                    out.host.append(tr.Event(e.name, s, t))
    return out


@dataclasses.dataclass
class Spans:
    window: Tuple[float, float]
    busy_s: float
    idle_by_span: Dict[str, float]      # idle seconds by label
    gaps: List[Tuple[str, float]]       # longest idle gaps, by label
    module_time: Dict[str, float]       # device seconds by program
    module_count: Dict[str, int]        # executions by program
    spans: List[Span]                   # ``serving.*`` spans in the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _label_key(e) -> Tuple[bool, float]:
    # an engine span names the gap before any harness span around it
    return (not e.name.startswith(SERVING), e.end - e.start)


_OUTSIDE = "outside the window's spans"


def _idle(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    idle, cur = [], lo
    for s, t in busy:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        idle.append((cur, hi))
    return idle


def _attribute(idle: Sequence[Tuple[float, float]], labels: Sequence
               ) -> Dict[str, float]:
    """Idle seconds by the span that covers each stretch of them, by one
    sweep over every boundary."""
    marks = sorted([(e.start, 1, i) for i, e in enumerate(labels)]
                   + [(e.end, 0, i) for i, e in enumerate(labels)]
                   + [(s, 2, -1) for s, _ in idle]
                   + [(t, 2, -1) for _, t in idle])
    out: Dict[str, float] = {}
    active: set = set()
    k, prev = 0, None
    for t, kind, i in marks:
        if prev is not None and t > prev:
            while k < len(idle) and idle[k][1] <= prev:
                k += 1
            if k < len(idle) and idle[k][0] <= prev:
                seg = min(t, idle[k][1]) - prev
                name = min((labels[j] for j in active), key=_label_key).name \
                    if active else _OUTSIDE
                out[name] = out.get(name, 0.0) + seg
        if kind == 1:
            active.add(i)
        elif kind == 0:
            active.discard(i)
        prev = t
    return out


def reduce(t: Trace, window: Optional[Tuple[float, float]] = None,
           top_gaps: int = 10) -> Spans:
    """Reduce ``t`` over ``window`` (default: ``t.window()``)."""
    window = window or t.window()
    if window is None or window[1] <= window[0]:
        raise ValueError("empty trace window")
    lo, hi = window
    busy = tr.merge((max(e.start, lo), min(e.end, hi)) for e in t.ops)
    idle = _idle(busy, lo, hi)
    labels = [e for e in list(t.host) + list(t.spans) if e.end > e.start]
    by_span = _attribute(idle, labels)
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top_gaps]:
        mid = (s + e) / 2
        inner = [x for x in labels if x.start <= mid <= x.end]
        gaps.append((min(inner, key=_label_key).name if inner else _OUTSIDE,
                     e - s))
    mt: Dict[str, float] = {}
    mc: Dict[str, int] = {}
    for m in t.modules:
        if lo <= m.start < hi:
            mt[m.name] = mt.get(m.name, 0.0) + (m.end - m.start)
            mc[m.name] = mc.get(m.name, 0) + 1
    return Spans(window=(lo, hi), busy_s=sum(b - a for a, b in busy),
                 idle_by_span=by_span, gaps=gaps, module_time=mt,
                 module_count=mc,
                 spans=[s for s in t.spans if s.end > lo and s.start < hi])


# ---------------------------------------------------------------------------
# Per-layer numbers
# ---------------------------------------------------------------------------

def step_ms(r: Spans, module: str) -> Optional[float]:
    """Mean device milliseconds per execution of ``module`` that started
    in the window."""
    n = r.module_count.get(module, 0)
    return 1e3 * r.module_time[module] / n if n else None


def prefill_latency_p90_ms(r: Spans, admit_s: float = ADMIT_S
                           ) -> Optional[float]:
    """90th percentile, over the requests admitted in the window's first
    ``admit_s`` seconds, of the start of their ``serving.admit`` span to
    the end of their ``first_token`` ``serving.sync``; a request with no
    first token by the window's end counts until the window's end."""
    lo, hi = r.window
    admits: Dict[object, float] = {}
    first: Dict[object, float] = {}
    for s in r.spans:
        rid = s.stats.get("rid")
        if s.name == "serving.admit" and lo <= s.start < lo + admit_s:
            admits.setdefault(rid, s.start)
        elif s.name == "serving.sync" and s.stats.get("kind") == \
                "first_token":
            first[rid] = min(first.get(rid, s.end), s.end)
    if not admits:
        return None
    lat = [min(first.get(rid, hi), hi) - t for rid, t in admits.items()]
    return float(np.percentile(lat, 90)) * 1e3


def host_ms_per_tick(r: Spans) -> Optional[float]:
    """Mean, over the ``serving.step`` spans inside the window, of their
    duration less that of the ``serving.sync`` spans inside them."""
    lo, hi = r.window
    steps = [s for s in r.spans if s.name == "serving.step"
             and lo <= s.start and s.end <= hi]
    if not steps:
        return None
    syncs = sorted((s.start, s.end) for s in r.spans
                   if s.name == "serving.sync")
    starts = [a for a, _ in syncs]
    host = []
    for st in steps:
        i = bisect.bisect_left(starts, st.start)
        waited = 0.0
        while i < len(syncs) and syncs[i][0] < st.end:
            waited += min(syncs[i][1], st.end) - syncs[i][0]
            i += 1
        host.append(st.end - st.start - waited)
    return 1e3 * float(np.mean(host))


def summary(r: Spans) -> dict:
    """Every number of the reduction, for a log line or a JSON file."""
    counts: Dict[str, int] = {}
    for s in r.spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    return {
        "window_s": r.window_s, "busy_s": r.busy_s,
        "idle_s": r.window_s - r.busy_s,
        "idle_by_span": dict(sorted(r.idle_by_span.items(),
                                    key=lambda kv: -kv[1])),
        "gaps": [[k, v] for k, v in r.gaps],
        "module_s": dict(sorted(r.module_time.items(),
                                key=lambda kv: -kv[1])),
        "module_count": r.module_count,
        "span_count": counts,
        "decode_step_ms": step_ms(r, DECODE),
        "prefill_chunk_ms": step_ms(r, CHUNK),
        "prefill_latency_p90_ms": prefill_latency_p90_ms(r),
        "host_ms_per_tick": host_ms_per_tick(r),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(summary(reduce(load(argv[0])))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
