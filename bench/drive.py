"""The open-loop drive of ``PagedServingEngine`` and what it records.

Before each ``engine.step()`` the requests that are due are submitted;
after it the tick's ``StepPlan`` is recorded with host-clock stamps.
``step()`` ends in a host sync on the sampled tokens, so a stamp taken
after it is device-complete.  Latencies count from a request's scheduled
arrival, never from when it was submitted.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .traffic import Req

__all__ = ["Clock", "Tick", "Drive"]


class Clock:
    """Host clock; tests substitute one that advances per tick."""

    def now(self) -> float:
        return time.perf_counter()

    def sleep(self, dt: float) -> None:
        time.sleep(dt)


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    admitted: Tuple[int, ...]
    # (rid, real prompt tokens in the chunk, chunk start position, last)
    prefill: Tuple[Tuple[int, int, int, bool], ...]
    # (rid, attended context length) of each slot that decoded a token
    decode: Tuple[Tuple[int, int], ...]
    slots: int


class Drive:
    """Feeds ``requests`` to ``engine`` on their schedule, offset by
    ``origin`` on ``clock``, and records every tick."""

    def __init__(self, engine, requests: Sequence[Req], clock: Clock,
                 origin: float,
                 on_tick: Optional[Callable[[object], None]] = None):
        self.engine = engine
        self.clock = clock
        self.origin = origin
        self.on_tick = on_tick
        self.pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self.by_rid: Dict[int, Req] = {r.rid: r for r in requests}
        self.submitted_at: Dict[int, float] = {}
        self.admitted_at: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.ticks: List[Tick] = []
        self._next = 0
        self.slots = engine.scheduler.max_concurrency

    def due(self, rid: int) -> float:
        return self.origin + self.by_rid[rid].arrival

    def _submit_due(self, now: float) -> None:
        while self._next < len(self.pending) \
                and self.origin + self.pending[self._next].arrival <= now:
            r = self.pending[self._next]
            self.engine.submit(r.prompt, r.max_new, rid=r.rid)
            self.submitted_at[r.rid] = now
            self._next += 1

    def _has_work(self) -> bool:
        s = self.engine.scheduler
        return bool(s.queue or s.active)

    def tick(self) -> Optional[Tick]:
        """Submit what is due, then run one engine step if there is work;
        otherwise wait for the next arrival.  Returns the recorded tick."""
        now = self.clock.now()
        self._submit_due(now)
        if not self._has_work():
            if self._next < len(self.pending):
                wait = self.origin + self.pending[self._next].arrival - now
                self.clock.sleep(max(0.0, min(wait, 0.01)))
            return None
        plan = self.engine.step()
        end = self.clock.now()
        prefill = []
        for c in plan.prefill:
            prefill.append((c.rid, c.end - c.start, c.start, c.last))
            if c.last:
                self.token_times.setdefault(c.rid, []).append(end)
        decode = []
        for rid, _slot in plan.decode:
            times = self.token_times.setdefault(rid, [])
            # the token fed this tick sits at position prompt + emitted - 1
            # and attends to every position up to and including it
            decode.append((rid, len(self.by_rid[rid].prompt) + len(times)))
            times.append(end)
        for rid, _slot in plan.admit:
            self.admitted_at[rid] = now
        t = Tick(start=now, end=end, admitted=tuple(r for r, _ in plan.admit),
                 prefill=tuple(prefill), decode=tuple(decode),
                 slots=self.slots)
        self.ticks.append(t)
        if self.on_tick is not None:
            self.on_tick(plan)
        return t

    def run_until(self, t_stop: float,
                  done: Optional[Callable[[], bool]] = None) -> None:
        """Tick until the clock passes ``t_stop`` or ``done()`` holds."""
        while self.clock.now() < t_stop:
            if done is not None and done():
                return
            self.tick()

    def backlog(self) -> int:
        """Requests submitted and not finished."""
        s = self.engine.scheduler
        return len(s.queue) + sum(not a.finished for a in s.active.values())

    def completed(self) -> Dict[int, List[int]]:
        return self.engine.scheduler.completed

    def served(self, rid: int) -> List[int]:
        """Tokens emitted so far for ``rid``, finished or not."""
        s = self.engine.scheduler
        if rid in s.completed:
            return list(s.completed[rid])
        if rid in s.active:
            return list(s.active[rid].tokens)
        return []

    def lateness(self) -> List[float]:
        """How late each request was submitted after it was due."""
        return [t - self.due(rid) for rid, t in self.submitted_at.items()]
