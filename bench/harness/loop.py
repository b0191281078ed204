"""The open loop: arrivals released on the wall clock whether or not the
system keeps up, every request timed from when it was due.

A pipeline is due at its scheduled arrival; the first stage's request is due
then, and each later stage's request is due when the stage before it
finished. A stall of the loop (a long engine step, a slow poll) therefore
shows in the latency of every request that fell due during it, and the
loop's own lateness in releasing arrivals is recorded beside it.

The loop runs warm-up, window and tail arrivals as one stream, and ends once
every pipeline due in the window has finished, or when the grace period
after the window runs out: a window pipeline unfinished by then has failed.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from bench.harness.traffic import Arrival


@dataclass
class PipelineRecord:
    arrival: Arrival
    due: float                       # absolute (time.monotonic)
    released_at: float
    pipeline: object
    log: object                      # the requests it submitted, in stage order
    finished_at: Optional[float] = None

    @property
    def in_window(self) -> bool:
        return self.arrival.phase == "window"


@dataclass
class LoopResult:
    records: List[PipelineRecord]
    t_zero: float                    # the window's start (absolute)
    window_s: float
    ended_at: float
    steps: int = 0
    counters_at_start: dict = field(default_factory=dict)
    counters_at_end: dict = field(default_factory=dict)


def _null_span(_name):
    return contextlib.nullcontext()


class OpenLoop:
    """Drive a system with ``arrivals`` (due times relative to
    ``t_zero``). ``start(arrival, due_abs)`` turns an arrival into a
    (pipeline, stage log); ``busy()`` says whether the system holds work,
    and ``step()`` advances it by one engine step;
    ``span(name)`` wraps each phase of an iteration (trace annotations in a
    traced run); ``on_tick(now)`` runs once per iteration;
    ``read_counters()`` snapshots the program's counters at the window's
    edges."""

    def __init__(self, arrivals: List[Arrival], *, t_zero: float,
                 window_s: float, grace_s: float,
                 start: Callable, busy: Callable, step: Callable,
                 read_counters: Callable,
                 span: Callable = _null_span,
                 on_tick: Optional[Callable] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.step = step
        self.arrivals = sorted(arrivals, key=lambda a: a.due)
        self.t_zero = t_zero
        self.window_s = window_s
        self.grace_s = grace_s
        self.start = start
        self.busy = busy
        self.read_counters = read_counters
        self.span = span
        self.on_tick = on_tick
        self.clock = clock
        self.sleep = sleep

    def run(self) -> LoopResult:
        pending = deque(self.arrivals)
        active: List[PipelineRecord] = []
        records: List[PipelineRecord] = []
        window_left = sum(1 for a in self.arrivals if a.phase == "window")
        t_end = self.t_zero + self.window_s
        hard_end = t_end + self.grace_s
        res = LoopResult(records, self.t_zero, self.window_s, 0.0)
        while True:
            now = self.clock()
            if not res.counters_at_start and now >= self.t_zero:
                res.counters_at_start = self.read_counters()
            if not res.counters_at_end and now >= t_end:
                res.counters_at_end = self.read_counters()
            with self.span("release"):
                while pending and self.t_zero + pending[0].due <= now:
                    a = pending.popleft()
                    due = self.t_zero + a.due
                    p, log = self.start(a, due)
                    rec = PipelineRecord(a, due, now, p, log)
                    records.append(rec)
                    active.append(rec)
            with self.span("poll"):
                still = []
                for rec in active:
                    if rec.pipeline.poll(now):
                        rec.finished_at = rec.pipeline.finished_at
                        window_left -= rec.in_window
                    else:
                        still.append(rec)
                active = still
            if self.on_tick is not None:
                self.on_tick(now)
            if (now >= t_end and window_left == 0) or now >= hard_end:
                break
            if self.busy():
                with self.span("engine.step"):
                    self.step()
                res.steps += 1
            else:
                nxt = self.t_zero + pending[0].due if pending else hard_end
                with self.span("wait"):
                    # short naps keep on_tick regular while the engine idles
                    self.sleep(max(min(nxt, hard_end, now + 0.05) - self.clock(), 0.0))
        res.ended_at = self.clock()
        if not res.counters_at_end:
            res.counters_at_end = self.read_counters()
        return res
