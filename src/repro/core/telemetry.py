"""Workflow-wide telemetry: per-request trace spans + time-series gauges.

The paper's thesis is that per-component metrics are not enough — the
controller needs *workflow-wide* visibility (queueing cascades, branch
frequencies, critical paths). This module provides:

  * Dapper-style trace spans per request stage (queue + service + transfer),
  * time-series gauges (queue depth, instance count, chunk size, pool
    utilization) sampled on events,
  * critical-path extraction over a request's spans,
  * timed code spans (``Telemetry.span``): a ``jax.profiler`` annotation
    named ``pw:<name>`` on the profiler's clock, beside the device ops of
    the same trace, plus an always-on count and total in integer
    nanoseconds per name. Nesting on a thread gives each span its parent in
    the trace; the dotted name gives it in the aggregate
    (``engine.plan.admit`` inside ``engine.plan``). Nothing is kept per
    call.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from time import perf_counter_ns
from typing import Dict, List, Tuple

SPAN_PREFIX = "pw:"


@dataclass
class Span:
    req_id: int
    comp: str
    instance_id: int
    enqueued: float
    started: float
    finished: float

    @property
    def queue_s(self) -> float:
        return self.started - self.enqueued

    @property
    def service_s(self) -> float:
        return self.finished - self.started


class _Timed:
    """One timed span: the profiler annotation (made only while a profile
    is being recorded) and the aggregate's update."""

    __slots__ = ("_label", "_agg", "_annotation", "_ann", "_t0")

    def __init__(self, label: str, agg: List[int], annotation):
        self._label = label
        self._agg = agg
        self._annotation = annotation   # jax.profiler.TraceAnnotation

    def __enter__(self):
        if self._annotation.is_enabled():
            self._ann = self._annotation(self._label)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        agg = self._agg
        agg[0] += 1
        agg[1] += dt
        return False


class Telemetry:
    """``max_series`` bounds each gauge's samples and the number of request
    spans kept (the oldest traces go first)."""

    def __init__(self, max_series: int = 100_000):
        self.spans: Dict[int, List[Span]] = defaultdict(list)
        self.gauges: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self._max = max_series
        self._n_spans = 0
        # span name -> its profiler label, [count, total ns], and the
        # annotation class
        self._timed: Dict[str, Tuple[str, List[int], type]] = {}

    # ------------------------------------------------------------ recording
    def record_span(self, span: Span):
        self.spans[span.req_id].append(span)
        self._n_spans += 1
        while self._n_spans > self._max:
            oldest = next(iter(self.spans))
            self._n_spans -= len(self.spans.pop(oldest))

    def span(self, name: str) -> _Timed:
        """Context manager timing the enclosed code as ``name``."""
        entry = self._timed.get(name)
        if entry is None:
            from jax.profiler import TraceAnnotation

            entry = self._timed[name] = (SPAN_PREFIX + name, [0, 0],
                                         TraceAnnotation)
        return _Timed(*entry)

    def span_totals(self) -> Dict[str, int]:
        """Each timed span's count and total as flat integers:
        ``<name>_n`` and ``<name>_ns``."""
        out: Dict[str, int] = {}
        for name, (_label, (n, ns), _cls) in self._timed.items():
            out[f"{name}_n"] = n
            out[f"{name}_ns"] = ns
        return out

    def gauge(self, name: str, t: float, value: float):
        series = self.gauges[name]
        if len(series) < self._max:
            series.append((t, value))

    # ------------------------------------------------------------ analysis
    def critical_path(self, req_id: int) -> List[Tuple[str, float, float]]:
        """Per-stage (component, queue_s, service_s) in execution order —
        the Dapper/CRISP-style view the paper argues RAG needs."""
        return [
            (s.comp, s.queue_s, s.service_s)
            for s in sorted(self.spans.get(req_id, []), key=lambda s: s.enqueued)
        ]

    def queue_time_share(self) -> Dict[str, float]:
        """Fraction of total request time spent queueing, per component —
        identifies where the queueing cascade forms."""
        q: Dict[str, float] = defaultdict(float)
        s: Dict[str, float] = defaultdict(float)
        for spans in self.spans.values():
            for sp in spans:
                q[sp.comp] += sp.queue_s
                s[sp.comp] += sp.service_s
        return {
            c: min(max(q[c] / max(q[c] + s[c], 1e-12), 0.0), 1.0)
            for c in set(q) | set(s)
        }

    def last(self, name: str, default: float = 0.0) -> float:
        """Latest value of a gauge (e.g. ``prefix_hit_rate/<comp>`` exported
        online by the controller's reallocation loop)."""
        series = self.gauges.get(name, [])
        return series[-1][1] if series else default

    def gauge_stats(self, name: str) -> Dict[str, float]:
        series = self.gauges.get(name, [])
        if not series:
            return {}
        vals = [v for _, v in series]
        return {
            "mean": sum(vals) / len(vals),
            "max": max(vals),
            "last": vals[-1],
            "n": len(vals),
        }

    def ascii_sparkline(self, name: str, width: int = 60) -> str:
        """Terminal-friendly gauge trace (for examples/ops runbooks)."""
        series = self.gauges.get(name, [])
        if not series:
            return "(no data)"
        vals = [v for _, v in series]
        # resample to `width` buckets
        step = max(len(vals) // width, 1)
        buckets = [max(vals[i : i + step]) for i in range(0, len(vals), step)][:width]
        lo, hi = min(buckets), max(buckets)
        chars = " ▁▂▃▄▅▆▇█"
        span = max(hi - lo, 1e-12)
        return "".join(chars[int((v - lo) / span * (len(chars) - 1))] for v in buckets)
