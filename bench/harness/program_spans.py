"""The program's own timed spans in a traced run's profile.

The program marks its layers with profiler annotations named
``pw:<name>`` (``pw:engine.plan``, ``pw:engine.step``, ...). They land in
the same profile as the device ops, on the same clock. ``totals`` sums
those that lie inside the traced window, per name. A program without such
spans gives an empty mapping, and so does a profile other than the one the
run reduced (its window differs).
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Dict, Tuple

from bench.harness import trace

PREFIX = "pw:"
# where ``bench/run.py`` and ``bench/calibrate.py`` have the runner record
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_work" / "trace"


def totals(ctx) -> Dict[str, Tuple[int, int]]:
    """Span name (without the prefix) -> (count, total ns) of the program's
    spans inside the traced window of ``ctx["reduced"]``."""
    red = ctx.get("reduced")
    if red is None:
        return {}
    try:
        path = trace.newest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return {}
    return _totals(path, os.stat(path).st_mtime_ns, tuple(red.window))


@functools.lru_cache(maxsize=2)
def _totals(path: str, _mtime_ns: int, window: Tuple[int, int]
            ) -> Dict[str, Tuple[int, int]]:
    events = [e for e in trace.load_xplane(path) if not trace.is_device(e.plane)]
    win = [e for e in events if e.name == trace.WINDOW_SPAN]
    if not win or (win[0].start, win[0].end) != window:
        return {}
    lo, hi = window
    out: Dict[str, Tuple[int, int]] = {}
    for e in events:
        if e.name.startswith(PREFIX) and e.start >= lo and e.end <= hi:
            name = e.name[len(PREFIX):]
            n, ns = out.get(name, (0, 0))
            out[name] = (n + 1, ns + e.end - e.start)
    return out
