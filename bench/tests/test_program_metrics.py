"""The per-layer metrics that read the program's own spans
(``pw:<name>`` profiler annotations) from a traced run's profile: each on
a profile recorded here on the CPU, and nothing where the program has no
spans or the profile is not the run's."""
import time
from types import SimpleNamespace

import jax
import pytest

from bench.harness import program_spans, spec, trace

from .conftest import ROOT


def _sleep_in(name, s):
    with jax.profiler.TraceAnnotation(name):
        time.sleep(s)


def _record(tmp_path, program=True):
    """A profile whose traced window holds two engine steps of 30 ms: a
    plan of 4 ms, then a wait of 20 ms for the device. A span before the
    window is left out."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        if program:
            _sleep_in("pw:engine.plan", 0.05)
        with jax.profiler.TraceAnnotation("bench:traced"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench:engine.step"):
                    if program:
                        with jax.profiler.TraceAnnotation("pw:engine.step"):
                            _sleep_in("pw:engine.plan", 0.004)
                            time.sleep(0.006)
                            _sleep_in("pw:engine.materialize.wait", 0.02)
                    else:
                        time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    events = trace.load_xplane(trace.newest_xplane(str(tmp_path)))
    win = [e for e in events if e.name == trace.WINDOW_SPAN][0]
    return {"reduced": SimpleNamespace(window=(win.start, win.end)),
            "counters": ({}, {})}


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    program_spans._totals.cache_clear()
    return tmp_path


def test_readers_on_a_recorded_profile(trace_dir):
    ctx = _record(trace_dir)
    totals = program_spans.totals(ctx)
    assert totals["engine.plan"][0] == 2 and totals["engine.step"][0] == 2
    plan = spec.load_reader("plan_build_ms", ROOT).read(ctx)
    assert 4.0 <= plan < 10.0
    busy = spec.load_reader("host_busy_pct", ROOT).read(ctx)
    assert 0.0 < busy < 100.0
    step_ns = totals["engine.step"][1]
    wait_ns = totals["engine.materialize.wait"][1]
    assert busy == pytest.approx(100.0 * (1 - wait_ns / step_ns))


@pytest.mark.parametrize("metric", ["plan_build_ms", "host_busy_pct"])
def test_nothing_to_read_gives_none(trace_dir, metric):
    reader = spec.load_reader(metric, ROOT)
    assert reader.read({"reduced": None, "counters": ({}, {})}) is None
    ctx = {"reduced": SimpleNamespace(window=(0, 1)), "counters": ({}, {})}
    assert reader.read(ctx) is None                       # no profile at all
    ctx = _record(trace_dir, program=False)               # a program without spans
    assert reader.read(ctx) is None
    program_spans._totals.cache_clear()
    ctx = _record(trace_dir)
    ctx["reduced"] = SimpleNamespace(window=(0, 1))       # another run's window
    assert reader.read(ctx) is None
