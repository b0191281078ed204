"""The reduction from a profiler trace to busy time, idle share, step
program and kernel time, and idle gaps by what the host was doing."""
import json

import pytest

from bench.harness import trace
from bench.harness.trace import Event

from .conftest import FIXTURES

H, D = "/host:CPU", "/device:TPU:0"
CALL = ('%closed_call.13 = bf16[256,2,8,128] custom-call(s32[32,47] %copy-done), '
        'custom_call_target="tpu_custom_call"')
KERNELS = {"paged_chunk_attention": {"module": "ragged_step", "op": "tpu_custom_call"},
           "paged_decode_attention": {"module": "decode_pallas", "op": "tpu_custom_call"}}


def synthetic():
    """Times in microseconds (a trace's are nanoseconds)."""
    us = 1000
    ev = [
        Event(H, "python", "bench:traced", 0, 1000),
        Event(H, "python", "bench:engine.step", 0, 400),
        Event(H, "python", "bench:plan", 50, 100),
        Event(H, "python", "bench:wait", 600, 800),
        Event(H, "python", "bench:materialize", 900, 1000),
        Event(H, "python", "unrelated", 0, 1000),
        Event(D, "XLA Modules", "jit__ragged_step_fn(1)", 100, 300),
        Event(D, "XLA Modules", "jit__decode_pallas_fn(2)", 450, 550),
        Event(D, "XLA Ops", "%while.5 = (s32[]) while(...)", 100, 300),
        Event(D, "XLA Ops", "%fusion.1 = bf16[8] fusion(...)", 100, 200),
        Event(D, "XLA Ops", CALL, 200, 300),
        Event(D, "XLA Ops", CALL.replace("closed_call.13", "closed_call.2"), 450, 520),
        Event(D, "XLA Ops", "%copy.2 = bf16[8] copy(...)", 520, 550),
        Event(D, "XLA Ops", "%fusion.9 = bf16[8] fusion(...)", 1100, 1200),  # after the window
    ]
    return [Event(e.plane, e.line, e.name, e.start * us, e.end * us) for e in ev]


def test_busy_idle_modules_kernels_and_gaps():
    red = trace.reduce(synthetic(), kernels=KERNELS)
    assert red.window == (0, 1_000_000) and red.window_s == 1e-3
    assert red.busy_s == pytest.approx(300e-6)
    assert red.in_flight_s == pytest.approx(800e-6)           # less the wait
    assert red.busy_in_flight_s == pytest.approx(300e-6)
    assert red.kernels == {"paged_chunk_attention": pytest.approx(100e-6),
                           "paged_decode_attention": pytest.approx(70e-6)}
    assert red.kernel_calls == {"paged_chunk_attention": 1, "paged_decode_attention": 1}
    assert trace.module_times(red, ["ragged_step"]) == [pytest.approx(200e-6)]
    assert sorted(trace.module_times(red, ["ragged_step", "decode_pallas"])) == [
        pytest.approx(100e-6), pytest.approx(200e-6)]
    assert [g[0] for g in red.gaps] == ["wait", "engine.step", "plan"]
    assert [g[1] for g in red.gaps] == [pytest.approx(450e-6), pytest.approx(150e-6),
                                       pytest.approx(100e-6)]
    assert red.ops["fusion"] == pytest.approx(100e-6)         # numbers stripped
    assert red.ops["while"] == pytest.approx(0.0)              # self time only
    assert red.ops["paged_chunk_attention"] == pytest.approx(100e-6)
    bd = trace.breakdown(red)
    assert bd["idle_gaps"][0][0] == "wait" and len(bd["device_ops"]) <= 10


def test_recorded_chip_trace():
    """Half a second of a traced qwen3b.rag_hot run on a TPU v5 lite (128-token
    blocks, one fused step at T = 256 in flight)."""
    ev = [Event(*e) for e in json.loads((FIXTURES / "trace_v5e_ragged.json").read_text())]
    red = trace.reduce(ev, kernels=KERNELS)
    assert red.devices == ["/device:TPU:0"]
    assert 0 < red.busy_s <= red.window_s
    chunk = red.kernels["paged_chunk_attention"]
    assert red.kernel_calls["paged_chunk_attention"] > 0 and chunk > 0
    assert red.kernels["paged_decode_attention"] == 0.0
    assert max(red.ops, key=red.ops.get) == "paged_chunk_attention"
    assert sum(red.ops.values()) <= red.busy_s * 1.0001


def test_busy_union_counts_overlap_once():
    ev = synthetic() + [Event(D, "XLA Ops", "overlapping", 150_000, 250_000)]
    assert trace.reduce(ev).busy_s == pytest.approx(300e-6)


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([e for e in synthetic() if e.name != "bench:traced"])
    with pytest.raises(ValueError):
        trace.reduce([e for e in synthetic() if e.plane == H])
