"""The reduction from a profiler trace to busy time, idle share, step
program and kernel time, and idle gaps by what the host was doing; on one
device and on four, where one step runs on several devices."""
import json

import numpy as np
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


# ---------------------------------------------------------------------------
# four devices: two replicas whose steps interleave
# ---------------------------------------------------------------------------

DEVS = [f"/device:TPU:{i}" for i in range(4)]


def four_device_events(layout: str):
    """Two replicas, each with its own compiled ragged and decode programs
    (their own fingerprints), stepping in turn. ``"mesh"``: every step runs
    on all four devices, as the program's group runs it (the replicas share
    one pool sharded over the mesh); ``"pairs"``: replica 0 on devices 0-1,
    replica 1 on devices 2-3, their steps overlapping in time. Each device's
    run of a step is 100 us, plus 10 us on devices 1 and 3; times in us."""
    steps = [(0, "jit__ragged_step_fn(11)"), (1, "jit__ragged_step_fn(22)"),
             (0, "jit__decode_paged_fn(33)"), (1, "jit__decode_paged_fn(44)"),
             (0, "jit__decode_paged_fn(33)"), (1, "jit__decode_paged_fn(44)")]
    ev = [Event(H, "python", "bench:traced", 0, 2000)]
    t = 100
    for replica, mod in steps:
        devs = DEVS if layout == "mesh" else DEVS[2 * replica: 2 * replica + 2]
        for i, dev in enumerate(devs):
            end = t + 100 + (10 if dev in (DEVS[1], DEVS[3]) else 0)
            ev += [Event(dev, "XLA Modules", mod, t, end),
                   Event(dev, "XLA Ops", "%fusion.3 = bf16[8] fusion(...)", t, end)]
        t += 200 if layout == "mesh" else 60 * (replica + 1)
    return [Event(e.plane, e.line, e.name, e.start * 1000, e.end * 1000) for e in ev]


def group_plans():
    ragged = {"kind": "ragged", "row_of": np.array([0, 0]), "slots": np.array([0, 1]),
              "p_end": np.array([0, 0]), "s_start": np.array([0, 0]), "sampled": 1}
    decode = {"kind": "decode", "ctx": np.array([3, 5]), "sampled": 2}
    return [ragged, ragged, decode, decode, decode, decode]


@pytest.mark.parametrize("layout", ["mesh", "pairs"])
def test_four_devices_one_launch_per_step(layout):
    red = trace.reduce(four_device_events(layout))
    assert red.devices == DEVS
    frags = ["ragged_step", "decode_paged"]
    runs = trace.launches(red, frags)
    per = 4 if layout == "mesh" else 2
    assert len(runs) == 6 and all(len(r) == per for r in runs)
    assert sorted(map(sorted, runs))[0] == [pytest.approx(100e-6)] * (per // 2) + [
        pytest.approx(110e-6)] * (per // 2)
    assert len(trace.module_times(red, frags)) == 6 * per


@pytest.mark.parametrize("layout", ["mesh", "pairs"])
def test_step_ms_and_step_mfu_are_per_chip_on_four_devices(layout):
    from bench.harness import spec

    from .conftest import ROOT

    red = trace.reduce(four_device_events(layout))
    cfg = json.loads((ROOT / "bench" / "configs" / "qwen2.5-3b-dp2tp2.json").read_text())
    ref = spec.load_reference(cfg["reference"], ROOT)
    dims = ref.Dims.from_config(cfg["model"])
    peaks = spec.load_peaks("TPU v5 lite", ROOT)
    plans = group_plans()
    ctx = {"reduced": red, "plans": plans, "dims": dims, "config": cfg, "reference": ref,
           "peaks": peaks}
    per = 4 if layout == "mesh" else 2
    # a step's time on one chip: 100 us on half its devices, 110 on the others
    assert spec.load_reader("step_ms", ROOT).read(ctx) == pytest.approx(0.105)
    flops = sum(ref.step_flops(dims, p) for p in plans)
    chip_s = 6 * per * 105e-6
    mfu = spec.load_reader("step_mfu", ROOT).read(ctx)
    assert mfu == pytest.approx(100 * flops / (chip_s * peaks["bf16_flops_per_s"]))
    # a plan without its launch, or a launch without its plan, reads nothing
    assert spec.load_reader("step_mfu", ROOT).read(dict(ctx, plans=plans[:-1])) is None
