"""Every per-layer metric reads a number in every cell that BENCHMARK.json
lists for it. Each cell gets a traced window as its own configuration
shapes it: its step programs (named as its ``step_modules`` say) on as many
devices as it has chips, one kernel call for each kernel it lists, every
replica stepping in turn, the program's spans recorded on this CPU, and the
counters its adapter reports. A reader that finds nothing where its cell
should give it something fails here, not as a missing number in a chip run."""
import json
import time

import jax
import numpy as np
import pytest

from bench.harness import program_spans, spec, trace
from bench.harness.trace import Event

from .conftest import ROOT

BENCH = spec.load_benchmark(ROOT)
PAIRS = [(m["name"], c) for m in BENCH["per_layer"] for c in m["workloads"]]


def _record_spans(tmp_path):
    """A profile whose traced window holds two engine steps with the
    program's spans; returns its events."""
    def span(name, s):
        with jax.profiler.TraceAnnotation(name):
            time.sleep(s)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:traced"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("pw:engine.step"):
                    span("pw:engine.plan", 0.002)
                    time.sleep(0.004)
                    span("pw:engine.materialize.wait", 0.02)
    finally:
        jax.profiler.stop_trace()
    return trace.load_xplane(trace.newest_xplane(str(tmp_path)))


def _device_events(cell, lo, hi):
    """Each replica's ragged step, then its decode step, on every device of
    the cell; a kernel the configuration lists is a custom call inside the
    module it names, and a step on several devices ends in an all-reduce."""
    cfg = cell.config
    dp = int(cfg.get("mesh", {}).get("dp", 1))
    devs = [f"/device:TPU:{i}" for i in range(cell.chips)]
    ev, t = [], lo + (hi - lo) // 10
    dur = (hi - lo) // (4 * dp + 2)
    for kind in ("ragged", "decode"):
        for r in range(dp):
            mod = f"jit__{cfg['step_modules'][kind]}_fn({r + 1}{len(kind)})"
            for dev in devs:
                ev.append(Event(dev, "XLA Modules", mod, t, t + dur))
                ev.append(Event(dev, "XLA Ops", "%fusion.1 = fusion(...)", t, t + dur // 2))
                if cell.chips > 1:
                    ev.append(Event(dev, "XLA Ops", "%all-reduce.3 = all-reduce(...)",
                                    t + dur // 2, t + dur))
                for where in cfg["kernels"].values():
                    if where["module"] in mod:
                        ev.append(Event(dev, "XLA Ops", f"%cc.1 = custom-call(), "
                                        f"custom_call_target=\"{where['op']}\"",
                                        t + dur // 2, t + dur))
            t += dur + dur // 4
    return ev


def _counters(cell):
    a = {"steps": 10, "tokens_out": 20, "prefill_tokens": 300, "prefix_hit_tokens": 0,
         "host_hit_tokens": 0}
    b = {"steps": 40, "tokens_out": 120, "prefill_tokens": 900, "prefix_hit_tokens": 256,
         "host_hit_tokens": 128}
    if cell.config["adapter"] == "engine_group":
        a["cross_replica_host_hits"], b["cross_replica_host_hits"] = 0, 1
    return a, b


def _plans(cell):
    dp = int(cell.config.get("mesh", {}).get("dp", 1))
    ragged = {"kind": "ragged", "row_of": np.array([0, 0, 1]), "slots": np.array([4, 5, 9]),
              "p_end": np.array([0, 0, 0]), "s_start": np.array([4, 4, 0]), "sampled": 2}
    decode = {"kind": "decode", "ctx": np.array([10, 30]), "sampled": 2}
    return [ragged] * dp + [decode] * dp


@pytest.fixture
def cell_ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    program_spans._totals.cache_clear()
    host = _record_spans(tmp_path)

    def make(name):
        cell = spec.find_cell(name, ROOT)
        win = [e for e in host if e.name == trace.WINDOW_SPAN][0]
        ev = host + _device_events(cell, win.start, win.end)
        ref = spec.load_reference(cell.config["reference"], ROOT)
        return {"reduced": trace.reduce(ev, kernels=cell.config["kernels"]),
                "plans": _plans(cell), "config": cell.config, "reference": ref,
                "dims": ref.Dims.from_config(cell.config["model"]),
                "peaks": spec.load_peaks("TPU v5 lite", ROOT), "counters": _counters(cell),
                "records": []}

    return make


@pytest.mark.parametrize("metric,cell", PAIRS, ids=[f"{m}@{c}" for m, c in PAIRS])
def test_each_metric_reads_in_each_cell_it_lists(cell_ctx, metric, cell):
    v = spec.load_reader(metric, ROOT).read(cell_ctx(cell))
    assert v is not None and np.isfinite(v), f"{metric} reads nothing in {cell}"


def test_every_metric_lists_its_cells():
    """No metric applies by default to cells that later changes add."""
    assert all("workloads" in m for m in BENCH["per_layer"])
    cells = {w["name"] for w in BENCH["workloads"]}
    assert all(set(m["workloads"]) <= cells for m in BENCH["per_layer"])


def test_rooflines_read_nothing_without_their_kernel(cell_ctx):
    """The mesh path runs no Pallas kernel: the kernels' rooflines find
    nothing there, which is why they do not list that cell."""
    ctx = cell_ctx("qwen3b.rag_hot.dp2tp2")
    for metric in ("paged_chunk_attention_roofline", "paged_decode_attention_roofline"):
        assert spec.load_reader(metric, ROOT).read(ctx) is None
    assert json.dumps(ctx["config"]["kernels"]) == "{}"


def test_collective_share_reads_nothing_on_one_chip(cell_ctx):
    """One chip exchanges nothing: the collectives' share finds nothing
    there, which is why it lists only the four-chip cell."""
    assert spec.load_reader("collective_pct", ROOT).read(cell_ctx("qwen3b.rag_hot")) is None
    v = spec.load_reader("collective_pct", ROOT).read(cell_ctx("qwen3b.rag_hot.dp2tp2"))
    assert 0 < v < 100
