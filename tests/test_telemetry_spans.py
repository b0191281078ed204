"""The engine's timed spans and admission counters: always-on aggregates in
``stats()``, ``pw:``-prefixed annotations in a profiler trace, nested as
the layers call each other, admission timestamps, the pool's eviction
count, and one trace id across the stages of a pipeline."""
import glob
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.apps import EnginePipeline, make_app
from repro.configs import get_arch, smoke_variant
from repro.core.telemetry import SPAN_PREFIX, Telemetry
from repro.serving.engine import GenerationEngine
from repro.serving.segments import assemble_prompt

ROOT = Path(__file__).resolve().parents[1]


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


def _doc_prompts(seed=0):
    """Prompts of a system prefix, four 32-token documents in a given order
    and a query."""
    rng = np.random.default_rng(seed)
    sys_toks = rng.integers(0, 300, 32)
    docs = [rng.integers(0, 300, 32) for _ in range(4)]

    def prompt(order):
        return assemble_prompt(rng.integers(0, 300, 8), [docs[i] for i in order],
                               doc_ids=list(order), system_tokens=sys_toks)

    return prompt


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(_cfg(), max_batch=4, max_seq=256)


def test_span_aggregates_count_calls_and_stay_integers():
    t = Telemetry()
    for _ in range(3):
        with t.span("outer"):
            with t.span("outer.inner"):
                pass
    totals = t.span_totals()
    assert totals["outer_n"] == 3 and totals["outer.inner_n"] == 3
    assert all(isinstance(v, int) for v in totals.values())
    assert totals["outer_ns"] >= totals["outer.inner_ns"] > 0


def test_request_spans_are_bounded():
    from repro.core.telemetry import Span

    t = Telemetry(max_series=4)
    for i in range(10):
        t.record_span(Span(i, "engine", 0, 0.0, 0.0, 0.0))
    assert sum(len(v) for v in t.spans.values()) == 4
    assert sorted(t.spans) == [6, 7, 8, 9]          # the oldest traces went


def test_one_step_plan_and_dispatch_per_dispatched_step(engine):
    prompt = _doc_prompts(seed=1)
    before = engine.stats()
    steps0 = engine.steps
    for order in ([0, 1], [2, 3], [1, 3]):
        engine.submit(prompt(order), max_new=3)
    n_dispatched = 0
    while engine.waiting or any(engine.slots):
        s0 = engine.steps
        engine.step()
        n_dispatched += engine.steps - s0
    after = engine.stats()

    def grew(name):
        return after[name] - before.get(name, 0)

    assert n_dispatched == engine.steps - steps0 > 0
    assert grew("engine.step_n") == grew("engine.plan_n") == n_dispatched
    for name in ("engine.dispatch", "engine.dispatch.inputs",
                 "engine.dispatch.launch", "engine.plan.admit",
                 "engine.plan.assemble"):
        assert grew(f"{name}_n") == n_dispatched, name
    assert grew("engine.step_ns") > grew("engine.plan_ns") > grew("engine.plan.admit_ns")
    assert grew("engine.dispatch_ns") >= (grew("engine.dispatch.inputs_ns")
                                          + grew("engine.dispatch.launch_ns"))
    engine.run_until_done()


def _load_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, int(ev.start_ns),
                            int(ev.start_ns) + int(ev.duration_ns)))
    return out


def test_profiler_trace_holds_the_nested_program_spans(engine, tmp_path):
    prompt = _doc_prompts(seed=2)
    engine.submit(prompt([3, 2]), max_new=2)
    engine.step()                      # compiled before the trace starts
    engine.submit(prompt([0, 3]), max_new=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.step()
        engine.step()
    finally:
        jax.profiler.stop_trace()
    engine.run_until_done()
    events = [e for e in _load_events(str(tmp_path))
              if e[2].startswith(SPAN_PREFIX)]
    names = {e[2][len(SPAN_PREFIX):] for e in events}
    assert {"engine.step", "engine.plan", "engine.plan.admit",
            "engine.dispatch", "engine.dispatch.inputs",
            "engine.dispatch.launch", "engine.copies",
            "engine.flush"} <= names

    def inside(child, parent):
        return (child[0], child[1]) == (parent[0], parent[1]) and \
            parent[3] <= child[3] and child[4] <= parent[4]

    def spans(name):
        return [e for e in events if e[2] == SPAN_PREFIX + name]

    for admit in spans("engine.plan.admit"):
        plan = [p for p in spans("engine.plan") if inside(admit, p)]
        assert len(plan) == 1
        assert any(inside(plan[0], s) for s in spans("engine.step"))
    for inputs in spans("engine.dispatch.inputs"):
        assert any(inside(inputs, d) for d in spans("engine.dispatch"))


def test_admission_timestamps_and_deferral_under_a_shared_burst():
    eng = GenerationEngine(_cfg(), max_batch=4, max_seq=256,
                           prefill_chunk_size=32)
    prompt = _doc_prompts(seed=3)
    # the same four documents in different orders: followers wait for the
    # leader to publish its document blocks
    reqs = [eng.submit(prompt(o), max_new=3)
            for o in ([0, 1, 2, 3], [2, 0, 3, 1], [3, 1, 0, 2])]
    eng.run_until_done()
    s = eng.stats()
    assert s["admit_deferred"] > 0
    assert s["admitted"] == len(reqs)
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    waits = sum(int((r.admitted_at - r.submitted_at) * 1e9) for r in reqs)
    assert s["admit_wait_ns"] == waits
    assert reqs[1].shared_prefix_tokens == 160       # the deferral paid off


def test_evictions_match_the_benchmark_adapters_count():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.adapters.generation_engine import _count_evictions

    # 12 blocks of 16: each 70-token request holds 5-6, and released blocks
    # stay warm until allocation takes them back
    eng = GenerationEngine(_cfg(), max_batch=2, max_seq=128, block_size=16,
                           n_blocks=12, prefill_chunk_size=32)
    _count_evictions(eng.kv.pool)
    rng = np.random.default_rng(4)
    for _ in range(6):
        eng.submit(rng.integers(0, 300, 70), max_new=2)
    eng.run_until_done()
    assert eng.stats()["evictions"] == eng.kv.pool.bench_evictions > 0


def test_pipeline_stages_share_one_trace_id(engine):
    app = make_app("crag", engine=engine)
    p = EnginePipeline(app, engine, query_tokens=np.arange(6, dtype=np.int32),
                       rng=np.random.default_rng(5), k_docs=2, max_new=3)
    for _ in range(500):
        if p.poll(0.0):
            break
        engine.step()
    assert p.done and len(p.requests) >= 2
    assert {r.trace_id for r in p.requests} == {p.trace_id}
    path = engine.telemetry.critical_path(p.trace_id)
    assert len(path) == len(p.requests)
    served = sorted(r.finished_at - r.admitted_at for r in p.requests)
    assert sorted(s for _c, _q, s in path) == pytest.approx(served)
    assert all(q >= 0 for _c, q, _s in path)


def test_every_kernel_carries_its_name():
    from repro.kernels.decode_attention import (
        decode_attention,
        paged_chunk_attention,
        paged_decode_attention,
    )

    f32, i32 = np.float32, np.int32
    q = jax.ShapeDtypeStruct((2, 4, 8), f32)
    pool = jax.ShapeDtypeStruct((6, 4, 2, 8), f32)
    tables = jax.ShapeDtypeStruct((2, 3), i32)
    per_row = jax.ShapeDtypeStruct((2,), i32)
    per_tok = jax.ShapeDtypeStruct((5,), i32)
    cache = jax.ShapeDtypeStruct((2, 16, 2, 8), f32)
    cases = {
        "decode_attention": (decode_attention, (q, cache, cache, per_row)),
        "paged_decode_attention": (paged_decode_attention,
                                   (q, pool, pool, tables, per_row)),
        "paged_chunk_attention": (
            paged_chunk_attention,
            (jax.ShapeDtypeStruct((5, 4, 8), f32), pool, pool, tables,
             per_tok, per_tok, per_tok, per_tok)),
    }
    for name, (fn, args) in cases.items():
        text = str(jax.make_jaxpr(lambda *a, fn=fn: fn(*a, interpret=True))(*args))
        assert f"name={name}" in text.replace(" ", ""), name
