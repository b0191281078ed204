"""Unit tests for the host-side control plane: StepPlan construction and
build-time bookkeeping, CopyEngine ordering/draining, the host tier's
reserve/fill swap split, and the load-driven streaming chunk policy."""
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.serving.control_plane import CopyEngine
from repro.serving.engine import GenerationEngine
from repro.serving.host_tier import HostBlockStore


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


def _engine(**kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_seq", 96)
    kw.setdefault("n_blocks", 16)
    kw.setdefault("prefill_chunk_size", 16)
    kw.setdefault("token_budget", 20)
    return GenerationEngine(_cfg(), **kw)


# --------------------------------------------------------------- StepPlan
def test_build_plan_fused_shape_and_grants():
    """A plan with no prefill row is a decode plan: one token a row and
    scratch-filled (B, max_blocks) tables, every active row emitting."""
    eng = _engine()
    a = eng.submit(np.arange(4) % 90, max_new=8)
    b = eng.submit(np.arange(6) % 90 + 1, max_new=8)
    assert eng.control.build_plan().kind == "ragged"  # both prompts prefill
    plan = eng.control.build_plan()
    assert plan is not None and plan.kind == "decode"
    B = eng.max_batch
    assert plan.tokens.shape == (B, 1) and plan.starts.shape == (B,)
    assert plan.tables.shape == (B, eng.max_blocks)
    assert plan.tokens.dtype == np.int32 and plan.tables.dtype == np.int32
    # no packed arrays on a decode plan
    assert plan.row_of is None and plan.n_valid is None
    # every decode row emits; positions were advanced at build time
    assert {r.req_id for r, _row, _fin in plan.emit_rows} == {a.req_id, b.req_id}
    assert plan.n_tokens == 2
    assert plan.starts[a.slot] == 4 and a.pos == 5
    assert plan.starts[b.slot] == 6 and b.pos == 7
    # rows the plan left empty, and table entries past a chain, hold the
    # scratch block
    free = [s for s in range(B) if s not in (a.slot, b.slot)]
    assert np.all(plan.tables[free] == eng._null_block)
    # nothing emitted yet: emission happens at materialize, not build
    assert a.out_tokens == [] and not a.done


def test_build_plan_ragged_shape_and_grants():
    eng = _engine()  # ragged is the default layout
    a = eng.submit(np.arange(4) % 90, max_new=8)          # short: completes
    b = eng.submit(np.arange(40) % 90 + 1, max_new=2)     # long: mid-prefill
    plan = eng.control.build_plan()
    assert plan is not None and plan.kind == "ragged"
    # flat packed layout: one axis, padded up to pack_align
    assert plan.tokens.ndim == 1
    assert plan.tokens.shape[0] % eng.pack_align == 0
    assert plan.tokens.shape == plan.row_of.shape == plan.slots.shape
    assert plan.tokens.shape == plan.positions.shape
    assert plan.tokens.dtype == np.int32 and plan.tables.dtype == np.int32
    # grants and bookkeeping are layout-independent
    assert a.prefill_pos == 4 and a.pos == 4
    emitted = {r.req_id for r, _row, _fin in plan.emit_rows}
    assert a.req_id in emitted and b.req_id not in emitted
    assert 0 < b.prefill_pos < len(b.prompt)
    assert plan.n_tokens <= eng.token_budget
    assert int(plan.n_valid.sum()) == plan.n_tokens
    # valid entries map to real slots; padding rows carry row_of == -1
    valid = int((plan.row_of >= 0).sum())
    assert valid == plan.n_tokens
    assert np.all(plan.row_of[valid:] == -1)
    # each emitting row's sampling index points at its own slot's tokens
    for req, row, _fin in plan.emit_rows:
        assert plan.row_of[plan.last_idx[row]] == row
    assert a.out_tokens == [] and not a.done


def test_build_plan_marks_device_resident_prev_tokens():
    eng = _engine()
    r = eng.submit(np.arange(4) % 90, max_new=8)
    eng.step()  # plan 0 dispatched: r's first token lives on device
    plan = eng.control.build_plan()
    assert plan is not None
    # r decodes now; its previous token was sampled by the plan the runner
    # dispatched last -> the row is marked for on-device substitution
    assert plan.prev_slots[r.slot] == r.slot
    assert plan.tokens[r.slot, 0] == 0  # placeholder, substituted on device
    # build-time bookkeeping advanced the position for the next plan
    assert r.pos == 5 and eng.kv.lengths[r.req_id] == 5


def test_build_plan_ragged_marks_device_resident_prev_tokens():
    eng = _engine()
    r = eng.submit(np.arange(4) % 90, max_new=8)
    eng.step()  # plan 0 dispatched: r's first token lives on device
    # a fresh prefill joins, so the next plan is a MIXED ragged batch
    # (decode-only plans keep the dedicated "decode" kind and dense layout)
    eng.submit(np.arange(12) % 90 + 1, max_new=2)
    plan = eng.control.build_plan()
    assert plan is not None and plan.kind == "ragged"
    # the decode token's flat index is advertised via decode_idx so the
    # runner can substitute the device-resident sample in the packed buffer
    di = int(plan.decode_idx[r.slot])
    assert di >= 0 and plan.prev_slots[r.slot] == r.slot
    assert plan.tokens[di] == 0        # placeholder, substituted on device
    assert plan.row_of[di] == r.slot and plan.positions[di] == r.pos - 1
    assert r.pos == 5 and eng.kv.lengths[r.req_id] == 5


def test_finishing_row_releases_slot_at_build_time():
    eng = _engine()
    r = eng.submit(np.arange(4) % 90, max_new=1)
    plan = eng.control.build_plan()
    [(req, _row, finishing)] = list(plan.emit_rows)
    assert req is r and finishing
    # slot and blocks released at build so the NEXT plan can admit into them;
    # emission (out_tokens, done) waits for materialize
    assert eng.slots[r.slot] is None
    assert r.req_id not in eng.kv.pool.tables
    assert not r.done and r.out_tokens == []


def test_chunk_policy_tracks_load():
    eng = _engine()
    eng.submit(np.arange(4) % 90, max_new=30)
    assert eng.control.build_plan() is not None
    low_chunk = eng.control.last_chunk_size
    assert eng.control.last_load < 1.0
    for i in range(6):  # saturate the batch + queue
        eng.submit(np.arange(10) % 90 + i, max_new=30)
    assert eng.control.build_plan() is not None
    assert eng.control.last_load == 1.0
    assert eng.control.last_chunk_size > low_chunk


# ------------------------------------------------------------- CopyEngine
def test_copy_engine_fifo_drain_and_counters():
    ce = CopyEngine(max_pending=32)
    ran = []
    for i in range(5):
        ce.submit(lambda i=i: ran.append(i))
    assert ce.backlog == 5 and ce.submitted == 5 and ce.drained == 0
    assert ce.drain(2) == 2
    assert ran == [0, 1]          # FIFO
    assert ce.drain() == 3        # None budget = drain all
    assert ran == [0, 1, 2, 3, 4]
    assert ce.backlog == 0 and ce.drained == 5 and ce.forced == 0


def test_copy_engine_force_drains_past_bound():
    ce = CopyEngine(max_pending=2)
    ran = []
    for i in range(4):
        ce.submit(lambda i=i: ran.append(i))
    # submits 3 and 4 each forced the oldest op out to hold the bound
    assert ce.backlog == 2 and ce.forced == 2 and ran == [0, 1]


def test_copy_engine_sync_drains_through_tag():
    ce = CopyEngine()
    ran = []
    ce.submit(lambda: ran.append("a"), tag="a")
    ce.submit(lambda: ran.append("b"), tag="b")
    ce.submit(lambda: ran.append("c"), tag="c")
    ce.sync("b")  # in-order: everything up to and including the last "b"
    assert ran == ["a", "b"] and ce.backlog == 1
    ce.sync("zzz")  # absent tag: no-op
    assert ran == ["a", "b"]


# --------------------------------------------------- host tier reserve/fill
def _store(n_blocks=4):
    return HostBlockStore((2, 4, 2, 4), np.float32, n_blocks=n_blocks)


def test_reserve_then_fill_then_restore_roundtrip():
    st = _store()
    slots = st.reserve_seq("t1", 2)
    assert slots is not None and len(slots) == 2
    assert st.n_swapped == 2 and st.swap_outs == 1
    k = np.full((2, 2, 4, 2, 4), 3.0, np.float32)
    v = np.full((2, 2, 4, 2, 4), 7.0, np.float32)
    st.fill_seq("t1", k, v)
    rk, rv = st.restore_seq("t1")
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    assert st.n_swapped == 0 and len(st.free) == st.n_blocks


def test_reserve_all_or_nothing_and_fill_tolerates_drop():
    st = _store(n_blocks=2)
    assert st.reserve_seq("big", 3) is None       # over capacity: no change
    assert len(st.free) == 2 and st.swap_outs == 0
    assert st.reserve_seq("none", 0) is None      # empty chain: refused
    slots = st.reserve_seq("t", 2)
    assert slots is not None
    st.drop_seq("t")                              # victim fell back/cancelled
    # the deferred fill drains after the drop: must be a harmless no-op
    st.fill_seq("t", np.zeros((2, 2, 4, 2, 4), np.float32),
                np.zeros((2, 2, 4, 2, 4), np.float32))
    assert len(st.free) == 2


def test_save_seq_is_reserve_plus_fill():
    st = _store()
    k = np.full((2, 1, 4, 2, 4), 1.0, np.float32)
    assert st.save_seq("s", k, k.copy())
    with pytest.raises(ValueError):
        st.reserve_seq("s", 1)  # duplicate tag refused on both paths
    rk, _rv = st.restore_seq("s")
    np.testing.assert_array_equal(rk, k)
