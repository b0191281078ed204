"""Randomized engine invariant harness: seeded bursty workloads (mixed fresh
and shared-prefix prompts, tiny block pools forcing preemption, FIFO and
EDF-slack admission) must drain leaving the paged pool pristine — zero leaked
blocks, scratch-block refcount intact, every non-truncated request holding
exactly max_new tokens, and bounded admission queue age (no starvation)."""
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.serving.engine import _NULL_SEQ, GenerationEngine


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


def _run_workload(seed: int, *, n_blocks, scheduler: str,
                  long_decode: bool = False, preempt: str = "recompute",
                  pipeline: bool = True, kernel: str = "reference",
                  kv_dtype: str = None,
                  greedy: bool = False, sanitize: bool = False):
    """Bursty seeded workload: waves of submits interleaved with engine steps.
    Prompts mix fresh random sequences with shared-retrieved-context prefixes
    (32 tokens = 2 full blocks at block_size=16). ``long_decode`` makes
    decode runs outgrow admission's slack block, forcing mid-decode pool
    exhaustion (preemption) on tiny pools."""
    rng = np.random.default_rng(seed)
    eng = GenerationEngine(
        _cfg(), max_batch=3, max_seq=96, n_blocks=n_blocks,
        prefill_chunk_size=16, token_budget=20,
        scheduler=scheduler, preempt=preempt,
        pipeline=pipeline, kernel=kernel, kv_dtype=kv_dtype,
        sanitize=sanitize,
    )
    ctx = rng.integers(0, 90, size=32).astype(np.int32)
    reqs = []
    for _ in range(4):  # bursts
        for _ in range(int(rng.integers(1, 4))):
            if long_decode:
                prompt = rng.integers(0, 90, size=int(rng.integers(3, 13)))
                max_new = int(rng.integers(28, 39))
            else:
                if rng.random() < 0.4:  # shared-prefix RAG request
                    tail = rng.integers(0, 90, size=int(rng.integers(1, 12)))
                    prompt = np.concatenate([ctx, tail])
                else:
                    prompt = rng.integers(0, 90, size=int(rng.integers(3, 45)))
                max_new = int(rng.integers(2, 9))
            reqs.append(eng.submit(
                prompt,
                max_new=max_new,
                temperature=0.0 if greedy else float(rng.choice([0.0, 0.0, 0.8])),
                priority=float(rng.random()),
            ))
        for _ in range(int(rng.integers(0, 4))):  # partial progress mid-burst
            eng.step()
    eng.run_until_done(max_steps=2000)
    return eng, reqs


@pytest.mark.parametrize(
    "seed,n_blocks,scheduler,long_decode,preempt",
    [
        (0, None, "fifo", False, "recompute"),   # fully provisioned pool
        (1, None, "edf_slack", False, "recompute"),  # EDF admission + grants
        (2, 8, "fifo", False, "recompute"),      # tiny pool: backpressure
        (3, 8, "fifo", False, "recompute"),
        (4, 10, "edf_slack", False, "recompute"),
        (5, 6, "fifo", True, "recompute"),       # long decodes: preemption
        (5, 6, "fifo", True, "swap"),            # swap-out preemption tier
        (6, 6, "edf_slack", True, "swap"),
        (3, 8, "fifo", False, "swap"),           # swap tier without churn
        (2, 8, "resident_first", False, "recompute"),  # eviction-aware
        (5, 6, "fifo", True, "cost"),            # per-victim cost model
        (6, 6, "edf_slack", True, "cost"),
    ],
)
def test_engine_invariants_after_drain(seed, n_blocks, scheduler,
                                       long_decode, preempt):
    eng, reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler=scheduler,
        long_decode=long_decode, preempt=preempt,
    )
    if long_decode:
        assert eng.preemptions >= 1  # the tiny pool must actually churn
    if preempt in ("swap", "cost") and eng.host_store is not None:
        # the host tier drains refcount-clean: every swap set was restored
        # (or dropped), and slot accounting closes over the store's capacity
        hs = eng.host_store
        assert hs.n_swapped == 0
        assert len(hs.free) + hs.n_keyed == hs.n_blocks
        assert eng.swap_ins == eng.swap_outs

    # every request drained
    assert all(r.done for r in reqs)
    assert not eng.waiting and not any(eng.slots)

    # zero leaked blocks: everything is free/warm-cached except the scratch
    pool = eng.kv.pool
    assert pool.n_free == pool.n_blocks - 1
    # scratch block intact: still owned by the null sequence, refcount 1,
    # and the only live refcount in the pool
    assert pool.tables == {_NULL_SEQ: [eng._null_block]}
    assert pool.refcounts == {eng._null_block: 1}
    assert eng.kv.lengths == {}

    # completion contract: eos_token=-1 never fires (sampled ids >= 0) and
    # max_seq is sized so no prompt+decode run hits the position cap, so
    # every non-truncated request holds exactly max_new tokens
    for r in reqs:
        assert r.first_token_at is not None and r.finished_at is not None
        if not r.truncated:
            assert len(r.out_tokens) == r.max_new, r.req_id
            assert r.pos < eng.max_seq - 1 or len(r.out_tokens) == r.max_new

    # accounting lines up across the engine counters
    assert eng.tokens_out == sum(len(r.out_tokens) for r in reqs)

    # no starvation: bounded admission queue age (in engine steps)
    assert max(r.queued_steps for r in reqs) <= 300
    assert len(eng.finished) == len(reqs)

    # streaming delivery: every completed request's tokens went through its
    # StreamingObject and the shared PriorityFlusher — non-empty StreamStats
    # and delivered == emitted, with the stream closed at finalize
    for r in reqs:
        assert r.stream is not None and r.stream.closed
        assert r.stream.stats.items_written == len(r.out_tokens)
        assert r.stream.stats.items_delivered == len(r.out_tokens)
        assert r.stream.stats.chunks_flushed >= 1 or not r.out_tokens
        assert r.delivered == r.out_tokens
    assert eng.flusher.backlog == 0


@pytest.mark.parametrize(
    "seed,n_blocks,preempt,pipeline,kv_dtype",
    [
        (0, None, "recompute", True, None),   # prefix sharing, full pool
        (5, 6, "swap", True, None),           # swap tier under pipelining
        (6, 6, "cost", False, None),          # cost preempt, sync oracle
        (5, 6, "swap", True, "int8"),         # quantized pool + swap tier
    ],
)
def test_invariants_under_kv_sanitizer(seed, n_blocks, preempt, pipeline,
                                       kv_dtype):
    """The full bursty workload under ``sanitize=True``: every pool, host-
    tier and copy-engine transition replays through the kvsan shadow state
    machine, which raises on any lifecycle violation (use-after-free,
    double-free, refcount underflow, fill-before-reserve, aliasing,
    swap-order). On drain the shadow must agree with the real pool: only
    the scratch block allocated, warm set sizes matching."""
    eng, reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler="fifo",
        long_decode=n_blocks is not None, preempt=preempt,
        pipeline=pipeline, kv_dtype=kv_dtype, sanitize=True)
    san = eng.sanitizer
    assert san is not None and san.violations == 0
    assert san.op_counts.get("device_alloc", 0) > 0
    if n_blocks is not None:
        assert eng.preemptions >= 1          # the shadow saw real churn
        assert san.op_counts.get("host_reserve", 0) > 0
        assert san.op_counts.get("host_restore", 0) > 0
        assert san.op_counts.get("copy_submit", 0) > 0
    assert all(r.done for r in reqs)
    shadow = san.stats()
    pool = eng.kv.pool
    assert shadow["device_allocated"] == 1   # the scratch block only
    assert shadow["device_warm"] == len(pool.cached)
    assert shadow["copy_pending"] == 0
    san.audit_host(eng.host_store) if eng.host_store is not None else None


@pytest.mark.parametrize(
    "seed,n_blocks,preempt,scheduler,long_decode",
    [
        (0, None, "recompute", "fifo", False),
        (5, 6, "recompute", "fifo", True),    # forced preemption (recompute)
        (5, 6, "swap", "fifo", True),         # forced preemption + swap tier
        (6, 6, "swap", "edf_slack", True),
        (5, 6, "cost", "fifo", True),         # per-victim swap-vs-recompute
        (6, 6, "cost", "edf_slack", True),
    ],
)
def test_pipelined_matches_sync_oracle(seed, n_blocks, preempt, scheduler,
                                       long_decode):
    """The acceptance bar for the runtime split: double-buffered dispatch must
    be greedy-token-identical (and, because the plan sequence is identical and
    the PRNG key splits once per dispatch, sampled-token-identical) to the
    synchronous oracle — including across swap preemption and re-admission."""
    sync_eng, sync_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler=scheduler,
        long_decode=long_decode, preempt=preempt, pipeline=False)
    pip_eng, pip_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler=scheduler,
        long_decode=long_decode, preempt=preempt, pipeline=True)
    assert not sync_eng.pipeline and pip_eng.pipeline
    if long_decode:
        assert pip_eng.preemptions >= 1
    for a, b in zip(sync_reqs, pip_reqs):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens, b.out_tokens)
    # the pipelined run actually pipelined: dispatches happened, and the
    # host-gap metric is being measured (present in the latency summary)
    summ = pip_eng.runner.summary()
    assert summ["dispatches"] > 0
    lat = pip_eng.latency_summary()
    assert "host_gap_total_s" in lat and "dispatches" in lat


# --------------------------------------------------------- Pallas hot path
@pytest.mark.parametrize(
    "seed,n_blocks,scheduler,long_decode,preempt,pipeline",
    [
        (2, 8, "fifo", False, "recompute", True),   # tiny pool backpressure
        (5, 6, "fifo", True, "swap", True),         # preemption + swap tier
    ],
)
def test_pallas_kernel_matches_reference(seed, n_blocks, scheduler,
                                         long_decode, preempt, pipeline):
    """``kernel="pallas"`` swaps the decode dispatch and the fused step onto
    the Pallas kernels (interpret mode off-TPU). Greedy/sampled tokens must
    be bit-identical to the reference XLA path on the invariant-harness
    workloads — including across swap preemption and pipelined dispatch —
    and the pool must drain clean."""
    ref_eng, ref_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler=scheduler,
        long_decode=long_decode, preempt=preempt, pipeline=pipeline,
        kernel="reference")
    pal_eng, pal_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler=scheduler,
        long_decode=long_decode, preempt=preempt, pipeline=pipeline,
        kernel="pallas")
    assert pal_eng.kernel == "pallas"
    if long_decode:
        assert pal_eng.preemptions >= 1
    for a, b in zip(ref_reqs, pal_reqs):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens, b.out_tokens)
    assert all(r.done for r in pal_reqs)
    pool = pal_eng.kv.pool
    assert pool.n_free == pool.n_blocks - 1  # zero leaked blocks


def test_pallas_kernel_rejects_unsupported_modes():
    from repro.configs import get_arch, smoke_variant
    cfg = smoke_variant(get_arch("smollm-135m"))
    with pytest.raises(ValueError):
        GenerationEngine(cfg, kernel="mosaic-gpu")


def test_chunk_tiles_counts_the_kernels_query_tiles():
    """``stats()["chunk_tiles"]`` sums, over the ragged steps, the query
    tiles ``paged_chunk_attention`` derives on device from each plan's
    packed ``row_of``."""
    import jax.numpy as jnp

    from repro.kernels.decode_attention import _chunk_tiles, chunk_query_tile

    cfg = _cfg()
    g = cfg.num_heads // cfg.num_kv_heads
    eng = GenerationEngine(cfg, max_batch=3, max_seq=96, n_blocks=24,
                           prefill_chunk_size=16, token_budget=20,
                           kernel="pallas")
    plans = []
    assemble = eng.control._assemble_ragged

    def spy(*args):
        plans.append(assemble(*args))
        return plans[-1]

    eng.control._assemble_ragged = spy
    rng = np.random.default_rng(3)
    for n in (30, 5, 12):
        eng.submit(rng.integers(0, 90, size=n), max_new=3)
    eng.run_until_done(max_steps=100)
    assert plans
    want = 0
    for p in plans:
        _, _, n = _chunk_tiles(jnp.asarray(p.row_of), eng.max_batch,
                               chunk_query_tile(g))
        want += int(n[0])
    assert eng.stats()["chunk_tiles"] == want > len(plans)


def test_decode_live_cells_counts_the_decode_kernels_live_cells():
    """``stats()["decode_live_cells"]`` sums, over the decode-only steps,
    the cells of ``paged_decode_attention``'s grid that run the math: each
    live row's columns up to the block holding its new token."""
    cfg = _cfg()
    eng = GenerationEngine(cfg, max_batch=4, max_seq=96, n_blocks=32,
                           block_size=8, prefill_chunk_size=16,
                           token_budget=20, kernel="pallas")
    plans = []
    assemble = eng.control._assemble_decode

    def spy(*args):
        plans.append(assemble(*args))
        return plans[-1]

    eng.control._assemble_decode = spy
    rng = np.random.default_rng(5)
    for n, new in ((30, 12), (5, 20), (12, 6)):
        eng.submit(rng.integers(0, 90, size=n), max_new=new)
    eng.run_until_done(max_steps=200)
    assert plans
    want = sum(int(p.starts[row]) // eng.block_size + 1
               for p in plans for _req, row, _fin in p.emit_rows)
    assert eng.stats()["decode_live_cells"] == want
    # fewer cells than the grid: empty rows and dead columns ran nothing
    assert len(plans) < want < len(plans) * eng.max_batch * eng.max_blocks


# ------------------------------------------------------------ int8 KV pools
def _greedy_agreement(reqs_a, reqs_b) -> float:
    match = total = 0
    for a, b in zip(reqs_a, reqs_b):
        n = min(len(a.out_tokens), len(b.out_tokens))
        match += sum(int(x == y)
                     for x, y in zip(a.out_tokens[:n], b.out_tokens[:n]))
        total += n
    return match / max(total, 1)


# pinned accuracy contract for int8 pools vs float, measured over full greedy
# sequences where one early flip cascades (random smoke weights leave tiny
# argmax gaps, so whole-sequence agreement runs well below the per-step rate);
# per-step logit error is bounded by the per-block absmax budget (see
# tests/test_kernel_conformance.py QTOL)
INT8_GREEDY_FLOOR = 0.75


@pytest.mark.parametrize(
    "seed,n_blocks,preempt,pipeline,long_decode",
    [
        (5, 6, "swap", True, True),    # forced preemption: host-tier scale
                                       # round-trip + pipelined dispatch
        (5, 6, "swap", False, True),   # same churn, sync oracle
        (4, 8, "recompute", True, False),  # backpressure, no preemption
    ],
)
def test_int8_pool_greedy_agreement(seed, n_blocks, preempt, pipeline,
                                    long_decode):
    """int8 pools must track the float engine's greedy tokens within the
    pinned floor — including across swap preemption (scales restored from
    the host tier verbatim) and pipelined dispatch — and drain the pool as
    clean as the float path."""
    fp_eng, fp_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler="fifo",
        long_decode=long_decode, preempt=preempt, pipeline=pipeline,
        greedy=True)
    q_eng, q_reqs = _run_workload(
        seed, n_blocks=n_blocks, scheduler="fifo",
        long_decode=long_decode, preempt=preempt, pipeline=pipeline,
        kv_dtype="int8", greedy=True)
    assert q_eng.kv_dtype == "int8" and q_eng.kv.quantized
    if long_decode:
        assert q_eng.preemptions >= 1
    if preempt == "swap":
        assert q_eng.swap_ins >= 1  # the host tier actually round-tripped
    agree = _greedy_agreement(fp_reqs, q_reqs)
    assert agree >= INT8_GREEDY_FLOOR, f"greedy agreement {agree:.1%}"
    assert all(r.done for r in q_reqs)
    pool = q_eng.kv.pool
    assert pool.n_free == pool.n_blocks - 1  # zero leaked blocks
    assert q_eng.kv.lengths == {}
    if q_eng.host_store is not None:
        assert q_eng.host_store.n_swapped == 0


def test_int8_pipelined_matches_sync_oracle():
    """Within the int8 engine, double-buffered dispatch must be token-
    identical to the sync oracle across swap preemption — the quantized
    state (pools AND scale pools) round-trips the host tier exactly."""
    sync_eng, sync_reqs = _run_workload(
        5, n_blocks=6, scheduler="fifo", long_decode=True,
        preempt="swap", pipeline=False, kv_dtype="int8")
    pip_eng, pip_reqs = _run_workload(
        5, n_blocks=6, scheduler="fifo", long_decode=True,
        preempt="swap", pipeline=True, kv_dtype="int8")
    assert pip_eng.preemptions >= 1 and pip_eng.swap_ins >= 1
    for a, b in zip(sync_reqs, pip_reqs):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens,
                                              b.out_tokens)


def test_int8_pallas_kernel_matches_reference():
    """kernel="pallas" on int8 pools (dequant inside the kernel) must be
    token-identical to the XLA reference path on the same workload."""
    ref_eng, ref_reqs = _run_workload(
        2, n_blocks=8, scheduler="fifo",
        kv_dtype="int8", kernel="reference")
    pal_eng, pal_reqs = _run_workload(
        2, n_blocks=8, scheduler="fifo",
        kv_dtype="int8", kernel="pallas")
    assert pal_eng.kernel == "pallas" and pal_eng.kv.quantized
    for a, b in zip(ref_reqs, pal_reqs):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens,
                                              b.out_tokens)


def test_quant_config_routes_to_paged_backend():
    """Regression: ``kv_cache_quant`` configs used to be excluded from the
    paged backend (dense fallback); pool-level int8 storage replaced that
    path, so the same config now reports backend="paged" with int8 pools."""
    cfg = _cfg().replace(kv_cache_quant=True)
    eng = GenerationEngine(cfg, max_batch=2, max_seq=64)
    assert eng.backend == "paged"
    assert eng.kv_dtype == "int8" and eng.kv.quantized
    assert eng.stats()["kv_dtype"] == "int8"
    r = eng.submit(np.arange(12) % 50, max_new=4)
    eng.run_until_done()
    assert r.done and len(r.out_tokens) == 4


# ------------------------------------------------------ ragged packing
def _unpack_ragged(plan, B):
    """Pure-numpy unpacker: rebuild each row's chunk from the flat packed
    buffer. Validates the packing invariants on the way: rows are contiguous
    runs in slot order, pad tokens carry row_of == -1, and a decode row's
    advertised flat index points at its own single token."""
    row_of = np.asarray(plan.row_of)
    assert plan.tokens.shape == row_of.shape == plan.slots.shape
    n_valid_total = int((row_of >= 0).sum())
    assert np.all(row_of[n_valid_total:] == -1), "pads must be a tail run"
    out = {}
    for b in range(B):
        idx = np.nonzero(row_of == b)[0]
        if len(idx) == 0:
            continue
        assert np.array_equal(idx, np.arange(idx[0], idx[0] + len(idx)))
        out[b] = {
            "tokens": np.asarray(plan.tokens)[idx],
            "slots": np.asarray(plan.slots)[idx],
            "positions": np.asarray(plan.positions)[idx],
            "p_end": np.asarray(plan.p_end)[idx],
            "s_start": np.asarray(plan.s_start)[idx],
            "flat0": int(idx[0]),
        }
        if plan.decode_idx[b] >= 0:
            assert len(idx) == 1 and plan.decode_idx[b] == idx[0]
        assert plan.last_idx[b] == idx[-1]
    return out


@pytest.mark.parametrize("seed,n_blocks", [(0, None), (2, 8)])
def test_ragged_plan_rows_match_requests_at_their_cursors(seed, n_blocks):
    """The packed layout is a pure re-encoding of each step's rows: a numpy
    unpacker applied to every ragged StepPlan must find each prefill row's
    own prompt tokens, cache slots, rope positions and attention spans at
    the cursor it had when the plan was built, each decode row's single
    token at its position, and grants inside the token budget, the chunk
    size, the prompt and the next cache-served span."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 90, size=int(rng.integers(3, 40)))
               for _ in range(6)]
    max_new = [int(rng.integers(2, 9)) for _ in prompts]
    eng = GenerationEngine(
        _cfg(), max_batch=3, max_seq=96, n_blocks=n_blocks,
        prefill_chunk_size=16, token_budget=20,
    )
    B, C = eng.max_batch, eng.prefill_chunk_size
    seen = []
    assemble = eng.control._assemble_ragged

    def spy(plan_id, active, *rest):
        # each row's request state as the assembler reads it
        rows = {r.slot: (r.prefilling, r.prefill_pos, r.pos, r.prefill_cap,
                         r.prompt, r.layout, list(r.shared_spans),
                         r.out_tokens[-1] if r.out_tokens else 0)
                for r in active}
        seen.append((assemble(plan_id, active, *rest), rows))
        return seen[-1][0]

    eng.control._assemble_ragged = spy
    reqs = [eng.submit(p, max_new=m) for p, m in zip(prompts, max_new)]
    eng.run_until_done(max_steps=1000)
    assert all(r.done for r in reqs)
    assert seen, "workload never produced a mixed/prefill plan"

    slot_tokens = valid_tokens = 0
    for plan, rows in seen:
        T = plan.tokens.shape[0]
        assert T % eng.pack_align == 0 and T < plan.n_tokens + eng.pack_align
        slot_tokens += T
        valid_tokens += plan.n_tokens
        assert int(plan.n_valid.sum()) == plan.n_tokens
        chunks = _unpack_ragged(plan, B)
        n_decode = sum(not row[0] for row in rows.values())
        granted = 0
        for b, (prefilling, cursor, pos, cap, prompt, lay, spans,
                host_tok) in rows.items():
            nv = int(plan.n_valid[b])
            if not prefilling:
                ch = chunks[b]
                assert nv == 1 and plan.starts[b] == pos
                want_tok = 0 if plan.prev_slots[b] >= 0 else host_tok
                np.testing.assert_array_equal(ch["tokens"], [want_tok])
                np.testing.assert_array_equal(ch["slots"], [pos])
                np.testing.assert_array_equal(ch["positions"], [pos])
                np.testing.assert_array_equal(ch["p_end"], [0])
                np.testing.assert_array_equal(ch["s_start"], [0])
                continue
            assert plan.starts[b] == cursor and plan.decode_idx[b] == -1
            granted += nv
            assert nv <= min(C, cap - cursor)
            assert not any(cursor < s < cursor + nv for s, _e in spans)
            if nv == 0:
                assert b not in chunks
                continue
            ch = chunks[b]
            sl = slice(cursor, cursor + nv)
            np.testing.assert_array_equal(ch["tokens"], prompt[sl])
            np.testing.assert_array_equal(ch["slots"],
                                          np.arange(cursor, cursor + nv))
            np.testing.assert_array_equal(ch["positions"], lay.pos_ids[sl])
            np.testing.assert_array_equal(ch["p_end"], lay.attn_p_end[sl])
            np.testing.assert_array_equal(ch["s_start"], lay.attn_s_start[sl])
        assert 0 < granted <= max(eng.token_budget - n_decode, 1)
        assert set(chunks) <= set(rows)

    # the counters the benchmark reads add up over the packed steps, and
    # the packing stays below the chunk-width slab of every row
    st = eng.stats()
    assert st["fused_slot_tokens"] == slot_tokens < len(seen) * B * C
    assert st["fused_valid_tokens"] == valid_tokens


# --------------------------------------------------------- multi-turn sessions
def _run_session_workload(seed, *, n_blocks=10, host_blocks=64, turns=3,
                          pipeline=True, scheduler="fifo", filler=True):
    """One multi-turn session on a tiny pool, with unique random filler
    requests between turns so the warm LRU must demote the session's history
    blocks to the host tier — the next turn's admission then promotes them
    back as the session hit class. All rng draws happen in a fixed order so
    pipelined/sync and session/flat variants see identical workloads."""
    from repro.serving.session import Session

    rng = np.random.default_rng(seed)
    eng = GenerationEngine(
        _cfg(), max_batch=2, max_seq=160, n_blocks=n_blocks,
        prefill_chunk_size=16, token_budget=20, scheduler=scheduler,
        pipeline=pipeline, host_blocks=host_blocks,
    )
    sess = Session(session_id=0, system_tokens=rng.integers(0, 90, size=20))
    turn_reqs, fillers = [], []
    for _ in range(turns):
        q = rng.integers(0, 90, size=12).astype(np.int32)
        r = eng.submit(sess.prompt(q), max_new=6, temperature=0.0)
        if filler:
            fillers += [eng.submit(rng.integers(0, 90, size=40), max_new=2,
                                   temperature=0.0) for _ in range(3)]
        eng.run_until_done(max_steps=2000)
        sess.commit(q, r.out_tokens)
        turn_reqs.append(r)
    return eng, sess, turn_reqs, fillers


@pytest.mark.parametrize(
    "seed,pipeline,scheduler",
    [
        (0, True, "fifo"),
        (1, True, "edf_slack"),
        (0, False, "fifo"),     # sequential sync oracle under session load
    ],
)
def test_session_invariants_after_drain(seed, pipeline, scheduler):
    """Session turns must leave BOTH tiers pristine after drain, and their
    history reuse must surface as the session hit class — separate from doc
    promotions, which a no-doc workload keeps at exactly zero."""
    eng, sess, turn_reqs, fillers = _run_session_workload(
        seed, pipeline=pipeline, scheduler=scheduler)
    assert all(r.done for r in turn_reqs + fillers)

    # HBM pool drains to scratch-only, exactly like the sessionless harness
    pool = eng.kv.pool
    assert pool.n_free == pool.n_blocks - 1
    assert pool.tables == {_NULL_SEQ: [eng._null_block]}
    assert eng.kv.lengths == {}
    # host tier refcount-clean: keyed blocks + free slots close the capacity
    hs = eng.host_store
    assert hs.n_swapped == 0
    assert len(hs.free) + hs.n_keyed == hs.n_blocks

    # the session class actually fired: later turns re-read earlier history
    # from HBM and/or via host promotion, and the tiny pool forced at least
    # one host promotion across the run
    assert turn_reqs[0].session_shared_tokens == 0  # first turn has no past
    reused = sum(r.session_shared_tokens + r.session_host_tokens
                 for r in turn_reqs[1:])
    promoted = sum(r.session_host_tokens for r in turn_reqs)
    assert reused > 0
    assert promoted > 0
    # accounting partition: session HBM hits are a subset of shared-prefix
    # hits; session promotions are disjoint from (zero, here) doc promotions
    for r in turn_reqs:
        assert r.session_shared_tokens <= r.shared_prefix_tokens
        assert r.session_shared_tokens + r.session_host_tokens \
            + r.host_prefix_tokens <= r.prefill_cap
    assert all(r.host_prefix_tokens == 0 for r in turn_reqs + fillers)
    assert all(r.session_host_tokens == 0 for r in fillers)

    # the distinct hit class reaches the reported summaries
    lat = eng.latency_summary()
    assert lat["session_hit_rate"] > 0.0
    assert lat["host_hit_rate"] == 0.0
    st = eng.stats()
    assert st["session_hit_tokens"] == eng.kv.session_host_token_hits > 0
    assert st["session_shared_tokens"] == eng.kv.session_token_hits > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_session_greedy_parity_with_flat_history(seed):
    """Sessions are a prompt-shaping layer only: carrying the history as a
    KIND_HISTORY segment (with all its block reuse) must produce exactly the
    tokens of resubmitting the same conversation as flat prompts with
    sessions disabled."""
    eng, sess, turn_reqs, _ = _run_session_workload(seed)

    rng = np.random.default_rng(seed)   # replay the identical draw order
    flat_eng = GenerationEngine(
        _cfg(), max_batch=2, max_seq=160, n_blocks=10,
        prefill_chunk_size=16, token_budget=20, host_blocks=64,
    )
    history = rng.integers(0, 90, size=20).astype(np.int32)
    flat_reqs = []
    for _ in range(len(turn_reqs)):
        q = rng.integers(0, 90, size=12).astype(np.int32)
        r = flat_eng.submit(np.concatenate([history, q]), max_new=6,
                            temperature=0.0)
        fill = [flat_eng.submit(rng.integers(0, 90, size=40), max_new=2,
                                temperature=0.0) for _ in range(3)]
        flat_eng.run_until_done(max_steps=2000)
        history = np.concatenate(
            [history, q, np.asarray(r.out_tokens, np.int32)])
        flat_reqs.append(r)
        del fill
    for a, b in zip(turn_reqs, flat_reqs):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens,
                                              b.out_tokens)
    # and the flat run never classified anything as session reuse
    assert flat_eng.stats()["session_hit_tokens"] == 0


@pytest.mark.parametrize("seed,scheduler", [(0, "fifo"), (1, "edf_slack")])
def test_session_pipelined_matches_sync(seed, scheduler):
    """Double-buffered dispatch stays token-identical to the sync oracle
    under multi-turn session load (history blocks demoting/promoting through
    the host tier between turns)."""
    sync = _run_session_workload(seed, pipeline=False, scheduler=scheduler)
    pip = _run_session_workload(seed, pipeline=True, scheduler=scheduler)
    for a, b in zip(sync[2] + sync[3], pip[2] + pip[3]):
        assert a.out_tokens == b.out_tokens, (a.req_id, a.out_tokens,
                                              b.out_tokens)
