"""Retrieval-aware prefix caching: segment keying, LRU warm cache, segmented
engine parity, and the measured-hit-rate -> allocation feedback loop."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.core.allocation import solve_allocation
from repro.core.components import Augmenter, Generator, Reranker, Retriever
from repro.core.profiling import (
    calibrate_generator_from_engine,
    generator_alpha_scale,
    profile_components,
)
from repro.serving.engine import GenerationEngine
from repro.serving.paged_cache import PagedKVCache, PagedPool, prefix_block_keys
from repro.serving.retrieval import DocTokenStore, ScoredDocs
from repro.serving.segments import (
    Segment,
    SegmentedPrompt,
    assemble_prompt,
    build_layout,
)


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


# ------------------------------------------------------- key edge cases


def test_prefix_block_keys_edges():
    bs = 16
    assert prefix_block_keys(np.zeros(0, np.int64), bs) == []
    assert prefix_block_keys(np.arange(bs - 1), bs) == []      # < one block
    one = prefix_block_keys(np.arange(bs), bs)                 # exactly one
    assert len(one) == 1
    two = prefix_block_keys(np.arange(2 * bs), bs)
    assert len(two) == 2 and two[0] == one[0]
    # chained: a different first block changes every later key
    other = prefix_block_keys(np.arange(2 * bs) + 1, bs)
    assert other[0] != two[0] and other[1] != two[1]


def test_flat_layout_reproduces_chained_hash():
    bs = 16
    toks = np.arange(40) % 90                    # 2 full blocks + partial tail
    lay = build_layout(toks, bs)
    assert lay.block_keys[:2] == prefix_block_keys(toks, bs)
    assert lay.block_keys[2] is None             # partial block: never keyed
    assert list(lay.pos_ids) == list(range(40))  # position == slot
    assert not lay.attn_p_end.any() and not lay.attn_s_start.any()
    empty = build_layout(np.zeros(0, np.int32), bs)
    assert empty.n_tokens == 0 and empty.block_keys == []
    single = build_layout(np.arange(bs), bs)
    assert len(single.block_keys) == 1 and single.block_keys[0] is not None


def test_doc_block_keys_survive_reordering():
    bs = 16
    sys_toks = np.arange(bs)
    a, b = np.arange(bs) + 100, np.arange(bs) + 200
    lay_ab = build_layout(assemble_prompt([7] * 4, [a, b], system_tokens=sys_toks), bs)
    lay_ba = build_layout(assemble_prompt([7] * 4, [b, a], system_tokens=sys_toks), bs)
    # doc A occupies ordinal 1 in [sys,A,B] and ordinal 2 in [sys,B,A] — with
    # the SAME key, because its chain restarts at the segment boundary
    assert lay_ab.block_keys[1] == lay_ba.block_keys[2]
    assert lay_ab.block_keys[2] == lay_ba.block_keys[1]
    assert lay_ab.block_keys[0] == lay_ba.block_keys[0]  # shared prelude
    # doc positions restart at the prelude end; doc tokens attend prelude+self
    assert lay_ab.pos_ids[bs] == bs and lay_ab.pos_ids[2 * bs] == bs
    assert lay_ab.attn_p_end[bs] == bs and lay_ab.attn_s_start[2 * bs] == 2 * bs


def test_unaligned_segment_boundary_blocks_never_keyed():
    bs = 8
    docs = [np.arange(10) + 100, np.arange(10) + 200]  # 10-token docs: unaligned
    lay = build_layout(assemble_prompt(None, docs), bs)
    # doc0 spans slots [0,10): only block 0 lies fully inside; block 1
    # straddles doc0/doc1, block 2 straddles doc1's end — never shared
    assert lay.block_keys[0] is not None
    assert lay.block_keys[1] is None
    assert lay.block_keys[2] is None
    # the full block of an aligned doc still keys under a shifted prelude
    lay2 = build_layout(assemble_prompt(None, [np.arange(16) + 300]), bs)
    assert all(k is not None for k in lay2.block_keys)


def test_truncated_layout_drops_out_of_cap_keys():
    bs = 8
    doc = np.arange(32) + 50
    full = build_layout(assemble_prompt(np.arange(4), [doc]), bs)
    cut = build_layout(assemble_prompt(np.arange(4), [doc]), bs, cap=20)
    assert cut.n_tokens == 20
    assert len(cut.block_keys) == 3                  # ceil(20/8)
    assert cut.block_keys[0] == full.block_keys[0]   # same chain prefix
    assert cut.block_keys[2] is None                 # partial tail block


# ------------------------------------------------------- LRU warm cache


def test_free_releases_chain_tail_first():
    pool = PagedPool(n_blocks=8, block_size=4, keep_on_release=lambda b: True)
    blocks = pool.allocate(1, 12)  # 3-block chain
    pool.free(1)
    assert list(pool.cached) == list(reversed(blocks))  # head evicted last


def test_hot_prefix_block_outlives_cold_blocks():
    """Regression (LRU warm cache): a hot shared prefix — hit again even by a
    request that backpressures — must outlive cold one-off blocks that were
    released after it. The old insertion-order FIFO evicted the hot blocks
    first."""
    cfg = _cfg()
    bs = 4
    cache = PagedKVCache(cfg, n_blocks=10, block_size=bs, max_blocks_per_seq=8)
    hot_ctx = np.arange(8) % 90          # 2 blocks
    cold_ctx = np.arange(8) % 90 + 300   # 2 blocks, never reused
    assert cache.admit_tokens(1, hot_ctx) is not None
    cache.register_prefix(1, hot_ctx)
    cache.release(1)                     # hot blocks warm (released FIRST)
    assert cache.admit_tokens(2, cold_ctx) is not None
    cache.register_prefix(2, cold_ctx)
    cache.release(2)                     # cold blocks warm, younger than hot
    # an active request pins the whole free list (6 blocks), leaving only the
    # 4 warm blocks as headroom
    assert cache.admit_tokens(3, np.arange(20) % 90 + 600) is not None
    # a hot-prefixed request arrives but cannot fit -> backpressure; its
    # prefix-index hits must still re-heat the hot blocks
    big_hot = np.concatenate([hot_ctx, np.arange(12) % 90 + 50])
    assert cache.admit_tokens(4, big_hot) is None
    # eviction pressure: 2 blocks must come from the warm set -> cold ones
    assert cache.admit_tokens(5, np.arange(4) % 90 + 800) is not None
    cache.release(3)
    adm = cache.admit_tokens(6, np.concatenate([hot_ctx, [1, 2, 3, 4]]))
    assert adm is not None and adm.n_shared == 8, "hot prefix was evicted"
    cache.release(5)
    cache.release(6)
    adm_cold = cache.admit_tokens(7, np.concatenate([cold_ctx, [1, 2, 3, 4]]))
    assert adm_cold is not None and adm_cold.n_shared == 0  # cold was evicted


# ------------------------------------------------- segmented engine


def _doc_prompts(n_docs=4, doc_len=32, seed=0):
    rng = np.random.default_rng(seed)
    sys_toks = rng.integers(0, 300, 32)
    docs = [rng.integers(0, 300, doc_len) for _ in range(n_docs)]

    def prompt(order, query):
        return assemble_prompt(query, [docs[i] for i in order],
                               doc_ids=list(order), system_tokens=sys_toks)

    return prompt, rng


def test_shuffled_docs_hit_and_exact_parity():
    """A shuffled-document request must reuse every aligned doc block (and
    the system prefix), and caching must not change a single greedy token
    relative to prefix_sharing=False."""
    cfg = _cfg()
    prompt, rng = _doc_prompts()
    orders = [[0, 1, 2, 3], [2, 0, 3, 1], [3, 2, 1, 0]]
    queries = [rng.integers(0, 300, 8) for _ in orders]
    outs = {}
    for sharing in (False, True):
        eng = GenerationEngine(cfg, max_batch=1, max_seq=256,
                               prefix_sharing=sharing)
        reqs = []
        for o, q in zip(orders, queries):
            reqs.append(eng.submit(prompt(o, q), max_new=4))
            eng.run_until_done()
        outs[sharing] = [r.out_tokens for r in reqs]
        if sharing:
            # warm requests: system (32) + all docs (128) of the 168-token
            # prompt served from cache; only the 8-token query computes
            assert reqs[1].shared_prefix_tokens == 160
            assert reqs[2].shared_prefix_tokens == 160
            assert eng.measured_hit_rate() > 0.5
            assert eng.latency_summary()["prefix_hit_rate"] > 0.5
    assert outs[True] == outs[False]


def test_concurrent_segmented_burst_shares_doc_prefill():
    """A cold burst of same-document requests in different orders must not
    each prefill the shared documents: admission defers followers until the
    leader publishes its (order-independent) doc blocks."""
    cfg = _cfg()
    prompt, rng = _doc_prompts()
    eng = GenerationEngine(cfg, max_batch=4, max_seq=256)
    orders = [[0, 1, 2, 3], [2, 0, 3, 1], [3, 1, 0, 2]]
    reqs = [eng.submit(prompt(o, rng.integers(0, 300, 8)), max_new=3)
            for o in orders]
    eng.run_until_done()
    assert all(r.done for r in reqs)
    assert reqs[1].shared_prefix_tokens == 160  # system + all 4 docs
    assert reqs[2].shared_prefix_tokens == 160


def test_flat_chained_hash_misses_on_reorder():
    """The conservative fallback: identical token content submitted flat
    recovers ~nothing once document order changes."""
    cfg = _cfg()
    prompt, rng = _doc_prompts()
    eng = GenerationEngine(cfg, max_batch=1, max_seq=256)
    eng.submit(prompt([0, 1, 2, 3], rng.integers(0, 300, 8)).tokens, max_new=2)
    eng.run_until_done()
    r = eng.submit(prompt([1, 0, 3, 2], rng.integers(0, 300, 8)).tokens, max_new=2)
    eng.run_until_done()
    assert r.shared_prefix_tokens == 32  # system prefix only; docs all miss


def test_segmented_interleave_modes_agree():
    """Segmented prompts give the same greedy tokens whether sampled tokens
    materialize eagerly (the sync oracle) or one plan late (pipelined)."""
    cfg = _cfg()
    prompt, rng = _doc_prompts(n_docs=3)
    orders = [[0, 1, 2], [2, 1, 0]]
    queries = [rng.integers(0, 300, 8) for _ in orders]
    outs = {}
    for pipeline in (False, True):
        eng = GenerationEngine(cfg, max_batch=2, max_seq=256,
                               pipeline=pipeline, prefill_chunk_size=32)
        reqs = [eng.submit(prompt(o, q), max_new=5)
                for o, q in zip(orders, queries)]
        eng.run_until_done()
        assert eng.pipeline == pipeline
        assert reqs[1].shared_prefix_tokens > 0  # the docs really re-shared
        outs[pipeline] = [r.out_tokens for r in reqs]
    assert outs[True] == outs[False]


def test_segmented_preemption_recovers_exactly():
    """Pool exhaustion mid-decode preempts a segmented request; its re-queued
    continuation (segments + generated tail) must reproduce the
    unconstrained greedy tokens exactly."""
    cfg = _cfg()
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 300, 16) for _ in range(2)]

    def prompt(order, q):
        return assemble_prompt(q, [docs[i] for i in order], doc_ids=list(order))

    p1, p2 = prompt([0, 1], np.arange(6)), prompt([1, 0], np.arange(6) + 10)
    want = []
    for p in (p1, p2):
        big = GenerationEngine(cfg, max_batch=1, max_seq=128)
        r = big.submit(p, max_new=30)
        big.run_until_done()
        want.append(r.out_tokens)
    small = GenerationEngine(cfg, max_batch=2, max_seq=128, n_blocks=9,
                             prefix_sharing=False)
    got = [small.submit(p, max_new=30) for p in (p1, p2)]
    small.run_until_done(max_steps=500)
    assert all(r.done for r in got)
    assert small.preemptions >= 1
    assert [r.out_tokens for r in got] == want


# ------------------------------------- retrieval -> prompt -> engine


def test_retrieval_to_segmented_prompt_roundtrip():
    retriever, reranker, augmenter = Retriever(), Reranker(), Augmenter()
    docs = retriever.retrieve("what is patchwork", k=8)
    assert isinstance(docs, ScoredDocs) and len(docs.scores) == len(docs)
    top = reranker.rerank("what is patchwork", docs, top_n=3)
    assert isinstance(top, ScoredDocs) and list(top) == list(docs)[:3]
    store = DocTokenStore(vocab=300, doc_len=16)
    sp = augmenter.build_prompt(np.arange(5), top, store,
                                system_tokens=np.arange(8))
    assert isinstance(sp, SegmentedPrompt)
    kinds = [s.kind for s in sp.segments]
    assert kinds == ["system", "doc", "doc", "doc", "tail"]
    assert [s.doc_id for s in sp.segments[1:4]] == list(top)
    assert len(sp) == 8 + 3 * 16 + 5

    eng = GenerationEngine(_cfg(), max_batch=1, max_seq=128)
    gen = Generator(engine=eng)
    out = gen.generate(sp, max_new=3)
    assert len(out) == 3


# --------------------------------- measured hit rate -> cost model -> LP


def test_generator_uses_measured_hit_rate_from_engine():
    cfg = _cfg()
    eng = GenerationEngine(cfg, max_batch=1, max_seq=128)
    gen = Generator(engine=eng)
    ctx = np.arange(64) % 90
    eng.submit(np.concatenate([ctx, [5]]), max_new=2)
    eng.run_until_done()
    eng.submit(np.concatenate([ctx, [6]]), max_new=2)
    eng.run_until_done()
    measured = eng.measured_hit_rate()
    assert measured > 0.3                       # second request hit 64/65
    assert gen.effective_hit_rate() == measured  # live telemetry wins
    feats = {"tokens_in": 100, "docs_tokens": 5000, "tokens_out": 16}
    assert gen.estimate_time(feats) < gen.estimate_time(feats, hit_rate=0.0)
    coeffs = calibrate_generator_from_engine(gen, eng)
    assert 0.0 <= coeffs["prefix_hit_rate"] <= 1.0


def test_allocation_discounts_generator_by_hit_rate():
    """High measured hit rate -> scaled Generator alpha -> the LP provisions
    measurably fewer Generator replicas for the same offered load."""
    from repro.apps.rag_apps import make_vanilla_rag

    app = make_vanilla_rag()
    profile_components(app.components)
    gen = app.components["VGenerator"]
    assert app.workflow_graph.nodes["VGenerator"].alpha_hit_rate == 0.0
    budgets = {"GPU": 64, "CPU": 512, "RAM": 4096}
    feats = {"tokens_in": 16.0, "docs_tokens": 2000.0, "tokens_out": 64.0}
    scale = generator_alpha_scale(gen, features=feats, hit_rate=0.9)
    assert scale > 1.2
    cold = solve_allocation(app.workflow_graph, budgets, source_rate=200.0,
                            resource_penalty=1e-6)
    hot = solve_allocation(app.workflow_graph, budgets, source_rate=200.0,
                           resource_penalty=1e-6,
                           alpha_scale={"VGenerator": scale})
    assert cold.status == hot.status == "optimal"
    assert hot.throughput == pytest.approx(cold.throughput, rel=1e-3)
    assert hot.instances["VGenerator"] < cold.instances["VGenerator"]
