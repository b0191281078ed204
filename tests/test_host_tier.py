"""Host-memory KV tier: store unit contracts, swap-preemption parity,
demote/promote lifecycle, cross-replica sharing, the host-aware cost-model
feedback, and the paged-cache accounting bugfix sweep (warm-revival
double-count, span normalization, O(1) warm LRU, measured-hit-rate
cold-start clamp)."""
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.core.components import Generator
from repro.core.profiling import generator_alpha_scale
from repro.serving.engine import (
    DataParallelEngineGroup,
    GenerationEngine,
    Request,
    _advance_cursor,
    _max_grant,
    normalize_spans,
)
from repro.serving.host_tier import HostBlockStore
from repro.serving.paged_cache import PagedKVCache, PagedPool
from repro.serving.segments import assemble_prompt, build_layout


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


# --------------------------------------------------------- store unit tests


def test_host_store_keyed_lifecycle_and_lru():
    store = HostBlockStore((2, 4, 1, 8), np.float32, n_blocks=3)
    blk = lambda fill: np.full((2, 4, 1, 8), fill, np.float32)
    assert store.put(b"a", blk(1), blk(-1), owner=0)
    assert store.put(b"b", blk(2), blk(-2), owner=0)
    assert store.contains(b"a") and not store.contains(b"z")
    # re-put of a resident key only re-heats (contents immutable by contract)
    assert store.put(b"a", blk(9), blk(9), owner=1)
    assert store.puts == 2
    k, v = store.read([b"a", b"b"], owner=1)
    assert k.shape == (2, 2, 4, 1, 8)  # (G, n_keys, bs, KVH, hd)
    np.testing.assert_array_equal(k[:, 0], blk(1))
    np.testing.assert_array_equal(v[:, 1], blk(-2))
    assert store.hits == 2 and store.cross_hits == 2  # owner 1 read owner 0's
    # capacity pressure evicts the LRU keyed slot: the read touched 'a' then
    # 'b', so 'a' is the oldest once 'c' consumes the last free slot
    assert store.put(b"c", blk(3), blk(-3))
    assert store.put(b"d", blk(4), blk(-4))
    assert store.evictions == 1 and not store.contains(b"a")
    assert store.contains(b"b") and store.contains(b"c")
    assert len(store.free) + store.n_keyed + store.n_swapped == store.n_blocks


def test_host_store_swap_sets_are_pinned_and_all_or_nothing():
    store = HostBlockStore((1, 2, 1, 2), np.float32, n_blocks=4)
    chain = lambda n, fill: np.full((1, n, 2, 1, 2), fill, np.float32)
    store.put(b"k1", chain(1, 7)[:, 0], chain(1, 7)[:, 0])
    assert store.save_seq("s1", chain(3, 1), chain(3, -1))
    # 3 pinned + 1 keyed: a 2-block swap set cannot fit (keyed eviction frees
    # only 1) -> all-or-nothing refusal, nothing pinned
    assert not store.save_seq("s2", chain(2, 2), chain(2, -2))
    assert store.n_swapped == 3
    with pytest.raises(ValueError):
        store.save_seq("s1", chain(1, 0), chain(1, 0))  # duplicate tag
    k, v = store.restore_seq("s1")
    np.testing.assert_array_equal(k, chain(3, 1))
    np.testing.assert_array_equal(v, chain(3, -1))
    assert store.n_swapped == 0
    assert len(store.free) + store.n_keyed == store.n_blocks
    store.drop_seq("missing")  # no-op, never raises


# -------------------------------------------------- swap preemption parity


def _pressure_engine(cfg, preempt, **kw):
    return GenerationEngine(cfg, max_batch=2, max_seq=64, n_blocks=8,
                            prefix_sharing=False, preempt=preempt, **kw)


def test_swap_preemption_matches_unconstrained_oracle():
    """Swap-out preemption must reproduce the unconstrained greedy tokens
    exactly (the same oracle the recompute strategy is held to), restore
    every swap set, and drain leak-free in BOTH tiers."""
    cfg = _cfg()
    prompts = [np.arange(30) % 90, np.arange(30) % 90 + 1]
    big = GenerationEngine(cfg, max_batch=2, max_seq=64)
    want = []
    for p in prompts:
        r = big.submit(p, max_new=24)
        big.run_until_done()
        want.append(r.out_tokens)

    eng = _pressure_engine(cfg, "swap")
    got = [eng.submit(p, max_new=24) for p in prompts]
    eng.run_until_done(max_steps=500)
    assert all(r.done for r in got)
    assert eng.swap_outs >= 1 and eng.swap_ins == eng.swap_outs
    assert [r.out_tokens for r in got] == want
    # device tier clean (scratch block only) and host tier refcount-clean
    assert eng.kv.pool.n_free == eng.kv.pool.n_blocks - 1
    hs = eng.host_store
    assert hs.n_swapped == 0
    assert len(hs.free) + hs.n_keyed == hs.n_blocks


def test_swap_and_recompute_token_identical_under_churn():
    """The two preemption strategies are interchangeable observationally:
    identical greedy streams on a bursty mixed workload (pipelined dispatch
    and the eager sync oracle)."""
    cfg = _cfg()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 90, size=int(rng.integers(4, 24)))
               for _ in range(5)]
    outs = {}
    for pipeline in (True, False):
        for mode in ("recompute", "swap"):
            eng = _pressure_engine(cfg, mode, pipeline=pipeline)
            reqs = [eng.submit(p, max_new=18) for p in prompts]
            eng.run_until_done(max_steps=2000)
            assert all(r.done for r in reqs)
            outs[(pipeline, mode)] = [r.out_tokens for r in reqs]
        assert outs[(pipeline, "swap")] == outs[(pipeline, "recompute")]


def test_swap_falls_back_to_recompute_when_host_tier_full():
    """A host store too small to pin any chain must not wedge the engine:
    every preemption falls back to recompute and the workload still drains
    with oracle-exact tokens."""
    cfg = _cfg()
    tiny = HostBlockStore.for_config(cfg, n_blocks=1, block_size=16)
    prompts = [np.arange(30) % 90, np.arange(30) % 90 + 1]
    big = GenerationEngine(cfg, max_batch=2, max_seq=64)
    want = []
    for p in prompts:
        r = big.submit(p, max_new=24)
        big.run_until_done()
        want.append(r.out_tokens)
    eng = _pressure_engine(cfg, "swap", host_store=tiny)
    got = [eng.submit(p, max_new=24) for p in prompts]
    eng.run_until_done(max_steps=500)
    assert all(r.done for r in got)
    assert eng.preemptions >= 1 and eng.swap_outs == 0  # all fell back
    assert [r.out_tokens for r in got] == want


def test_swap_tags_namespaced_across_dp_replicas():
    """Regression: DP replicas number req_ids independently but share one
    host store — swap sets must be namespaced by replica or concurrent
    swap-outs of same-id requests collide (save_seq raises)."""
    cfg = _cfg()
    grp = DataParallelEngineGroup(cfg, dp=2, max_batch=2, max_seq=64,
                                  n_blocks_per_replica=8, preempt="swap",
                                  prefix_sharing=False)
    e0, e1 = grp.engines
    reqs = []
    for eng, off in ((e0, 0), (e1, 1)):
        reqs += [eng.submit(np.arange(30) % 90 + off + 3 * i, max_new=24)
                 for i in range(2)]
    r0, r1 = reqs[0], reqs[2]
    assert r0.req_id == r1.req_id  # the collision setup
    assert e0._swap_tag(r0) != e1._swap_tag(r1)
    grp.run_until_done(max_steps=2000)  # must not raise on concurrent swaps
    assert all(r.done for r in reqs)
    assert e0.swap_outs + e1.swap_outs >= 1
    assert grp.host_store.n_swapped == 0


# ------------------------------------------------ demote / promote lifecycle


def test_warm_eviction_demotes_and_admission_promotes():
    """A document evicted from the warm HBM LRU must come back as a host-tier
    hit: admission promotes its blocks (one copy, zero prefill) and the
    decode is token-exact vs a cold engine."""
    cfg = _cfg()
    eng = GenerationEngine(cfg, max_batch=1, max_seq=128, n_blocks=10,
                           host_blocks=32)
    ctx = np.arange(64) % 90
    r1 = eng.submit(np.concatenate([ctx, [5]]), max_new=2)
    eng.run_until_done()
    assert r1.done and eng.host_store.puts == 0  # nothing evicted yet
    # churn through fresh prompts until the warm ctx blocks are reclaimed —
    # each reclamation must demote the block's contents to the host store
    for i in range(3):
        eng.submit(np.arange(40) % 90 + 100 + 17 * i, max_new=2)
        eng.run_until_done()
    assert eng.host_store.puts > 0
    prefill_before = eng.prefill_tokens
    r2 = eng.submit(np.concatenate([ctx, [6]]), max_new=3)
    eng.run_until_done()
    assert r2.host_prefix_tokens > 0  # the second-chance hit class
    assert r2.host_prefix_tokens + r2.shared_prefix_tokens >= 48
    # promoted spans are skipped by the prefill cursor like HBM hits
    assert eng.prefill_tokens - prefill_before <= 17
    cold = GenerationEngine(cfg, max_batch=1, max_seq=128, prefix_sharing=False)
    rc = cold.submit(np.concatenate([ctx, [6]]), max_new=3)
    cold.run_until_done()
    assert r2.out_tokens == rc.out_tokens
    # promotion re-published the keys: a third request HBM-hits
    r3 = eng.submit(np.concatenate([ctx, [7]]), max_new=2)
    eng.run_until_done()
    assert r3.shared_prefix_tokens >= 48 and r3.host_prefix_tokens == 0


def test_cross_replica_host_hits_in_dp_group():
    """A doc prefilled on replica 0 must be a host hit on replica 1 (shared
    write-through store), token-exact vs a lone engine, with the cross-hit
    counter attributing the transfer."""
    cfg = _cfg()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 300, 32) for _ in range(3)]

    def prompt(order, q):
        return assemble_prompt(q, [docs[j] for j in order], doc_ids=list(order),
                               system_tokens=np.arange(16))

    grp = DataParallelEngineGroup(cfg, dp=2, max_batch=2, max_seq=192,
                                  host_blocks=64)
    p0, p1 = prompt([0, 1, 2], np.arange(8)), prompt([2, 0, 1], np.arange(8) + 50)
    r0 = grp.engines[0].submit(p0, max_new=3)
    grp.run_until_done()
    r1 = grp.engines[1].submit(p1, max_new=3)
    grp.run_until_done()
    assert r0.done and r1.done
    assert r1.host_prefix_tokens > 0 and r1.shared_prefix_tokens == 0
    st = grp.stats()
    assert st["cross_replica_host_hits"] > 0
    assert st["host_hit_tokens"] == r1.host_prefix_tokens
    lone = GenerationEngine(cfg, max_batch=2, max_seq=192)
    a = lone.submit(p0, max_new=3)
    lone.run_until_done()
    b = lone.submit(p1, max_new=3)
    lone.run_until_done()
    assert (r0.out_tokens, r1.out_tokens) == (a.out_tokens, b.out_tokens)


# ------------------------------------- satellite: warm-revival double-count


def test_admit_counts_duplicate_warm_hits_once():
    """Regression (admit_tokens capacity accounting): two segments hashing to
    the SAME warm block must charge ONE revival against n_free — the old
    per-ordinal count rejected exact-fit admissions that acquire/revive could
    actually satisfy."""
    cfg = _cfg()
    bs = 4
    kv = PagedKVCache(cfg, n_blocks=8, block_size=bs, max_blocks_per_seq=8)
    doc = np.arange(bs) + 100
    # [doc][doc][query]: both doc ordinals key identically -> one physical block
    dup = assemble_prompt(np.arange(4), [doc, doc])
    lay = build_layout(dup, bs)
    assert lay.block_keys[0] == lay.block_keys[1]  # the duplicate-key setup
    assert kv.admit_tokens(1, dup.tokens, lay) is not None
    kv.register_prefix(1, dup.tokens, lay)
    kv.release(1)  # the keyed doc + tail blocks park in the warm LRU
    assert len(kv.pool.cached) == 2
    # pin 5 of the free blocks, leaving n_free == 3 (1 free + 2 warm)
    kv.pool.allocate(99, 5 * bs)
    assert kv.pool.n_free == 3
    # re-admission needs exactly 3: 1 unique warm revival + 2 fresh (the
    # final-token block + decode slack). The double-count made this 4 > 3.
    adm = kv.admit_tokens(2, dup.tokens, build_layout(dup, bs))
    assert adm is not None, "exact-fit admission spuriously rejected"
    assert adm.n_shared == 2 * bs  # both ordinals served from the one block
    assert kv.pool.n_free == 0     # consumed exactly n_new + unique warm
    table = kv.pool.tables[2]
    assert table[0] == table[1] and kv.pool.refcounts[table[0]] == 2
    # and no leak on the way out: everything returns except the pinned seq
    kv.release(2)
    kv.pool.free(99)
    assert kv.pool.n_free == kv.pool.n_blocks


def test_admit_backpressure_below_exact_fit_is_all_or_nothing():
    cfg = _cfg()
    bs = 4
    kv = PagedKVCache(cfg, n_blocks=8, block_size=bs, max_blocks_per_seq=8)
    doc = np.arange(bs) + 100
    dup = assemble_prompt(np.arange(4), [doc, doc])
    lay = build_layout(dup, bs)
    assert kv.admit_tokens(1, dup.tokens, lay) is not None
    kv.register_prefix(1, dup.tokens, lay)
    kv.release(1)
    kv.pool.allocate(99, 5 * bs)
    kv.pool.allocate(98, 1 * bs)  # n_free == 2 < the 3 required
    free_before = (list(kv.pool.free_list), list(kv.pool.cached),
                   dict(kv.pool.refcounts))
    assert kv.admit_tokens(2, dup.tokens, build_layout(dup, bs)) is None
    assert (list(kv.pool.free_list), list(kv.pool.cached),
            dict(kv.pool.refcounts)) == free_before
    assert 2 not in kv.pool.tables


# --------------------------------------- satellite: span normalization


def test_normalize_spans_sorts_merges_and_drops_empties():
    assert normalize_spans([]) == []
    assert normalize_spans([(5, 5), (9, 7)]) == []
    assert normalize_spans([(32, 48), (0, 16), (8, 24)]) == [(0, 24), (32, 48)]
    assert normalize_spans([(0, 16), (16, 32)]) == [(0, 32)]  # adjacent coalesce
    assert normalize_spans([(0, 16), (0, 16)]) == [(0, 16)]   # duplicates
    assert normalize_spans([(16, 64), (0, 80)]) == [(0, 80)]  # containment


def test_cursor_advance_over_unsorted_overlapping_spans():
    """Regression: out-of-order/overlapping shared spans must neither strand
    the cursor inside a cached span nor jump it over an uncached gap, and
    grants must stop at the next span boundary."""
    req = Request(req_id=0, prompt=np.arange(64), max_new=1)
    req.prefill_cap = 64
    req.shared_spans = normalize_spans([(32, 48), (0, 16), (8, 24)])
    req.prefill_pos = 0
    _advance_cursor(req)
    assert req.prefill_pos == 24  # NOT 48: [24, 32) is an uncached gap
    assert _max_grant(req, 100) == 8  # clipped at the next span start (32)
    req.prefill_pos += 8
    _advance_cursor(req)
    assert req.prefill_pos == 48  # hops the second span
    assert _max_grant(req, 100) == 16  # the uncached tail [48, 64)
    # a cursor landing mid-span (e.g. restored state) still escapes it
    req.prefill_pos = 40
    req.shared_spans = normalize_spans([(32, 48)])
    _advance_cursor(req)
    assert req.prefill_pos == 48
    # spans past the cap clamp to the cap
    req.prefill_cap = 40
    req.prefill_pos = 32
    _advance_cursor(req)
    assert req.prefill_pos == 40


# ------------------------------------------- satellite: O(1) warm-LRU ops


def test_warm_lru_order_preserved_and_o1_ops():
    """The warm queue is an insertion-ordered dict: eviction pops the oldest,
    touch/revive are O(1) dict ops, and the LRU semantics survived the
    list -> dict migration."""
    pool = PagedPool(n_blocks=6, block_size=4, keep_on_release=lambda b: True)
    assert isinstance(pool.cached, dict)  # O(1) membership/remove by design
    a = pool.allocate(1, 8)   # 2 blocks
    b = pool.allocate(2, 8)
    pool.free(1)              # a's chain warms first (tail-first order)
    pool.free(2)
    order = list(pool.cached)
    assert order == list(reversed(a)) + list(reversed(b))
    # touch re-heats to the MRU end without disturbing the rest
    pool.touch(order[0])
    assert list(pool.cached) == order[1:] + [order[0]]
    # revive via share removes from the queue in O(1)
    pool.share(3, order[1])
    assert order[1] not in pool.cached and pool.refcounts[order[1]] == 1
    # eviction under pressure pops exactly the LRU head order
    pool.allocate(4, 2 * 4)   # consumes the 2 remaining free blocks
    evicted = pool._pop_block()
    assert evicted == order[2]  # oldest surviving warm block
    assert list(pool.cached) == [order[3], order[0]]


# ------------------------------- satellite: measured hit-rate cold start


def test_measured_hit_rate_cold_start_clamp():
    cfg = _cfg()
    eng = GenerationEngine(cfg, max_batch=1, max_seq=64)
    # empty window and window=0 both return the documented cold default
    assert eng.measured_hit_rate() == eng.cold_start_hit_rate == 0.0
    assert eng.measured_hit_rate(window=0) == 0.0
    assert eng.measured_hit_rate(default=0.7) == 0.7
    # a single tiny finished request (below the min-token window) must NOT
    # swing the measured rate to 1.0 — that's the alpha_scale stampede
    r = Request(req_id=0, prompt=np.arange(4), max_new=1)
    r.prefill_cap = 4
    r.shared_prefix_tokens = 4
    eng.finished.append(r)
    assert eng.measured_hit_rate(default=0.25) == 0.25
    assert eng.measured_host_hit_rate(default=0.25) == 0.25
    # once the window is warm, the measurement wins
    big = Request(req_id=1, prompt=np.arange(96), max_new=1)
    big.prefill_cap = 96
    big.shared_prefix_tokens = 48
    big.host_prefix_tokens = 24
    eng.finished.append(big)
    assert eng.measured_hit_rate(default=0.25) == pytest.approx(52 / 100)
    assert eng.measured_host_hit_rate(default=0.25) == pytest.approx(24 / 100)
    # windows smaller than one request still clamp consistently
    assert eng.measured_hit_rate(window=1, min_tokens=200, default=0.5) == 0.5


def test_generator_falls_back_to_static_rate_on_cold_engine():
    """The controller-visible behavior: a Generator attached to a just-started
    engine bills its configured/calibrated static rates, not a noisy (or
    empty) first-window measurement."""
    cfg = _cfg()
    eng = GenerationEngine(cfg, max_batch=1, max_seq=64)
    gen = Generator(engine=eng)
    gen.calibrate({"prefix_hit_rate": 0.6, "host_hit_rate": 0.2})
    assert gen.effective_hit_rate() == 0.6      # cold engine -> static
    assert gen.effective_host_hit_rate() == 0.2
    # the alpha_scale feedback therefore stays put instead of stampeding
    scale = generator_alpha_scale(gen, hit_rate=gen.effective_hit_rate(),
                                  baseline_hit_rate=0.6,
                                  host_hit_rate=gen.effective_host_hit_rate(),
                                  baseline_host_hit_rate=0.2)
    assert scale == pytest.approx(1.0)


# ------------------------------------------- host-aware cost model + LP


def test_generator_host_hit_rate_discounts_between_tiers():
    g = Generator()
    feats = {"tokens_in": 100, "docs_tokens": 10000, "tokens_out": 32}
    cold = g.estimate_time(feats, hit_rate=0.0, host_hit_rate=0.0)
    host = g.estimate_time(feats, hit_rate=0.0, host_hit_rate=0.9)
    hbm = g.estimate_time(feats, hit_rate=0.9, host_hit_rate=0.0)
    assert hbm < host < cold  # promotion is cheap, HBM hits are free
    ttft_host = g.estimate_ttft(feats, hit_rate=0.0, host_hit_rate=0.9)
    assert ttft_host < g.estimate_ttft(feats, hit_rate=0.0, host_hit_rate=0.0)
    # tiers partition the prompt: host share clamps into the HBM remainder
    both = g.estimate_time(feats, hit_rate=0.8, host_hit_rate=0.8)
    assert both >= g.estimate_time(feats, hit_rate=0.8, host_hit_rate=0.2)
    scale = generator_alpha_scale(g, features=feats, hit_rate=0.0,
                                  host_hit_rate=0.9)
    assert scale > 1.2  # host tier alone buys real LP capacity


def test_controller_exports_host_hit_rate_gauge():
    from repro.apps.rag_apps import make_vanilla_rag
    from repro.core.controller import PatchworkRuntime
    from repro.data.workload import make_workload

    app = make_vanilla_rag()
    rt = PatchworkRuntime(app, {"GPU": 8, "CPU": 64, "RAM": 256}, slo_s=2.0)
    rt.run(make_workload(rate=8, duration_s=12, seed=0))
    names = set(rt.telemetry.gauges)
    assert any(n.startswith("host_hit_rate/") for n in names), names
    assert any(n.startswith("prefix_hit_rate/") for n in names), names
