"""Sharded paged KV pools: mesh layouts, parity oracles, and contracts.

Single-device tests run in-process (a 1-device mesh must be bit-identical to
the unsharded engine — placement only, no math change). The real TP=4 run —
greedy-token parity vs the tp=1 oracle on a prefix-sharing RAG workload, plus
the collective-schedule audit (no all-gathers in the fused step, a fully
collective-free pool gather/scatter) — runs in a subprocess with 8 forced
host devices, like test_shardmap_tp.py.
"""
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.launch.mesh import make_mesh, make_serving_mesh, mesh_axis_sizes
from repro.serving.engine import DataParallelEngineGroup, GenerationEngine
from repro.serving.paged_cache import PagedKVCache, PagedPool
from repro.serving.segments import assemble_prompt
from repro.serving.sharded_pool import ShardedPoolLayout, block_range, make_pool_layout


def _rag_prompts(cfg, n=6, seed=0):
    """Shared-document RAG burst: overlapping doc ids in shuffled order, so
    prefix sharing (segment-scoped keys) actually fires."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, cfg.vocab_size, 24) for _ in range(4)]
    sys_toks = np.arange(16) % cfg.vocab_size
    prompts = []
    for i in range(n):
        order = rng.permutation(4)[:2]
        prompts.append(assemble_prompt(
            rng.integers(0, cfg.vocab_size, 7),
            [docs[j] for j in order],
            doc_ids=[int(j) for j in order],
            system_tokens=sys_toks,
        ))
    return prompts


# ---------------------------------------------------------------------------
# single-device: degenerate-mesh parity + pspec policy
# ---------------------------------------------------------------------------


def test_tp1_mesh_bit_identical_to_unsharded():
    """A 1-device ("model",) mesh changes array placement only: greedy tokens
    AND pool contents must be bit-identical to the layout-less engine on a
    prefix-sharing RAG workload."""
    cfg = smoke_variant(get_arch("smollm-135m"))

    ref = GenerationEngine(cfg, max_batch=3, max_seq=128, seed=0)
    ref_reqs = [ref.submit(p, max_new=8) for p in _rag_prompts(cfg)]
    ref.run_until_done()

    layout = ShardedPoolLayout(make_serving_mesh(tp=1))
    eng = GenerationEngine(cfg, max_batch=3, max_seq=128, seed=0, pool_layout=layout)
    reqs = [eng.submit(p, max_new=8) for p in _rag_prompts(cfg)]
    eng.run_until_done()

    assert eng.measured_hit_rate() > 0  # the workload actually shares prefixes
    assert [r.out_tokens for r in ref_reqs] == [r.out_tokens for r in reqs]
    np.testing.assert_array_equal(np.asarray(ref.kv.k), np.asarray(eng.kv.k))
    np.testing.assert_array_equal(np.asarray(ref.kv.v), np.asarray(eng.kv.v))
    assert eng.stats()["tp_degree"] == 1


def test_single_device_audits_collective_free():
    """On one device every step program is trivially communication-free —
    the audit plumbing itself must report that."""
    cfg = smoke_variant(get_arch("smollm-135m"))
    eng = GenerationEngine(cfg, max_batch=2, max_seq=64, seed=0,
                           pool_layout=ShardedPoolLayout(make_serving_mesh(tp=1)))
    for which in ("fused", "decode", "pool"):
        census = eng.audit_collectives(which)
        assert all(v == 0 for v in census.values()), (which, census)


def test_mesh_axis_sizes_roundtrip():
    """mesh_axis_sizes inverts make_mesh for every shape/axes pair the
    serving layer builds (single-device shapes here; multi-device in the
    subprocess test)."""
    for shape, axes in [((1,), ("model",)), ((1, 1), ("data", "model"))]:
        mesh = make_mesh(shape, axes)
        assert mesh_axis_sizes(mesh) == dict(zip(axes, shape))
    assert mesh_axis_sizes(make_serving_mesh(tp=1)) == {"model": 1}
    assert mesh_axis_sizes(make_serving_mesh(tp=1, dp=1)) == {"model": 1}
    with pytest.raises(ValueError):
        make_serving_mesh(tp=64, dp=64)  # more devices than any host has


def test_pool_pspec_policy():
    """KV-head dim shards over "model" only when divisible; block dim shards
    over "data" only when dp_blocks is requested; blocks NEVER shard over
    "model" (the block-table gather must stay shard-local)."""
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import pool_pspecs

    cfg = replace(smoke_variant(get_arch("qwen2.5-3b")), num_heads=8, num_kv_heads=4)
    assert pool_pspecs(cfg, {"model": 4}) == P(None, None, None, "model", None)
    assert pool_pspecs(cfg, {"model": 4, "data": 2}, dp_blocks=True) == \
        P(None, "data", None, "model", None)
    # indivisible KV heads: explicit policy leaves the dim unsharded
    cfg3 = replace(cfg, num_kv_heads=3, num_heads=9)
    assert pool_pspecs(cfg3, {"model": 4}) == P(None, None, None, None, None)
    assert pool_pspecs(cfg, {"model": 1}) == P(None, None, None, None, None)


def test_make_pool_layout_degenerate_is_none():
    """tp=1/dp=1 (or nothing) must return None: callers keep the legacy
    unsharded code path, which is the bit-parity guarantee. A dp>1 request
    with tp omitted is NOT degenerate (regression: `not tp` used to
    short-circuit it to None, silently dropping the DP request)."""
    assert make_pool_layout() is None
    assert make_pool_layout(tp=1) is None
    assert make_pool_layout(tp=1, dp=1) is None
    lay = make_pool_layout(tp=1, dp=1, dp_blocks=True)
    assert lay is None  # dp_blocks without a multi-axis mesh is still degenerate
    with pytest.raises(ValueError):
        make_pool_layout(dp=64)  # dp-only request reaches mesh construction
        # (and fails here only because one CPU device can't host 64 replicas)


# ---------------------------------------------------------------------------
# block-table contract (regression for the historical int32/-1 ambiguity)
# ---------------------------------------------------------------------------


def test_table_array_contract_int32_minus1():
    """The one contract every caller assumes: int32 dtype, -1 padding (never
    0 — block 0 is an ordinary allocatable block)."""
    pool = PagedPool(n_blocks=8, block_size=4)
    pool.allocate(7, 10)  # 3 blocks; free_list pops from the END, so block 0
    tbl = pool.table_array([7, 99], max_blocks=5)
    assert tbl.dtype == np.int32
    assert tbl.shape == (2, 5)
    assert list(tbl[0, :3]) == pool.tables[7]
    # padding is -1, not 0, even though block 0 exists and is allocatable
    assert set(tbl[0, 3:]) == {-1}
    assert set(tbl[1]) == {-1}  # unknown sequence: fully padded


def test_batch_tables_matches_table_array():
    cfg = smoke_variant(get_arch("smollm-135m"))
    kv = PagedKVCache(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=6)
    kv.admit_tokens(1, np.arange(9))
    bt = kv.batch_tables([1, 2])
    np.testing.assert_array_equal(bt, kv.pool.table_array([1, 2], kv.max_blocks))
    assert bt.dtype == np.int32 and bt[1, 0] == -1


def test_engine_consumers_honor_padding():
    """gather clamps -1 to block 0 and masks by validity; the fused step
    rewrites -1 entries to the scratch block before tracing. If either caller
    regressed to 0-padding, block 0's real contents would silently alias into
    foreign sequences — catch the contract at its consumers."""
    import jax.numpy as jnp

    from repro.serving.paged_cache import gather_paged_batch, paged_validity

    pool_kv = jnp.arange(2 * 4 * 2 * 1 * 1, dtype=jnp.float32).reshape(2, 4, 2, 1, 1)
    row = np.array([[2, -1, -1]], np.int32)
    gathered = gather_paged_batch(pool_kv, jnp.asarray(row))
    # padded entries read block 0 (clamped) ...
    np.testing.assert_array_equal(
        np.asarray(gathered[:, 0, 2:4, 0, 0]), np.asarray(pool_kv[:, 0, :, 0, 0])
    )
    # ... and validity masks exactly the unbacked/overlength slots
    valid = np.asarray(paged_validity(jnp.asarray(row[0]), 2, 2, 3))
    assert list(valid) == [True, True, False, False, False, False]


# ---------------------------------------------------------------------------
# DP: block ranges + independent admission
# ---------------------------------------------------------------------------


def test_block_range_partition():
    assert block_range(10, 2, 0) == (0, 5)
    assert block_range(10, 2, 1) == (5, 10)
    assert block_range(10, 3, 2) == (6, 10)  # remainder to the last replica
    spans = [block_range(10, 3, r) for r in range(3)]
    assert spans[0][0] == 0 and spans[-1][1] == 10
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))  # disjoint cover
    with pytest.raises(ValueError):
        block_range(10, 2, 2)


def test_paged_cache_block_range_restricts_admission():
    cfg = smoke_variant(get_arch("smollm-135m"))
    kv = PagedKVCache(cfg, n_blocks=16, block_size=4, block_range=(8, 12))
    assert kv.pool.n_owned == 4 and kv.pool.n_free == 4
    adm = kv.admit_tokens(1, np.arange(8))  # 2 prompt blocks + 1 slack
    assert adm is not None
    assert all(8 <= b < 12 for b in kv.pool.tables[1])
    assert kv.admit_tokens(2, np.arange(8)) is None  # range exhausted: backpressure
    assert 0.74 < kv.utilization() <= 1.0  # utilization is over OWNED blocks
    with pytest.raises(ValueError):
        PagedKVCache(cfg, n_blocks=16, block_range=(12, 20))


def test_dp_group_independent_admission_and_parity():
    """Two replicas over one shared pool array: disjoint block ranges, both
    serve traffic, and greedy outputs match the lone-engine oracle."""
    cfg = smoke_variant(get_arch("smollm-135m"))

    ref = GenerationEngine(cfg, max_batch=3, max_seq=128, seed=0)
    ref_reqs = [ref.submit(p, max_new=8) for p in _rag_prompts(cfg)]
    ref.run_until_done()

    grp = DataParallelEngineGroup(cfg, dp=2, max_batch=3, max_seq=128, seed=0)
    reqs = [grp.submit(p, max_new=8) for p in _rag_prompts(cfg)]
    grp.run_until_done()

    assert [r.out_tokens for r in ref_reqs] == [r.out_tokens for r in reqs]
    e0, e1 = grp.engines
    assert e0.kv._arrays is e1.kv._arrays  # one shared pool array
    owned0 = set(e0.kv.pool.free_list) | set(e0.kv.pool.refcounts) | set(e0.kv.pool.cached)
    owned1 = set(e1.kv.pool.free_list) | set(e1.kv.pool.refcounts) | set(e1.kv.pool.cached)
    assert not owned0 & owned1  # admission stayed in disjoint block ranges
    st = grp.stats()
    assert st["dp_degree"] == 2 and st["tokens_out"] == 8 * len(reqs)
    assert all(s["tokens_out"] > 0 for s in st["replicas"])  # both replicas served


# ---------------------------------------------------------------------------
# cost model + LP: the tp_degree term
# ---------------------------------------------------------------------------


def test_generator_tp_speedup_and_estimates():
    from repro.core.components import Generator

    g1, g4 = Generator(), Generator(tp_degree=4)
    assert g1.tp_speedup() == 1.0
    s4 = g4.tp_speedup()
    assert 1.0 < s4 < 4.0  # sub-linear: collectives don't parallelize
    feats = {"tokens_in": 128, "docs_tokens": 2000, "tokens_out": 64}
    assert g4.estimate_time(feats) < g1.estimate_time(feats)
    assert g4.estimate_ttft(feats) < g1.estimate_ttft(feats)
    # the flat engine overhead does not shrink with the mesh
    assert g4.estimate_time({"tokens_in": 0, "docs_tokens": 0, "tokens_out": 0}) \
        == pytest.approx(g1.base_time_s)


def test_fit_tp_comm_fraction_inverts_speedup_model():
    from repro.core.components import Generator
    from repro.core.profiling import fit_tp_comm_fraction

    g = Generator(tp_degree=4)
    f = fit_tp_comm_fraction(4, g.tp_speedup())  # round-trip the model
    assert f == pytest.approx(g.tp_comm_fraction)
    assert fit_tp_comm_fraction(1, 1.0) == 0.0
    assert fit_tp_comm_fraction(4, 5.0) == 0.0   # super-linear clamps to 0
    assert fit_tp_comm_fraction(4, 0.5) == 1.0   # slowdown clamps to 1
    g.calibrate({"tp_comm_fraction": 0.2})
    assert g.tp_comm_fraction == 0.2 and g.tp_speedup() < 4 / (1 + 0.08 * 3)


def test_solve_allocation_tp_degree_term():
    """A tp-sharded component burns t chips per replica at sub-linear per-chip
    efficiency: plan throughput can only drop, replica counts reflect t-chip
    bundles, and tp=1 (or no dict) leaves the solution untouched."""
    from repro.core.allocation import random_graph, solve_allocation

    g = random_graph(6, seed=0)
    budgets = {"CPU": 64, "GPU": 16}
    base = solve_allocation(g, budgets)
    same = solve_allocation(g, budgets, tp_degree={"c3": 1})
    assert same.throughput == pytest.approx(base.throughput)
    assert same.instances == base.instances

    tp = solve_allocation(g, budgets, tp_degree={"c3": 4})
    assert tp.status == "optimal"
    assert tp.throughput <= base.throughput + 1e-9
    # per-component efficiency dict (the controller's calibrated path)
    # overrides the default model: a worse efficiency can only cost capacity
    worse = solve_allocation(g, budgets, tp_degree={"c3": 4},
                             tp_efficiency={"c3": 0.3})
    assert worse.throughput <= tp.throughput + 1e-9
    dom_alloc = tp.resources["c3"]
    base_alloc = base.resources["c3"]
    # per-replica bundle is 4x: same resource units -> ~1/4 the replicas
    for rt in dom_alloc:
        if base_alloc[rt] > 0 and base.instances["c3"] >= 4:
            assert tp.instances["c3"] <= base.instances["c3"]
            break


# ---------------------------------------------------------------------------
# the real thing: tp=4 on 8 forced host devices (subprocess)
# ---------------------------------------------------------------------------

TP4_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
from dataclasses import replace
import numpy as np
import pytest
from repro.configs import get_arch, smoke_variant
from repro.launch.mesh import make_mesh, make_serving_mesh, mesh_axis_sizes
from repro.serving.engine import GenerationEngine
from repro.serving.segments import assemble_prompt
from repro.serving.sharded_pool import ShardedPoolLayout

# a GQA config whose heads divide tp=4 (smoke default kv=2 does not)
cfg = replace(smoke_variant(get_arch("qwen2.5-3b")), num_heads=8, num_kv_heads=4)

rng = np.random.default_rng(0)
docs = [rng.integers(0, cfg.vocab_size, 24) for _ in range(4)]
def prompts():
    r = np.random.default_rng(1)
    out = []
    for i in range(5):
        order = r.permutation(4)[:2]
        out.append(assemble_prompt(
            r.integers(0, cfg.vocab_size, 7),
            [docs[j] for j in order], doc_ids=[int(j) for j in order],
            system_tokens=np.arange(16) % cfg.vocab_size,
        ))
    return out

# multi-device mesh round-trips
assert mesh_axis_sizes(make_mesh((2, 4), ("data", "model"))) == {"data": 2, "model": 4}
assert mesh_axis_sizes(make_serving_mesh(tp=4, dp=2)) == {"data": 2, "model": 4}

# explicit layout validation: indivisible heads are rejected, not degraded
bad = replace(cfg, num_kv_heads=3, num_heads=9)
try:
    ShardedPoolLayout(make_serving_mesh(tp=4)).validate(bad)
    raise SystemExit("validate() should have rejected kv_heads=3 @ tp=4")
except ValueError:
    pass

# tp=1 oracle (plain single-device engine semantics on device 0)
ref = GenerationEngine(cfg, max_batch=3, max_seq=128, seed=0)
ref_reqs = [ref.submit(p, max_new=8) for p in prompts()]
ref.run_until_done()
assert ref.measured_hit_rate() > 0.1, ref.measured_hit_rate()

# tp=4 sharded-pool engine
layout = ShardedPoolLayout(make_serving_mesh(tp=4))
eng = GenerationEngine(cfg, max_batch=3, max_seq=128, seed=0, pool_layout=layout)
reqs = [eng.submit(p, max_new=8) for p in prompts()]
eng.run_until_done()

assert [r.out_tokens for r in ref_reqs] == [r.out_tokens for r in reqs], \
    "tp=4 greedy tokens diverged from the tp=1 oracle"
assert abs(eng.measured_hit_rate() - ref.measured_hit_rate()) < 1e-9
assert eng.stats()["tp_degree"] == 4

# pool arrays really are sharded over the model axis by KV head
spec = eng.kv.k.sharding.spec
assert tuple(spec) == (None, None, None, "model", None), spec

# collective-schedule audit: the fused interleaved step and the batched
# decode may communicate ONLY through all-reduces (the Megatron post-
# attention/post-MLP output reductions); the bare pool gather/scatter
# roundtrip (the decode chunk-scatter path) is collective-free entirely
fused = eng.audit_collectives("fused")
assert fused["all-gather"] == 0, fused
assert fused["all-to-all"] == 0 and fused["reduce-scatter"] == 0, fused
assert fused["all-reduce"] > 0, fused
decode = eng.audit_collectives("decode")
assert decode["all-gather"] == 0, decode
pool = eng.audit_collectives("pool")
assert all(v == 0 for v in pool.values()), pool
print("SHARDED_POOL_TP4_OK", fused)
"""


@pytest.mark.slow
def test_tp4_parity_and_collective_schedule():
    res = subprocess.run(
        [sys.executable, "-c", TP4_SCRIPT],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-4000:])
    assert "SHARDED_POOL_TP4_OK" in res.stdout


DP2_AUDIT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
from repro.analysis.jaxpr_audit import audit_engine
from repro.configs import get_arch, smoke_variant
from repro.launch.mesh import make_serving_mesh
from repro.serving.engine import DataParallelEngineGroup
from repro.serving.sharded_pool import ShardedPoolLayout

cfg = smoke_variant(get_arch("smollm-135m"))
for dp_blocks in (True, False):
    layout = ShardedPoolLayout(make_serving_mesh(tp=1, dp=2),
                               dp_blocks=dp_blocks)
    grp = DataParallelEngineGroup(cfg, dp=2, max_batch=2, max_seq=64,
                                  pool_layout=layout)
    for i, eng in enumerate(grp.engines):
        fused = eng.audit_collectives("fused")
        decode = eng.audit_collectives("decode")
        pool = eng.audit_collectives("pool")
        # the block-table gather/scatter NEVER all-gathers, on any replica,
        # sharded blocks or not; nothing reshards (no a2a/reduce-scatter)
        for c in (fused, decode, pool):
            assert c["all-gather"] == 0, (dp_blocks, i, c)
            assert c["all-to-all"] == 0 and c["reduce-scatter"] == 0, \
                (dp_blocks, i, c)
        if dp_blocks:
            # GSPMD partitions the block-axis gather into a masked LOCAL
            # gather plus a bounded data-axis all-reduce combine: at most
            # one combine per pool read (k+v in the step programs, one in
            # the bare roundtrip) — never a block all-gather
            assert 0 < fused["all-reduce"] <= 2, (i, fused)
            assert 0 < decode["all-reduce"] <= 2, (i, decode)
            assert 0 < pool["all-reduce"] <= 1, (i, pool)
        else:
            # replicated blocks: replicas compute independently, every
            # step program is collective-free entirely
            for c in (fused, decode, pool):
                assert all(v == 0 for v in c.values()), (i, c)
    # the full declarative contract audit (repro.analysis) holds per replica
    report = audit_engine(grp.engines[0], warm=False)
    assert report.ok, report.render()
print("SHARDED_POOL_DP2_AUDIT_OK")
"""


@pytest.mark.slow
def test_dp2_collective_audit_both_block_layouts():
    """DP-mesh audit_collectives coverage (DataParallelEngineGroup): with
    dp_blocks the partitioner may insert only bounded data-axis all-reduce
    combines; with replicated blocks every step program is collective-free.
    Zero all-gathers in every configuration, on every replica."""
    res = subprocess.run(
        [sys.executable, "-c", DP2_AUDIT_SCRIPT],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-4000:])
    assert "SHARDED_POOL_DP2_AUDIT_OK" in res.stdout
