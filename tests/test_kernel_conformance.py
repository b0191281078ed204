"""Kernel conformance suite: the Pallas hot-path kernels against jnp oracles.

Seeded property sweeps drive ``paged_decode_attention`` and
``paged_chunk_attention`` through randomized shapes and the edge geometry the
serving engine actually produces — length-1 rows, block-boundary-exact
lengths, single- and multi-block tables, ragged decode+prefill mixes,
RAW block tables with -1 pad entries (and interior holes), packed pad tokens,
and non-power-of-two head dims. Every case runs in interpret mode (the CPU CI
path); a mirrored compiled-mode sweep runs only where Mosaic lowering exists
(TPU) and is skipped elsewhere.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (
    _chunk_tiles,
    _max_tiles,
    chunk_query_tile,
    chunk_tile_count,
    paged_chunk_attention,
    paged_decode_attention,
    ref_paged_chunk_attention,
    ref_paged_decode_attention,
)

ON_TPU = jax.default_backend() == "tpu"
TOL = dict(rtol=2e-5, atol=2e-5)
# int8 pools vs the fp32 oracle on the ORIGINAL values: the explicit error
# budget the quantized serving path promises (per-block absmax, ~1/254 of
# each block's absmax per element, amplified through the softmax)
QTOL = dict(rtol=0.05, atol=0.08)


# ------------------------------------------------------------------ builders
def _make_pool(rng, n_blocks, bs, kvh, hd):
    k = rng.standard_normal((n_blocks, bs, kvh, hd)).astype(np.float32)
    v = rng.standard_normal((n_blocks, bs, kvh, hd)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


def _make_tables(rng, lengths, bs, mb, n_blocks, holes=False):
    """RAW tables: -1 beyond each row's allocated blocks; optionally punch an
    interior hole (an unbacked page BELOW the length) to exercise the
    in-kernel -1 masking, not just tail padding."""
    B = len(lengths)
    tables = np.full((B, mb), -1, np.int32)
    free = list(rng.permutation(n_blocks))
    for b, ln in enumerate(lengths):
        need = -(-ln // bs) if ln else 0
        for j in range(need):
            tables[b, j] = free.pop()
        if holes and need > 2:
            tables[b, rng.integers(1, need - 1)] = -1
    return tables


def _decode_case(rng, *, B, kvh, g, hd, bs, mb, n_blocks, lengths=None,
                 holes=False):
    lengths = (np.asarray(lengths, np.int32) if lengths is not None
               else rng.integers(1, mb * bs + 1, size=B).astype(np.int32))
    kp, vp = _make_pool(rng, n_blocks, bs, kvh, hd)
    tables = _make_tables(rng, lengths, bs, mb, n_blocks, holes=holes)
    q = jnp.asarray(rng.standard_normal((B, kvh * g, hd)).astype(np.float32))
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)


def _chunk_case(rng, *, B, kvh, g, hd, bs, mb, n_blocks, pad_tokens=0,
                segmented=False):
    """A ragged fused batch: each row is either a decode token or a prefill
    chunk at a random start offset; optional packed pad tokens (row_of=-1)
    and segmented-prompt spans (prelude + own-segment attention)."""
    lengths = rng.integers(1, mb * bs + 1, size=B).astype(np.int32)
    kp, vp = _make_pool(rng, n_blocks, bs, kvh, hd)
    tables = _make_tables(rng, lengths, bs, mb, n_blocks)
    row_of, slots, p_end, s_start = [], [], [], []
    for b, ln in enumerate(lengths):
        if rng.random() < 0.4 or ln < 3:          # decode row: one token
            row_of.append(b)
            slots.append(int(ln) - 1)
            p_end.append(0)
            s_start.append(0)
        else:                                      # prefill chunk
            c = int(rng.integers(1, min(int(ln), 6) + 1))
            p0 = int(ln) - c
            for s in range(p0, p0 + c):
                row_of.append(b)
                slots.append(s)
                if segmented and p0 > 1:
                    pe = int(rng.integers(1, p0 + 1))
                    p_end.append(pe)
                    s_start.append(int(rng.integers(pe, s + 1)))
                else:
                    p_end.append(0)
                    s_start.append(0)
    for _ in range(pad_tokens):
        row_of.append(-1)
        slots.append(0)
        p_end.append(0)
        s_start.append(0)
    T = len(row_of)
    q = jnp.asarray(rng.standard_normal((T, kvh * g, hd)).astype(np.float32))
    mk = lambda xs: jnp.asarray(np.asarray(xs, np.int32))
    return (q, kp, vp, jnp.asarray(tables), mk(row_of), mk(slots),
            mk(p_end), mk(s_start))


def _assert_decode_matches(case, interpret):
    q, kp, vp, tables, lengths = case
    got = paged_decode_attention(q, kp, vp, tables, lengths,
                                 interpret=interpret)
    with jax.default_matmul_precision("highest"):  # a float32 oracle on TPU too
        want = ref_paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _assert_chunk_matches(case, interpret):
    # jitted whole: one compile a case instead of one per eager op
    q, kp, vp, tables, row_of, slots, p_end, s_start = case
    got = jax.jit(functools.partial(paged_chunk_attention,
                                    interpret=interpret))(*case)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_paged_chunk_attention)(*case)
    valid = np.asarray(row_of) >= 0
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got)), "pad rows must be garbage-but-FINITE"
    np.testing.assert_allclose(got[valid], want[valid], **TOL)


# ------------------------------------------------- decode: seeded shape sweep
@pytest.mark.parametrize("seed", range(4))
def test_paged_decode_random_shapes(seed):
    rng = np.random.default_rng(seed)
    for _ in range(3):
        kvh = int(rng.choice([1, 2, 3]))
        g = int(rng.choice([1, 2, 4]))
        hd = int(rng.choice([16, 32, 48]))   # 48: non-power-of-two head dim
        bs = int(rng.choice([4, 8, 16]))
        mb = int(rng.integers(1, 5))
        case = _decode_case(rng, B=int(rng.integers(1, 5)), kvh=kvh, g=g,
                            hd=hd, bs=bs, mb=mb, n_blocks=4 * mb + 4)
        _assert_decode_matches(case, interpret=True)


@pytest.mark.parametrize("lengths", [
    [1],                  # length-1: a single valid slot
    [8, 16],              # block-boundary exact (bs=8)
    [3, 8, 5],            # single-block rows under a multi-block table
    [24, 17, 9, 1],       # multi-block, boundary, interior, minimal
])
def test_paged_decode_edge_lengths(lengths):
    rng = np.random.default_rng(hash(tuple(lengths)) % 2**32)
    case = _decode_case(rng, B=len(lengths), kvh=2, g=2, hd=32, bs=8,
                        mb=3, n_blocks=16, lengths=lengths)
    _assert_decode_matches(case, interpret=True)


def test_paged_decode_raw_table_with_holes():
    """Regression: tables reach the kernel UNCLAMPED — tail -1 pads and
    interior -1 holes must be masked inside the kernel, not by the caller."""
    rng = np.random.default_rng(7)
    case = _decode_case(rng, B=3, kvh=2, g=2, hd=32, bs=4, mb=6,
                        n_blocks=24, lengths=[24, 20, 24], holes=True)
    q, kp, vp, tables, lengths = case
    assert (np.asarray(tables) == -1).any()
    _assert_decode_matches(case, interpret=True)


# ------------------------------------- decode: the engine's table convention
def _engine_decode_case(rng, *, pool, B=6, kvh=2, g=2, hd=32, bs=8, mb=9):
    """A decode batch laid out as the serving engine hands it over: tables
    padded with a null block (not -1), empty rows at length 0, live rows of
    1 to 8 blocks, some with a spare allocated block past their length.
    The null block and every block past a row's length hold NaN (NaN
    scales on an int8 pool), so a cell that read one would poison its row.
    Returns (q, k, v, k_scale, v_scale) as the kernel takes them, the same
    values with those blocks zeroed for the oracle (a bf16 pool in float32,
    so the oracle's products stay exact), the tables and the lengths."""
    lengths = rng.integers(1, 8 * bs + 1, size=B)
    order = rng.permutation(B)
    lengths[order[:2]] = 0                     # empty rows
    lengths[order[2]] = 8 * bs                 # eight whole blocks
    lengths[order[3]] = rng.integers(1, bs + 1)  # one block
    n_blocks = B * mb + 1
    null = n_blocks - 1
    tables = np.full((B, mb), null, np.int32)
    free = list(rng.permutation(null))
    dead = [null]
    for b, ln in enumerate(lengths):
        need = -(-ln // bs)
        tables[b, :need] = [free.pop() for _ in range(need)]
        if ln and need < mb and rng.random() < 0.5:
            tables[b, need] = free.pop()
            dead.append(tables[b, need])
    # values a bf16 pool holds exactly
    kp, vp = (np.array(x.astype(jnp.bfloat16), np.float32)
              for x in _make_pool(rng, n_blocks, bs, kvh, hd))
    q = jnp.asarray(rng.standard_normal((B, kvh * g, hd)).astype(np.float32))
    if pool == "int8":
        kq, ks, vq, vs = _quantize_pool(kp, vp)
        ks_z, vs_z = ks.copy(), vs.copy()
        ks[dead], vs[dead] = np.nan, np.nan
        ks_z[dead], vs_z[dead] = 0.0, 0.0
        got = (kq, vq, ks, vs)
        zeroed = (kq, vq, ks_z, vs_z)
    else:
        kz, vz = kp.copy(), vp.copy()
        kp[dead], vp[dead] = np.nan, np.nan
        kz[dead], vz[dead] = 0.0, 0.0
        got = (kp, vp, None, None)
        zeroed = (kz, vz, None, None)

    def dev(k, v, ks, vs, dt=None):
        return (q, jnp.asarray(k, dt), jnp.asarray(v, dt),
                None if ks is None else jnp.asarray(ks),
                None if vs is None else jnp.asarray(vs))

    dt = jnp.bfloat16 if pool == "bfloat16" else None
    return (dev(*got, dt=dt), dev(*zeroed), jnp.asarray(tables),
            jnp.asarray(lengths))


def _assert_dead_cells_unread(rng, pool, interpret, **shape):
    (q, k, v, ks, vs), (_, kz, vz, ksz, vsz), tables, lengths = (
        _engine_decode_case(rng, pool=pool, **shape))
    got = np.asarray(paged_decode_attention(
        q, k, v, tables, lengths, k_scale=ks, v_scale=vs,
        interpret=interpret))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_paged_decode_attention(
            q, kz, vz, tables, lengths, k_scale=ksz, v_scale=vsz))
    live = np.asarray(lengths) > 0
    assert np.all(np.isfinite(got)), "a dead cell was read"
    np.testing.assert_array_equal(got[~live], 0.0)   # empty rows: zeros
    np.testing.assert_allclose(got[live], want[live], **TOL)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
def test_paged_decode_never_reads_dead_cells(pool, seed):
    """Null-block padding, empty rows and blocks past a row's length are
    never read: NaN there leaves every output finite, and live rows match
    the oracle on the same pool with those blocks zeroed."""
    _assert_dead_cells_unread(np.random.default_rng(800 + seed), pool,
                              interpret=True)


# -------------------------------------------------- chunk: seeded shape sweep
@pytest.mark.parametrize("seed", range(4))
def test_paged_chunk_random_mixes(seed):
    rng = np.random.default_rng(100 + seed)
    for _ in range(2):
        kvh = int(rng.choice([1, 2]))
        g = int(rng.choice([1, 2, 4]))
        hd = int(rng.choice([16, 32, 48]))
        bs = int(rng.choice([4, 8]))
        mb = int(rng.integers(1, 4))
        case = _chunk_case(rng, B=int(rng.integers(1, 4)), kvh=kvh, g=g,
                           hd=hd, bs=bs, mb=mb, n_blocks=3 * mb + 4,
                           pad_tokens=int(rng.integers(0, 4)))
        _assert_chunk_matches(case, interpret=True)


def test_paged_chunk_segmented_spans():
    """Segmented-prompt masking (prelude + own segment) inside the kernel
    must match the oracle's span semantics exactly."""
    rng = np.random.default_rng(42)
    case = _chunk_case(rng, B=3, kvh=2, g=2, hd=32, bs=8, mb=3,
                       n_blocks=16, segmented=True)
    _assert_chunk_matches(case, interpret=True)


def test_paged_chunk_all_pad_row_is_finite():
    """A fully-masked query row (packed pad, row_of=-1) must produce finite
    output — the l=max(l,eps) guard — never NaN."""
    rng = np.random.default_rng(5)
    case = _chunk_case(rng, B=2, kvh=1, g=2, hd=16, bs=4, mb=2,
                       n_blocks=8, pad_tokens=3)
    _assert_chunk_matches(case, interpret=True)


def test_paged_chunk_raw_minus_one_tables():
    """Ragged plans hand the kernel tables where every unallocated entry is
    -1 (no scratch-block reroute). Check some -1s are actually present."""
    rng = np.random.default_rng(11)
    case = _chunk_case(rng, B=4, kvh=2, g=1, hd=32, bs=4, mb=4, n_blocks=24)
    assert (np.asarray(case[3]) == -1).any()
    _assert_chunk_matches(case, interpret=True)


# ------------------------------------------ chunk: query tiles of tq tokens
def _packed_case(rng, runs, *, g, kvh=2, hd=32, bs=8, n_pad=0, holes=None,
                 segs=None):
    """A packed buffer laid out run by run. ``runs``: (row, first slot,
    tokens) in packed order, a row may recur; ``n_pad`` pad tokens close
    it. Each row's table holds its blocks plus one allocated block past its
    last slot and two -1 columns after that (dead columns of every tile);
    ``holes`` {row: [cols]} punches -1 entries; ``segs`` {row: (prelude
    end, [doc starts])} gives the row segmented spans."""
    ends = {}
    for row, s0, n in runs:
        ends[row] = max(ends.get(row, 0), s0 + n)
    B = max(ends, default=0) + 1
    lengths = [ends.get(b, 0) for b in range(B)]
    mb = -(-max(lengths + [1]) // bs) + 3
    n_blocks = B * mb + 1
    kp, vp = _make_pool(rng, n_blocks, bs, kvh, hd)
    tables = np.full((B, mb), -1, np.int32)
    free = list(rng.permutation(n_blocks))
    for b, ln in enumerate(lengths):
        for j in range(-(-ln // bs) + 1 if ln else 0):
            tables[b, j] = free.pop()
    for b, cols in (holes or {}).items():
        tables[b, cols] = -1
    row_of, slots, p_end, s_start = [], [], [], []
    for row, s0, n in runs:
        for s in range(s0, s0 + n):
            row_of.append(row)
            slots.append(s)
            pe, ss = 0, 0
            if row in (segs or {}):
                prelude, docs = segs[row]
                if s >= prelude:
                    pe, ss = prelude, max(d for d in docs if d <= s)
            p_end.append(pe)
            s_start.append(ss)
    row_of += [-1] * n_pad
    slots += [0] * n_pad
    p_end += [0] * n_pad
    s_start += [0] * n_pad
    q = rng.standard_normal((len(row_of), kvh * g, hd)).astype(np.float32)
    mk = lambda xs: jnp.asarray(np.asarray(xs, np.int32))
    return (jnp.asarray(q), kp, vp, jnp.asarray(tables), mk(row_of),
            mk(slots), mk(p_end), mk(s_start))


# each layout is given the query tile tq and returns _packed_case's keywords
TILE_LAYOUTS = {
    # one prefill run over two whole tiles and a part of a third
    "split_run": lambda tq: dict(runs=[(0, 5, 2 * tq + 3)]),
    # a run of exactly one tile, then a decode row
    "exact_tile": lambda tq: dict(runs=[(0, 0, tq), (1, 9, 1)]),
    # one-token decode runs between prefill runs, pads at the tail
    "decode_between": lambda tq: dict(
        runs=[(0, 3, tq + 2), (1, 12, 1), (2, 30, 1), (3, 0, 5), (4, 17, 1)],
        n_pad=3),
    # row 0 in two separate runs, together longer than a tile
    "row_in_two_runs": lambda tq: dict(
        runs=[(0, 2, 4), (1, 7, 1), (0, 6, tq)]),
    # nothing but pad tokens
    "all_pad": lambda tq: dict(runs=[], n_pad=8),
    # prelude of 3, then documents; the one from tq - 2 crosses the tile
    # boundary at tq
    "segments_cross_tile": lambda tq: dict(
        runs=[(0, 0, 2 * tq), (1, 20, 1)],
        segs={0: (3, [3, tq - 2, tq + 5])}),
    # -1 holes inside the attended columns, beside dead columns past each
    # tile's last slot (the row's spare allocated block, then -1 columns)
    "holes_and_dead_columns": lambda tq: dict(
        runs=[(0, 25, 5), (1, 16, 1), (0, 30, tq)],
        holes={0: [1], 1: [0]}),
}


@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
@pytest.mark.parametrize("g", [1, 2, 3, 4, 8])
def test_paged_chunk_query_tiles(g, layout):
    """Every query-tile geometry against the oracle at every
    shape-derived tile width (tq = 128, 64, 40, 32, 16 for G = 1..8)."""
    rng = np.random.default_rng(g * 100 + sorted(TILE_LAYOUTS).index(layout))
    case = _packed_case(rng, g=g, **TILE_LAYOUTS[layout](chunk_query_tile(g)))
    _assert_chunk_matches(case, interpret=True)


@pytest.mark.parametrize("g", [1, 2, 3, 4, 8])
def test_chunk_query_tile_fills_mxu_rows(g):
    tq = chunk_query_tile(g)
    assert tq % 8 == 0 and tq >= 8 and tq * g <= 128 < (tq + 8) * g


@pytest.mark.parametrize("seed", range(6))
def test_chunk_tile_count_matches_device_tiling(seed):
    """The host count behind ``engine.stats()["chunk_tiles"]`` equals the
    tiles the kernel derives on device, on random buffers of runs, pads and
    rows split across runs; every tile holds up to tq tokens of one row,
    and the count stays under the grid's static bound."""
    rng = np.random.default_rng(700 + seed)
    for _ in range(4):
        B, T = int(rng.integers(1, 9)), int(rng.integers(1, 161))
        g = int(rng.choice([1, 2, 3, 4, 8]))
        tq = chunk_query_tile(g)
        n_max = _max_tiles(T, B, tq)
        row_of = np.full(T, -1, np.int32)
        t = 0
        while t < T:
            n = int(rng.integers(1, 40))
            row_of[t:t + n] = int(rng.integers(-1, B))
            t += n
        tile, lane, n_tiles = jax.jit(_chunk_tiles, static_argnums=(1, 2))(
            jnp.asarray(row_of), B, tq)
        tile, lane = np.asarray(tile), np.asarray(lane)
        assert int(n_tiles[0]) == chunk_tile_count(row_of, g) <= n_max
        live = row_of >= 0
        assert np.all(tile[~live] == n_max)
        assert np.all(tile[live] < int(n_tiles[0]))
        pairs = set(zip(tile[live].tolist(), lane[live].tolist()))
        assert len(pairs) == live.sum()                # one lane a token
        for i in set(tile[live].tolist()):
            assert len(set(row_of[tile == i].tolist())) == 1


# --------------------------------------------------- quantized (int8) pools
def _quantize_pool(kp, vp):
    """Per-(block, KV-head) absmax int8 quantization in the pool storage
    layout: scales (n_blocks, KVH) f32, stored = clip(round(x/s)),
    dequant = stored * s — the same contract ``paged_cache`` maintains."""
    def q(x):
        x = np.asarray(x)
        s = np.abs(x).max(axis=(1, 3)) / 127.0                # (nb, KVH)
        qx = np.clip(np.round(x / np.maximum(s, 1e-30)[:, None, :, None]),
                     -127, 127)
        return qx.astype(np.int8), s.astype(np.float32)

    kq, ks = q(kp)
    vq, vs = q(vp)
    return kq, ks, vq, vs


def _dequant(qx, s):
    return jnp.asarray(qx.astype(np.float32) * s[:, None, :, None])


@pytest.mark.parametrize("seed", range(3))
def test_paged_decode_quantized_pool(seed):
    """int8 decode kernel: bit-exact vs the fp oracle on the DEQUANTIZED
    pool (the kernel's dequant is just ``q * s`` in VMEM), and inside the
    explicit QTOL budget vs the fp32 oracle on the original values."""
    rng = np.random.default_rng(400 + seed)
    case = _decode_case(rng, B=int(rng.integers(1, 5)), kvh=2, g=2, hd=32,
                        bs=8, mb=3, n_blocks=16)
    q, kp, vp, tables, lengths = case
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    got = paged_decode_attention(q, jnp.asarray(kq), jnp.asarray(vq), tables,
                                 lengths, k_scale=jnp.asarray(ks),
                                 v_scale=jnp.asarray(vs), interpret=True)
    want_dq = ref_paged_decode_attention(q, _dequant(kq, ks), _dequant(vq, vs),
                                         tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_dq), **TOL)
    want_fp = ref_paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_fp), **QTOL)


def test_paged_decode_quantized_raw_tables_with_holes():
    """-1 pads and interior holes must be masked before the dequant multiply
    — a hole block's garbage scale must never leak into the output."""
    rng = np.random.default_rng(17)
    case = _decode_case(rng, B=3, kvh=2, g=2, hd=32, bs=4, mb=6,
                        n_blocks=24, lengths=[24, 20, 24], holes=True)
    q, kp, vp, tables, lengths = case
    assert (np.asarray(tables) == -1).any()
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    got = paged_decode_attention(q, jnp.asarray(kq), jnp.asarray(vq), tables,
                                 lengths, k_scale=jnp.asarray(ks),
                                 v_scale=jnp.asarray(vs), interpret=True)
    want = ref_paged_decode_attention(q, _dequant(kq, ks), _dequant(vq, vs),
                                      tables, lengths)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("seed", range(3))
def test_paged_chunk_quantized_pool(seed):
    """int8 ragged-chunk kernel under RAW -1 tables and packed pad tokens:
    same dual oracle as the decode case; pad rows stay finite."""
    rng = np.random.default_rng(500 + seed)
    case = _chunk_case(rng, B=int(rng.integers(2, 4)), kvh=2, g=2, hd=32,
                       bs=4, mb=3, n_blocks=13,
                       pad_tokens=int(rng.integers(1, 4)))
    q, kp, vp, tables, row_of, slots, p_end, s_start = case
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    got = paged_chunk_attention(q, jnp.asarray(kq), jnp.asarray(vq), tables,
                                row_of, slots, p_end, s_start,
                                k_scale=jnp.asarray(ks),
                                v_scale=jnp.asarray(vs), interpret=True)
    want_dq = ref_paged_chunk_attention(q, _dequant(kq, ks), _dequant(vq, vs),
                                        tables, row_of, slots, p_end, s_start)
    want_fp = ref_paged_chunk_attention(q, kp, vp, tables, row_of, slots,
                                        p_end, s_start)
    valid = np.asarray(row_of) >= 0
    got = np.asarray(got)
    assert np.all(np.isfinite(got)), "pad rows must be garbage-but-FINITE"
    np.testing.assert_allclose(got[valid], np.asarray(want_dq)[valid], **TOL)
    np.testing.assert_allclose(got[valid], np.asarray(want_fp)[valid], **QTOL)


@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
def test_paged_chunk_query_tiles_quantized(layout):
    """The query tiles over an int8 pool: exact against the oracle on the
    dequantized pool, within QTOL of the float32 one."""
    g = 4
    rng = np.random.default_rng(900 + sorted(TILE_LAYOUTS).index(layout))
    case = _packed_case(rng, g=g, **TILE_LAYOUTS[layout](chunk_query_tile(g)))
    q, kp, vp, tables, row_of, slots, p_end, s_start = case
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    got = np.asarray(jax.jit(functools.partial(
        paged_chunk_attention, interpret=True))(
        q, jnp.asarray(kq), jnp.asarray(vq), tables, row_of, slots, p_end,
        s_start, k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)))
    ref = jax.jit(ref_paged_chunk_attention)
    want_dq = ref(q, _dequant(kq, ks), _dequant(vq, vs), tables, row_of,
                  slots, p_end, s_start)
    want_fp = ref(q, kp, vp, tables, row_of, slots, p_end, s_start)
    valid = np.asarray(row_of) >= 0
    assert np.all(np.isfinite(got)), "pad rows must be garbage-but-FINITE"
    np.testing.assert_allclose(got[valid], np.asarray(want_dq)[valid], **TOL)
    np.testing.assert_allclose(got[valid], np.asarray(want_fp)[valid], **QTOL)


# -------------------------------------------------------------- compiled mode
@pytest.mark.skipif(not ON_TPU, reason="compiled Mosaic kernels need a TPU")
@pytest.mark.parametrize("seed", range(2))
def test_paged_decode_compiled(seed):
    rng = np.random.default_rng(200 + seed)
    case = _decode_case(rng, B=4, kvh=2, g=2, hd=64, bs=16, mb=4,
                        n_blocks=32)
    _assert_decode_matches(case, interpret=False)


@pytest.mark.skipif(not ON_TPU, reason="compiled Mosaic kernels need a TPU")
@pytest.mark.parametrize("seed", range(2))
def test_paged_decode_never_reads_dead_cells_compiled(seed):
    _assert_dead_cells_unread(np.random.default_rng(850 + seed), "bfloat16",
                              interpret=False, hd=128, bs=16)


@pytest.mark.skipif(not ON_TPU, reason="compiled Mosaic kernels need a TPU")
@pytest.mark.parametrize("seed", range(2))
def test_paged_chunk_compiled(seed):
    rng = np.random.default_rng(300 + seed)
    case = _chunk_case(rng, B=4, kvh=2, g=2, hd=64, bs=16, mb=4,
                       n_blocks=32, pad_tokens=2)
    _assert_chunk_matches(case, interpret=False)


@pytest.mark.skipif(not ON_TPU, reason="compiled Mosaic kernels need a TPU")
@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
@pytest.mark.parametrize("g", [1, 3, 8])
def test_paged_chunk_query_tiles_compiled(g, layout):
    rng = np.random.default_rng(600 + g)
    case = _packed_case(rng, g=g, hd=128, bs=16,
                        **TILE_LAYOUTS[layout](chunk_query_tile(g)))
    _assert_chunk_matches(case, interpret=False)
