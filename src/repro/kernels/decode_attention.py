"""Pallas TPU GQA decode-attention kernels (the serving hot loop).

One new token attends a seq_len KV cache: HBM-bandwidth-bound. Grid
(B*KVH, n_kv_blocks): each cell streams one KV block into VMEM, scores all G
group queries of that kv head against it (G x block_k tile on the MXU), and
maintains the online softmax in VMEM scratch. The cache is read exactly once
— the roofline-optimal traffic pattern.

Validity (cache slots filled so far) comes from a per-row length input.

``paged_decode_attention`` is the block-table variant backing the paged
serving engine (vLLM-style PagedAttention): the KV pool is a global array of
fixed-size blocks, and a scalar-prefetched per-sequence block table drives
the BlockSpec index_map, so each grid cell DMAs exactly the physical block
the logical position maps to — no contiguous cache materialization. Its grid
cell is one (row, table column) pair, and only the columns up to the row's
last live block (``(length - 1) // bs``) do work: past it the index_maps
repeat the block already in VMEM (no DMA) and the cell skips the math. A row
of length 0 is empty: it attends nothing, runs no cell's math and returns
zeros.
``ref_paged_decode_attention`` is the jnp gather oracle the kernel (and the
engine's XLA decode path) are checked against.

``paged_chunk_attention`` is the ragged fused-step variant: T packed query
tokens from B sequences (decode rows and prefill chunks mixed in one flat
buffer) each attend their own sequence's paged KV through the shared block
table, with the segmented-prompt span mask (prelude + own segment + causal
self) applied inside the kernel per query row. Its grid cell is one (query
tile, KV block) pair: a tile holds up to ``tq`` tokens of one row, tq·G
query rows filling the MXU (a decode row is a tile of one token), so a
prefill chunk streams its sequence's KV once a tile instead of once a
token, and a tile reads only the blocks up to its last attended slot.

Both kernels tolerate RAW block tables: pad entries (-1) are masked inside
the kernel (index_maps clamp them to block 0 purely so the DMA has a legal
source; the scores of those slots are forced to -inf). Callers no longer
need to pre-clamp or reroute tables before handing them to the kernels, and
a table padded with a scratch block instead is never read past a row's last
live block. Fully-masked query rows (a packed pad token, ``row_of < 0``, or
an empty decode row) produce finite output — never NaN — and must be
discarded by the caller.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def default_interpret() -> bool:
    """Interpret-mode default for the serving engine: compiled Mosaic on TPU,
    the Pallas interpreter everywhere else (CPU CI runs the same kernel code
    path end-to-end, just without the Mosaic lowering)."""
    return jax.default_backend() != "tpu"


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, block_k: int, nkv: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)   # (G, hd)
    k = k_ref[0].astype(jnp.float32)   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)   # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                           # (G, bk)
    valid_len = len_ref[0]
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < valid_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(j == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q, k_cache, v_cache, lengths, *, block_k: int = 512, scale=None,
    interpret: bool = True,
):
    """q: (B, H, hd); k/v_cache: (B, Sc, KVH, hd); lengths: (B,) valid slots.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    Sc, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_k = min(block_k, Sc)
    while Sc % block_k:
        block_k //= 2
    nkv = Sc // block_k

    qf = q.reshape(B, KVH, G, hd).reshape(B * KVH, G, hd)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * KVH, Sc, hd)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * KVH, Sc, hd)
    lens = jnp.asarray(lengths, jnp.int32).reshape(B)
    lens_rep = jnp.repeat(lens, KVH)

    kernel = functools.partial(_decode_kernel, block_k=block_k, nkv=nkv, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, nkv),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (b,)),
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(lens_rep, qf, kf, vf)
    return out.reshape(B, KVH * G, hd)


# ---------------------------------------------------------------------------
# paged (block-table) decode attention
# ---------------------------------------------------------------------------
#
# Mosaic tiling: a VMEM block's last two dims must be multiples of (8, 128)
# or span the whole array. The pool is (n_blocks, bs, KVH, hd), so a grid
# cell takes ALL KV heads of one block — a (1, bs, KVH, hd) block whose last
# two dims are the array's — and loops over the heads in-kernel. Scales are
# presented as (n_blocks, 1, KVH) for the same reason, and applied to the
# (G, bs) scores and the (G, hd) value product instead of the (bs, hd) block
# (a per-(block, head) constant commutes with both dots).


_HIGHEST = jax.lax.Precision.HIGHEST


def _online_softmax_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref,
                          m_ref, l_ref, valid, *, kvh: int, scale: float):
    """One KV block's online-softmax update for every KV head of a cell.
    ``valid`` (1, bs) bool masks this block's slots; q_ref block (1, KVH, G,
    hd), k/v_ref block (1, bs, KVH, hd), scratch acc (KVH, G, hd) and m/l
    (KVH, G, 1)."""
    for h in range(kvh):
        q = q_ref[0, h]                              # (G, hd)
        # MXU operands in the query's dtype (an int8 block converts exactly);
        # float32 operands ask for float32 accuracy, which the MXU's default
        # single bfloat16 pass does not give
        prec = _HIGHEST if q.dtype == jnp.float32 else None
        k = k_ref[0, :, h, :].astype(q.dtype)        # (bs, hd)
        v = v_ref[0, :, h, :].astype(q.dtype)        # (bs, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec,
        ) * scale                                    # (G, bs)
        if ks_ref is not None:
            # int8 pool: the block came over HBM->VMEM at one byte per
            # element; dequantize with this (block, head)'s scale
            s = s * ks_ref[0, :, h:h + 1]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                            # (G, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )                                            # (G, hd)
        if vs_ref is not None:
            pv = pv * vs_ref[0, :, h:h + 1]
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = m_cur


def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finish(o_ref, acc_ref, l_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _split_rest(rest, quantized):
    if quantized:
        return rest
    return (None, None) + tuple(rest)


def decode_live_cells(lengths, block_size: int, max_blocks: int) -> int:
    """The grid cells of ``paged_decode_attention`` that run the math for
    per-row ``lengths`` (0 = empty row): each row's columns up to its last
    live one, ``(length - 1) // block_size`` at most ``max_blocks - 1``
    (-1 for an empty row), as the kernel's wrapper derives them on device."""
    lengths = np.asarray(lengths, np.int64)
    last = np.minimum((lengths - 1) // block_size, max_blocks - 1)
    return int((last + 1).sum())


def _paged_decode_kernel(tab_ref, len_ref, last_ref, q_ref, k_ref, v_ref,
                         *rest, block_size: int, nkv: int, kvh: int,
                         scale: float, quantized: bool = False):
    ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = _split_rest(rest, quantized)
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    # past the row's last live block (and on an empty row) the index_maps
    # repeat the block already in VMEM, so there is no DMA; skip the math
    @pl.when(j <= last_ref[b])
    def _update():
        # logical position of this block's slots = j*bs + offset; valid when
        # below the sequence length AND backed by a real page — a raw -1
        # table entry is masked here in the kernel (the index_map clamps it
        # to block 0 only so the DMA has a legal source), so callers may pass
        # unclamped tables even when interior entries are holes
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = (tab_ref[b, j] >= 0) & (kpos < len_ref[b])
        _online_softmax_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref,
                              m_ref, l_ref, valid, kvh=kvh, scale=scale)

    @pl.when(j == nkv - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def _scratch(kvh, g, hd):
    return [
        pltpu.VMEM((kvh, g, hd), jnp.float32),
        pltpu.VMEM((kvh, g, 1), jnp.float32),
        pltpu.VMEM((kvh, g, 1), jnp.float32),
    ]


def _scale_specs(k_scale, v_scale, n_blocks, kvh, sc_map):
    """(n_blocks, KVH) scales as (n_blocks, 1, KVH): a (1, 1, KVH) block
    spans the array's last two dims, as Mosaic requires."""
    spec = pl.BlockSpec((1, 1, kvh), sc_map)
    return ([spec, spec],
            [k_scale.reshape(n_blocks, 1, kvh), v_scale.reshape(n_blocks, 1, kvh)])


def paged_decode_attention(
    q, k_pool, v_pool, block_tables, lengths, *, scale=None,
    k_scale=None, v_scale=None, interpret: bool = True,
):
    """Block-table-driven decode attention over a paged KV pool.

    q: (B, H, hd); k/v_pool: (n_blocks, bs, KVH, hd) — ONE layer group's
    global pool; block_tables: (B, max_blocks) int32 (-1 = unallocated);
    lengths: (B,) valid tokens per sequence, 0 for an empty row. Returns
    (B, H, hd).

    Grid (B, max_blocks), one cell per (row, table column). The scalar-
    prefetched block table feeds the K/V BlockSpec index_map, so cell
    (b, j) DMAs the one physical block (all KV heads) that column j of row
    b maps to, for j up to the row's last live column ``(length - 1) //
    bs`` (also scalar-prefetched). Past it the index_map repeats that last
    block, which is already in VMEM, so no DMA is issued, and the cell
    skips the online-softmax update: a table padded with a scratch block
    is never read there. A row of length 0 runs no cell's math (its
    index_map stays on column 0) and returns zeros, which the caller
    discards. The table may also be RAW: -1 entries (pad or interior
    holes) are masked to -inf inside the kernel, independent of the length
    check.

    ``k_scale``/``v_scale`` ((n_blocks, KVH) float32, both or neither) mark
    an int8-quantized pool: each cell DMAs its block at half the HBM bytes
    and dequantizes in VMEM with the block's per-KV-head scale — the scale
    BlockSpec rides the same table-driven index_map as K/V.
    """
    B, H, hd = q.shape
    nb, bs, KVH = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    G = H // KVH
    mb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_scale is not None

    qf = q.reshape(B, KVH, G, hd)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32).reshape(B)
    # each row's last live column, -1 on an empty row (``decode_live_cells``)
    last = jnp.minimum((lens - 1) // bs, mb - 1)

    def q_map(b, j, *_):
        return (b, 0, 0, 0)

    def block_of(b, j, tab_ref, len_ref, last_ref):
        col = jnp.maximum(jnp.minimum(j, last_ref[b]), 0)
        return jnp.maximum(tab_ref[b, col], 0)

    def kv_map(b, j, *refs):
        return (block_of(b, j, *refs), 0, 0, 0)

    def sc_map(b, j, *refs):
        return (block_of(b, j, *refs), 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, block_size=bs, nkv=mb, kvh=KVH, scale=scale,
        quantized=quantized,
    )
    in_specs = [
        pl.BlockSpec((1, KVH, G, hd), q_map),
        pl.BlockSpec((1, bs, KVH, hd), kv_map),
        pl.BlockSpec((1, bs, KVH, hd), kv_map),
    ]
    operands = [tables, lens, last, qf, k_pool, v_pool]
    if quantized:
        specs, scales = _scale_specs(k_scale, v_scale, nb, KVH, sc_map)
        in_specs += specs
        operands += scales
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVH, G, hd), q_map),
        scratch_shapes=_scratch(KVH, G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*operands)
    return out.reshape(B, KVH * G, hd)


def ref_paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                               scale=None, k_scale=None, v_scale=None):
    """jnp gather oracle: materialize each sequence's contiguous view from its
    block table (jnp.take over the block axis) and run masked softmax
    attention. This is also the numerics contract for the engine's XLA decode
    path. ``k_scale``/``v_scale`` dequantize an int8 pool after the gather."""
    B, H, hd = q.shape
    bs, KVH = k_pool.shape[1], k_pool.shape[2]
    mb = block_tables.shape[1]
    tables = jnp.asarray(block_tables, jnp.int32)
    safe = jnp.maximum(tables, 0)

    def gather(pool, sc=None):
        g = jnp.take(pool, safe, axis=0)  # (B, mb, bs, KVH, hd)
        if sc is not None:
            s = jnp.take(sc, safe, axis=0)  # (B, mb, KVH)
            g = g.astype(jnp.float32) * s[:, :, None, :, None]
        return g.reshape(B, mb * bs, KVH, hd)

    slots = jnp.arange(mb * bs)
    valid = (tables[:, slots // bs] >= 0) & (
        slots[None] < jnp.asarray(lengths, jnp.int32)[:, None]
    )
    from repro.models.attention import decode_attention as xla_decode

    out = xla_decode(q[:, None], gather(k_pool, k_scale),
                     gather(v_pool, v_scale), valid, scale=scale)
    return out[:, 0]


# ---------------------------------------------------------------------------
# packed (ragged fused-step) chunk attention
# ---------------------------------------------------------------------------
#
# Query tiling: the packed tokens of one batch row are taken ``tq`` at a time
# (in packed order) as one query tile, and a grid cell is one (tile, logical
# KV block) pair, so a block is DMA'd once for up to ``tq`` tokens. Tokens
# are grouped by row before they are tiled, so a row whose tokens sit in
# separate runs of the flat buffer still makes ceil(count / tq) tiles, and
# the tile count stays under ceil(T / tq) + min(B, T) whatever the packing.

_MXU_ROWS = 128


def chunk_query_tile(groups: int) -> int:
    """Query tokens per tile of ``paged_chunk_attention`` for ``groups``
    query heads per KV head: the most whose ``tq * groups`` query rows fit
    the MXU's 128 rows, as a multiple of 8 and at least 8."""
    return max(8, _MXU_ROWS // groups // 8 * 8)


def chunk_tile_count(row_of, groups: int) -> int:
    """The query tiles ``paged_chunk_attention`` launches for the packed
    tokens ``row_of`` (-1 = pad, in no tile): ceil(count / tq) per row."""
    row_of = np.asarray(row_of)
    counts = np.bincount(row_of[row_of >= 0])
    tq = chunk_query_tile(groups)
    return int((-(-counts // tq)).sum())


def _max_tiles(T: int, n_rows: int, tq: int) -> int:
    """Static bound on the tiles of T packed tokens of ``n_rows`` rows:
    sum(ceil(count / tq)) <= ceil(T / tq) + rows - 1, and a tile holds a
    token."""
    return min(T, -(-T // tq) + min(n_rows, T) - 1)


def _chunk_tiles(row_of, n_rows: int, tq: int):
    """Device side of the tiling. Returns per token its tile (the bound
    ``_max_tiles`` for a pad) and lane within the tile, and the number of
    tiles."""
    n_max = _max_tiles(row_of.shape[0], n_rows, tq)
    live = row_of >= 0
    hot = (row_of[:, None] == jnp.arange(n_rows)[None]).astype(jnp.int32)
    rank = jnp.sum(jnp.cumsum(hot, axis=0) * hot, axis=1) - 1   # within row
    row_tiles = (jnp.sum(hot, axis=0) + tq - 1) // tq           # (B,)
    first = jnp.cumsum(row_tiles) - row_tiles
    tile = jnp.where(live, first[jnp.maximum(row_of, 0)] + rank // tq, n_max)
    lane = jnp.maximum(rank, 0) % tq
    return tile, lane, jnp.sum(row_tiles).reshape(1)


def _paged_chunk_kernel(tab_ref, row_ref, last_ref, n_ref, slot_ref, pend_ref,
                        sstart_ref, q_ref, k_ref, v_ref, *rest,
                        block_size: int, nkv: int, kvh: int, scale: float,
                        quantized: bool = False):
    ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = _split_rest(rest, quantized)
    i = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # logical kv block

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    # past the tile's last attended block (or on a tile with no tokens) the
    # index_maps repeat the previous block, so there is no DMA; skip the math
    @pl.when((i < n_ref[0]) & (j <= last_ref[i]))
    def _update():
        # the segmented-prompt span mask per query row (serving.segments): a
        # token attends the shared prelude (slot < p_end) plus its own
        # document segment up to itself (s_start <= slot <= own slot);
        # flat prompts and decode rows pass p_end = s_start = 0, plain causal.
        # A query row with no token has slot -1 and masks everything; raw -1
        # table entries mask the whole block.
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        backed = tab_ref[row_ref[i], j] >= 0
        span = (kpos < pend_ref[0]) | (
            (kpos >= sstart_ref[0]) & (kpos <= slot_ref[0])
        )                                            # (tq * G, bs)
        _online_softmax_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref,
                              m_ref, l_ref, backed & span, kvh=kvh,
                              scale=scale)

    @pl.when(j == nkv - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def paged_chunk_attention(
    q, k_pool, v_pool, block_tables, row_of, slots, p_end, s_start, *,
    scale=None, k_scale=None, v_scale=None, interpret: bool = True,
):
    """Ragged fused-step attention: T packed query tokens over a paged pool.

    q: (T, H, hd) — the flat fused batch, decode rows and prefill chunks
    packed back to back (no chunk-width padding); k/v_pool: (n_blocks, bs,
    KVH, hd) — ONE layer group's global pool, already holding the packed
    chunk's own K/V (the stack writes before attention); block_tables:
    (B, max_blocks) int32, RAW (-1 entries masked in-kernel); row_of: (T,)
    int32 owning batch row per token (-1 = packed pad token, output
    garbage-but-finite, caller discards);
    slots: (T,) absolute cache slot of each token; p_end / s_start: (T,)
    segmented-prompt attention spans (zeros = plain causal over slots).
    Returns (T, H, hd).

    Grid (n_tiles_max, max_blocks), one cell per (query tile, KV block). A
    tile is up to ``tq = chunk_query_tile(G)`` tokens of one row, so its
    (KVH, tq·G, hd) q block fills the MXU's rows; a decode row is a tile
    with one token. The tiles are derived here from ``row_of`` (small XLA
    gathers in, one gather out); tile ``i`` streams the blocks
    ``block_tables[row][0 .. last_col[i]]`` that its tokens can attend
    (up to max(slot, p_end - 1)), and each block is DMA'd once for the whole
    tile. Later columns, and tiles with no tokens, map to the block already
    in VMEM and skip the online-softmax update. The span mask is each
    token's own, per query row. ``k_scale``/``v_scale`` ((n_blocks, KVH)
    float32) mark an int8 pool, dequantized in VMEM after the block DMA.
    """
    T, H, hd = q.shape
    nb, bs, KVH = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    G = H // KVH
    B, mb = block_tables.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    tq = chunk_query_tile(G)
    n_max = _max_tiles(T, B, tq)

    row_of = jnp.asarray(row_of, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    p_end = jnp.asarray(p_end, jnp.int32)
    s_start = jnp.asarray(s_start, jnp.int32)
    tile, lane, n_tiles = _chunk_tiles(row_of, B, tq)
    tok = jnp.full((n_max, tq), -1, jnp.int32).at[tile, lane].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")
    tile_row = jnp.zeros((n_max,), jnp.int32).at[tile].set(row_of, mode="drop")
    last_slot = jnp.zeros((n_max,), jnp.int32).at[tile].max(
        jnp.maximum(slots, p_end - 1), mode="drop")
    last_col = jnp.minimum(last_slot // bs, mb - 1)

    has_tok = tok >= 0
    src = jnp.maximum(tok, 0)

    def per_row(x, empty):   # (T,) -> (n_max, tq * G, 1), one value a q row
        x = jnp.where(has_tok, x[src], empty)
        return jnp.repeat(x, G, axis=1)[..., None]

    qt = q.reshape(T, KVH, G, hd)[src]                # (n_max, tq, KVH, G, hd)
    qt = qt.transpose(0, 2, 1, 3, 4).reshape(n_max, KVH, tq * G, hd)
    tables = jnp.asarray(block_tables, jnp.int32)

    def q_map(i, j, *_):
        return (i, 0, 0, 0)

    def row_map(i, j, *_):
        return (i, 0, 0)

    def block_of(i, j, tab_ref, row_ref, last_ref, n_ref):
        # a tile with no tokens repeats the last live cell's block
        src = jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))
        col = jnp.where(i < n_ref[0], jnp.minimum(j, last_ref[src]),
                        last_ref[src])
        return jnp.maximum(tab_ref[row_ref[src], col], 0)

    def kv_map(i, j, *refs):
        return (block_of(i, j, *refs), 0, 0, 0)

    def sc_map(i, j, *refs):
        return (block_of(i, j, *refs), 0, 0)

    kernel = functools.partial(
        _paged_chunk_kernel, block_size=bs, nkv=mb, kvh=KVH, scale=scale,
        quantized=quantized,
    )
    in_specs = [
        pl.BlockSpec((1, tq * G, 1), row_map),
        pl.BlockSpec((1, tq * G, 1), row_map),
        pl.BlockSpec((1, tq * G, 1), row_map),
        pl.BlockSpec((1, KVH, tq * G, hd), q_map),
        pl.BlockSpec((1, bs, KVH, hd), kv_map),
        pl.BlockSpec((1, bs, KVH, hd), kv_map),
    ]
    operands = [
        tables, tile_row, last_col, n_tiles,
        per_row(slots, -1), per_row(p_end, 0), per_row(s_start, 0),
        qt, k_pool, v_pool,
    ]
    if quantized:
        specs, scales = _scale_specs(k_scale, v_scale, nb, KVH, sc_map)
        in_specs += specs
        operands += scales
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_max, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVH, tq * G, hd), q_map),
        scratch_shapes=_scratch(KVH, tq * G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_max, KVH, tq * G, hd), q.dtype),
        interpret=interpret,
        name="paged_chunk_attention",
    )(*operands)
    out = out.reshape(n_max, KVH, tq, G, hd).transpose(0, 2, 1, 3, 4)
    flat = jnp.where(row_of >= 0, tile * tq + lane, 0)
    return out.reshape(n_max * tq, H, hd)[flat]


def ref_paged_chunk_attention(q, k_pool, v_pool, block_tables, row_of, slots,
                              p_end, s_start, scale=None, k_scale=None,
                              v_scale=None):
    """jnp gather oracle for ``paged_chunk_attention``. Gathers each ROW's
    contiguous view once (B small slabs, not one per packed token — the
    naive per-token gather moves T/B times more pool bytes and dominates the
    step on gather-bound backends), scores every token against every row's
    slab, then selects each token's own row from the score tensor. The V
    contraction routes each token's probabilities to its own row's slab
    (zeros elsewhere), so no per-token V view is materialized either. This
    is also the numerics contract for the engine's packed XLA path."""
    T, H, hd = q.shape
    bs, KVH = k_pool.shape[1], k_pool.shape[2]
    B, mb = block_tables.shape
    S = mb * bs
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    tables = jnp.asarray(block_tables, jnp.int32)
    row_of = jnp.asarray(row_of, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    p_end = jnp.asarray(p_end, jnp.int32)
    s_start = jnp.asarray(s_start, jnp.int32)
    rows = jnp.maximum(row_of, 0)
    safe = jnp.maximum(tables, 0)

    def gather(pool, sc=None):
        g = jnp.take(pool, safe, axis=0)  # (B, mb, bs, KVH, hd)
        if sc is not None:
            s = jnp.take(sc, safe, axis=0)  # (B, mb, KVH)
            g = g.astype(jnp.float32) * s[:, :, None, :, None]
        return g.reshape(B, S, KVH, hd)

    K, V = gather(k_pool, k_scale), gather(v_pool, v_scale)
    qg = q.reshape(T, KVH, G, hd)
    scores = jnp.einsum(
        "tkgh,bskh->tbkgs", qg, K, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.take_along_axis(
        scores, rows[:, None, None, None, None], axis=1
    )[:, 0]                                           # (T, KVH, G, S)

    per_tok_tables = tables[rows]                     # (T, mb) — table ints only
    s_idx = jnp.arange(S)
    backed = (row_of[:, None] >= 0) & (per_tok_tables[:, s_idx // bs] >= 0)
    span = (s_idx[None] < p_end[:, None]) | (
        (s_idx[None] >= s_start[:, None]) & (s_idx[None] <= slots[:, None])
    )
    valid = backed & span
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)

    route = (rows[:, None] == jnp.arange(B)[None]).astype(V.dtype)
    p_full = probs.astype(V.dtype)[:, None] * route[:, :, None, None, None]
    out = jnp.einsum(
        "tbkgs,bskh->tkgh", p_full, V, preferred_element_type=jnp.float32
    )
    return out.reshape(T, H, hd).astype(q.dtype)
