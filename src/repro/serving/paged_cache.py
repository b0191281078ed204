"""Paged KV-cache manager (vLLM-style PagedAttention, TPU adaptation).

The generation engine's contiguous per-slot cache wastes memory on short
requests and fragments under continuous batching. The paged manager keeps a
global pool of fixed-size blocks and a per-sequence block table; attention
gathers a sequence's blocks on the fly. On TPU the gather is a cheap
`jnp.take` along the block axis (XLA lowers it to dynamic-slice loops into
VMEM), so the adaptation is table-driven gathers rather than CUDA
page-table pointer chasing.

Blocks are reference counted so concurrent RAG requests that embed the same
retrieved documents share prefix blocks instead of recomputing them. Two
keying schemes feed one prefix index:

* whole-prompt chained hashes (``prefix_block_keys``) — the conservative
  fallback for flat, unsegmented prompts: a block matches only when the
  entire prompt prefix up to it matches;
* segment-scoped keys (``serving.segments.build_layout``) — SegmentedPrompt
  requests key each document segment's full blocks by (prelude, doc content)
  chains that restart at segment boundaries, so a document's KV blocks are
  shared across requests and survive re-ranking/reordering. Blocks straddling
  a segment boundary are never keyed (partial tails are never shared).

Admission walks a request's block ordinals sharing every indexed block (holes
between hits become prefill compute spans), and releases keep refcount-0
blocks warm in an LRU eviction queue (prefix-index hits re-heat a block even
when the hitting request backpressures).

Beneath the device pool sits an optional host-memory tier
(``serving.host_tier.HostBlockStore``): warm blocks evicted from HBM demote
their contents to host, and admission promotes host-resident keyed blocks
back — a second-chance hit class between an HBM hit and a full prefill miss
(``Admission.n_host``). The store may be shared across DP replicas, making a
document prefilled on one replica a host-hit on another.

Pool layout per layer-kind group (matching models.model.init_cache):
    k/v: (G, n_blocks, block_size, KVH, hd)
Block tables: (max_seqs, max_blocks_per_seq) int32, -1 = unallocated
(``PagedPool.table_array`` documents the full contract).

Under a TP/DP mesh the pool arrays are sharded — KV-head dim over the model
axis, optionally block dim over the data axis — while every structure in this
file's allocator stays replicated host-side metadata; see
``serving.sharded_pool`` and ``docs/architecture.md``.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PagedPool:
    """Host-side allocator for one cache pool (reference-counted blocks).

    Blocks have three states: *allocated* (refcount >= 1, owned by one or more
    sequences), *cached* (refcount 0 but kept warm because a prefix index
    still points at them — reclaimed lazily, oldest first, when allocation
    needs room), and *free*. ``n_free`` counts free + cached since both are
    allocatable."""

    n_blocks: int
    block_size: int
    free_list: List[int] = field(default_factory=list)
    tables: Dict[int, List[int]] = field(default_factory=dict)  # seq -> blocks
    refcounts: Dict[int, int] = field(default_factory=dict)     # block -> refs
    # warm blocks in LRU order: an insertion-ordered dict keyed by block id
    # (values unused), so membership, revive and re-heat are all O(1) — the
    # historical list needed O(n) ``remove``/``pop(0)`` on the hot path
    cached: Dict[int, None] = field(default_factory=dict)
    on_free: Optional[Callable[[int], None]] = None             # block truly freed
    keep_on_release: Optional[Callable[[int], bool]] = None     # warm-cache policy
    n_owned: int = 0     # blocks this allocator may hand out (DP block range)
    evictions: int = 0   # warm blocks that allocation took back
    # optional analysis.kvsan.KVSanitizer: every state transition below
    # mirrors into its shadow machine, which raises on lifecycle violations
    # (use-after-free, double-free, refcount underflow). None = no overhead.
    sanitizer: Optional[Any] = None

    def __post_init__(self):
        if not self.free_list:
            self.free_list = list(range(self.n_blocks))
        if not self.n_owned:
            # a DP replica owns only its block range (its seeded free_list);
            # a whole-pool allocator owns every block
            self.n_owned = len(self.free_list)

    @property
    def n_free(self) -> int:
        return len(self.free_list) + len(self.cached)

    def blocks_needed(self, n_tokens: int) -> int:
        return (n_tokens + self.block_size - 1) // self.block_size

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_needed(n_tokens) <= self.n_free

    def _pop_block(self) -> int:
        if self.free_list:
            return self.free_list.pop()
        if not self.cached:
            raise MemoryError("paged pool exhausted: no free or warm block")
        b = next(iter(self.cached))  # evict least-recently-used warm block
        del self.cached[b]
        self.evictions += 1
        if self.sanitizer is not None:
            self.sanitizer.device_warm_evict(b)
        if self.on_free is not None:
            self.on_free(b)
        return b

    def touch(self, block_id: int):
        """LRU heat signal: a prefix-index hit moves a warm block to the back
        of the eviction queue even when the hitting request cannot be admitted
        yet (backpressure) — a hot shared prefix must outlive cold one-off
        blocks released after it. O(1)."""
        if self.refcounts.get(block_id, 0) == 0 and block_id in self.cached:
            if self.sanitizer is not None:
                self.sanitizer.device_touch(block_id)
            del self.cached[block_id]
            self.cached[block_id] = None  # re-insert at the MRU end

    def allocate(self, seq_id: int, n_tokens: int) -> List[int]:
        need = self.blocks_needed(n_tokens)
        if need > self.n_free:
            raise MemoryError(
                f"paged pool exhausted: need {need} blocks, {self.n_free} free"
            )
        blocks = [self._pop_block() for _ in range(need)]
        for b in blocks:
            self.refcounts[b] = 1
            if self.sanitizer is not None:
                self.sanitizer.device_alloc(b, seq_id)
        self.tables.setdefault(seq_id, []).extend(blocks)
        return blocks

    def share(self, seq_id: int, block_id: int) -> int:
        """Append an already-written block to ``seq_id``'s table, bumping its
        refcount (copy-on-nothing prefix sharing: only fully written, immutable
        prompt blocks are ever shared). Reviving a warm cached block removes it
        from the eviction queue (O(1))."""
        if self.sanitizer is not None:
            self.sanitizer.device_share(block_id, seq_id)
        if self.refcounts.get(block_id, 0) == 0:
            self.cached.pop(block_id, None)
        self.refcounts[block_id] = self.refcounts.get(block_id, 0) + 1
        self.tables.setdefault(seq_id, []).append(block_id)
        return block_id

    def extend_for(self, seq_id: int, new_len: int) -> Optional[int]:
        """Ensure capacity for new_len tokens; returns a newly allocated
        block id if one was needed."""
        have = len(self.tables.get(seq_id, [])) * self.block_size
        if new_len <= have:
            return None
        return self.allocate(seq_id, new_len - have)[0]

    def free(self, seq_id: int):
        # release in reverse chain order: a chain's head blocks (most likely
        # to be re-hit — every prefix match starts there) land at the back of
        # the LRU queue, so tails are evicted before heads
        for b in reversed(self.tables.pop(seq_id, [])):
            if self.sanitizer is not None:
                self.sanitizer.device_release(b, seq_id)
            self.refcounts[b] = self.refcounts.get(b, 1) - 1
            if self.refcounts[b] <= 0:
                del self.refcounts[b]
                if self.keep_on_release is not None and self.keep_on_release(b):
                    self.cached[b] = None  # stays warm for prefix reuse
                    if self.sanitizer is not None:
                        self.sanitizer.device_warm(b)
                else:
                    self.free_list.append(b)
                    if self.sanitizer is not None:
                        self.sanitizer.device_free(b)
                    if self.on_free is not None:
                        self.on_free(b)

    def table_array(self, seq_ids: List[int], max_blocks: int) -> np.ndarray:
        """Dense block-table rows for a batch of sequences.

        CONTRACT (the one all callers and device ops assume — regression-
        tested in tests/test_sharded_pool.py):

        * dtype is exactly ``np.int32`` (block-table gathers are traced with
          int32 index arithmetic; an int64 table retraces every jit);
        * entries past a sequence's chain are padded with ``-1`` ("no block"),
          NEVER ``0`` — block 0 is an ordinary allocatable block (and usually
          the engine's scratch block), so 0-padding would silently alias it;
        * device-side consumers must therefore treat negatives as absent:
          gathers clamp (``gather_paged_batch``/``paged_validity``), scatters
          re-route padded slots to the scratch block
          (``write_paged_chunk_batch``). The engine's decode step
          additionally rewrites ``-1`` entries to its scratch block id
          before tracing; its packed mixed step masks them.
        """
        out = np.full((len(seq_ids), max_blocks), -1, dtype=np.int32)
        for i, sid in enumerate(seq_ids):
            blocks = self.tables.get(sid, [])[:max_blocks]
            out[i, : len(blocks)] = blocks
        assert out.dtype == np.int32  # the contract above; never silently widen
        return out

    def utilization(self) -> float:
        """Allocated fraction of the blocks THIS allocator owns (a DP
        replica's utilization is over its block range, not the shared pool)."""
        return 1.0 - self.n_free / max(self.n_owned, 1)


# ---------------------------------------------------------------------------
# device-side paged operations (pure JAX; jit-able)
# ---------------------------------------------------------------------------


def write_paged(pool_kv, block_table_row, pos, new_kv, block_size: int):
    """Write one token's (G, KVH, hd) entry at absolute position ``pos`` for
    the sequence whose blocks are ``block_table_row`` (max_blocks,) int32.

    pool_kv: (G, n_blocks, block_size, KVH, hd)."""
    blk_idx = block_table_row[pos // block_size]
    off = pos % block_size
    return pool_kv.at[:, blk_idx, off].set(new_kv.astype(pool_kv.dtype))


def write_paged_chunk(pool_kv, block_table_row, start, new_kv, block_size: int,
                      n_valid=None, null_dest: int = 0):
    """Vectorized bulk write of a C-token chunk at absolute positions
    ``start .. start+C-1`` (one scatter instead of C sequential updates).

    pool_kv: (G, n_blocks, bs, KVH, hd); new_kv: (G, C, KVH, hd).
    ``n_valid`` (traced scalar) masks trailing padding tokens: their writes
    are routed to slot 0 of the ``null_dest`` block (the engine reserves a
    scratch block that no sequence ever reads)."""
    G, nb, bs = pool_kv.shape[0], pool_kv.shape[1], pool_kv.shape[2]
    C = new_kv.shape[1]
    pos = start + jnp.arange(C)
    blk = jnp.maximum(block_table_row[pos // bs], 0)
    dest = blk * bs + pos % bs
    if n_valid is not None:
        dest = jnp.where(jnp.arange(C) < n_valid, dest, null_dest * bs)
    flat = pool_kv.reshape(G, nb * bs, *pool_kv.shape[3:])
    flat = flat.at[:, dest].set(new_kv.astype(pool_kv.dtype))
    return flat.reshape(pool_kv.shape)


def write_paged_chunk_batch(pool_kv, block_tables, starts, new_kv, block_size: int,
                            n_valid=None, null_dest: int = 0):
    """Multi-row chunk scatter: write B sequences' C-token chunks in one
    update (the engine's audited ``pool`` step program).

    pool_kv: (G, n_blocks, bs, KVH, hd); block_tables: (B, mb) int32;
    starts/n_valid: (B,) absolute start position and valid-token count per
    row; new_kv: (G, B, C, KVH, hd). Rows' padding tokens (index >= n_valid)
    are routed to slot 0 of the ``null_dest`` scratch block, so duplicate
    scratch writes may race — nothing ever reads the scratch block."""
    G, nb, bs = pool_kv.shape[0], pool_kv.shape[1], pool_kv.shape[2]
    B, C = new_kv.shape[1], new_kv.shape[2]
    pos = starts[:, None] + jnp.arange(C)                      # (B, C)
    blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)
    dest = jnp.maximum(blk, 0) * bs + pos % bs
    if n_valid is not None:
        dest = jnp.where(jnp.arange(C)[None, :] < n_valid[:, None], dest, null_dest * bs)
    flat = pool_kv.reshape(G, nb * bs, *pool_kv.shape[3:])
    flat = flat.at[:, dest.reshape(-1)].set(
        new_kv.reshape(G, B * C, *new_kv.shape[3:]).astype(flat.dtype)
    )
    return flat.reshape(pool_kv.shape)


def write_paged_packed(pool_kv, block_tables, row_of, slots, new_kv,
                       block_size: int, null_dest: int = 0):
    """Ragged fused-step scatter: write T packed tokens' K/V entries straight
    into the pool, each through its owning row's block table.

    pool_kv: (n_blocks, bs, KVH, hd) — ONE layer group's pool slice (no G
    axis; the stack scan supplies per-group slices); block_tables: (B, mb)
    int32, RAW (-1 allowed); row_of/slots: (T,) owning batch row (-1 = packed
    pad token) and absolute cache slot per token; new_kv: (T, KVH, hd).
    Pad tokens and writes landing on unbacked table entries are routed to
    slot 0 of the ``null_dest`` scratch block (racy duplicates are fine —
    nothing ever reads the scratch block)."""
    nb, bs = pool_kv.shape[0], pool_kv.shape[1]
    tables = jnp.asarray(block_tables, jnp.int32)
    blk = tables[jnp.maximum(row_of, 0), slots // bs]          # (T,)
    dest = jnp.where(
        (row_of >= 0) & (blk >= 0), blk * bs + slots % bs, null_dest * bs
    )
    flat = pool_kv.reshape(nb * bs, *pool_kv.shape[2:])
    return flat.at[dest].set(new_kv.astype(flat.dtype)).reshape(pool_kv.shape)


# ---------------------------------------------------------------------------
# int8 quantized pool scatters (per-block, per-KV-head running-max scales)
# ---------------------------------------------------------------------------


def _quantized_scatter(pool_kv, scales, dest, new_vals):
    """Core of every quantized write: scatter float K/V entries into an int8
    pool, maintaining per-(block, KV-head) absmax scales.

    pool_kv: (G, nb, bs, KVH, hd) int8; scales: (G, nb, KVH) float32;
    dest: (N,) flat slot indices (block * bs + offset, pads already routed to
    the scratch block); new_vals: (N,) float entries (G, N, KVH, hd).

    Scales are a running max (``new_scale = max(old, absmax(new)/127)``) so a
    block's already-written slots never clip. When a write grows a block's
    scale, the block's existing int8 payload is re-quantized in place
    (``round(q * old/new)``) — only the *affected* blocks are gathered and
    rewritten, never the whole pool. Duplicate block ids in ``dest`` rescale
    to identical values, so the duplicate scatter writes are benign."""
    G, nb, bs = pool_kv.shape[0], pool_kv.shape[1], pool_kv.shape[2]
    blk = dest // bs                                           # (N,)
    absmax = jnp.max(jnp.abs(new_vals.astype(jnp.float32)), axis=-1)  # (G,N,KVH)
    blk_max = jnp.zeros_like(scales).at[:, blk].max(absmax)
    new_scales = jnp.maximum(scales, blk_max / 127.0)
    # rescale affected blocks whose scale grew (ratio < 1 elsewhere is 1)
    ratio = jnp.where(new_scales > 0.0,
                      scales / jnp.maximum(new_scales, 1e-30), 1.0)
    old_blocks = pool_kv[:, blk].astype(jnp.float32)           # (G,N,bs,KVH,hd)
    r = ratio[:, blk]                                          # (G,N,KVH)
    rescaled = jnp.clip(jnp.round(old_blocks * r[:, :, None, :, None]),
                        -127, 127)
    pool_kv = pool_kv.at[:, blk].set(rescaled.astype(pool_kv.dtype))
    # quantize the incoming entries with their destination block's new scale
    s_dest = jnp.maximum(new_scales[:, blk], 1e-30)            # (G,N,KVH)
    q = jnp.clip(jnp.round(new_vals.astype(jnp.float32) / s_dest[:, :, :, None]),
                 -127, 127)
    flat = pool_kv.reshape(G, nb * bs, *pool_kv.shape[3:])
    flat = flat.at[:, dest].set(q.astype(pool_kv.dtype))
    return flat.reshape(pool_kv.shape), new_scales


def write_paged_chunk_q(pool_kv, scales, block_table_row, start, new_kv,
                        block_size: int, n_valid=None, null_dest: int = 0):
    """Quantized ``write_paged_chunk``: same destination routing, int8 store
    with running-max scales. Returns ``(pool, scales)``."""
    bs = pool_kv.shape[2]
    C = new_kv.shape[1]
    pos = start + jnp.arange(C)
    blk = jnp.maximum(block_table_row[pos // bs], 0)
    dest = blk * bs + pos % bs
    if n_valid is not None:
        dest = jnp.where(jnp.arange(C) < n_valid, dest, null_dest * bs)
    return _quantized_scatter(pool_kv, scales, dest, new_kv)


def write_paged_packed_q(pool_kv, scales, block_tables, row_of, slots, new_kv,
                         block_size: int, null_dest: int = 0):
    """Quantized ``write_paged_packed``: one layer group's pool slice (no G
    axis), scales slice (nb, KVH). Returns ``(pool, scales)``."""
    bs = pool_kv.shape[1]
    tables = jnp.asarray(block_tables, jnp.int32)
    blk = tables[jnp.maximum(row_of, 0), slots // bs]          # (T,)
    dest = jnp.where(
        (row_of >= 0) & (blk >= 0), blk * bs + slots % bs, null_dest * bs
    )
    p, s = _quantized_scatter(pool_kv[None], scales[None], dest, new_kv[None])
    return p[0], s[0]


def dequantize_blocks(blocks, block_scales, out_dtype=jnp.float32):
    """Dequantize gathered int8 blocks (..., bs, KVH, hd) with matching
    per-block scales (..., KVH): broadcast-multiply over slot and head dims."""
    return blocks.astype(out_dtype) * block_scales[..., None, :, None].astype(out_dtype)


def gather_paged_dq(pool_kv, scales, block_table_row, max_blocks: int,
                    out_dtype=jnp.float32):
    """``gather_paged`` for quantized pools: materialize a dequantized
    contiguous view. With ``scales=None`` falls back to the plain gather."""
    if scales is None:
        return gather_paged(pool_kv, block_table_row, max_blocks)
    safe = jnp.maximum(block_table_row[:max_blocks], 0)
    g = jnp.take(pool_kv, safe, axis=1)        # (G, mb, bs, KVH, hd)
    s = jnp.take(scales, safe, axis=1)         # (G, mb, KVH)
    g = dequantize_blocks(g, s, out_dtype)
    G, nb, bs, KVH, hd = g.shape
    return g.reshape(G, nb * bs, KVH, hd)


def gather_paged_batch_dq(pool_kv, scales, block_tables,
                          out_dtype=jnp.float32):
    """``gather_paged_batch`` for quantized pools: batched dequantized view.
    With ``scales=None`` falls back to the plain gather."""
    if scales is None:
        return gather_paged_batch(pool_kv, block_tables)
    safe = jnp.maximum(block_tables, 0)
    g = jnp.take(pool_kv, safe, axis=1)        # (G, B, mb, bs, KVH, hd)
    s = jnp.take(scales, safe, axis=1)         # (G, B, mb, KVH)
    g = dequantize_blocks(g, s, out_dtype)
    G, B, mb, bs = g.shape[:4]
    return g.reshape(G, B, mb * bs, *g.shape[4:])


def gather_paged(pool_kv, block_table_row, max_blocks: int):
    """Materialize a sequence's contiguous cache view from its pages:
    (G, max_blocks*block_size, KVH, hd). Unallocated pages read block 0 and
    must be masked by validity downstream."""
    safe = jnp.maximum(block_table_row[:max_blocks], 0)
    gathered = jnp.take(pool_kv, safe, axis=1)  # (G, max_blocks, bs, KVH, hd)
    G, nb, bs, KVH, hd = gathered.shape
    return gathered.reshape(G, nb * bs, KVH, hd)


def gather_paged_batch(pool_kv, block_tables):
    """Batched gather: block_tables (B, max_blocks) -> (G, B, mb*bs, KVH, hd),
    the contiguous per-slot view the batched decode step consumes."""
    safe = jnp.maximum(block_tables, 0)
    g = jnp.take(pool_kv, safe, axis=1)  # (G, B, mb, bs, KVH, hd)
    G, B, mb, bs = g.shape[:4]
    return g.reshape(G, B, mb * bs, *g.shape[4:])


def paged_validity(block_table_row, length, block_size: int, max_blocks: int):
    """(max_blocks*block_size,) bool: slot is backed by a real page AND below
    the sequence length."""
    slots = jnp.arange(max_blocks * block_size)
    backed = block_table_row[slots // block_size] >= 0
    return backed & (slots < length)


# ---------------------------------------------------------------------------
# prefix hashing (host side)
# ---------------------------------------------------------------------------


def _chunk_hash(prev: bytes, tokens_block: np.ndarray) -> bytes:
    """Rolling block hash: H_i = sha1(H_{i-1} || tokens of block i). Chained
    so a block matches only when the entire prefix up to it matches."""
    h = hashlib.sha1(prev)
    h.update(np.ascontiguousarray(tokens_block, dtype=np.int64).tobytes())
    return h.digest()


def prefix_block_keys(tokens, block_size: int) -> List[bytes]:
    """Chained hash keys for every FULL block of ``tokens``.

    Invariants:

    * returns exactly ``len(tokens) // block_size`` keys — the trailing
      partial block (if any) is NEVER keyed, because a partially filled block
      is still mutable and must not be shared;
    * ``keys[i]`` is a function of tokens ``[0, (i+1)*block_size)`` — the
      whole prefix, not just block ``i`` — so two requests may share block
      ``i`` only when their first ``(i+1)*block_size`` tokens are identical
      (exactly the condition under which classic causal K/V is bit-identical);
    * deterministic across processes (sha1 over the int64 token bytes), so
      keys are stable cache identities, not per-run ids.
    """
    toks = np.asarray(tokens)
    keys: List[bytes] = []
    prev = b""
    for i in range(len(toks) // block_size):
        prev = _chunk_hash(prev, toks[i * block_size : (i + 1) * block_size])
        keys.append(prev)
    return keys


@dataclass
class Admission:
    """Result of admission-controlled allocation for a prompt.

    ``shared_spans`` covers BOTH hit classes — HBM-shared blocks and blocks
    promoted from the host tier hold exact KV either way, so the prefill
    cursor may skip all of them; ``n_shared``/``n_host`` split the token
    counts per tier for the telemetry/cost-model feedback paths.

    Session-history blocks (``segments.KIND_HISTORY``, multi-turn
    conversations) are additionally classified out of each tier:
    ``n_shared_session <= n_shared`` and ``n_host_session <= n_host`` count
    the subset of hit tokens that are conversation history — the very
    prefix-heavy hit class the host tier carries between turns, reported
    separately from doc hits in ``latency_summary`` and the Generator cost
    model."""

    n_shared: int                       # prompt tokens served from HBM-shared blocks
    shared_spans: List[Tuple[int, int]]  # token ranges prefill may skip
    n_host: int = 0                     # prompt tokens promoted from the host tier
    n_shared_session: int = 0           # session-history subset of n_shared
    n_host_session: int = 0             # session-history subset of n_host


class PoolArrays:
    """Device-side k/v pool arrays, boxed so they can be shared.

    DP replicas run independent admission over disjoint block ranges of ONE
    pool array (the data-axis story of serving.sharded_pool): every replica's
    PagedKVCache holds the same PoolArrays box, and the engines' functional
    array updates (``cache.k = new_k``) publish through it, so a replica
    always steps against the latest array containing every replica's blocks.
    Disjoint block ranges make the interleaved updates conflict-free.

    Quantized pools (``kv_dtype="int8"``) carry per-(block, KV-head) float32
    scale pools in ``k_scale``/``v_scale`` (shape (G, n_blocks, KVH)); both
    are ``None`` for float pools."""

    __slots__ = ("k", "v", "k_scale", "v_scale")

    def __init__(self, k, v, k_scale=None, v_scale=None):
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale


class PagedKVCache:
    """End-to-end paged cache for one model: pools per layer-group position.

    Usage (mirrors the engine's flow):
        cache = PagedKVCache(cfg, n_blocks=256, block_size=16)
        adm = cache.admit_tokens(seq_id, prompt_tokens)       # host: allocate
        cache.write_prefill(seq_id, k_entries)                # device: copy-in
        cache.register_prefix(seq_id, prompt_tokens)          # publish blocks
        kv, valid = cache.sequence_view(seq_id, length)
        cache.release(seq_id)

    ``admit_tokens``/``register_prefix`` take an optional
    ``serving.segments.SegmentLayout``: segmented prompts key per-document
    blocks independently of document order, so hits can be non-contiguous
    (``Admission.shared_spans`` lists every skippable token range).

    Mesh sharding: ``layout`` (serving.sharded_pool.ShardedPoolLayout) places
    the k/v arrays over a TP/DP mesh — partitioned over the KV-head dim on
    the model axis, optionally over the block dim on the data axis. All host
    metadata (block tables, refcounts, prefix index, warm LRU) stays
    replicated host state regardless of the mesh. ``block_range`` restricts
    allocation to [lo, hi) for a DP replica with independent admission, and
    ``arrays`` shares one PoolArrays box between such replicas. Without a
    layout, construction and math are bit-identical to the single-device
    engine."""

    def __init__(self, cfg, n_blocks: int = 256, block_size: int = 16,
                 max_blocks_per_seq: int = 64, prefix_sharing: bool = True,
                 layout=None, block_range: Optional[Tuple[int, int]] = None,
                 arrays: Optional[PoolArrays] = None, host_store=None,
                 host_write_through: bool = False, client_tag=None,
                 kv_dtype: Optional[str] = None, sanitize: bool = False,
                 sanitizer=None):
        """``host_store`` (serving.host_tier.HostBlockStore) attaches the
        host-memory tier: warm blocks evicted from HBM demote their contents
        there, and ``admit_tokens`` promotes host-resident keys back as a
        second-chance hit class. ``host_write_through`` additionally copies
        every newly published prefix block to host at ``register_prefix``
        time — the DP-group setting, so replicas share doc blocks without
        waiting for an eviction. ``client_tag`` identifies this cache to the
        (possibly shared) store for cross-replica hit accounting.

        ``kv_dtype="int8"`` stores the pools quantized with per-(block,
        KV-head) float32 scale pools alongside (``k_scale``/``v_scale``);
        ``None`` (default) stores ``cfg.dtype`` floats. Prefix keys stay
        token-content hashes either way, so sharing and the segment index are
        dtype-oblivious.

        ``sanitize=True`` attaches an ``analysis.kvsan.KVSanitizer`` that
        mirrors every block lifecycle transition (pool, host tier, copy
        engine) in a shadow state machine and raises ``KVSanError`` on
        use-after-free / double-free / refcount underflow / swap-ordering
        violations — a debug mode. ``sanitizer`` injects a shared instance
        (DP groups: one sanitizer spans all replicas of a shared pool)."""
        from repro.models import transformer as tfm

        self.cfg = cfg
        self.block_size = block_size
        self.max_blocks = max_blocks_per_seq
        self.layout = layout
        p = tfm.period(cfg)
        G = cfg.num_layers // p
        if kv_dtype is not None and kv_dtype not in ("int8",):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        dtype = jnp.int8 if kv_dtype == "int8" else jnp.dtype(cfg.dtype)
        lo, hi = block_range if block_range is not None else (0, n_blocks)
        if not (0 <= lo < hi <= n_blocks):
            raise ValueError(f"block_range {(lo, hi)} outside [0, {n_blocks})")
        if sanitizer is None and sanitize:
            from repro.analysis.kvsan import KVSanitizer

            sanitizer = KVSanitizer()
        self.sanitizer = sanitizer
        self.pool = PagedPool(
            n_blocks, block_size,
            free_list=list(range(lo, hi)),
            on_free=self._forget_block,
            keep_on_release=lambda b: b in self._block_key,
            sanitizer=sanitizer,
        )
        if sanitizer is not None and host_store is not None \
                and getattr(host_store, "sanitizer", None) is None:
            host_store.sanitizer = sanitizer
        if arrays is None:
            k = jnp.zeros(
                (G, n_blocks, block_size, cfg.num_kv_heads, cfg.head_dim), dtype
            )
            if layout is not None:
                layout.validate(cfg)
                k = jax.device_put(k, layout.pool_sharding(cfg, n_blocks))
            if kv_dtype == "int8":
                ks = jnp.zeros((G, n_blocks, cfg.num_kv_heads), jnp.float32)
                arrays = PoolArrays(k, jnp.zeros_like(k), ks, jnp.zeros_like(ks))
            else:
                arrays = PoolArrays(k, jnp.zeros_like(k))
        self._arrays = arrays
        if self.kv_dtype is None and arrays.k_scale is not None:
            self.kv_dtype = "int8"  # shared box from a quantized sibling
        self.lengths: Dict[int, int] = {}
        self.prefix_sharing = prefix_sharing
        self.host_store = host_store
        self.host_write_through = host_write_through
        self.client_tag = client_tag if client_tag is not None else id(self)
        # optional async copy engine (serving.control_plane.CopyEngine): when
        # attached, demotions and write-through publishes defer their blocking
        # host materialization off the step's critical path. None = sync copies
        # (standalone cache usage), bit-identical host-tier contents either way.
        self.copy_engine = None
        self._wt_pending: List[Tuple[int, bytes]] = []  # (block, key) to write through
        self._prefix_index: Dict[bytes, int] = {}   # chain hash -> block id
        self._block_key: Dict[int, bytes] = {}      # reverse map for eviction
        self.shared_token_hits = 0                  # prompt tokens served from shared blocks
        self.host_token_hits = 0                    # prompt tokens promoted from host
        # session-history (KIND_HISTORY) subsets of the two counters above —
        # the multi-turn hit class, tracked separately from doc hits
        self.session_token_hits = 0
        self.session_host_token_hits = 0

    # k/v proxy the shared PoolArrays box: DP replicas see each other's
    # functional updates; the single-engine case is a plain attribute pair
    @property
    def k(self):
        return self._arrays.k

    @k.setter
    def k(self, value):
        self._arrays.k = value

    @property
    def v(self):
        return self._arrays.v

    @v.setter
    def v(self, value):
        self._arrays.v = value

    # scale pools proxy the same shared box (None for float pools)
    @property
    def k_scale(self):
        return self._arrays.k_scale

    @k_scale.setter
    def k_scale(self, value):
        self._arrays.k_scale = value

    @property
    def v_scale(self):
        return self._arrays.v_scale

    @v_scale.setter
    def v_scale(self, value):
        self._arrays.v_scale = value

    @property
    def quantized(self) -> bool:
        return self._arrays.k_scale is not None

    def reset_block_scales(self, ids) -> None:
        """Zero the scale-pool entries of freshly allocated blocks. Scales
        are a running max that only grows while a block is written; a reused
        block must not inherit the previous tenant's (possibly much larger)
        absmax, or the new tenant's entries quantize with needless error.
        No-op for float pools."""
        if not self.quantized or not len(ids):
            return
        idx = jnp.asarray(np.asarray(ids, np.int32))
        self.k_scale = self.k_scale.at[:, idx].set(0.0)
        self.v_scale = self.v_scale.at[:, idx].set(0.0)

    # ----------------------------------------------------------- host side
    def _forget_block(self, block_id: int):
        key = self._block_key.pop(block_id, None)
        if key is not None and self._prefix_index.get(key) == block_id:
            del self._prefix_index[key]
            if self.host_store is not None:
                # demotion: the block is being reclaimed but its contents are
                # still intact (the new owner writes later) — mirror them to
                # the host tier so the key stays promotable instead of dying
                # with the HBM block. Already-resident keys (write-through
                # configs) only re-heat: don't pay the two device->host
                # copies just for put() to discard them.
                if self.host_store.contains(key):
                    self.host_store.touch(key)
                elif self.copy_engine is not None:
                    # deferred demotion: the device-side slices are captured
                    # NOW (immutable array values — a later reuse of the pool
                    # block cannot corrupt them); only the blocking host
                    # materialization waits for a copy-engine drain slot
                    k_blk, v_blk = self.k[:, block_id], self.v[:, block_id]
                    ks_blk = vs_blk = None
                    if self.quantized:
                        ks_blk = self.k_scale[:, block_id]
                        vs_blk = self.v_scale[:, block_id]
                    store, owner = self.host_store, self.client_tag

                    def _demote(key=key, k_blk=k_blk, v_blk=v_blk,
                                ks_blk=ks_blk, vs_blk=vs_blk):
                        if store.contains(key):
                            store.touch(key)  # raced with a write-through/put
                        else:
                            store.put(
                                key, np.asarray(k_blk), np.asarray(v_blk),
                                owner=owner,
                                k_scale=None if ks_blk is None else np.asarray(ks_blk),
                                v_scale=None if vs_blk is None else np.asarray(vs_blk),
                            )

                    self.copy_engine.submit(_demote, tag=key)
                else:
                    ks = vs = None
                    if self.quantized:
                        ks = np.asarray(self.k_scale[:, block_id])
                        vs = np.asarray(self.v_scale[:, block_id])
                    self.host_store.put(
                        key, np.asarray(self.k[:, block_id]),
                        np.asarray(self.v[:, block_id]), owner=self.client_tag,
                        k_scale=ks, v_scale=vs,
                    )

    def _block_hits(self, tokens, layout) -> Dict[int, int]:
        """Block ordinal -> cached block id, for every keyed block already in
        the prefix index. Never includes the block holding the final prompt
        token — at least one token must run through the model to produce the
        first-sample logits. Hits touch warm blocks (LRU heat) even when the
        caller subsequently backpressures."""
        if not self.prefix_sharing or not len(tokens):
            return {}
        last_block = (len(tokens) - 1) // self.block_size
        hits: Dict[int, int] = {}
        for ordinal, key in enumerate(layout.block_keys):
            if key is None or ordinal == last_block:
                continue
            b = self._prefix_index.get(key)
            if b is not None:
                hits[ordinal] = b
                self.pool.touch(b)
        return hits

    def _host_block_hits(self, n_tokens: int, layout,
                         hbm_hits: Dict[int, int]) -> Dict[int, bytes]:
        """Block ordinal -> prefix key for every keyed block that misses the
        HBM index but is resident in the host tier (the second-chance hit
        class). Same exclusions as ``_block_hits``: the final prompt token's
        block always runs through the model."""
        if (self.host_store is None or not self.prefix_sharing
                or not n_tokens):
            return {}
        last_block = (n_tokens - 1) // self.block_size
        out: Dict[int, bytes] = {}
        for ordinal, key in enumerate(layout.block_keys):
            if key is None or ordinal == last_block or ordinal in hbm_hits:
                continue
            if self.host_store.contains(key):
                out[ordinal] = key
                # re-heat now: allocation below may demote evicted HBM blocks
                # into the store, and its LRU must take colder keys before a
                # key we are about to promote
                self.host_store.touch(key)
        return out

    def _promote_host_blocks(self, promote: List[Tuple[int, bytes]]):
        """Copy host-resident blocks into freshly allocated device blocks
        (one batched host->device scatter) and publish their keys in the HBM
        index, so the next request with the same document HBM-hits."""
        keys = [key for _, key in promote]
        ids = jnp.asarray(np.asarray([b for b, _ in promote], np.int32))
        if self.quantized:
            k_np, v_np, ks_np, vs_np = self.host_store.read(
                keys, owner=self.client_tag)
            self.k_scale = self.k_scale.at[:, ids].set(jnp.asarray(ks_np))
            self.v_scale = self.v_scale.at[:, ids].set(jnp.asarray(vs_np))
        else:
            k_np, v_np = self.host_store.read(keys, owner=self.client_tag)
        self.k = self.k.at[:, ids].set(jnp.asarray(k_np))
        self.v = self.v.at[:, ids].set(jnp.asarray(v_np))
        for b, key in promote:
            if key not in self._prefix_index:  # first writer wins, as ever
                self._prefix_index[key] = b
                self._block_key[b] = key
                if self.sanitizer is not None:
                    self.sanitizer.device_key(b, key)

    def admit_tokens(self, seq_id: int, tokens, layout=None) -> Optional[Admission]:
        """Admission-controlled allocation for a prompt. Reuses every cached
        keyed block (+1 slack block for decode), and returns the admission
        record (shared token count + skippable spans) — or None when the pool
        cannot fit the request (backpressure). Flat prompts fall back to the
        whole-prompt chained hash (hits form one leading span); segmented
        prompts can hit per-document blocks anywhere in the layout.

        Invariants (each has a dedicated regression test):

        * **all-or-nothing**: on backpressure (None) NOTHING was allocated,
          shared or promoted — free-block count, refcounts, ``tables[seq_id]``
          and the host tier are untouched, so a deferred request retries with
          no cleanup. Headroom accounting counts new blocks AND warm revivals
          (a shared warm block leaves the LRU queue and consumes ``n_free``);
          revivals are counted by UNIQUE block id — two segments hashing to
          the same block revive it once, and double-counting it used to make
          admission spuriously reject at exact-fit capacity (regression-
          tested in tests/test_host_tier.py).
        * on success, ``tables[seq_id]`` holds exactly
          ``blocks_needed(len(tokens)) + 1`` entries in prompt-block order
          (the +1 is the decode slack block), shared hits refcount-bumped in
          place, misses freshly allocated with refcount 1. Host-tier hits are
          misses for allocation purposes (they consume a fresh block) but
          their KV is copied in from the host store, their key is published
          in the HBM index, and their tokens count as cache-served.
        * the block containing the FINAL prompt token is never served from
          cache: at least one prompt token must run through the model to
          produce the first-sample logits (``_block_hits`` skips it).
        * ``Admission.shared_spans`` are disjoint, sorted, block-aligned
          token ranges covering BOTH hit tiers; ``n_shared + n_host ==
          sum(hi - lo for lo, hi in spans)``, and the engine's prefill cursor
          may skip exactly these ranges.
        * hits touch warm blocks (LRU re-heat) even if the caller then
          backpressures — a hot shared prefix must outlive cold blocks.
        """
        from repro.serving.segments import build_layout

        Lp = len(tokens)
        if layout is None:
            layout = build_layout(np.asarray(tokens), self.block_size)
        bs = self.block_size
        n_blocks = self.pool.blocks_needed(Lp)
        hits = self._block_hits(tokens, layout)
        host_hits = self._host_block_hits(Lp, layout, hits)
        # new blocks (misses + 1 decode slack) plus warm revivals both consume
        # n_free headroom — count them, or allocation below can raise instead
        # of backpressuring. Revivals count per unique block id: the first
        # share of a warm block consumes it from the LRU queue, further
        # shares of the same block only bump its refcount.
        n_new = n_blocks - len(hits) + 1
        n_warm = sum(
            1 for b in set(hits.values()) if self.pool.refcounts.get(b, 0) == 0
        )
        if n_new + n_warm > self.pool.n_free:
            return None
        promote: List[Tuple[int, int, bytes]] = []  # (ordinal, block, key)
        fresh: List[int] = []
        for ordinal in range(n_blocks):
            if ordinal in hits:
                self.pool.share(seq_id, hits[ordinal])
            else:
                b = self.pool.allocate(seq_id, 1)[0]
                fresh.append(b)
                if ordinal in host_hits:
                    promote.append((ordinal, b, host_hits[ordinal]))
        fresh.extend(self.pool.allocate(seq_id, 1))  # decode slack block
        self.reset_block_scales(fresh)
        # allocation above may have demoted evicted HBM blocks into the host
        # store, whose own LRU can (despite the re-heat in _host_block_hits)
        # drop a pending-promote key under extreme pressure — such ordinals
        # degrade to ordinary misses (their fresh block prefills normally)
        promote = [(o, b, k) for o, b, k in promote
                   if self.host_store.contains(k)]
        if promote:
            self._promote_host_blocks([(b, k) for _o, b, k in promote])
        n_shared = len(hits) * bs
        n_host = len(promote) * bs
        # session-history classification: a hit block whose span lies inside a
        # KIND_HISTORY segment is the multi-turn hit class, split out of each
        # tier's count (empty set for prompts without history segments)
        hist = layout.history_block_set() if layout.seg_spans else set()
        n_shared_session = sum(bs for o in hits if o in hist)
        n_host_session = sum(bs for o, _b, _k in promote if o in hist)
        self.lengths[seq_id] = 0
        self.shared_token_hits += n_shared
        self.host_token_hits += n_host
        self.session_token_hits += n_shared_session
        self.session_host_token_hits += n_host_session
        spans: List[Tuple[int, int]] = []
        for ordinal in sorted(set(hits) | {o for o, _b, _k in promote}):
            lo, hi = ordinal * bs, (ordinal + 1) * bs
            if spans and spans[-1][1] == lo:
                spans[-1] = (spans[-1][0], hi)
            else:
                spans.append((lo, hi))
        return Admission(n_shared, spans, n_host,
                         n_shared_session=n_shared_session,
                         n_host_session=n_host_session)

    def register_prefix(self, seq_id: int, tokens, layout=None):
        """Publish this sequence's fully written prompt blocks into the prefix
        index so later requests reuse them.

        Invariants:

        * **only immutable blocks are published**: keyed blocks are FULL
          blocks lying inside one segment (``(i+1) * block_size <=
          len(tokens)`` holds for every keyed ordinal ``i``), and decode
          writes land strictly after the prompt — so a published block's
          contents never change while the index points at it.
        * MUST be called only after the prompt's K/V has actually been
          written through ordinal ``i`` (the engine calls it when the prefill
          cursor completes); publishing earlier would let a follower gather
          zeros.
        * first writer wins: an already-indexed key is never re-pointed, so
          concurrent identical prompts converge on one physical block chain.
        * the reverse map ``_block_key`` stays exact: a block evicted from
          the warm cache drops its index entry (``_forget_block``), so the
          index never dangles into reallocated blocks — the no-leak invariant
          the randomized engine harness checks.
        """
        if not self.prefix_sharing:
            return
        from repro.serving.segments import build_layout

        if layout is None:
            layout = build_layout(np.asarray(tokens), self.block_size)
        table = self.pool.tables.get(seq_id, [])
        published: List[Tuple[int, bytes]] = []
        for i, key in enumerate(layout.block_keys):
            if key is None or i >= len(table):
                continue
            if key not in self._prefix_index:
                self._prefix_index[key] = table[i]
                self._block_key[table[i]] = key
                if self.sanitizer is not None:
                    self.sanitizer.device_key(table[i], key)
                published.append((table[i], key))
        if published and self.host_store is not None and self.host_write_through:
            if self.copy_engine is not None:
                # the pipelined control plane registers prefixes at plan-BUILD
                # time, BEFORE the plan that writes the completing chunk has
                # been dispatched — gathering ``self.k`` here would capture
                # incomplete blocks. Queue the publish; ``flush_write_through``
                # (called by the engine's post-dispatch drain) does the gather
                # against the post-dispatch arrays.
                self._wt_pending.extend(published)
            else:
                # write-through to the host tier (one batched device->host
                # gather): a DP-shared store makes these blocks promotable on
                # sibling replicas immediately, not only after an HBM eviction
                ids = jnp.asarray(np.asarray([b for b, _ in published], np.int32))
                k_np = np.asarray(jnp.take(self.k, ids, axis=1))
                v_np = np.asarray(jnp.take(self.v, ids, axis=1))
                ks_np = vs_np = None
                if self.quantized:
                    ks_np = np.asarray(jnp.take(self.k_scale, ids, axis=1))
                    vs_np = np.asarray(jnp.take(self.v_scale, ids, axis=1))
                for j, (_b, key) in enumerate(published):
                    self.host_store.put(
                        key, k_np[:, j], v_np[:, j], owner=self.client_tag,
                        k_scale=None if ks_np is None else ks_np[:, j],
                        v_scale=None if vs_np is None else vs_np[:, j],
                    )

    def flush_write_through(self) -> None:
        """Drain queued write-through publishes (copy-engine mode only).

        MUST run after the plan that completes the published chunks has been
        dispatched: the gather then reads the step's output arrays, so the
        captured values are the blocks' final contents regardless of when the
        copy engine drains the host materialization. Blocks whose key was
        forgotten in the meantime are skipped — the demotion path already
        mirrored (or deliberately dropped) them."""
        if not self._wt_pending or self.copy_engine is None:
            self._wt_pending.clear()
            return
        pend = [(b, key) for b, key in self._wt_pending
                if self._block_key.get(b) == key]
        self._wt_pending = []
        if not pend:
            return
        ids = jnp.asarray(np.asarray([b for b, _ in pend], np.int32))
        kg = jnp.take(self.k, ids, axis=1)
        vg = jnp.take(self.v, ids, axis=1)
        ksg = vsg = None
        if self.quantized:
            ksg = jnp.take(self.k_scale, ids, axis=1)
            vsg = jnp.take(self.v_scale, ids, axis=1)
        store, owner = self.host_store, self.client_tag

        def _publish(kg=kg, vg=vg, ksg=ksg, vsg=vsg, pend=tuple(pend)):
            k_np, v_np = np.asarray(kg), np.asarray(vg)
            ks_np = None if ksg is None else np.asarray(ksg)
            vs_np = None if vsg is None else np.asarray(vsg)
            for j, (_b, key) in enumerate(pend):
                store.put(key, k_np[:, j], v_np[:, j], owner=owner,
                          k_scale=None if ks_np is None else ks_np[:, j],
                          v_scale=None if vs_np is None else vs_np[:, j])

        self.copy_engine.submit(_publish, tag="write_through")

    def admit(self, seq_id: int, prompt_len: int) -> bool:
        """Length-only admission (no prefix sharing); kept for callers that
        stream K/V in without token identity."""
        if not self.pool.can_allocate(prompt_len + self.block_size):
            return False  # backpressure: engine keeps the request queued
        self.reset_block_scales(
            self.pool.allocate(seq_id, prompt_len + self.block_size))
        self.lengths[seq_id] = 0
        return True

    def release(self, seq_id: int):
        self.pool.free(seq_id)
        self.lengths.pop(seq_id, None)

    def batch_tables(self, seq_ids: List[int]) -> np.ndarray:
        """Block-table rows truncated to ``max_blocks`` — same contract as
        ``PagedPool.table_array`` (int32, pad = -1, never 0)."""
        return self.pool.table_array(seq_ids, self.max_blocks)

    # --------------------------------------------------------- device side
    def write_token(self, seq_id: int, k_entry, v_entry):
        """k/v_entry: (G, KVH, hd) for the next position of seq_id."""
        pos = self.lengths[seq_id]
        new_blk = self.pool.extend_for(seq_id, pos + 1)
        if new_blk is not None:
            self.reset_block_scales([new_blk])
        # pad-ok: writes touch only positions < lengths[seq], which sit in
        # blocks extend_for just reserved — the row is fully backed there.
        row = jnp.asarray(self.pool.table_array([seq_id], self.max_blocks)[0])
        if self.quantized:
            self.k, self.k_scale = write_paged_chunk_q(
                self.k, self.k_scale, row, pos, k_entry[:, None], self.block_size)
            self.v, self.v_scale = write_paged_chunk_q(
                self.v, self.v_scale, row, pos, v_entry[:, None], self.block_size)
        else:
            self.k = write_paged(self.k, row, pos, k_entry, self.block_size)
            self.v = write_paged(self.v, row, pos, v_entry, self.block_size)
        self.lengths[seq_id] = pos + 1

    def write_prefill(self, seq_id: int, k_seq, v_seq):
        """k/v_seq: (G, Lp, KVH, hd) — bulk vectorized copy of a prefilled
        prompt (single scatter; no host loop)."""
        Lp = k_seq.shape[1]
        # pad-ok: the Lp tokens being written were block-reserved by the
        # caller's allocate(); pads beyond ceil(Lp/bs) are never addressed.
        row = jnp.asarray(self.pool.table_array([seq_id], self.max_blocks)[0])
        if self.quantized:
            self.k, self.k_scale = write_paged_chunk_q(
                self.k, self.k_scale, row, 0, k_seq, self.block_size)
            self.v, self.v_scale = write_paged_chunk_q(
                self.v, self.v_scale, row, 0, v_seq, self.block_size)
        else:
            self.k = write_paged_chunk(self.k, row, 0, k_seq, self.block_size)
            self.v = write_paged_chunk(self.v, row, 0, v_seq, self.block_size)
        self.lengths[seq_id] = Lp

    def sequence_view(self, seq_id: int) -> Tuple:
        """Returns (k, v, valid): contiguous gathered view + validity mask
        (dequantized to float32 for quantized pools)."""
        # pad-ok: gather_paged_dq clamps pad rows and paged_validity masks
        # them out of the returned view, so -1 entries read as invalid.
        row = jnp.asarray(self.pool.table_array([seq_id], self.max_blocks)[0])
        k = gather_paged_dq(self.k, self.k_scale, row, self.max_blocks)
        v = gather_paged_dq(self.v, self.v_scale, row, self.max_blocks)
        valid = paged_validity(row, self.lengths[seq_id], self.block_size, self.max_blocks)
        return k, v, valid

    def utilization(self) -> float:
        return self.pool.utilization()
