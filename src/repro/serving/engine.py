"""Generation engine: continuous batching over the model zoo.

Real JAX execution at laptop scale (smoke-size models on CPU); the cluster
simulation calibrates its Generator cost model against this engine. The
engine implements the standard serving loop:

    submit(prompt) -> admission -> prefill -> batched decode steps
    with per-slot positions -> emit tokens until max_new/eos.

Two cache backends:

* ``paged`` (default, full-attention GQA stacks): a vLLM-style block pool
  (`serving.paged_cache`) with admission gated on free blocks, chunked
  prefill (long retrieved contexts stream through in fixed chunks instead of
  being bucketed and truncated to a power of two), block-table-driven decode
  (the jnp gather oracle of `kernels.decode_attention.paged_decode_attention`)
  and prefix-block sharing, so concurrent RAG requests embedding the same
  retrieved documents reuse cache blocks instead of recomputing them. On
  pool exhaustion the youngest request is preempted and re-queued (its
  continuation re-prefills, reusing its own published prefix blocks).

* ``dense`` (fallback + parity oracle): the original contiguous per-slot
  cache with power-of-two prompt buckets; architectures the paged path does
  not cover (MLA, recurrent/hybrid state, ring SWA, enc-dec, int8 cache)
  land here automatically.

Scheduling (paged backend): Sarathi-style batched chunked prefill with
decode interleaving (``interleave=True``, the default). Prefill no longer
completes inside admission — each request carries a persistent prefill
cursor (``Request.prefill_pos``) and every ``step()`` assembles one mixed
batch: a decode token for every decode-phase slot plus prefill chunks from
one or more mid-prefill slots, bounded by a per-step ``token_budget``, then
runs a single fused forward (`models.prefill_chunk` with per-row
start/n_valid — decode rows are chunks of one valid token). Decode slots
therefore emit a token on every step even while a long retrieved context is
prefilling (bounded TPOT under bursty RAG load), and TTFT stretches only by
chunk quantization. A `core.scheduler.QueuePolicy` (FIFO or EDF-slack)
orders both admission and the per-step prefill-budget grants.
``interleave=False`` keeps the sequential blocking-prefill loop as the
parity oracle; greedy decode is token-exact across the two modes.

Preemption (paged backend): pool exhaustion picks the youngest active
request and applies the engine's ``preempt`` strategy —

* ``"recompute"`` (default): release the victim's blocks and re-queue its
  continuation (prompt + generated tokens); re-admission repays the prefill.
* ``"swap"``: park the victim's block chain in the host tier
  (`serving.host_tier.HostBlockStore`, one batched device→host gather) and
  restore it verbatim on re-admission — greedy-token-identical to recompute
  without repaying the prefill (falls back to recompute when the host store
  cannot pin the chain). ``benchmarks/swap_preemption.py`` compares the two
  under forced pool pressure.

The host tier also backs the warm-cache LRU (evicted warm blocks demote to
host; admission promotes them back as a second-chance hit class) and, when
shared across a ``DataParallelEngineGroup``, gives replicas cross-replica
document-block sharing. Eviction-aware admission closes the loop: the
``resident_first`` scheduler policy prefers requests whose doc blocks are
HBM- or host-resident (``core.scheduler``).

Runtime / control-plane split (interleaved paged mode): the engine is a thin
orchestrator over three layers — a host-side ``ControlPlane`` that builds an
immutable ``StepPlan`` per step (``serving.control_plane``), a
``DeviceRunner`` that executes plans through the engine's own jitted step
programs with deferred (double-buffered) materialization and device-resident
prev tokens (``serving.device_runner``), and a ``CopyEngine`` draining
device<->host copies (swap fills, demotions, write-through) off the critical
path between dispatches. ``pipeline=True`` (default) materializes sampled
tokens one plan late so plan N+1 is built while step N runs;
``pipeline=False`` materializes eagerly and is the greedy-token-exact sync
oracle — the plan sequence is identical in both modes because all state a
plan build reads is updated at build time. Token delivery is out-of-band:
every request carries a ``StreamingObject`` whose chunks drain through one
shared ``PriorityFlusher`` in EDF-slack order, with chunk size driven by
measured load (``streaming_chunk_policy``). ``latency_summary`` reports the
measured host gap (wall time the device sat idle between dispatches).
"""
from __future__ import annotations

import copy
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.scheduler import QueuePolicy, make_policy
from repro.core.streaming import PriorityFlusher, StreamingObject
from repro.core.telemetry import Span, Telemetry
from repro.kernels.decode_attention import default_interpret
from repro.models import (
    decode_step,
    decode_step_paged,
    forward,
    init_cache,
    init_params,
    paged_cache_supported,
    prefill_chunk,
    prefill_packed,
)
from repro.serving.control_plane import ControlPlane, CopyEngine
from repro.serving.device_runner import DeviceRunner, PlanExec
from repro.serving.host_tier import HostBlockStore
from repro.serving.paged_cache import (
    PagedKVCache,
    PoolArrays,
    _quantized_scatter,
    gather_paged_batch,
    gather_paged_batch_dq,
    write_paged_chunk,
    write_paged_chunk_batch,
    write_paged_chunk_batch_q,
    write_paged_chunk_q,
)
from repro.serving.sampler import sample_tokens
from repro.serving.segments import SegmentedPrompt, build_layout
from repro.serving.sharded_pool import ShardedPoolLayout, block_range

_NULL_SEQ = -1  # owner of the reserved scratch block


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray
    max_new: int
    temperature: float = 0.0
    priority: float = 0.0            # predicted slack (EDF); smaller = more urgent
    out_tokens: List[int] = field(default_factory=list)
    slot: int = -1
    pos: int = 0
    prefill_pos: int = 0             # cache slots already populated (computed/shared)
    prefill_cap: int = 0             # effective prompt length (post-truncation)
    done: bool = False
    truncated: bool = False          # prompt exceeded engine capacity
    shared_prefix_tokens: int = 0    # prompt tokens served from HBM-shared blocks
    host_prefix_tokens: int = 0      # non-session prompt tokens promoted from host
    # multi-turn session hit class (serving.session.Session): conversation-
    # history (KIND_HISTORY) hit tokens, split out of the two tiers above.
    # session_shared_tokens is a SUBSET of shared_prefix_tokens (HBM hits are
    # free either way); session_host_tokens is DISJOINT from
    # host_prefix_tokens, so host promotions partition into doc vs session
    # classes for telemetry and the Generator cost model.
    session_shared_tokens: int = 0
    session_host_tokens: int = 0
    segprompt: Optional[SegmentedPrompt] = None  # retrieval-aware structure
    layout: Any = None               # SegmentLayout (built at admission)
    probe_layout: Any = None         # residency-probe layout (pre-admission)
    shared_spans: List = field(default_factory=list)  # token ranges served from cache
    swapped: bool = False            # KV chain parked in the host tier
    swap_len: int = 0                # cache length to restore on swap-in
    queued_steps: int = 0            # engine steps spent waiting for admission
    trace_id: int = -1               # shared by the stages of one pipeline
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None  # first took a slot
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    token_gaps: List[float] = field(default_factory=list)  # inter-token intervals
    max_token_gap: float = 0.0       # worst inter-token stall (decode SLO signal)
    planned: int = 0                 # tokens scheduled by plans (>= len(out_tokens))
    _tok_src: tuple = (-1, -1)       # (plan_id, row) holding the last sampled token
    swap_keys: List = field(default_factory=list)  # prefix keys of the swap chain
    stream: Optional[StreamingObject] = None       # out-of-band token delivery
    delivered: List[int] = field(default_factory=list)  # tokens flushed downstream

    @property
    def prefilling(self) -> bool:
        return self.slot >= 0 and self.prefill_pos < self.prefill_cap

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of this request's (truncated) prompt served from shared
        cache blocks — the per-request quantity the LP allocator consumes."""
        return self.shared_prefix_tokens / self.prefill_cap if self.prefill_cap else 0.0

    @property
    def host_hit_rate(self) -> float:
        """Fraction of the prompt promoted from the host tier (the
        second-chance hit class between an HBM hit and a prefill miss),
        excluding session-history promotions (``session_hit_rate``)."""
        return self.host_prefix_tokens / self.prefill_cap if self.prefill_cap else 0.0

    @property
    def session_hit_rate(self) -> float:
        """Fraction of the prompt that is session history promoted from the
        host tier — the multi-turn hit class, disjoint from
        ``host_hit_rate``."""
        return self.session_host_tokens / self.prefill_cap if self.prefill_cap else 0.0


def normalize_spans(spans) -> List:
    """Sorted, disjoint, coalesced ``[lo, hi)`` spans (empties dropped).

    The cursor/grant helpers below assume this normal form; admission output
    is normalized by construction, but spans that arrive unsorted or
    overlapping (hand-built, or merged across hit tiers) could otherwise
    leave the prefill cursor inside a cached span or jump it past an uncached
    gap — regression-tested in tests/test_host_tier.py."""
    out: List = []
    for lo, hi in sorted((int(s), int(e)) for s, e in spans if e > s):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _advance_cursor(req: Request) -> None:
    """Skip the prefill cursor over cache-served spans: shared/promoted
    blocks already hold the K/V, so the cursor jumps to the next slot needing
    compute (fully-cached documents cost zero prefill steps). Requires
    ``req.shared_spans`` in the ``normalize_spans`` normal form — one sorted
    pass, never past an uncached gap."""
    for s, e in req.shared_spans:
        if s <= req.prefill_pos < e:
            req.prefill_pos = e
        elif s > req.prefill_pos:
            break
    req.prefill_pos = min(req.prefill_pos, req.prefill_cap)


def _max_grant(req: Request, limit: int) -> int:
    """Largest prefill chunk startable at the cursor: clipped by the chunk
    size, the prompt end, and the next shared span (shared blocks are
    immutable — a chunk must never write into them)."""
    c = min(limit, req.prefill_cap - req.prefill_pos)
    for s, _e in req.shared_spans:
        if s > req.prefill_pos:
            c = min(c, s - req.prefill_pos)
            break  # spans are sorted: the first span ahead is the binding one
    return max(c, 0)


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


class GenerationEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params=None,
        max_batch: int = 4,
        max_seq: int = 256,
        seed: int = 0,
        eos_token: int = -1,
        backend: str = "paged",
        block_size: int = 16,
        prefill_chunk_size: int = 64,
        n_blocks: Optional[int] = None,
        prefix_sharing: bool = True,
        interleave: bool = True,
        token_budget: Optional[int] = None,
        scheduler: Any = "fifo",
        max_finished: int = 10_000,
        mesh: Any = None,
        pool_layout: Optional[ShardedPoolLayout] = None,
        kv: Optional[PagedKVCache] = None,
        preempt: str = "recompute",
        host_store: Optional[HostBlockStore] = None,
        host_blocks: Optional[int] = None,
        pipeline: bool = True,
        flusher: Optional[PriorityFlusher] = None,
        host_bw_bytes_s: float = 8e9,
        copy_budget: int = 4,
        kernel: Optional[str] = None,
        ragged: bool = True,
        pack_align: int = 4,
        kv_dtype: Optional[str] = None,
        sanitize: bool = False,
    ):
        """``mesh`` / ``pool_layout`` shard the paged backend over a device
        mesh: params become TP-resident (Megatron layout, embed/lm_head
        replicated), the KV pool arrays shard over the model axis by KV head,
        and the three step programs are pjit-compiled with pinned pool
        shardings — every block-table gather and chunk scatter is local per
        shard, so the only communication is the post-attention/MLP output
        reductions (``audit_collectives`` asserts this). With neither given
        the engine is bit-identical to the historical single-device path.
        ``kv`` injects a pre-built PagedKVCache — the DataParallelEngineGroup
        uses this to hand replicas block-range slices of one shared pool (and
        a shared host store).

        ``preempt`` selects the pool-exhaustion strategy: ``"recompute"``
        (release + re-queue the continuation), ``"swap"`` (park the block
        chain in the host tier, restore on re-admission) or ``"cost"``
        (per-victim: swap when the estimated copy time beats the estimated
        residency-discounted re-prefill time — see ``_swap_is_cheaper``).
        ``host_store`` / ``host_blocks`` attach the host-memory tier
        explicitly; ``host_blocks`` sizes a fresh store, and
        ``preempt="swap"``/``"cost"`` provision one automatically
        (device-pool-sized) when neither is given.

        ``pipeline`` (interleaved paged mode only) defers sampled-token
        materialization one step so plan N+1 is built while step N runs;
        ``pipeline=False`` is the eager sync oracle, greedy-token-identical.

        ``kernel`` selects the paged hot-path attention implementation:
        ``"pallas"`` runs ``kernels.paged_decode_attention`` for decode
        plans and ``kernels.paged_chunk_attention`` for the ragged fused
        step; ``"reference"`` is the jnp gather oracle. ``None`` lets the
        platform pick: compiled Pallas on a TPU, the reference elsewhere
        (where Pallas would only run in its interpreter). A mesh needs an
        explicit ``"reference"``. ``ragged``
        (interleaved mode) packs the fused mixed batch into one flat token
        buffer (decode rows cost one slot, not a chunk-width row; tables go
        to the device RAW, unbacked pages masked in the kernel);
        ``ragged=False`` keeps the legacy chunk-width padded layout as the
        packing oracle. ``pack_align`` rounds the flat buffer length to
        bound jit retraces. ``kernel="pallas"`` is single-device only (the
        Pallas calls don't partition under shard_map meshes yet) and
        requires the ragged layout for fused steps.
        ``flusher`` shares one PriorityFlusher across engines (DP groups);
        ``host_bw_bytes_s`` calibrates the cost model's swap estimate;
        ``copy_budget`` bounds per-step async copy draining.

        ``kv_dtype="int8"`` stores the paged pools quantized (per-block,
        per-KV-head absmax scales ride alongside in parallel scale pools;
        see serving.paged_cache) — half the KV bytes in HBM *and* on the
        host tier, and half the HBM read traffic on the decode hot path
        (the kernels dequantize in VMEM after the block DMA). Defaults to
        ``"int8"`` when ``cfg.kv_cache_quant`` is set, so quant configs that
        historically fell back to the dense engine now serve paged.
        Single-device only for now (the scale pools don't shard).

        ``sanitize=True`` attaches an ``analysis.kvsan.KVSanitizer`` shadow
        state machine to the pool allocator, the host tier and the copy
        engine: every block lifecycle transition is validated as it happens
        and violations (use-after-free, double-free, refcount underflow,
        fill-before-reserve, swap-ordering) raise ``KVSanError`` with
        operation backtraces. Debug mode — a few dict ops plus a captured
        call site per pool operation."""
        self.cfg = cfg
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else init_params(cfg, key)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_token = eos_token
        if backend == "paged" and not paged_cache_supported(cfg):
            backend = "dense"  # arch outside the paged contract: parity oracle path
        self.backend = backend
        self.interleave = interleave and backend == "paged"
        self.scheduler: QueuePolicy = make_policy(scheduler)
        # eviction-aware admission: residency-aware policies score a waiting
        # request by how much of its prompt is HBM-/host-resident. Never
        # mutate a caller-supplied policy object: bind into a per-engine copy
        # — rebinding a shared instance (one object passed to every replica
        # of a DP group, or reused for a simcluster queue) would score
        # foreign queues against THIS engine's cache state.
        if isinstance(scheduler, QueuePolicy):
            self.scheduler = copy.copy(self.scheduler)
        self.scheduler.bind_residency(self._residency)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.waiting: List[Request] = []
        # rolling window of completed requests backing latency_summary();
        # bounded so a long-lived engine doesn't retain every prompt ever served
        self.finished: List[Request] = []
        self.max_finished = max_finished
        self._next_id = 0
        self._key = jax.random.PRNGKey(seed + 1)
        self.steps = 0
        self.tokens_out = 0
        self.prefill_tokens = 0
        self.preemptions = 0
        # admission: first admissions, their summed wait since submit, and
        # the admit passes that stopped at a prefilling leader or for want
        # of blocks
        self.admitted = 0
        self.admit_wait_ns = 0
        self.admit_deferred = 0
        self.admit_blocked = 0
        # timed spans at the layers' boundaries (profiler annotations plus
        # always-on totals) and one span per finished request
        self.telemetry = Telemetry(max_series=max_finished)
        self.swap_outs = 0
        self.swap_ins = 0
        if preempt not in ("recompute", "swap", "cost"):
            raise ValueError(f"unknown preempt strategy {preempt!r}")
        self.preempt = preempt
        if kernel is None:
            kernel = "reference" if default_interpret() else "pallas"
        if kernel not in ("reference", "pallas"):
            raise ValueError(f"unknown kernel {kernel!r}")
        if kernel == "pallas" and (pool_layout is not None or mesh is not None):
            raise ValueError(
                "kernel='pallas' is single-device only: the Pallas paged "
                "kernels do not partition under shard_map meshes yet; pass "
                "kernel='reference' on a mesh"
            )
        if kernel == "pallas" and not ragged:
            raise ValueError(
                "kernel='pallas' requires the ragged fused layout: the "
                "chunk kernel consumes the packed token buffer"
            )
        self.kernel = kernel
        self.ragged = bool(ragged)
        self.pack_align = max(int(pack_align), 1)
        self._interpret = default_interpret()
        # fused-batch occupancy: device slots dispatched vs slots holding a
        # real token — 1 - valid/slot is the padding-FLOP fraction the
        # ragged layout exists to remove
        self.fused_slot_tokens = 0
        self.fused_valid_tokens = 0
        # query tiles the ragged steps' chunk attention launches
        # (fused_valid_tokens / chunk_tiles is tokens per tile)
        self.chunk_tiles = 0
        # cells of one layer's decode-kernel grid that ran the math, summed
        # over the decode steps (over steps x max_batch x max_blocks: the
        # live share of the grid)
        self.decode_live_cells = 0
        self.host_store = host_store
        self.pipeline = bool(pipeline) and self.interleave
        self.flusher = flusher if flusher is not None else PriorityFlusher()
        self.host_bw_bytes_s = host_bw_bytes_s
        self.copy_budget = copy_budget
        self.cost_swap_choices = 0
        self.cost_recompute_choices = 0
        self.swap_reshared_blocks = 0
        self._copy = CopyEngine()
        self._inflight: Optional[PlanExec] = None
        self._build_emitted: Optional[Dict[int, List[int]]] = None

        if self.backend == "paged":
            if kv_dtype is None and cfg.kv_cache_quant:
                kv_dtype = "int8"  # quant configs store int8 pools now
            if kv_dtype is not None and (mesh is not None or pool_layout is not None
                                         or (kv is not None and kv.layout is not None)):
                raise ValueError(
                    "kv_dtype='int8' is single-device only: the parallel "
                    "scale pools do not shard over a mesh yet"
                )
            self.block_size = block_size
            self.max_blocks = -(-max_seq // block_size)
            self.prefill_chunk_size = prefill_chunk_size
            # budget for one step's valid tokens (decode rows + prefill chunks);
            # default leaves room for every decode slot plus one full chunk
            self.token_budget = token_budget or (max_batch + prefill_chunk_size)
            # the prefill view carries slack blocks so a padded chunk write
            # never runs past the end of the gathered cache
            self._view_blocks = self.max_blocks + -(-prefill_chunk_size // block_size)
            if n_blocks is None:
                # full provisioning: every slot can reach max_seq (+ slack), +1 scratch
                n_blocks = max_batch * (self.max_blocks + 1) + 1
            if pool_layout is None and mesh is not None:
                pool_layout = ShardedPoolLayout(mesh)
            if kv is not None and kv.layout is not None:
                pool_layout = kv.layout
            self.pool_layout = pool_layout
            if pool_layout is not None:
                pool_layout.validate(cfg)
                # TP-resident weights: resharding happens once at engine
                # construction (deployment), never per step
                self.params = pool_layout.place_params(cfg, self.params)
            if kv is not None:
                self.kv = kv
                kv_dtype = kv.kv_dtype  # injected pool decides the format
                if self.host_store is None:
                    self.host_store = kv.host_store  # DP group's shared tier
            else:
                if self.host_store is None and (host_blocks
                                                or preempt in ("swap", "cost")):
                    self.host_store = HostBlockStore.for_config(
                        cfg, host_blocks or n_blocks, block_size,
                        kv_dtype=kv_dtype,
                    )
                self.kv = PagedKVCache(
                    cfg, n_blocks, block_size, self.max_blocks,
                    prefix_sharing=prefix_sharing, layout=pool_layout,
                    host_store=self.host_store, kv_dtype=kv_dtype,
                    sanitize=sanitize,
                )
            self.kv_dtype = kv_dtype
            # sanitizer (if any) also shadows the copy engine's tag queue so
            # the swap-in sync(tag) happens-before edge is enforced
            self.sanitizer = getattr(self.kv, "sanitizer", None)
            self._copy.sanitizer = self.sanitizer
            # paged-path model calls never use the dense per-slot quant
            # branch: when the pool is quantized the gathered views are
            # already dequantized floats (and the _q writes requantize), so
            # the oracle programs run the stack with kv_cache_quant off
            self._oracle_cfg = (cfg.replace(kv_cache_quant=False)
                                if cfg.kv_cache_quant else cfg)
            # reserved scratch block: swallows masked padding/inactive-slot
            # writes and backs clamped gathers of unallocated table entries
            self._null_block = self.kv.pool.allocate(_NULL_SEQ, 1)[0]
            # async copy engine: the cache's demotion/write-through copies and
            # the engine's swap-set fills drain through it between dispatches
            self.kv.copy_engine = self._copy
            self.control = ControlPlane(self)
            self.runner = DeviceRunner(self)
            if pool_layout is not None:
                # pin the pool arrays' sharding across steps: without
                # out_shardings the partitioner could legally re-place the
                # carried pools each call, silently re-sharding per step
                rep = pool_layout.replicated()
                pool_s = pool_layout.pool_sharding(cfg, self.kv.pool.n_blocks)
                # scale outputs are None on meshes (int8 pools don't shard):
                # empty pytree leaves under the tuple, no sharding to pin
                out_s = (rep, pool_s, pool_s, None, None)
                self._decode_paged_jit = jax.jit(self._decode_paged_fn, out_shardings=out_s)
                self._prefill_chunk_jit = jax.jit(self._prefill_chunk_fn, out_shardings=out_s)
                self._fused_step_jit = jax.jit(self._fused_step_fn, out_shardings=out_s)
                self._ragged_step_jit = jax.jit(self._ragged_step_fn, out_shardings=out_s)
            else:
                self._decode_paged_jit = jax.jit(self._decode_paged_fn)
                self._prefill_chunk_jit = jax.jit(self._prefill_chunk_fn)
                self._fused_step_jit = jax.jit(self._fused_step_fn)
                self._ragged_step_jit = jax.jit(self._ragged_step_fn)
            if kernel == "pallas":
                # pallas decode replaces the gather-oracle program wholesale;
                # the oracle jit stays live for parity runs and audits
                self._decode_dispatch_jit = jax.jit(self._decode_pallas_fn)
            else:
                self._decode_dispatch_jit = self._decode_paged_jit
        else:
            self.pool_layout = None
            self.kv_dtype = None
            self.sanitizer = None
            self.cache = init_cache(cfg, max_batch, max_seq)
            self._decode_jit = jax.jit(self._decode_fn)
            self._prefill_jit: Dict[int, Any] = {}

    # ------------------------------------------------------------------ API
    def submit(self, prompt, max_new: int = 16, temperature: float = 0.0,
               priority: float = 0.0, trace_id: Optional[int] = None) -> Request:
        """``prompt`` is a flat token array, or a ``SegmentedPrompt`` whose
        per-document segments enable order-independent KV reuse (paged
        backend; the dense oracle flattens it). ``trace_id`` files the
        request's span under a trace shared by several requests (the stages
        of one pipeline; the request's own id when None)."""
        segprompt = prompt if isinstance(prompt, SegmentedPrompt) else None
        if segprompt is not None:
            prompt = segprompt.tokens
        prompt = np.atleast_1d(np.asarray(prompt, np.int32))
        if prompt.size == 0:
            prompt = np.zeros(1, np.int32)  # empty prompt: decode from pad token
            segprompt = None
        req = Request(self._next_id, prompt, max_new, temperature, priority)
        req.trace_id = req.req_id if trace_id is None else int(trace_id)
        req.segprompt = segprompt
        req.submitted_at = time.monotonic()
        # out-of-band delivery: tokens stream through a per-request
        # StreamingObject whose chunks drain via the shared PriorityFlusher
        # in EDF-slack order (req.priority IS the predicted slack)
        req.stream = StreamingObject(priority=priority)
        req.stream.on_chunk(self._make_chunk_cb(req))
        self._next_id += 1
        self.waiting.append(req)
        return req

    def _make_chunk_cb(self, req: Request):
        def cb(chunk):
            if chunk is None:
                return  # EOS marker: nothing left to transport
            self.flusher.submit(req.stream, chunk, req.delivered.extend)
        return cb

    @property
    def pending(self) -> bool:
        """True while a dispatched plan's tokens await materialization."""
        return self._inflight is not None

    def run_until_done(self, max_steps: int = 10_000) -> None:
        while (self.waiting or any(self.slots) or self.pending) and max_steps:
            self.step()
            max_steps -= 1
        self._drain_copies(full=True)
        self._flush_streams()

    def stats(self) -> Dict[str, Any]:
        s: Dict[str, Any] = {
            "backend": self.backend,
            "interleave": self.interleave,
            "pipeline": self.pipeline,
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "prefill_tokens": self.prefill_tokens,
            "preemptions": self.preemptions,
            "stream_backlog": self.flusher.backlog,
            "admitted": self.admitted,
            "admit_wait_ns": self.admit_wait_ns,
            "admit_deferred": self.admit_deferred,
            "admit_blocked": self.admit_blocked,
            **self.telemetry.span_totals(),
        }
        if self.backend == "paged":
            s["utilization"] = self.kv.utilization()
            s["prefix_hit_tokens"] = self.kv.shared_token_hits
            s["host_hit_tokens"] = self.kv.host_token_hits
            s["session_hit_tokens"] = self.kv.session_host_token_hits
            s["session_shared_tokens"] = self.kv.session_token_hits
            s["free_blocks"] = self.kv.pool.n_free
            s["evictions"] = self.kv.pool.evictions
            s["measured_hit_rate"] = self.measured_hit_rate()
            s["measured_host_hit_rate"] = self.measured_host_hit_rate()
            s["measured_session_hit_rate"] = self.measured_session_hit_rate()
            s["tp_degree"] = self.pool_layout.tp_degree if self.pool_layout else 1
            s["preempt"] = self.preempt
            s["kv_dtype"] = self.kv_dtype or str(jnp.dtype(self.cfg.dtype))
            s["kernel"] = self.kernel
            s["ragged"] = self.ragged
            s["fused_slot_tokens"] = self.fused_slot_tokens
            s["fused_valid_tokens"] = self.fused_valid_tokens
            s["chunk_tiles"] = self.chunk_tiles
            s["decode_live_cells"] = self.decode_live_cells
            s["padded_token_fraction"] = (
                1.0 - self.fused_valid_tokens / self.fused_slot_tokens
                if self.fused_slot_tokens else 0.0
            )
            s["swap_outs"] = self.swap_outs
            s["swap_ins"] = self.swap_ins
            s["swap_reshared_blocks"] = self.swap_reshared_blocks
            s["cost_swap_choices"] = self.cost_swap_choices
            s["cost_recompute_choices"] = self.cost_recompute_choices
            s["copy_backlog"] = self._copy.backlog
            s["copy_ops_drained"] = self._copy.drained
            s["stream_chunk_size"] = self.control.last_chunk_size
            s.update(self.runner.summary())
            if self.host_store is not None:
                s["host_store"] = self.host_store.stats()
        return s

    def warmup_step_variants(self) -> int:
        """Pre-compile every packed fused-step variant off the serving clock.

        The ragged layout trades the padded slab's single static shape for
        one jit variant per tail-aligned packed length; a production engine
        captures those buckets at startup rather than paying compiles
        mid-serve (the padding-FLOP win only shows once the variants are
        warm). The packed length is bounded by the token budget — decode
        rows displace prefill grants one for one (with the +1 floor grant)
        — and by the padded slab, so the sweep is small. Each dummy call
        packs only masked pad tokens (``row_of = -1``) and its pool outputs
        are discarded, leaving engine state untouched. Returns the number
        of variants compiled."""
        if self.backend != "paged" or not self.interleave or not self.ragged:
            return 0
        B, C = self.max_batch, self.prefill_chunk_size
        budget = self.token_budget or B * C
        cap = min(max(budget + 1, B + 1), B * C)
        cap_pad = -(-cap // self.pack_align) * self.pack_align
        tables = jnp.full((B, self._view_blocks), -1, jnp.int32)
        li = jnp.zeros((B,), jnp.int32)
        n = 0
        prev = jnp.zeros((B,), jnp.int32)
        no_slot = jnp.full((B,), -1, jnp.int32)
        for T in range(self.pack_align, cap_pad + 1, self.pack_align):
            z = jnp.zeros((T,), jnp.int32)
            pad = jnp.full((T,), -1, jnp.int32)
            out = self._ragged_step_jit(
                self.params, self.kv.k, self.kv.v, self.kv.k_scale,
                self.kv.v_scale, tables, z, pad, z, z, z, z, li,
            )
            # the runner's packed prev-token substitution is per-length too
            self.runner._subst_packed_jit(z, prev, no_slot, li)
            jax.block_until_ready(out[0])
            n += 1
        return n

    def step_program(self, which: str) -> Tuple[Any, tuple]:
        """Return ``(jitted, example_args)`` for one of the engine's device
        step programs, the single entry point behind every static audit
        (collective census, jaxpr contract audit, cache sentinel):

        * ``"fused_ragged"`` — the packed mixed-batch step (production path
          when ``ragged=True``), against a representative packed buffer.
        * ``"fused_padded"`` — the padded-slab fused step (the ragged
          path's shape-stable fallback and oracle).
        * ``"decode"`` — the live decode dispatch: the Pallas paged-decode
          program when ``kernel="pallas"``, else the gather oracle.
        * ``"decode_ref"`` — always the gather-oracle decode jit (stays
          live for parity runs even under the Pallas kernel).
        * ``"pool"`` — a bare gather_paged_batch + write_paged_chunk_batch
          roundtrip (the decode chunk-scatter path in isolation), freshly
          jitted with the engine's pool shardings when on a mesh.

        Example args are shaped like real dispatches (pad-only tables, zero
        tokens) so lowering/tracing them exercises the production shapes
        without touching engine state."""
        B, C = self.max_batch, self.prefill_chunk_size
        k, v = self.kv.k, self.kv.v
        tokens = jnp.zeros((B, C), jnp.int32)
        starts = jnp.zeros((B,), jnp.int32)
        n_valid = jnp.ones((B,), jnp.int32)
        seg = jnp.zeros((B, C), jnp.int32)
        if which == "fused_ragged":
            T = -(-(B * C) // self.pack_align) * self.pack_align
            flat = jnp.zeros((T,), jnp.int32)
            tables = jnp.full((B, self._view_blocks), -1, jnp.int32)
            return self._ragged_step_jit, (
                self.params, k, v, self.kv.k_scale, self.kv.v_scale,
                tables, flat, flat, flat, flat, flat,
                flat, jnp.zeros((B,), jnp.int32),
            )
        if which == "fused_padded":
            tables = jnp.full((B, self._view_blocks), self._null_block,
                              jnp.int32)
            return self._fused_step_jit, (
                self.params, k, v, self.kv.k_scale, self.kv.v_scale,
                tables, tokens, starts, n_valid, seg, seg, seg,
            )
        if which in ("decode", "decode_ref"):
            tables = jnp.full((B, self.max_blocks), self._null_block, jnp.int32)
            jitted = (self._decode_dispatch_jit if which == "decode"
                      else self._decode_paged_jit)
            return jitted, (
                self.params, k, v, self.kv.k_scale, self.kv.v_scale,
                tables, tokens[:, :1], starts,
            )
        if which == "pool":
            bs = self.block_size

            def roundtrip(k_pool, tables, starts, new_kv, n_valid):
                view = gather_paged_batch(k_pool, tables)
                out = write_paged_chunk_batch(
                    k_pool, tables, starts, new_kv, bs, n_valid, self._null_block
                )
                return out, view

            G, KVH, hd = k.shape[0], k.shape[3], k.shape[4]
            new_kv = jnp.zeros((G, B, C, KVH, hd), k.dtype)
            tables = jnp.full((B, self._view_blocks), self._null_block, jnp.int32)
            if self.pool_layout is not None:
                pool_s = self.pool_layout.pool_sharding(self.cfg, self.kv.pool.n_blocks)
                entry_s = self.pool_layout.kv_entry_sharding(self.cfg)
                new_kv = jax.device_put(new_kv, entry_s)
                fn = jax.jit(roundtrip, out_shardings=(pool_s, entry_s))
            else:
                fn = jax.jit(roundtrip)
            return fn, (k, tables, starts, new_kv, n_valid)
        raise ValueError(f"unknown step program {which!r}")

    def audit_collectives(self, which: str = "fused") -> Dict[str, int]:
        """Compile one of the engine's step programs against representative
        inputs and census its collective ops (models.shardmap_tp
        .count_collectives) — the schedule audit behind the sharded-pool
        contract: ``"fused"`` (the interleaved mixed batch) and ``"decode"``
        (block-table batched decode) must show ZERO all-gathers — the
        gather/scatter over host-resident block tables never communicates —
        and only the Megatron all-reduces; ``"pool"`` (a bare
        gather_paged_batch + write_paged_chunk_batch roundtrip, the decode
        chunk-scatter path in isolation) must be collective-free entirely.

        Richer checks (per-axis jaxpr census, int8 dtype flow, callback
        scan, cache sentinel) live in repro.analysis.jaxpr_audit, built on
        the same step_program() targets."""
        from repro.models.shardmap_tp import count_collectives

        alias = {"fused": "fused_ragged" if self.ragged else "fused_padded",
                 "decode": "decode_ref"}
        jitted, args = self.step_program(alias.get(which, which))
        return count_collectives(jitted.lower(*args).compile())

    # token-weighted windows below this many prompt tokens are "cold": right
    # after engine start a single finished request would swing the measured
    # rate to 0.0 or 1.0 and stampede the LP's alpha_scale feedback
    hit_rate_min_tokens: int = 64
    cold_start_hit_rate: float = 0.0  # documented cold-start default

    # cursor helpers shared with the control plane (module-level functions,
    # re-exported as methods so ControlPlane needs only the engine handle)
    _advance_cursor = staticmethod(_advance_cursor)
    _max_grant = staticmethod(_max_grant)

    def _measured_rate(self, hit_tokens, window: int,
                       min_tokens: Optional[int],
                       default: Optional[float]) -> float:
        """Shared window + cold-start clamp for the per-tier measured rates:
        when the window holds fewer than ``min_tokens`` prompt tokens
        (including the empty window, and ``window=0``), the sample is too
        small to trust — returns ``default`` when given (the Generator
        passes its configured/calibrated static rate), else the engine's
        ``cold_start_hit_rate``. ``hit_tokens`` extracts a finished request's
        hit-token count for the tier being measured."""
        done = [r for r in (self.finished[-window:] if window > 0 else [])
                if r.prefill_cap > 0]
        total = sum(r.prefill_cap for r in done)
        lo = self.hit_rate_min_tokens if min_tokens is None else min_tokens
        if total < max(lo, 1):
            return self.cold_start_hit_rate if default is None else default
        return sum(hit_tokens(r) for r in done) / total

    def measured_hit_rate(self, window: int = 256,
                          min_tokens: Optional[int] = None,
                          default: Optional[float] = None) -> float:
        """Rolling token-weighted prefix hit rate over recently finished
        requests — the online signal the Generator cost model and the LP
        allocator consume (instead of a static configured rate), with the
        ``_measured_rate`` cold-start clamp."""
        return self._measured_rate(lambda r: r.shared_prefix_tokens,
                                   window, min_tokens, default)

    def measured_host_hit_rate(self, window: int = 256,
                               min_tokens: Optional[int] = None,
                               default: Optional[float] = None) -> float:
        """Rolling token-weighted host-tier hit rate (non-session prompt
        tokens promoted from the host store), with the same cold-start clamp
        as ``measured_hit_rate``."""
        return self._measured_rate(lambda r: r.host_prefix_tokens,
                                   window, min_tokens, default)

    def measured_session_hit_rate(self, window: int = 256,
                                  min_tokens: Optional[int] = None,
                                  default: Optional[float] = None) -> float:
        """Rolling token-weighted session-history hit rate (conversation-
        history tokens promoted from the host store between turns — disjoint
        from ``measured_host_hit_rate``'s doc class), same cold-start
        clamp."""
        return self._measured_rate(lambda r: r.session_host_tokens,
                                   window, min_tokens, default)

    def latency_summary(self) -> Dict[str, float]:
        """TTFT/TPOT/e2e percentiles (seconds) over finished requests — the
        timestamps `Request` records but `stats()` aggregates away. TPOT is
        the per-token inter-arrival distribution pooled across requests (the
        SLO quantity: a sequential prefill stalling every decode slot shows up
        directly as fat-tailed TPOT); ``gap_p95`` is the p95 of the
        per-request WORST inter-token stall. Paged engines also report the
        measured host gap — wall time the device sat idle between the end of
        one dispatched step and the next dispatch (total and per-dispatch
        mean) — the quantity the pipelined control-plane split shrinks."""
        done = [r for r in self.finished
                if r.first_token_at is not None and r.finished_at is not None]
        out: Dict[str, float] = {"n_finished": float(len(done))}
        if self.backend == "paged":
            rs = self.runner.summary()
            out["host_gap_total_s"] = float(rs["host_gap_s"])
            out["host_gap_mean_s"] = float(rs["host_gap_mean_s"])
            out["dispatches"] = float(rs["dispatches"])
        if not done:
            return out
        ttft = [r.first_token_at - r.submitted_at for r in done]
        e2e = [r.finished_at - r.submitted_at for r in done]
        tpot = [g for r in done for g in r.token_gaps]
        gaps = [r.max_token_gap for r in done if len(r.out_tokens) > 1]
        for name, xs in (("ttft", ttft), ("tpot", tpot), ("e2e", e2e), ("gap", gaps)):
            if xs:
                out[f"{name}_p50"] = float(np.percentile(xs, 50))
                out[f"{name}_p95"] = float(np.percentile(xs, 95))
        capped = [r for r in done if r.prefill_cap > 0]
        if capped:
            # token-weighted measured hit rate + per-request distribution
            out["prefix_hit_rate"] = float(
                sum(r.shared_prefix_tokens for r in capped)
                / sum(r.prefill_cap for r in capped)
            )
            out["prefix_hit_rate_p50"] = float(
                np.percentile([r.prefix_hit_rate for r in capped], 50)
            )
            out["host_hit_rate"] = float(
                sum(r.host_prefix_tokens for r in capped)
                / sum(r.prefill_cap for r in capped)
            )
            # the multi-turn session hit class: history KV promoted from the
            # host tier between turns, reported separately from doc hits
            out["session_hit_rate"] = float(
                sum(r.session_host_tokens for r in capped)
                / sum(r.prefill_cap for r in capped)
            )
        return out

    def _residency(self, req: Request) -> float:
        """Eviction-aware admission signal: fraction of a waiting request's
        prompt whose keyed blocks are resident — HBM-indexed blocks weigh
        1.0, host-tier blocks 0.5 (a promotion still costs a copy). Bound
        into the queue policy (``resident_first`` orders by it); the probe
        layout is computed once per request and cached (content is fixed,
        residency lookups stay live)."""
        if self.backend != "paged" or not self.kv.prefix_sharing:
            return 0.0
        lay = req.layout if req.layout is not None else req.probe_layout
        if lay is None:
            lay = build_layout(
                req.segprompt if req.segprompt is not None else req.prompt,
                self.block_size, self._prompt_cap(req),
            )
            req.probe_layout = lay
        host = self.kv.host_store
        tok = 0.0
        for key in lay.block_keys:
            if key is None:
                continue
            if key in self.kv._prefix_index:
                tok += self.block_size
            elif host is not None and host.contains(key):
                tok += 0.5 * self.block_size
        return tok / max(lay.n_tokens, 1)

    # ------------------------------------------------------------ admission
    def _prompt_cap(self, req: Request) -> int:
        # same cap as the dense path (eff = min(Lp, bucket <= max_seq)): a
        # full-length prompt samples one token from the last-position logits
        # and finishes before any decode write could overflow the block table
        return min(len(req.prompt), self.max_seq)

    def _try_admit(self, req: Request) -> bool:
        if self.backend != "paged":
            return True  # dense: a free slot is the only admission resource
        if req.swapped:
            return self._swap_in(req)
        cap = self._prompt_cap(req)
        # fit check against blocks THIS engine may allocate (a DP replica owns
        # a block range of the shared pool); -1 for the reserved scratch block
        if self.kv.pool.blocks_needed(cap + self.block_size) > self.kv.pool.n_owned - 1:
            # can never fit, even with the whole pool free: fail the request
            # instead of wedging the queue
            req.done = True
            req.truncated = True
            req.finished_at = time.monotonic()
            self.finished.append(req)
            self._record_span(req)
            if req.stream is not None and not req.stream.closed:
                req.stream.close()
            return False
        layout = build_layout(
            req.segprompt if req.segprompt is not None else req.prompt,
            self.block_size, cap,
        )
        adm = self.kv.admit_tokens(req.req_id, req.prompt[:cap], layout)
        if adm is None:
            return False  # backpressure: stays queued until blocks free up
        req.layout = layout
        req.shared_spans = normalize_spans(adm.shared_spans)
        req.shared_prefix_tokens = adm.n_shared
        # host promotions partition into the doc/other class and the session-
        # history class (multi-turn conversations) — disjoint counters, same
        # promote cost, separately measured hit rates
        req.host_prefix_tokens = adm.n_host - adm.n_host_session
        req.session_shared_tokens = adm.n_shared_session
        req.session_host_tokens = adm.n_host_session
        return True

    # ----------------------------------------------------- swap preemption
    def _swap_tag(self, req: Request):
        """Store tag for a request's swap set. Namespaced by the cache's
        client tag: DP replicas number req_ids independently AND share one
        host store, so a bare req_id would collide across replicas."""
        return (self.kv.client_tag, req.req_id)

    def _swap_out(self, victim: Request) -> bool:
        """Park a victim's block chain in the host tier. The capacity check
        and slot pinning are synchronous (``reserve_seq`` — all-or-nothing,
        so a False return still means "fall back to recompute" immediately),
        but the actual copy is deferred: the device-side gathers are
        dispatched here (JAX arrays are immutable, so the captured values
        are fixed even if the pool blocks are reused by later plans) and the
        blocking host materialization drains through the copy engine between
        dispatches. ``_swap_in`` syncs the tag before reading.

        The chain's prefix keys are captured pre-release (``swap_keys``) so
        re-admission can re-share any block whose key is still live in the
        HBM index instead of restoring a private duplicate."""
        blocks = list(self.kv.pool.tables.get(victim.req_id, []))
        if self.host_store is None or not blocks:
            return False
        tag = self._swap_tag(victim)
        if self.host_store.reserve_seq(tag, len(blocks)) is None:
            return False
        victim.swap_keys = [self.kv._block_key.get(b) for b in blocks]
        ids = jnp.asarray(np.asarray(blocks, np.int32))
        k_gather = jnp.take(self.kv.k, ids, axis=1)
        v_gather = jnp.take(self.kv.v, ids, axis=1)
        # quantized pools park int8 payloads (half the swap bytes) plus
        # their per-block scales — the restore must see both
        ks_gather = vs_gather = None
        if self.kv.quantized:
            ks_gather = jnp.take(self.kv.k_scale, ids, axis=1)
            vs_gather = jnp.take(self.kv.v_scale, ids, axis=1)
        store = self.host_store

        def _fill(k_gather=k_gather, v_gather=v_gather,
                  ks_gather=ks_gather, vs_gather=vs_gather):
            store.fill_seq(
                tag, np.asarray(k_gather), np.asarray(v_gather),
                k_scales=None if ks_gather is None else np.asarray(ks_gather),
                v_scales=None if vs_gather is None else np.asarray(vs_gather),
            )

        self._copy.submit(_fill, tag=tag)
        victim.swap_len = self.kv.lengths.get(victim.req_id, victim.pos)
        victim.swapped = True
        self.kv.release(victim.req_id)
        if victim.slot >= 0 and self.slots[victim.slot] is victim:
            self.slots[victim.slot] = None
        victim.slot = -1
        self.waiting.insert(0, victim)
        self.preemptions += 1
        self.swap_outs += 1
        return True

    def _swap_in(self, req: Request) -> bool:
        """Restore a swapped-out request and resume its cursor/position
        state exactly where swap-out left it — no prefill is repaid.
        All-or-nothing: on backpressure the swap set stays pinned and the
        request stays queued.

        Re-sharing: a chain block whose prefix key is STILL live in the HBM
        index (the shared copy survived the victim's absence — including the
        victim's own released blocks sitting in the warm LRU) is re-attached
        as a refcounted share instead of a private duplicate restored from
        host; only the remaining ordinals are copied back. The saved
        contents stay the fallback for any block whose key was evicted
        meanwhile, so the restore is unconditionally exact either way."""
        tag = self._swap_tag(req)
        self._copy.sync(tag)  # our deferred fill must land before the read
        n = self.host_store.saved_blocks(tag)
        keys = req.swap_keys if len(req.swap_keys) == n else [None] * n
        shared: Dict[int, int] = {}
        if self.kv.prefix_sharing:
            for i, key in enumerate(keys):
                if key is not None:
                    b = self.kv._prefix_index.get(key)
                    if b is not None:
                        shared[i] = b
        # capacity: fresh allocations plus warm (refcount-0) blocks revived
        # by sharing — counted by unique block, mirroring admit_tokens
        n_fresh = n - len(shared)
        n_warm = sum(1 for b in set(shared.values())
                     if self.kv.pool.refcounts.get(b, 0) == 0)
        if n_fresh + n_warm > self.kv.pool.n_free:
            return False  # backpressure: blocks not yet available
        if self.kv.quantized:
            k_np, v_np, ks_np, vs_np = self.host_store.restore_seq(tag)
        else:
            k_np, v_np = self.host_store.restore_seq(tag)
            ks_np = vs_np = None
        fresh_ords: List[int] = []
        fresh_ids: List[int] = []
        for i in range(n):
            if i in shared:
                self.kv.pool.share(req.req_id, shared[i])
            else:
                b = self.kv.pool.allocate(req.req_id, 1)[0]
                fresh_ords.append(i)
                fresh_ids.append(b)
        if fresh_ids:
            ids = jnp.asarray(np.asarray(fresh_ids, np.int32))
            self.kv.k = self.kv.k.at[:, ids].set(jnp.asarray(k_np[:, fresh_ords]))
            self.kv.v = self.kv.v.at[:, ids].set(jnp.asarray(v_np[:, fresh_ords]))
            if ks_np is not None:
                # restored blocks bring their saved scales back verbatim (no
                # reset: the int8 payloads are only meaningful under them)
                self.kv.k_scale = self.kv.k_scale.at[:, ids].set(
                    jnp.asarray(ks_np[:, fresh_ords]))
                self.kv.v_scale = self.kv.v_scale.at[:, ids].set(
                    jnp.asarray(vs_np[:, fresh_ords]))
        self.kv.lengths[req.req_id] = req.swap_len
        self.swap_reshared_blocks += len(shared)
        req.swap_keys = []
        req.swapped = False
        self.swap_ins += 1
        return True

    def _swap_is_cheaper(self, victim: Request) -> bool:
        """Cost model behind ``preempt="cost"``: estimated swap time (chain
        bytes over host-link bandwidth, both directions) vs estimated
        recompute time (tokens to re-prefill x measured per-token step time,
        discounted by the fraction of the chain still resident in the HBM
        prefix index — those blocks re-share for free at re-admission)."""
        chain = self.kv.pool.tables.get(victim.req_id, [])
        if self.host_store is None or not chain:
            return False
        shape = self.kv.k.shape  # (G, n_blocks, bs, KVH, hd)
        blk_bytes = 2 * shape[0] * int(np.prod(shape[2:])) * self.kv.k.dtype.itemsize
        if self.kv.quantized:
            # int8 payloads already halve blk_bytes via itemsize; the f32
            # per-(block, KV-head) scales ride along (k + v planes)
            blk_bytes += 2 * shape[0] * shape[3] * 4
        swap_s = 2.0 * len(chain) * blk_bytes / max(self.host_bw_bytes_s, 1.0)
        tok_s = self.runner.token_time_ema
        if tok_s is None:
            tok_s = 1e-3  # prior before any plan has materialized
        resident = sum(1 for b in set(chain) if b in self.kv._block_key)
        residency = resident / max(len(chain), 1)
        n_tok = self.kv.lengths.get(victim.req_id, victim.pos)
        recompute_s = n_tok * tok_s * (1.0 - residency)
        return swap_s < recompute_s

    # ------------------------------------------------------------ internals
    def _decode_fn(self, params, cache, tokens, pos):
        return decode_step(self.cfg, params, cache, tokens, pos)

    # ---------------------------------------------------------- paged path
    def _set_pools(self, k_pool, v_pool, k_sc, v_sc) -> None:
        """Land a step program's pool outputs back in the cache box (scales
        only exist for int8 pools — None otherwise, nothing to store)."""
        self.kv.k = k_pool
        self.kv.v = v_pool
        if k_sc is not None:
            self.kv.k_scale = k_sc
            self.kv.v_scale = v_sc

    def _prefill_chunk_fn(self, params, k_pool, v_pool, k_sc, v_sc, table_row,
                          tokens, start, n_valid, positions, p_end, s_start):
        """One chunked-prefill step for a single request (B=1): gather the
        sequence view, run the chunk through the stack, scatter its K/V back
        into the pool (padding rerouted to the scratch block).
        ``positions``/``p_end``/``s_start`` (1, C) carry the segmented-prompt
        rope positions and attention spans (see serving.segments).
        ``k_sc``/``v_sc`` are the (G, n_blocks, KVH) scale pools of an int8
        pool (None for float pools): the view gather dequantizes and the
        write-back requantizes under the running per-block absmax. All paged
        step programs return (logits, k_pool, v_pool, k_sc, v_sc)."""
        kview = gather_paged_batch_dq(k_pool, k_sc, table_row[None],
                                      out_dtype=jnp.dtype(self.cfg.dtype))
        vview = gather_paged_batch_dq(v_pool, v_sc, table_row[None],
                                      out_dtype=jnp.dtype(self.cfg.dtype))
        caches = ({"k": kview, "v": vview},)
        logits, new_caches = prefill_chunk(
            self._oracle_cfg, params, caches, tokens, start, positions, p_end,
            s_start
        )
        pc = tokens.shape[1]
        newk = jax.lax.dynamic_slice_in_dim(new_caches[0]["k"], start, pc, axis=2)[:, 0]
        newv = jax.lax.dynamic_slice_in_dim(new_caches[0]["v"], start, pc, axis=2)[:, 0]
        if k_sc is None:
            k_pool = write_paged_chunk(
                k_pool, table_row, start, newk, self.block_size, n_valid,
                self._null_block
            )
            v_pool = write_paged_chunk(
                v_pool, table_row, start, newv, self.block_size, n_valid,
                self._null_block
            )
        else:
            k_pool, k_sc = write_paged_chunk_q(
                k_pool, k_sc, table_row, start, newk, self.block_size,
                n_valid, self._null_block
            )
            v_pool, v_sc = write_paged_chunk_q(
                v_pool, v_sc, table_row, start, newv, self.block_size,
                n_valid, self._null_block
            )
        return logits[0, n_valid - 1], k_pool, v_pool, k_sc, v_sc

    def _fused_step_fn(self, params, k_pool, v_pool, k_sc, v_sc, tables,
                       tokens, starts, n_valid, positions, p_end, s_start):
        """One fused interleaved step: every row is a chunk at its own cursor —
        decode rows carry one valid token at slot ``starts[b]``, prefill
        rows carry ``n_valid[b]`` prompt tokens. Gather each row's sequence
        view, run one batched chunked forward, scatter all rows' new K/V back
        into the pool (padding rerouted to the scratch block), and return each
        row's last-valid-token logits. ``positions``/``p_end``/``s_start``
        (B, C) carry per-row segmented-prompt rope positions and attention
        spans (flat rows: positions == slots, spans zero)."""
        kview = gather_paged_batch_dq(k_pool, k_sc, tables,
                                      out_dtype=jnp.dtype(self.cfg.dtype))  # (G,B,Sv,KVH,hd)
        vview = gather_paged_batch_dq(v_pool, v_sc, tables,
                                      out_dtype=jnp.dtype(self.cfg.dtype))
        caches = ({"k": kview, "v": vview},)
        logits, new_caches = prefill_chunk(
            self._oracle_cfg, params, caches, tokens, starts, positions,
            p_end, s_start
        )
        B, C = tokens.shape
        b = jnp.arange(B)
        idx = starts[:, None] + jnp.arange(C)                 # (B, C) view slots
        newk = new_caches[0]["k"][:, b[:, None], idx]          # (G,B,C,KVH,hd)
        newv = new_caches[0]["v"][:, b[:, None], idx]
        if k_sc is None:
            k_pool = write_paged_chunk_batch(
                k_pool, tables, starts, newk, self.block_size, n_valid,
                self._null_block
            )
            v_pool = write_paged_chunk_batch(
                v_pool, tables, starts, newv, self.block_size, n_valid,
                self._null_block
            )
        else:
            k_pool, k_sc = write_paged_chunk_batch_q(
                k_pool, k_sc, tables, starts, newk, self.block_size, n_valid,
                self._null_block
            )
            v_pool, v_sc = write_paged_chunk_batch_q(
                v_pool, v_sc, tables, starts, newv, self.block_size, n_valid,
                self._null_block
            )
        return logits[b, jnp.maximum(n_valid - 1, 0)], k_pool, v_pool, k_sc, v_sc

    def _ragged_step_fn(self, params, k_pool, v_pool, k_sc, v_sc, tables,
                        tokens, row_of, slots, positions, p_end, s_start,
                        last_idx):
        """One ragged fused step: T packed tokens (flat buffer, no
        chunk-width padding) read and write the pool directly through RAW
        block tables — ``models.prefill_packed`` scatters each token's K/V
        before attending, and unbacked pages are masked inside the
        attention (kernel or oracle, per ``self.kernel``) instead of being
        rerouted to the scratch block. Returns each row's last-valid-token
        logits, gathered by ``last_idx`` so the sampler keeps its (B,)
        contract."""
        logits, k_pool, v_pool, k_sc, v_sc = prefill_packed(
            self.cfg, params, k_pool, v_pool, tables, tokens, row_of, slots,
            positions, p_end, s_start, block_size=self.block_size,
            null_block=self._null_block, impl=self.kernel,
            interpret=self._interpret, k_scales=k_sc, v_scales=v_sc,
        )
        return logits[last_idx], k_pool, v_pool, k_sc, v_sc

    def _decode_pallas_fn(self, params, k_pool, v_pool, k_sc, v_sc, tables,
                          tokens, pos):
        """Pallas-native batched decode: scatter the new token's K/V, then
        stream each row's block chain through ``paged_decode_attention`` —
        no contiguous view is ever materialized (the gather oracle
        ``_decode_paged_fn`` remains the numerics contract). Int8 pools DMA
        half the KV bytes per block; the kernel dequantizes in VMEM."""
        return decode_step_paged(
            self.cfg, params, k_pool, v_pool, tables, tokens, pos,
            block_size=self.block_size, null_block=self._null_block,
            interpret=self._interpret, k_scales=k_sc, v_scales=v_sc,
        )

    def _decode_paged_fn(self, params, k_pool, v_pool, k_sc, v_sc, tables,
                         tokens, pos):
        """Batched block-table decode: gather each slot's contiguous view
        (the jnp gather oracle of kernels.decode_attention), run the shared
        decode step, scatter the new K/V entries back into the pool."""
        dt = jnp.dtype(self.cfg.dtype)
        caches = (
            {"k": gather_paged_batch_dq(k_pool, k_sc, tables, out_dtype=dt),
             "v": gather_paged_batch_dq(v_pool, v_sc, tables, out_dtype=dt)},
        )
        logits, new_caches = decode_step(self._oracle_cfg, params, caches,
                                         tokens, pos)
        b = jnp.arange(tables.shape[0])
        newk = new_caches[0]["k"][:, b, pos]  # (G,B,KVH,hd)
        newv = new_caches[0]["v"][:, b, pos]
        bs = self.block_size
        dest = jnp.maximum(tables[b, pos // bs], 0) * bs + pos % bs

        if k_sc is not None:
            k_pool, k_sc = _quantized_scatter(k_pool, k_sc, dest, newk)
            v_pool, v_sc = _quantized_scatter(v_pool, v_sc, dest, newv)
            return logits, k_pool, v_pool, k_sc, v_sc

        def scatter(pool, new):
            G, nb = pool.shape[0], pool.shape[1]
            flat = pool.reshape(G, nb * bs, *pool.shape[3:])
            return flat.at[:, dest].set(new.astype(flat.dtype)).reshape(pool.shape)

        return logits, scatter(k_pool, newk), scatter(v_pool, newv), None, None

    def _seg_arrays(self, req: Request, pos: int, c: int, width: int) -> tuple:
        """(positions, p_end, s_start) (1, width) slices of the request's
        layout at [pos, pos+c) — the segmented-prompt rope positions and
        attention spans for one chunk (padding columns are masked out by
        n_valid downstream; zeros are fine there)."""
        positions = np.zeros((1, width), np.int32)
        p_end = np.zeros((1, width), np.int32)
        s_start = np.zeros((1, width), np.int32)
        lay = req.layout
        positions[0, :c] = lay.pos_ids[pos : pos + c]
        p_end[0, :c] = lay.attn_p_end[pos : pos + c]
        s_start[0, :c] = lay.attn_s_start[pos : pos + c]
        return positions, p_end, s_start

    def _prefill_paged(self, req: Request, slot: int):
        cap = self._prompt_cap(req)
        req.truncated = cap < len(req.prompt)
        toks = np.asarray(req.prompt[:cap], np.int32)
        pc = self.prefill_chunk_size
        # pad-ok: prefill gathers only blocks already reserved for this
        # request; gather_paged_batch clamps pads inside the jitted fn.
        table = jnp.asarray(
            self.kv.pool.table_array([req.req_id], self._view_blocks)[0]
        )
        req.prefill_cap = cap
        req.prefill_pos = 0
        _advance_cursor(req)  # shared blocks already carry their K/V
        last = None
        while req.prefill_pos < cap:
            pos = req.prefill_pos
            C = _max_grant(req, pc)
            chunk = np.zeros((1, pc), np.int32)
            chunk[0, :C] = toks[pos : pos + C]
            positions, p_end, s_start = self._seg_arrays(req, pos, C, pc)
            last, *pools = self._prefill_chunk_jit(
                self.params, self.kv.k, self.kv.v, self.kv.k_scale,
                self.kv.v_scale, table, jnp.asarray(chunk),
                pos, C, jnp.asarray(positions), jnp.asarray(p_end),
                jnp.asarray(s_start),
            )
            self._set_pools(*pools)
            req.prefill_pos = pos + C
            self.prefill_tokens += C
            _advance_cursor(req)
        self.kv.lengths[req.req_id] = cap
        self.kv.register_prefix(req.req_id, toks, req.layout)
        req.slot = slot
        req.pos = cap
        req.prefill_pos = cap
        self._key, sk = jax.random.split(self._key)
        tok = int(sample_tokens(sk, jnp.asarray(last)[None], req.temperature)[0])
        self._emit(req, tok)

    def _preempt(self, victim: Request):
        """Apply the engine's preemption strategy to ``victim``.

        ``swap``: park the block chain in the host tier and re-queue with all
        cursor state intact (``_swap_out``; falls back to recompute when the
        store cannot pin the chain).

        ``recompute``: release the blocks and re-queue the continuation
        (prompt + generated tokens); re-admission re-prefills, reusing any of
        its own prefix blocks that survived in the warm cache (or, with a
        host store attached, were demoted to it). A mid-prefill victim
        restarts its cursor from scratch (its partial K/V is discarded).

        ``cost``: per-victim choice — swap when ``_swap_is_cheaper`` says the
        copy beats the residency-discounted re-prefill."""
        # the victim's continuation (out_tokens) and swap snapshot must be
        # complete: land any still-inflight plan before capturing state
        self._sync_inflight()
        strategy = self.preempt
        if strategy == "cost":
            strategy = "swap" if self._swap_is_cheaper(victim) else "recompute"
            if strategy == "swap":
                self.cost_swap_choices += 1
            else:
                self.cost_recompute_choices += 1
        if strategy == "swap" and self._swap_out(victim):
            return
        self.kv.release(victim.req_id)
        if victim.slot >= 0 and self.slots[victim.slot] is victim:
            self.slots[victim.slot] = None
        victim.slot = -1
        if victim.segprompt is not None:
            victim.segprompt = victim.segprompt.extended(victim.out_tokens)
        victim.prompt = np.concatenate(
            [np.asarray(victim.prompt, np.int32),
             np.asarray(victim.out_tokens, np.int32)]
        )
        victim.shared_prefix_tokens = 0
        victim.host_prefix_tokens = 0
        victim.session_shared_tokens = 0
        victim.session_host_tokens = 0
        victim.shared_spans = []
        victim.layout = None
        victim.probe_layout = None  # continuation content changed
        victim.prefill_pos = 0
        victim.prefill_cap = 0
        self.waiting.insert(0, victim)
        self.preemptions += 1

    def _ensure_decode_capacity(self):
        """Every decode-phase slot needs a block backing its next write
        position (mid-prefill slots hold their full allocation from
        admission); preempt youngest-first when the pool runs dry."""
        for r in [r for r in self.slots if r is not None]:
            if r.slot < 0 or self.slots[r.slot] is not r:
                continue  # already preempted this round
            if r.prefilling:
                continue
            while True:
                try:
                    nb = self.kv.pool.extend_for(r.req_id, r.pos + 1)
                    if nb is not None:
                        # a fresh block's scale slot must not inherit the
                        # previous tenant's absmax (running-max quantization)
                        self.kv.reset_block_scales([nb])
                    break
                except MemoryError:
                    active = [x for x in self.slots if x is not None]
                    victim = max(active, key=lambda x: x.req_id)
                    self._preempt(victim)
                    if victim is r:
                        break

    # ---------------------------------------------------------- dense path
    def _prefill_one(self, req: Request, slot: int):
        Lp = len(req.prompt)
        bucket = min(_bucket(Lp), self.max_seq)
        eff = min(Lp, bucket)  # tokens that actually entered the cache
        req.truncated = eff < Lp
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :eff] = req.prompt[:eff]
        if bucket not in self._prefill_jit:

            def pf(params, tokens):
                logits, _, caches = forward(self.cfg, params, {"tokens": tokens}, want_cache=True)
                return logits, caches

            self._prefill_jit[bucket] = jax.jit(pf)
        logits, pcache = self._prefill_jit[bucket](self.params, jnp.asarray(toks))
        # write this request's cache into the batch cache at `slot`
        self.cache = _merge_cache(self.cache, pcache, slot, self.max_seq)
        self.prefill_tokens += eff
        req.slot = slot
        req.pos = eff  # NOT Lp: a truncated prompt must not overrun its cache
        req.prefill_pos = eff
        req.prefill_cap = eff
        last = np.asarray(logits)[0, eff - 1]
        self._key, sk = jax.random.split(self._key)
        tok = int(sample_tokens(sk, jnp.asarray(last[None]), req.temperature)[0])
        self._emit(req, tok)

    # ------------------------------------------------------------- stepping
    def step(self) -> Dict[int, List[int]]:
        """One engine iteration. Interleaved paged mode: the control plane
        builds one StepPlan (admission + fused mixed batch) and the device
        runner dispatches it; sampled tokens materialize this step
        (``pipeline=False``, the sync oracle) or next step (``pipeline=True``,
        double-buffered). Sequential mode: admit (blocking whole-prompt
        prefill), then one batched decode. Returns the tokens whose emission
        LANDED this step — in pipelined mode that is the previous plan's."""
        with self.telemetry.span("engine.step"):
            for r in self.waiting:
                r.queued_steps += 1
            if self.interleave:
                return self._step_planned()
            out = self._step_sequential()
            self._drain_copies(full=True)
            self._flush_streams()
            return out

    def _step_planned(self) -> Dict[int, List[int]]:
        emitted: Dict[int, List[int]] = {}
        # preemption inside build may have to sync the inflight plan; its
        # emissions land in this step's result
        self._build_emitted = emitted
        try:
            self.runner.probe_idle()
            plan = self.control.build_plan()
        finally:
            self._build_emitted = None
        ex = self.runner.dispatch(plan) if plan is not None else None
        if ex is not None:
            self.steps += 1
        # drain deferred copies while the device chews on the new plan (fully
        # on idle steps — nothing to overlap with)
        self._drain_copies(full=ex is None)
        prev, self._inflight = self._inflight, ex
        if prev is not None:
            _merge_emitted(emitted, self._materialize(prev))
        if self._inflight is not None and (not self.pipeline or self.eos_token >= 0):
            # sync oracle — or eos enabled: completion must be observed
            # before the next plan is built, so pipelining degenerates
            cur, self._inflight = self._inflight, None
            _merge_emitted(emitted, self._materialize(cur))
        self._flush_streams()
        return emitted

    def _flush_streams(self) -> None:
        """Deliver the streams' pending chunks through the flusher."""
        with self.telemetry.span("engine.flush"):
            self.flusher.flush()

    def _materialize(self, ex: PlanExec) -> Dict[int, List[int]]:
        """Land a dispatched plan's emissions: pull the sampled tokens to the
        host, write them to out_tokens + streams, finalize finishing rows
        (and eos hits, which only exist with ``eos_token >= 0`` — the sync
        path above)."""
        toks = self.runner.materialize(ex)
        emitted: Dict[int, List[int]] = {}
        with self.telemetry.span("engine.emit"):
            for req, row, finishing in ex.plan.emit_rows:
                tok = int(toks[row])
                self._emit_token(req, tok)
                emitted.setdefault(req.req_id, []).append(tok)
                if finishing or tok == self.eos_token:
                    self._finalize(req)
        return emitted

    def _sync_inflight(self) -> None:
        """Materialize the inflight plan NOW (mid-build): preemption must see
        complete out_tokens before capturing a victim's continuation/swap
        state. Emissions merge into the current step's result."""
        if self._inflight is None:
            return
        ex, self._inflight = self._inflight, None
        out = self._materialize(ex)
        if self._build_emitted is not None:
            _merge_emitted(self._build_emitted, out)

    def _retire_slot(self, req: Request) -> None:
        """Build-time completion: free the slot and release the block chain
        as soon as the plan DECIDES the request is done (count-based), so the
        next plan can reuse both. Device program order guarantees the
        released blocks' final writes land before any later plan touches
        them. Emission-side effects happen at materialize."""
        if req.slot >= 0 and self.slots[req.slot] is req:
            self.slots[req.slot] = None
        self.kv.release(req.req_id)

    def _drain_copies(self, full: bool = False) -> None:
        """Advance the async copy engine: the whole backlog when ``full``
        (idle steps, drain/exit paths), else up to ``copy_budget`` ops —
        bounded host work per step, scheduled between dispatches."""
        with self.telemetry.span("engine.copies"):
            if self.backend == "paged":
                self.kv.flush_write_through()
            self._copy.drain(None if full else self.copy_budget)

    def _step_sequential(self) -> Dict[int, List[int]]:
        blocked = False
        for slot in range(self.max_batch):
            while self.slots[slot] is None and self.waiting and not blocked:
                i = self.scheduler.select(self.waiting)
                req = self.waiting[i]
                was_swapped = req.swapped  # _try_admit clears it on restore
                if not self._try_admit(req):
                    if req.done:  # unfittable request failed out; try the next
                        self.waiting.pop(i)
                        continue
                    self.admit_blocked += 1
                    blocked = True  # the policy's head-of-line waits for blocks
                    break
                self.waiting.pop(i)
                self.slots[slot] = req
                self._mark_admitted(req)
                if was_swapped:
                    # restored in place: KV, position and cursor resume as
                    # they were (sequential victims are always decode-phase)
                    req.slot = slot
                elif self.backend == "paged":
                    self._prefill_paged(req, slot)
                else:
                    self._prefill_one(req, slot)

        if self.backend == "paged":
            self._ensure_decode_capacity()
        active = [r for r in self.slots if r is not None]
        if not active:
            return {}
        return self._decode_batch(active)

    def _prefix_pending(self, req: Request) -> bool:
        """True while an active request is still mid-prefill on content this
        request could share: the same first cache block (flat prompts), or any
        shareable document segment (segmented prompts — the leader's doc
        blocks are order-independent, so a follower reuses them wherever its
        reranker placed the doc). Deferring admission until the leader
        publishes its blocks lets a same-context RAG burst reuse them instead
        of re-running the shared prefill (prefill spans steps now, so
        admission cannot rely on the leader having finished)."""
        if not self.kv.prefix_sharing:
            return False
        bs = self.block_size
        docs = _shareable_doc_heads(req.segprompt, bs)
        if docs:
            for r in self.slots:
                if (r is not None and r.prefilling
                        and docs & _shareable_doc_heads(r.segprompt, bs)):
                    return True
        if len(req.prompt) <= bs:
            return False
        head = np.asarray(req.prompt[:bs])
        for r in self.slots:
            if (r is not None and r.prefilling and len(r.prompt) >= bs
                    and np.array_equal(np.asarray(r.prompt[:bs]), head)):
                return True
        return False

    def _decode_batch(self, active: List[Request]) -> Dict[int, List[int]]:
        """One batched decode over the active decode-phase slots."""
        tokens = np.zeros((self.max_batch, 1), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        temps = np.zeros((self.max_batch,), np.float32)
        for r in active:
            tokens[r.slot, 0] = r.out_tokens[-1] if r.out_tokens else 0
            pos[r.slot] = r.pos
            temps[r.slot] = r.temperature

        if self.backend == "paged":
            tables = np.full((self.max_batch, self.max_blocks), self._null_block, np.int32)
            rows = self.kv.batch_tables([r.req_id for r in active])
            for i, r in enumerate(active):
                valid = rows[i] >= 0
                tables[r.slot, valid] = rows[i][valid]
            logits, *pools = self._decode_dispatch_jit(
                self.params, self.kv.k, self.kv.v, self.kv.k_scale,
                self.kv.v_scale,
                jnp.asarray(tables), jnp.asarray(tokens), jnp.asarray(pos),
            )
            self._set_pools(*pools)
            for r in active:
                self.kv.lengths[r.req_id] = r.pos + 1
        else:
            logits, self.cache = self._decode_jit(
                self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos)
            )
        self.steps += 1
        self._key, sk = jax.random.split(self._key)
        emitted: Dict[int, List[int]] = {}
        toks = np.asarray(sample_tokens(sk, logits, jnp.asarray(temps)))
        for r in list(active):
            tok = int(toks[r.slot])
            r.pos += 1
            self._emit(r, tok)
            emitted.setdefault(r.req_id, []).append(tok)
            if r.done:
                self.slots[r.slot] = None
        return emitted

    def _mark_admitted(self, req: Request) -> None:
        """A request took a slot: the first time, stamp ``admitted_at`` and
        count its wait since submit (a restore after preemption keeps the
        first stamp)."""
        if req.admitted_at is None:
            req.admitted_at = time.monotonic()
            self.admitted += 1
            self.admit_wait_ns += int((req.admitted_at - req.submitted_at) * 1e9)

    def _record_span(self, req: Request) -> None:
        """The finished request's span: queued from submit, served from
        admission (never admitted: from its end) to its end."""
        end = req.finished_at
        start = req.admitted_at if req.admitted_at is not None else end
        self.telemetry.record_span(Span(req.trace_id, "engine", 0,
                                        req.submitted_at, start, end))

    def _emit_token(self, req: Request, tok: int):
        """Emission side effects of one materialized token: timestamps,
        out_tokens, counters, and the out-of-band stream write."""
        now = time.monotonic()
        if req.first_token_at is None:
            req.first_token_at = now
        elif req.last_token_at is not None:
            req.token_gaps.append(now - req.last_token_at)
            req.max_token_gap = max(req.max_token_gap, now - req.last_token_at)
        req.last_token_at = now
        req.out_tokens.append(tok)
        self.tokens_out += 1
        if req.stream is not None:
            req.stream.write(tok)

    def _finalize(self, req: Request):
        """Completion side effects (idempotent): done flag, finished window,
        stream close — plus slot/block release for paths that did not already
        retire at plan-build time (sequential mode, eos hits)."""
        if req.done:
            return
        req.done = True
        req.finished_at = (req.last_token_at if req.last_token_at is not None
                           else time.monotonic())
        self.finished.append(req)
        self._record_span(req)
        if len(self.finished) > self.max_finished:
            del self.finished[: -self.max_finished]
        if req.slot >= 0 and self.slots[req.slot] is req:
            self.slots[req.slot] = None
        if self.backend == "paged":
            self.kv.release(req.req_id)  # no-op if already released
        if req.stream is not None and not req.stream.closed:
            req.stream.close()

    def _emit(self, req: Request, tok: int):
        """Eager emit (sequential + dense paths): token side effects plus the
        historical completion check applied immediately."""
        self._emit_token(req, tok)
        req.planned = len(req.out_tokens)
        if (
            len(req.out_tokens) >= req.max_new
            or tok == self.eos_token
            or req.pos >= self.max_seq - 1
        ):
            self._finalize(req)


class DataParallelEngineGroup:
    """DP replicas of the paged engine over ONE block pool, partitioned by
    block range — the data-axis half of the sharded-pool layout.

    Each replica is a full GenerationEngine with **independent admission**:
    its own free list over a disjoint block range (``sharded_pool.
    block_range``), its own refcounts, prefix index and warm LRU — no
    cross-replica coordination on the hot path, which is the point of DP.
    All replicas share one ``PoolArrays`` box (and one params tree), so on a
    ("data", "model") mesh the arrays shard blocks over "data" and KV heads
    over "model" and each replica's blocks are its data-shard. Replicas do
    NOT share HBM prefix blocks (each index only points into its own range),
    but a shared ``HostBlockStore`` (``host_store=`` / ``host_blocks=``)
    gives them the next-best thing: every replica write-throughs its newly
    published prefix blocks to the host tier, so a document prefilled on
    replica 0 is a *host hit* on replica 1 — one host->device block copy
    instead of a re-prefill, off the admission hot path. Content-hash keys
    make the sharing exact, and the store's ``cross_hits`` counter makes it
    observable (``stats()["cross_replica_host_hits"]``).

    ``submit`` routes least-loaded (fewest active + queued requests);
    ``step`` advances every replica once. Greedy outputs are identical to a
    lone engine serving the same request — same params, same per-request
    math — which tests/test_sharded_pool.py checks.

    Known startup cost: each replica traces/compiles its own step programs
    (its scratch-block id is baked into the trace as a constant), so group
    construction compiles ~3*dp programs; passing the scratch id as a traced
    operand would let replicas share one compilation."""

    def __init__(self, cfg, dp: int = 2, max_batch: int = 4, max_seq: int = 256,
                 block_size: int = 16, n_blocks_per_replica: Optional[int] = None,
                 prefix_sharing: bool = True, pool_layout: Optional[ShardedPoolLayout] = None,
                 seed: int = 0, host_store: Optional[HostBlockStore] = None,
                 host_blocks: Optional[int] = None,
                 kv_dtype: Optional[str] = None, sanitize: bool = False,
                 params=None, **engine_kwargs):
        if dp < 1:
            raise ValueError("dp must be >= 1")
        max_blocks = -(-max_seq // block_size)
        per = n_blocks_per_replica or (max_batch * (max_blocks + 1) + 1)
        total = per * dp
        self.pool_layout = pool_layout
        if kv_dtype is None and cfg.kv_cache_quant:
            kv_dtype = "int8"
        if kv_dtype is not None and pool_layout is not None:
            raise ValueError("kv_dtype='int8' does not shard over a mesh yet")
        if host_store is None and (host_blocks
                                   or engine_kwargs.get("preempt") in ("swap", "cost")):
            host_store = HostBlockStore.for_config(
                cfg, host_blocks or total, block_size, kv_dtype=kv_dtype
            )
        self.host_store = host_store
        # one shared transport: chunks from every replica's streams flush in
        # global EDF-slack order, not per-replica order
        self.flusher = PriorityFlusher()
        engine_kwargs.setdefault("flusher", self.flusher)
        self.engines: List[GenerationEngine] = []
        arrays: Optional[PoolArrays] = None
        # one sanitizer spans the whole group: replicas allocate from
        # disjoint ranges of one shared pool array, so a shared shadow also
        # catches cross-replica double-ownership of a block
        self.sanitizer = None
        if sanitize:
            from repro.analysis.kvsan import KVSanitizer

            self.sanitizer = KVSanitizer()
        for rank in range(dp):
            lo, hi = block_range(total, dp, rank)
            kv = PagedKVCache(
                cfg, total, block_size, max_blocks, prefix_sharing=prefix_sharing,
                layout=pool_layout, block_range=(lo, hi), arrays=arrays,
                host_store=host_store, client_tag=rank, kv_dtype=kv_dtype,
                sanitizer=self.sanitizer,
                # write-through: siblings should host-hit a doc without
                # waiting for the producing replica to evict it from HBM
                host_write_through=host_store is not None,
            )
            eng = GenerationEngine(
                cfg, params=params, max_batch=max_batch, max_seq=max_seq,
                seed=seed, block_size=block_size, kv=kv, pool_layout=pool_layout,
                **engine_kwargs,
            )
            arrays = kv._arrays   # replicas 1.. attach to replica 0's box
            params = eng.params   # and reuse its (placed) params tree
            self.engines.append(eng)

    def submit(self, prompt, max_new: int = 16, temperature: float = 0.0,
               priority: float = 0.0, trace_id: Optional[int] = None) -> Request:
        eng = min(
            self.engines,
            key=lambda e: len(e.waiting) + sum(s is not None for s in e.slots),
        )
        return eng.submit(prompt, max_new, temperature, priority, trace_id)

    def step(self) -> None:
        for eng in self.engines:
            if eng.waiting or any(eng.slots) or eng.pending:
                eng.step()

    def run_until_done(self, max_steps: int = 10_000) -> None:
        while max_steps and any(
            e.waiting or any(e.slots) or e.pending for e in self.engines
        ):
            self.step()
            max_steps -= 1
        for eng in self.engines:
            eng._drain_copies(full=True)
        self.flusher.flush()

    def stats(self) -> Dict[str, Any]:
        per = [e.stats() for e in self.engines]
        out = {
            "dp_degree": len(self.engines),
            "tokens_out": sum(s["tokens_out"] for s in per),
            "prefill_tokens": sum(s["prefill_tokens"] for s in per),
            "preemptions": sum(s["preemptions"] for s in per),
            "host_hit_tokens": sum(s.get("host_hit_tokens", 0) for s in per),
            "replicas": per,
        }
        if self.host_store is not None:
            out["cross_replica_host_hits"] = self.host_store.cross_hits
            out["host_store"] = self.host_store.stats()
        return out


def _merge_emitted(into: Dict[int, List[int]], more: Dict[int, List[int]]) -> None:
    for rid, toks in more.items():
        into.setdefault(rid, []).extend(toks)


def _shareable_doc_heads(segprompt, block_size: int) -> set:
    """Content fingerprints of a prompt's document segments big enough to
    yield at least one shareable (full) block."""
    if segprompt is None:
        return set()
    from repro.serving.segments import KIND_DOC

    return {
        seg.tokens.tobytes()
        for seg in segprompt.segments
        if seg.kind == KIND_DOC and len(seg.tokens) >= block_size
    }


def _merge_cache(batch_cache, one_cache, slot: int, max_seq: int):
    """Write a B=1 prefill cache into batch slot `slot` (padding seq dims)."""

    def merge(bc, oc):
        if bc.ndim < 2:
            return bc
        # layouts: (G, B, ...) — batch axis 1
        oc = oc.astype(bc.dtype)
        pad = [(0, 0)] * oc.ndim
        changed = False
        for ax in range(2, oc.ndim):
            if oc.shape[ax] != bc.shape[ax]:
                pad[ax] = (0, bc.shape[ax] - oc.shape[ax])
                changed = True
        if changed:
            oc = jnp.pad(oc, pad)
        return bc.at[:, slot].set(oc[:, 0])

    return jax.tree.map(merge, batch_cache, one_cache)
