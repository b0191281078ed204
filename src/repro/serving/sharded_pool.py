"""Sharded paged KV pools: mesh placement for the paged serving engine.

Patchwork's Generator scales along the accelerator-mesh axis, so the paged
engine must serve under TP/DP meshes, not just a single device. This module
is the glue between the host-side block allocator (``serving.paged_cache``)
and the mesh sharding policy (``models.sharding``):

* **TP (model axis, by KV head).** Pool arrays ``(G, n_blocks, bs, KVH, hd)``
  are partitioned over the KV-head dim: each model-axis shard holds
  ``KVH / tp`` heads of EVERY block. Block ids, refcounts, the prefix index
  and the warm-cache LRU stay replicated *host-side* metadata — one admission
  decision drives all shards — and the device-side block-table gather /
  chunk-scatter stay purely local per shard (``models.sharding.pool_pspecs``
  documents why the block axis must NOT shard over "model"). The engine's
  fused step then communicates only through the Megatron reductions after the
  attention/MLP output projections; ``GenerationEngine.audit_collectives``
  compiles the step and asserts the schedule (no all-gathers).

* **DP (data axis, by block range).** Optionally the block axis shards over
  "data": DP replicas own disjoint *block ranges* of one pool array, each
  replica running fully independent admission (own free list, own refcounts,
  own prefix index). ``block_range`` computes a replica's slice;
  ``DataParallelEngineGroup`` (serving.engine) wires replica engines to one
  shared array holder. Cross-replica *content* sharing happens one tier
  down: a ``serving.host_tier.HostBlockStore`` shared by the group mirrors
  every replica's published prefix blocks host-side (content-hash keys are
  replica-agnostic), so a document prefilled in one replica's block range is
  a host-tier promotion — not a re-prefill — in another's.

``tp = 1`` (or no mesh) is bit-identical to the unsharded engine: layout-less
construction takes exactly the legacy code path, and a 1-device mesh changes
placement only, not math — both are tier-1 parity oracles
(tests/test_sharded_pool.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ShardedPoolLayout:
    """How a paged engine's arrays map onto a device mesh.

    ``mesh`` must carry a "model" axis (TP) and may carry a "data" axis (DP).
    ``dp_blocks`` opts the pool's block axis into data-axis sharding (only
    meaningful when DP replicas share one pool array through
    ``DataParallelEngineGroup``; a lone engine keeps its blocks replicated
    over "data" so any replica count can address the whole pool)."""

    mesh: jax.sharding.Mesh
    dp_blocks: bool = False

    @property
    def axis_sizes(self) -> dict:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def tp_degree(self) -> int:
        return self.axis_sizes.get("model", 1)

    @property
    def dp_degree(self) -> int:
        return self.axis_sizes.get("data", 1)

    # ------------------------------------------------------------ shardings
    def pool_sharding(self, cfg, n_blocks: Optional[int] = None) -> NamedSharding:
        """Placement for the k/v pool arrays (G, n_blocks, bs, KVH, hd).
        Pass ``n_blocks`` when known so the data-axis block sharding can obey
        the explicit divisibility policy (indivisible -> replicated)."""
        from repro.models.sharding import pool_pspecs

        return NamedSharding(
            self.mesh,
            pool_pspecs(cfg, self.axis_sizes, dp_blocks=self.dp_blocks,
                        n_blocks=n_blocks),
        )

    def kv_entry_sharding(self, cfg) -> NamedSharding:
        """Placement for per-sequence K/V entry batches — gathered views
        (G, B, S, KVH, hd) and chunk writes (G, B, C, KVH, hd): same KV-head
        partition as the pool (derived from pool_pspecs, the single source of
        the policy), block/batch axes replicated."""
        from repro.models.sharding import pool_pspecs

        kvh = pool_pspecs(cfg, self.axis_sizes)[3]
        return NamedSharding(self.mesh, P(None, None, None, kvh, None))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_shardings(self, cfg, params):
        """NamedSharding tree for TP-resident serve params (embed/lm_head
        replicated; see models.sharding.serve_engine_pspecs)."""
        from repro.models.sharding import serve_engine_pspecs

        abstract = jax.eval_shape(lambda t: t, params)
        pspecs = serve_engine_pspecs(cfg, abstract, self.axis_sizes)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def place_params(self, cfg, params):
        return jax.tree.map(
            jax.device_put, params, self.param_shardings(cfg, params)
        )

    # ----------------------------------------------------------- validation
    def validate(self, cfg) -> None:
        """The TP partition is explicit, never padded: reject a config whose
        head counts don't divide the model axis instead of silently falling
        back to replicated pools (the caller asked for sharding)."""
        tp = self.tp_degree
        if tp <= 1:
            return
        if cfg.num_kv_heads % tp:
            raise ValueError(
                f"sharded pool: num_kv_heads={cfg.num_kv_heads} does not "
                f"divide the model axis ({tp}); each shard must own an equal "
                f"slice of every block's KV heads"
            )
        if cfg.num_heads % tp:
            raise ValueError(
                f"sharded pool: num_heads={cfg.num_heads} does not divide "
                f"the model axis ({tp}); query heads must align with the "
                f"KV-head shards for attention to stay shard-local"
            )


def block_range(n_blocks: int, dp_degree: int, dp_rank: int) -> Tuple[int, int]:
    """[lo, hi) block ids owned by DP replica ``dp_rank`` of ``dp_degree``.

    Replicas partition the pool by contiguous block range so that, on a mesh
    whose "data" axis shards the block dim, a replica's blocks are its local
    shard. The remainder (when dp doesn't divide n_blocks) goes to the last
    replica — block counts per replica differ by at most one chunk."""
    if not 0 <= dp_rank < dp_degree:
        raise ValueError(f"dp_rank {dp_rank} outside [0, {dp_degree})")
    per = n_blocks // dp_degree
    lo = dp_rank * per
    hi = (dp_rank + 1) * per if dp_rank < dp_degree - 1 else n_blocks
    return lo, hi


def make_pool_layout(
    mesh=None, tp: Optional[int] = None, dp: int = 1, dp_blocks: bool = False,
) -> Optional[ShardedPoolLayout]:
    """Build a layout from either an existing mesh or a (tp, dp) request.

    Returns None for the degenerate no-mesh/tp=1/dp=1 case so callers keep
    the legacy unsharded path (bit-identical, no placement machinery)."""
    from repro.launch.mesh import make_mesh

    if mesh is not None:
        return ShardedPoolLayout(mesh, dp_blocks=dp_blocks)
    tp = tp or 1
    if tp <= 1 and dp <= 1:
        return None
    if dp > 1:
        mesh = make_mesh((dp, tp), ("data", "model"))
    else:
        mesh = make_mesh((tp,), ("model",))
    return ShardedPoolLayout(mesh, dp_blocks=dp_blocks)
