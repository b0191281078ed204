"""The system under test: the program's ``DataParallelEngineGroup`` on a
(data, model) mesh of several devices.

Each replica is a full paged engine with its own admission, slots and
block range of one pool; the pool and the weights are split over the mesh
(blocks over the data axis, KV heads and weight columns over the model
axis), every replica's step programs run on the whole mesh, and the group
routes each request to its least-loaded replica. One host block store is
shared by the replicas: every replica writes its new document blocks
through to it, so a document prefilled on one replica is a host hit on the
other. The mesh path runs the reference attention kernels (the program's
Pallas kernels are single-device).

It provides what ``generation_engine.py`` provides, and borrows that
adapter's helpers for whatever does not depend on the group: the model's
configuration and weights, a prompt's segments and a step plan's work.
Counters are summed over the replicas, with the store's
``cross_replica_host_hits`` beside them. A request whose admission took
blocks another replica wrote is marked ``check_first``, so that the
correctness sample always holds one where the window has any.
"""
from __future__ import annotations

from pathlib import Path
from typing import Callable, Dict

from bench.harness import spec

single = spec.load_adapter("generation_engine", Path(__file__).resolve().parents[2])

import_program = single.import_program
prompt_segments = single.prompt_segments
plan_view = single.plan_view


def request_view(req) -> dict:
    """The one-device view, and whether the request's admission promoted
    host blocks that another replica wrote."""
    return dict(single.request_view(req),
                check_first=getattr(req, "cross_replica_host_hits", 0) > 0)


def weight_shardings(config: dict, dims, shapes: Dict) -> Dict:
    """Each benchmark weight's placement on the mesh, as the program places
    the parameter it becomes, so that the engine serves the benchmark's
    arrays as they are and no device holds a whole copy besides its share."""
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.sharded_pool import ShardedPoolLayout

    pcfg = single.program_config(config, dims)
    tree = single.program_params(shapes, pcfg)
    mesh = make_serving_mesh(int(config["mesh"]["tp"]), int(config["mesh"]["dp"]))
    placed = ShardedPoolLayout(mesh, dp_blocks=True).param_shardings(pcfg, tree)
    name = {id(v): k for k, v in shapes.items()}
    return {name[id(leaf)]: sh for leaf, sh in zip(jax.tree.leaves(tree),
                                                   jax.tree.leaves(placed))}


def build(config: dict, weights: Dict, dims):
    """The replica group with a configuration's ``engine`` settings, each
    replica's as in the one-device engine, and its ``mesh`` (``tp``
    devices to a replica, ``dp`` replicas) and ``host_blocks``."""
    from repro.launch.serve import build_engine

    pcfg = single.program_config(config, dims)
    eng, mesh = config["engine"], config["mesh"]
    group = build_engine(
        pcfg, tp=int(mesh["tp"]), dp=int(mesh["dp"]),
        params=single.program_params(weights, pcfg), max_batch=int(eng["max_batch"]),
        max_seq=int(eng["max_seq"]), n_blocks=int(eng["n_blocks"]),
        block_size=int(eng["block_size"]),
        prefill_chunk_size=int(eng["prefill_chunk_size"]),
        token_budget=int(eng["token_budget"]), pack_align=int(eng["pack_align"]),
        scheduler=eng["scheduler"], kernel=eng["kernel"],
        host_blocks=int(eng["host_blocks"]))
    for e in group.engines:
        single._count_evictions(e.kv.pool)
        _mark_cross_replica_hits(e, group.host_store)
    return group


def _mark_cross_replica_hits(e, store) -> None:
    """Each admission records on its request the host blocks it promoted
    that another replica wrote (the store's ``cross_hits`` grow inside it)."""
    admit = e._try_admit

    def counted(req):
        before = store.cross_hits
        ok = admit(req)
        req.cross_replica_host_hits = (getattr(req, "cross_replica_host_hits", 0)
                                       + store.cross_hits - before)
        return ok

    e._try_admit = counted


def warm(group) -> int:
    """Compile every program of every replica that the window can run (each
    replica compiles its own step programs): the step programs fed as the
    runner feeds them on a mesh, and the host tier's block copies. Returns
    the number of programs warmed."""
    return sum(_warm_steps(e) + _warm_block_copies(e) for e in group.engines)


def _packed_lengths(e) -> list:
    """The packed lengths of the fused step, as the engine bounds them: the
    token budget plus the one-token floor grant, within the padded slab,
    in steps of ``pack_align``."""
    B, C = e.max_batch, e.prefill_chunk_size
    cap = min(max((e.token_budget or B * C) + 1, B + 1), B * C)
    cap_pad = -(-cap // e.pack_align) * e.pack_align
    return list(range(e.pack_align, cap_pad + 1, e.pack_align))


def _warm_steps(e) -> int:
    """Every step after a replica's first is handed the token substitution's
    output, which lies on the mesh because the previous step's sampled
    tokens do. The engine's own warm-up passes tokens made on the host,
    which XLA compiles as another program and the window never runs; so
    each packed length and the decode step are warmed with substituted
    tokens instead."""
    import jax
    import jax.numpy as jnp

    B = e.max_batch
    prev = jax.device_put(jnp.zeros((B,), jnp.int32), e.pool_layout.replicated())
    none = jnp.full((B,), -1, jnp.int32)
    first = jnp.zeros((B,), jnp.int32)
    tables = jnp.full((B, e._view_blocks), -1, jnp.int32)
    state = (e.params, e.kv.k, e.kv.v, e.kv.k_scale, e.kv.v_scale, tables)
    lengths = _packed_lengths(e)
    for T in lengths:
        z = jnp.zeros((T,), jnp.int32)
        toks = e.runner._subst_packed_jit(z, prev, none, first)
        jax.block_until_ready(e._ragged_step_jit(
            *state, toks, jnp.full((T,), -1, jnp.int32), z, z, z, z, first)[0])
    fn, args = e.step_program("decode")
    toks = e.runner._subst_jit(args[6], prev, none)
    jax.block_until_ready(fn(*args[:6], toks, args[7])[0])
    return len(lengths) + 1


def _warm_block_copies(e) -> int:
    """The host tier's copies are eager operations, one program for each
    number of blocks: the write-through gather and the promotion scatter,
    for one block up to a whole sequence's. The results are dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kv = e.kv
    n = 0
    for k in range(1, e.max_blocks + 1):
        ids = jnp.asarray(np.arange(k, dtype=np.int32))
        host = np.zeros((kv.k.shape[0], k) + kv.k.shape[2:], kv.k.dtype)
        jax.block_until_ready((jnp.take(kv.k, ids, axis=1),
                               kv.k.at[:, ids].set(jnp.asarray(host))))
        n += 2
    return n


def busy(group) -> bool:
    return any(single.busy(e) for e in group.engines)


def step(group) -> None:
    group.step()


def sync(group) -> None:
    """Wait until every step dispatched so far has run on the devices."""
    for e in group.engines:
        single.sync(e)


def counters(group) -> Dict[str, int]:
    """Each replica's counters and pool occupancy, summed, and the host
    blocks promoted on a replica other than the one that wrote them."""
    out: Dict[str, int] = {}
    for e in group.engines:
        for k, v in single.counters(e).items():
            out[k] = out.get(k, 0) + v
    # the replicas hold disjoint ranges of one pool: a replica's own count
    # of held blocks would take in the other's
    out["blocks_held"] = (group.engines[0].kv.pool.n_blocks - out["blocks_free"]
                          - out["blocks_warm"])
    out["cross_replica_host_hits"] = int(group.host_store.cross_hits)
    return out


def trace_hooks(group, span: Callable, on_plan: Callable) -> None:
    """The one-device hooks on every replica: host spans around each
    replica's calls into its layers, and ``on_plan`` for every step plan
    any replica dispatches."""
    for e in group.engines:
        single.trace_hooks(e, span, on_plan)
