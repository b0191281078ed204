"""Manual tensor-parallel decode layer via shard_map (explicit collectives).

The framework's baseline distribution is pjit/GSPMD (models/sharding.py):
the partitioner chooses the collective schedule. This module provides the
complementary shard_map path for the serving-critical TP block, with the
Megatron schedule written EXPLICITLY:

    column-parallel:  y_local = x @ W1_local          (no comm)
    row-parallel:     z = psum(y_local @ W2_local)    (one all-reduce)

Two reasons to have it: (a) the collective schedule is pinned by
construction — a §Perf lever when GSPMD's choice is wrong; (b) it documents
exactly which collectives the baseline SHOULD emit, which the dry-run HLO
parse is cross-checked against.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def tp_block_reference(x, w_in, w_out):
    """Unsharded oracle: x:(B,D) @ w_in:(D,F) -> gelu -> @ w_out:(F,D)."""
    return jax.nn.gelu(x @ w_in) @ w_out


def make_tp_block(mesh: Mesh, axis: str = "model"):
    """Returns a jitted shard_map TP block. Weights must be passed sharded:
    w_in column-split (D, F/axis), w_out row-split (F/axis, D); x replicated
    along `axis`."""

    def local_block(x, w_in_local, w_out_local):
        h = jax.nn.gelu(x @ w_in_local)             # (B, F/axis), local
        z_partial = h @ w_out_local                 # (B, D), partial sum
        return jax.lax.psum(z_partial, axis)        # ONE all-reduce

    sharded = shard_map(
        local_block,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(axis, None)),
        out_specs=P(),
    )
    return jax.jit(sharded)


def shard_tp_weights(mesh: Mesh, w_in, w_out, axis: str = "model"):
    """Place full weights with the TP layout the block expects."""
    w_in_s = jax.device_put(w_in, NamedSharding(mesh, P(None, axis)))
    w_out_s = jax.device_put(w_out, NamedSharding(mesh, P(axis, None)))
    return w_in_s, w_out_s


def tp_block_pjit(mesh: Mesh, axis: str = "model"):
    """The same block through pjit/GSPMD (for schedule comparison)."""

    def block(x, w_in, w_out):
        return jax.nn.gelu(x @ w_in) @ w_out

    return jax.jit(
        block,
        in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P(None, axis)),
            NamedSharding(mesh, P(axis, None)),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


def count_collectives(compiled) -> dict:
    """Collective op census of a compiled function (schedule audit)."""
    import re

    txt = compiled.as_text()
    out = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute"):
        out[op] = len(re.findall(rf"\b{op}(?:-start)?\(", txt))
    return out
