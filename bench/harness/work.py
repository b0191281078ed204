"""The algorithm's work in the served steps, counted from what each step
computed: model FLOPs per computed token and per sampled logit, and each
paged attention kernel's FLOPs and bytes from per-token context spans.

Cached tokens are not work (a step never computes them). A document token
attends the prelude and its own document; every other token attends all
slots before it. Logits count only where a token is sampled. Table width,
dead table columns and padding never count, so a kernel that stops wasting
them reads a higher share of its roofline.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np


def linear_flops_per_token(d) -> float:
    """Projections and MLP of every layer, for one computed token."""
    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = 2 * (d.d_model * qd + 2 * d.d_model * kvd + qd * d.d_model
                     + 3 * d.d_model * d.d_ff)
    return float(per_layer * d.layers)


def logit_flops(d) -> float:
    """The output head for one sampled token."""
    return 2.0 * d.d_model * d.vocab


def attn_flops(d, ctx) -> float:
    """Scores and value sum of one layer for tokens with contexts ``ctx``."""
    return 4.0 * d.heads * d.head_dim * float(np.sum(ctx))


def token_contexts(slots, p_end, s_start) -> np.ndarray:
    """Slots each token attends: ``[0, p_end) + [s_start, slot]``."""
    slots, p_end, s_start = (np.asarray(a, np.int64) for a in (slots, p_end, s_start))
    return p_end + slots - s_start + 1


def _union_len(intervals: Iterable[Tuple[int, int]]) -> int:
    total, end = 0, -1
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total


def chunk_kernel_work(d, kv_bytes: int, act_bytes: int, row_of, slots, p_end,
                      s_start) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``paged_chunk_attention`` over all layers for one
    packed step: each row's attended slots read once, every query read and
    every output written once."""
    row_of, slots = np.asarray(row_of), np.asarray(slots)
    p_end, s_start = np.asarray(p_end), np.asarray(s_start)
    flops = attn_flops(d, token_contexts(slots, p_end, s_start))
    kv_slots = 0
    for r in np.unique(row_of):
        m = row_of == r
        iv = [(0, int(p)) for p in np.unique(p_end[m])]
        for s in np.unique(s_start[m]):
            iv.append((int(s), int(slots[m][s_start[m] == s].max()) + 1))
        kv_slots += _union_len(iv)
    kv = kv_slots * d.kv_heads * d.head_dim * 2 * kv_bytes
    qo = len(slots) * d.heads * d.head_dim * 2 * act_bytes
    return flops * d.layers, float(kv + qo) * d.layers


def decode_kernel_work(d, kv_bytes: int, act_bytes: int, ctx) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``paged_decode_attention`` over all layers for one
    decode step whose rows attend ``ctx`` slots each."""
    ctx = np.asarray(ctx, np.int64)
    kv = float(ctx.sum()) * d.kv_heads * d.head_dim * 2 * kv_bytes
    qo = len(ctx) * d.heads * d.head_dim * 2 * act_bytes
    return attn_flops(d, ctx) * d.layers, (kv + qo) * d.layers


def step_model_flops(d, plan: Dict) -> float:
    """Model FLOPs of one step: every computed token through every layer,
    its attention over its own context, and the head where a token is
    sampled."""
    if plan["kind"] == "ragged":
        ctx = token_contexts(plan["slots"], plan["p_end"], plan["s_start"])
    elif plan["kind"] == "decode":
        ctx = np.asarray(plan["ctx"])
    else:
        raise ValueError(f"no work count for a {plan['kind']!r} step")
    return (len(ctx) * linear_flops_per_token(d) + attn_flops(d, ctx) * d.layers
            + plan["sampled"] * logit_flops(d))


def min_time(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
