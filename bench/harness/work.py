"""What every architecture's work counts share: which cache slots each
computed token attends, how many slots a packed step's rows attend in all,
and the least time a count of operations and bytes can take on a chip.

What a token costs depends on the architecture, so the model FLOPs of a
step and each kernel's (FLOPs, bytes) live in the reference module that a
configuration names (``bench/references/<reference>.py``: ``step_flops``
and ``kernel_work``), which builds them from the helpers here.

Cached tokens are not work (a step never computes them). A document token
attends the prelude and its own document; every other token attends all
slots before it. Table width, dead table columns and padding never count,
so a kernel that stops wasting them reads a higher share of its roofline.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

import numpy as np


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


def attended_slots(row_of, slots, p_end, s_start) -> int:
    """Cache slots a packed step reads if each row reads every slot that
    any of its tokens attends once: the union of the rows' spans."""
    row_of, slots = np.asarray(row_of), np.asarray(slots)
    p_end, s_start = np.asarray(p_end), np.asarray(s_start)
    total = 0
    for r in np.unique(row_of):
        m = row_of == r
        iv = [(0, int(p)) for p in np.unique(p_end[m])]
        for s in np.unique(s_start[m]):
            iv.append((int(s), int(slots[m][s_start[m] == s].max()) + 1))
        total += _union_len(iv)
    return total


def min_time(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def roofline_pct(ctx, kernel: str) -> Optional[float]:
    """``kernel``'s share of its roofline in a traced window: the least time
    the work of the traced steps that run it (the configuration's reference,
    ``kernel_work``) could take at the chip's peaks, over the time its calls
    took. None where it made no call or no traced step runs it."""
    red = ctx["reduced"]
    spent = red.kernels.get(kernel, 0.0) if red is not None else 0.0
    if spent <= 0:
        return None
    counts = [ctx["reference"].kernel_work(kernel, ctx["dims"], ctx["config"], p)
              for p in ctx["plans"]]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return 100.0 * sum(min_time(*c, ctx["peaks"]) for c in counts) / spent
