"""Whether what the timed path served is correct.

Once the window has closed, a sample drawn from the seed of the requests
the window's pipelines finished (the one with the most served tokens always
among them, and one that the adapter's view marks ``check_first`` where
any is, then others until some hundreds of served tokens are in) is run
once through the configuration's plain reference: each prompt exactly as it
was submitted, followed by the tokens the system served. At each served
position the reference's logits say how far the served token's logit lies
below the reference's best; the widest such gap over the sample is compared
with the configuration's limit. Served tokens are greedy (the pipelines
submit at temperature 0), so a sound system's gap is rounding alone.

A control reads, at the same positions, the gap of the token that the
reference computed one precision step below the served bfloat16 (int8, or
float8) puts first. The calibration script runs the controls; the
benchmark's own runs do not.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample(records: List[dict], seed: int, min_tokens: int, max_requests: int
           ) -> List[dict]:
    """Finished window requests to compare: the longest and the first, in an
    order drawn from ``seed``, of those marked ``check_first`` (served
    through a path the cell exists to guard, such as blocks another replica
    wrote) whatever the limits, then the others in that order until
    ``min_tokens`` served tokens are in."""
    pool = [req for r in records if r["phase"] == "window"
            for req in r["requests"] if req["done"] and req["out_tokens"]
            and not req["truncated"]]
    if not pool:
        return []
    longest = max(range(len(pool)), key=lambda i: len(pool[i]["out_tokens"]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 5])
    rest = [i for i in rng.permutation(len(pool)) if i != longest]
    marked = [] if pool[longest].get("check_first") else [
        i for i in rest if pool[i].get("check_first")][:1]
    out = [pool[i] for i in [longest] + marked]
    n = sum(len(req["out_tokens"]) for req in out)
    for i in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        if i not in marked:
            out.append(pool[i])
            n += len(pool[i]["out_tokens"])
    return out


def sequence(ref, req: dict):
    """The reference's input for one request: prompt segments as submitted,
    then the served tokens but the last, and the rows whose logits predict
    each served token."""
    kinds = [k for k, _ in req["segments"]]
    lens = [len(t) for _, t in req["segments"]]
    out = np.asarray(req["out_tokens"], np.int32)
    prompt = np.concatenate([t for _, t in req["segments"]]).astype(np.int32)
    pos, p_end, s_start = ref.segment_layout(kinds + ["tail"], lens + [len(out) - 1])
    tokens = np.concatenate([prompt, out[:-1]])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    return tokens, pos, p_end, s_start, rows, out


def gaps(ref, dims, w, items: List[dict], controls=()) -> Dict[str, float]:
    """Widest gap of the served tokens against the reference (and of each
    control's own first choices, ``controls`` naming the precisions), over
    ``items``."""
    served, n = 0.0, 0
    ctrl = {c: 0.0 for c in controls}
    for req in items:
        tokens, pos, pe, ss, rows, out = sequence(ref, req)
        want = ref.forward_logits(w, dims, tokens, pos, pe, ss, rows)
        best = want.max(axis=1)
        bad = (out < 0) | (out >= dims.vocab)
        got = np.where(bad, np.inf, best - want[np.arange(len(out)), np.clip(out, 0, dims.vocab - 1)])
        served = max(served, float(got.max()))
        n += len(out)
        for c in controls:
            low = ref.forward_logits(w, dims, tokens, pos, pe, ss, rows, quant=c)
            pick = low.argmax(axis=1)
            ctrl[c] = max(ctrl[c], float((best - want[np.arange(len(out)), pick]).max()))
    res = {"gap_max": served, "tokens": n, "requests": len(items)}
    res.update({f"control_{c}_gap_max": v for c, v in ctrl.items()})
    return res
