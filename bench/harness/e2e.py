"""End-to-end metrics from the client's side, on the host's clock.

Every number here is taken over all the work of the window: every engine
request of every pipeline due in the window, timed from when it was due; a
request that never produced its first token counts with the time it had
waited when the run ended, so it sits in the tail. Counts and per-class
figures beside them are reported and decide nothing.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else float("nan")


def stage_rows(rec, ended_at: float) -> List[dict]:
    """One row per engine request of a pipeline, in stage order, with the
    time it was due: the pipeline's due time for the first stage, the end
    of the previous stage for each later one."""
    rows, due = [], rec["due"]
    for req in rec["requests"]:
        first = req["first_token_at"]
        ttft = (first if first is not None else ended_at) - due
        rows.append({"due": due, "ttft": ttft, "produced": first is not None,
                     "submitted_at": req["submitted_at"], "gaps": req["token_gaps"]})
        due = req["finished_at"] if req["finished_at"] is not None else ended_at
    return rows


def token_times(req) -> np.ndarray:
    if req["first_token_at"] is None:
        return np.zeros(0)
    return req["first_token_at"] + np.concatenate([[0.0], np.cumsum(req["token_gaps"])])


def compute(records: List[dict], t_zero: float, window_s: float,
            ended_at: float) -> Dict[str, object]:
    """records: plain dicts of every pipeline the run released (see
    ``runner.plain_records``). Returns the end-to-end metrics (``metrics``)
    and the counts that go with them (``counts``)."""
    win = [r for r in records if r["phase"] == "window"]
    rows = [row for r in win for row in stage_rows(r, ended_at)]
    ttft = [row["ttft"] for row in rows]
    itl = [g for row in rows for g in row["gaps"]]
    met = [r["finished_at"] is not None
           and r["finished_at"] - r["due"] <= r["deadline_s"] for r in win]
    lo, hi = t_zero, t_zero + window_s
    done_tokens = sum(int(((t >= lo) & (t < hi)).sum())
                      for r in records for req in r["requests"]
                      for t in [token_times(req)])
    metrics = {
        "itl_p95_ms": 1e3 * pct(itl, 95),
        "slo_attain_pct": 100.0 * float(np.mean(met)) if met else float("nan"),
        "out_tok_s": done_tokens / window_s,
    }
    per_class = {}
    for c in sorted({r["slo_class"] for r in win}):
        m = [ok for r, ok in zip(win, met) if r["slo_class"] == c]
        lat = [r["finished_at"] - r["due"] for r in win
               if r["slo_class"] == c and r["finished_at"] is not None]
        per_class[c] = {"pipelines": len(m), "attain_pct": 100.0 * float(np.mean(m)),
                        "mean_e2e_s": float(np.mean(lat)) if lat else float("nan"),
                        "p95_e2e_s": pct(lat, 95)}
    lateness = [r["released_at"] - r["due"] for r in win]
    counts = {
        "slo_attain_pct": metrics["slo_attain_pct"],
        "pipelines": len(win),
        "pipelines_finished": sum(r["finished_at"] is not None for r in win),
        "requests": len(rows),
        "requests_without_token": sum(not row["produced"] for row in rows),
        "token_gaps": len(itl),
        "ttft_p50_ms": 1e3 * pct(ttft, 50),
        "itl_p50_ms": 1e3 * pct(itl, 50),
        "per_class": per_class,
        "release_late_p95_ms": 1e3 * pct(lateness, 95),
        "release_late_max_ms": 1e3 * max(lateness) if lateness else float("nan"),
        "truncated_requests": sum(req["truncated"] for r in win for req in r["requests"]),
        "unfinished": [unfinished(r, t_zero) for r in win if r["finished_at"] is None],
    }
    return {"metrics": metrics, "counts": counts}


def unfinished(rec: dict, t_zero: float) -> dict:
    """What a window pipeline that never finished had done: its due time in
    the window, its answer budget, and its requests so far."""
    reqs = rec["requests"]
    return {"index": rec["index"], "class": rec["slo_class"],
            "due_s": rec["due"] - t_zero, "max_new": rec["max_new"],
            "requests": len(reqs), "requests_done": sum(q["done"] for q in reqs),
            "tokens_out": sum(len(q["out_tokens"]) for q in reqs),
            "budgets": [q["max_new"] for q in reqs]}

