"""Seeded open-loop traffic: the same multiset of requests in every run, in
an order and with token contents drawn from the seed.

Derived from the program's seeded generator (``core/workload.py``: a
weighted mixture of pipeline classes, one fixed draw order) and changed in
one respect: the work does not depend on the run's seed. Each phase of a
run (warm-up, measured window, tail) has a fixed set of request shapes
(class, query length, answer budget, documents per retrieval, the
pipeline's own path draws) and a fixed set of due times, both drawn and put
in order once from the mix's ``shape_seed``; the run's ``--seed`` draws
every token (and the weights). Runs with different seeds therefore offer
the same load in the same order and differ in contents only: with some tens
of pipelines in a window, a seed that also reordered them moved the
throughput read in the window by up to 30% (TPU v5e, qwen2.5-3b).

A mix is a JSON file of parameters (``bench/traffic/<name>.json``). The
keys this generator reads:

    arrival               the arrival process, ``bench/arrivals/<name>.py``,
                          which reads its own keys (a rate, bursts)
    requests              what an arrival becomes, ``bench/requests/<name>.py``,
                          which reads its own keys (documents, sessions)
    warmup_s, tail_s      arrivals before the window and after it (the tail
                          keeps the load on while the window's pipelines finish)
    grace_s               how long after the window its pipelines may take
    shape_seed            fixes the shapes and due times of every phase
    classes               {name: {"weight", "deadline_s"}}
    k_docs                [lo, hi] documents per retrieval, inclusive
    query_len             [lo, hi] tokens per query, inclusive
    max_new               {"median", "sigma", "min", "max"}: log-normal answer
                          budget, clipped
    trace_s               seconds of the window a traced run records
    about                 one paragraph for the reader
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields
from statistics import NormalDist
from typing import Dict, List

import numpy as np

PHASES = ("warmup", "window", "tail")
KEYS = ("about", "arrival", "requests", "warmup_s", "tail_s", "grace_s",
        "shape_seed", "classes", "k_docs", "query_len", "max_new", "trace_s")


@dataclass(frozen=True)
class Arrival:
    due: float          # seconds from the window's start (warm-up < 0)
    phase: str          # "warmup" | "window" | "tail"
    index: int          # dense, in due order
    slo_class: str
    deadline_s: float   # class deadline, from the due time
    query_len: int
    max_new: int        # answer budget of the pipeline's answer stages
    k_docs: int
    complexity: float   # in [0, 1): drives data-dependent stage counts
    path_seed: int      # the pipeline's own draws (path, retrieval): fixed
    token_seed: int     # the query's tokens: from the run's seed


def _stratified(n: int, rng) -> np.ndarray:
    """n points of [0, 1), one in each of n equal strata, shuffled."""
    return rng.permutation((np.arange(n) + rng.random(n)) / n)


def _class_counts(classes: Dict[str, dict], n: int) -> List[str]:
    """Exactly proportional class labels (largest remainder)."""
    names = sorted(classes)
    w = np.asarray([float(classes[c]["weight"]) for c in names])
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [c for c, k in zip(names, counts) for _ in range(k)]


def shapes(traffic: dict, phase: str, n: int) -> List[dict]:
    """The fixed request shapes of one phase: a function of the mix alone."""
    rng = np.random.default_rng([int(traffic["shape_seed"]), PHASES.index(phase)])
    labels = rng.permutation(_class_counts(traffic["classes"], n))
    qlo, qhi = traffic["query_len"]
    klo, khi = traffic["k_docs"]
    mn = traffic["max_new"]
    inv = NormalDist().inv_cdf
    answer = [int(min(max(round(mn["median"] * math.exp(mn["sigma"] * inv(u))),
                          mn["min"]), mn["max"]))
              for u in _stratified(n, rng)]
    qlen = (qlo + np.floor(_stratified(n, rng) * (qhi - qlo + 1))).astype(int)
    kd = (klo + np.floor(_stratified(n, rng) * (khi - klo + 1))).astype(int)
    cx = _stratified(n, rng)
    seeds = rng.integers(0, 2**31 - 1, n)
    return [dict(slo_class=str(labels[i]), query_len=int(qlen[i]),
                 max_new=answer[i], k_docs=int(kd[i]), complexity=float(cx[i]),
                 path_seed=int(seeds[i])) for i in range(n)]


def schedule(traffic: dict, seed: int, window_s: float, arrival) -> List[Arrival]:
    """Every arrival of a run: warm-up, window and tail, due-time ordered.
    ``arrival`` is the mix's arrival process (``spec.load_arrival``)."""
    spans = {"warmup": (-float(traffic["warmup_s"]), float(traffic["warmup_s"])),
             "window": (0.0, float(window_s)),
             "tail": (float(window_s), float(traffic["tail_s"]))}
    order_rng = np.random.default_rng([int(traffic["shape_seed"]), 11])
    tok_rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 11])
    out: List[Arrival] = []
    for phase in PHASES:
        start, length = spans[phase]
        if length <= 0:
            continue
        due = np.asarray(arrival.times(traffic, start, length, order_rng), float)
        n = len(due)
        if n == 0:
            continue
        if not ((due >= start) & (due < start + length)).all():
            raise ValueError(f"arrival process {traffic['arrival']!r} put a due "
                             f"time outside [{start}, {start + length})")
        order = order_rng.permutation(n)
        tok = tok_rng.integers(0, 2**31 - 1, n)
        for i, s in enumerate(shapes(traffic, phase, n)[j] for j in order):
            cls = traffic["classes"][s["slo_class"]]
            out.append(Arrival(due=float(due[i]), phase=phase, index=0,
                               deadline_s=float(cls["deadline_s"]),
                               token_seed=int(tok[i]), **s))
    out.sort(key=lambda a: a.due)
    return [Arrival(**{**{f.name: getattr(a, f.name) for f in fields(a)},
                       "index": i}) for i, a in enumerate(out)]


def trace_bytes(arrivals: List[Arrival]) -> bytes:
    """Canonical serialization: byte equality is schedule equality."""
    return "".join(
        f"{a.due:.9f}\t{a.phase}\t{a.index}\t{a.slo_class}\t{a.deadline_s:.9f}\t"
        f"{a.query_len}\t{a.max_new}\t{a.k_docs}\t{a.complexity:.9f}\t"
        f"{a.path_seed}\t{a.token_seed}\n" for a in arrivals).encode()


def run_rng(seed: int, *stream: int):
    """A generator drawn from the run's ``--seed`` (any whole number up to
    64 bits) and a fixed stream id."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, *stream])


def query_tokens(arrival: Arrival, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(arrival.token_seed)
    return rng.integers(0, vocab, arrival.query_len).astype(np.int32)
