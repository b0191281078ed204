"""Arrivals as RAG pipelines (``apps.EnginePipeline``) over a skewed corpus.

Each arrival becomes one pipeline of its class (``vrag``, ``crag``,
``srag``, ``planrag``: the program's apps), which submits its stages to the
engine at temperature 0 with EDF-slack priorities from one shared slack
model, and draws its own path from the arrival's fixed ``path_seed``.

Documents. The corpus holds ``universe`` documents whose lengths are drawn
once per document id, uniformly from ``doc_len`` (inclusive, any whole
number: no alignment to the cache's blocks), and whose tokens come from the
run's seed. Popularity is Zipf: document ``d`` (0 the most popular) is
retrieved with weight ``1 / (d + 1) ** zipf_s``. Each pipeline has its own
``candidates`` documents, drawn without replacement by popularity and put
in a random order (its retrieval ranking), all fixed by the arrival's
shape; every retrieval stage of the pipeline takes ``k_docs`` of them (the
program's retriever picks which), so a plan's sub-queries see overlapping
documents. A web search (crag after a failed grade) returns documents of
its own that no other pipeline retrieves. ``hot_docs`` documents, the most
popular, are computed at set-up, one request each, as a deployment that has
served its corpus for a while holds them; the rest enter the cache only
when traffic asks for them, and the pool (smaller than the corpus) evicts.

    doc_len       [lo, hi] tokens per document, inclusive
    universe      documents in the corpus
    zipf_s        popularity exponent
    candidates    documents a pipeline's retrieval ranks
    hot_docs      most popular documents computed at set-up
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.harness import traffic as tr

KEYS = ("doc_len", "universe", "zipf_s", "candidates", "hot_docs")


class Corpus:
    """Documents by id: lengths fixed by the mix, tokens by the run's seed.
    Ids at and above ``universe`` are web results, outside the corpus."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic, self.seed, self.vocab = traffic, seed, vocab
        self.universe = int(traffic["universe"])
        w = 1.0 / np.arange(1, self.universe + 1) ** float(traffic["zipf_s"])
        self.popularity = w / w.sum()
        lo, hi = traffic["doc_len"]
        self.mean_len = (lo + hi) // 2
        self._docs: Dict[int, np.ndarray] = {}

    def length(self, doc_id: int) -> int:
        lo, hi = self.traffic["doc_len"]
        rng = np.random.default_rng([int(self.traffic["shape_seed"]), 101, int(doc_id)])
        return int(rng.integers(lo, hi + 1))

    def tokens(self, doc_id: int) -> np.ndarray:
        d = int(doc_id)
        if d not in self._docs:
            rng = tr.run_rng(self.seed, 7, d)
            self._docs[d] = rng.integers(0, self.vocab, self.length(d)).astype(np.int32)
        return self._docs[d]

    def candidates(self, arrival: tr.Arrival) -> List[int]:
        """The pipeline's ranked documents: a function of its shape alone."""
        rng = np.random.default_rng([int(self.traffic["shape_seed"]), 103,
                                     arrival.path_seed])
        n = int(self.traffic["candidates"])
        ids = rng.choice(self.universe, size=n, replace=False, p=self.popularity)
        return [int(d) for d in rng.permutation(ids)]

    def web(self, arrival: tr.Arrival, j: int) -> int:
        return self.universe + 64 * arrival.index + int(j)


class PipelineDocs:
    """The corpus as one pipeline's retriever sees it: its slot ``j`` is its
    ``j``-th candidate, and the program's web-search ids are web results of
    its own."""

    def __init__(self, corpus: Corpus, arrival: tr.Arrival, web_offset: int):
        self.corpus, self.arrival, self.web_offset = corpus, arrival, web_offset
        self.ranked = corpus.candidates(arrival)
        self.doc_len = corpus.mean_len       # the pipelines' cost features only

    def resolve(self, slot: int) -> int:
        if slot >= self.web_offset:
            return self.corpus.web(self.arrival, slot - self.web_offset)
        return self.ranked[slot]

    def tokens_for(self, slots) -> List[np.ndarray]:
        return [self.corpus.tokens(self.resolve(int(s))) for s in slots]


class StageLog:
    """The engine as one pipeline sees it: submits pass through, and each
    request is kept with the prompt exactly as it was submitted."""

    def __init__(self, engine):
        self.engine = engine
        self.requests: List = []
        self.prompts: List = []

    def submit(self, prompt, **kw):
        req = self.engine.submit(prompt, **kw)
        self.requests.append(req)
        self.prompts.append(prompt)
        return req


class Source:
    """Turns the mix's arrivals into pipelines on ``engine``."""

    def __init__(self, engine, traffic: dict, seed: int, vocab: int):
        from repro.apps import make_app
        from repro.core.slack import SlackModel

        self.engine, self.traffic, self.vocab = engine, traffic, vocab
        self.corpus = Corpus(traffic, seed, vocab)
        self.apps = {c: make_app(c, engine=engine) for c in sorted(traffic["classes"])}
        self.slack = SlackModel()

    def prewarm(self) -> Dict[str, float]:
        """Compute the ``hot_docs`` most popular documents once: one request
        each (the document, then a one-token tail), one token out, served to
        completion."""
        from repro.serving.segments import KIND_DOC, KIND_TAIL, Segment, SegmentedPrompt

        n, hot = 0, int(self.traffic["hot_docs"])
        for d in range(hot):
            toks = self.corpus.tokens(d)
            self.engine.submit(SegmentedPrompt([Segment(toks, KIND_DOC, doc_id=d),
                                                Segment(np.zeros(1, np.int32), KIND_TAIL)]),
                               max_new=1)
            n += len(toks) + 1
        if hot:
            self.engine.run_until_done()
        return {"hot_docs": hot, "hot_tokens": n,
                "hot_share": float(self.corpus.popularity[:hot].sum())}

    def start(self, a: tr.Arrival, due_abs: float):
        """One arrival as an ``EnginePipeline``: its class's app, its own
        draws fixed by the arrival's shape, its deadline from the due time,
        and its ranked documents."""
        from repro.apps import EnginePipeline

        log = StageLog(self.engine)
        docs = PipelineDocs(self.corpus, a, EnginePipeline.web_offset)
        p = EnginePipeline(
            self.apps[a.slo_class], log, query_tokens=tr.query_tokens(a, self.vocab),
            rng=np.random.default_rng(a.path_seed), complexity=a.complexity,
            k_docs=a.k_docs, max_new=a.max_new, deadline=due_abs + a.deadline_s,
            slack=self.slack, doc_store=docs)
        p.n_docs = len(docs.ranked)
        return p, log
