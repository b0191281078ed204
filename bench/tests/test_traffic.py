"""The traffic generator: seeded, byte-identical, and the same work for
every seed; the corpus: lengths fixed by the mix, skewed popularity."""
import json
from collections import Counter

import numpy as np

from bench.harness import spec
from bench.harness import traffic as tr

from .conftest import FIXTURES, ROOT


def mixes():
    out = [json.loads((FIXTURES / "tiny_traffic.json").read_text())]
    out += [json.loads(p.read_text()) for p in sorted((ROOT / "bench" / "traffic").glob("*.json"))]
    return out


def schedule(mix, seed, seconds):
    return tr.schedule(mix, seed, seconds, spec.load_arrival(mix["arrival"]))


def corpus(mix, seed=1, vocab=1000):
    return spec.load_requests(mix["requests"]).Corpus(mix, seed, vocab)


def test_same_seed_same_bytes():
    for mix in mixes():
        a = tr.trace_bytes(schedule(mix, 2**31 + 5, 40.0))
        b = tr.trace_bytes(schedule(mix, 2**31 + 5, 40.0))
        assert a == b
        assert a != tr.trace_bytes(schedule(mix, 2**31 + 6, 40.0))


def test_every_seed_offers_the_same_work_in_the_same_order():
    for mix in mixes():
        def work(seed):
            return [(a.due, a.phase, a.slo_class, a.query_len, a.max_new, a.k_docs,
                     a.complexity, a.path_seed) for a in schedule(mix, seed, 40.0)]
        assert work(1) == work(987654321012)
        a, b = schedule(mix, 1, 40.0), schedule(mix, 987654321012, 40.0)
        assert [x.token_seed for x in a] != [x.token_seed for x in b]
        docs = [corpus(mix).candidates(x) for x in a]
        assert docs == [corpus(mix, seed=987654321012).candidates(x) for x in b]


def test_window_holds_rate_times_seconds_and_exact_class_shares():
    mix = json.loads((FIXTURES / "tiny_traffic.json").read_text())
    arr = schedule(mix, 3, 40.0)
    win = [a for a in arr if a.phase == "window"]
    assert len(win) == round(mix["rate_per_s"] * 40.0)
    assert all(0.0 <= a.due < 40.0 for a in win)
    assert all(a.due < 0 for a in arr if a.phase == "warmup")
    assert [a.index for a in arr] == list(range(len(arr)))
    assert all(x.due <= y.due for x, y in zip(arr, arr[1:]))
    counts = Counter(a.slo_class for a in win)
    w = {c: v["weight"] for c, v in mix["classes"].items()}
    for c, n in counts.items():
        assert abs(n - len(win) * w[c] / sum(w.values())) < 1


def test_lengths_within_bounds_and_doc_lengths_fixed_by_the_mix():
    for mix in mixes():
        arr = schedule(mix, 11, 30.0)
        lo, hi = mix["max_new"]["min"], mix["max_new"]["max"]
        assert all(lo <= a.max_new <= hi for a in arr)
        assert all(mix["query_len"][0] <= a.query_len <= mix["query_len"][1] for a in arr)
        assert all(mix["k_docs"][0] <= a.k_docs <= mix["k_docs"][1] for a in arr)
        c1, c2 = corpus(mix, seed=1), corpus(mix, seed=2)
        lens = [c1.length(d) for d in range(200)]
        assert all(mix["doc_len"][0] <= n <= mix["doc_len"][1] for n in lens)
        for d in range(20):
            assert len(c1.tokens(d)) == lens[d] == len(c2.tokens(d))
            assert not np.array_equal(c1.tokens(d), c2.tokens(d))


def test_documents_are_not_block_aligned():
    """Free-form lengths: most documents end inside a block of any size the
    engine may use."""
    mix = mixes()[-1]
    lens = np.asarray([corpus(mix).length(d) for d in range(400)])
    for block in (16, 64, 128):
        assert (lens % block != 0).mean() > 0.8


def test_popularity_is_skewed_and_candidates_distinct():
    mix = mixes()[-1]
    c = corpus(mix)
    arr = schedule(mix, 1, 51.0)
    picks = Counter()
    for a in arr:
        cand = c.candidates(a)
        assert len(cand) == len(set(cand)) == mix["candidates"]
        assert all(0 <= d < mix["universe"] for d in cand)
        picks.update(cand)
    hot = int(mix["hot_docs"])
    in_hot = sum(n for d, n in picks.items() if d < hot) / sum(picks.values())
    # the hot head draws far more than its share of the corpus, but not all
    assert hot / mix["universe"] * 5 < in_hot < 0.9
    assert len(picks) > hot


def test_a_pipeline_sees_its_own_ranking_and_web_results_of_its_own():
    mix = mixes()[-1]
    rq = spec.load_requests(mix["requests"])
    c = corpus(mix)
    a, b = [x for x in schedule(mix, 1, 51.0) if x.phase == "window"][:2]
    da, db = rq.PipelineDocs(c, a, 10_000), rq.PipelineDocs(c, b, 10_000)
    assert [da.resolve(j) for j in range(mix["candidates"])] == c.candidates(a)
    web = [da.resolve(10_000 + j) for j in range(3)] + [db.resolve(10_000 + j) for j in range(3)]
    assert len(set(web)) == 6 and min(web) >= mix["universe"]
    assert [len(t) for t in da.tokens_for([0, 10_000])] == [c.length(c.candidates(a)[0]),
                                                            c.length(web[0])]
