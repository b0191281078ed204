"""The open loop times every request from when it was due, so a stall of
the host shows in the latency of what fell due during it."""
import numpy as np

from bench.harness import e2e, runner, spec
from bench.harness.loop import OpenLoop
from bench.harness.traffic import Arrival


class Clock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, d):
        self.t += max(d, 1e-4)


class Req:
    def __init__(self, t):
        self.submitted_at, self.first_token_at, self.finished_at = t, None, None
        self.last = None
        self.token_gaps, self.out_tokens = [], []
        self.done, self.truncated, self.max_new = False, False, 3


class Engine:
    """Each step takes ``step_s`` of host time and emits one token to every
    request it holds; a request is done after three tokens."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s, self.live = clock, step_s, []

    def submit(self, prompt, **kw):
        r = Req(self.clock.t)
        self.live.append(r)
        return r

    def step(self):
        self.clock.t += self.step_s
        for r in self.live:
            t = self.clock.t
            if r.first_token_at is None:
                r.first_token_at = t
            else:
                r.token_gaps.append(t - r.last)
            r.last = t
            r.out_tokens.append(1)
            if len(r.out_tokens) == 3:
                r.done, r.finished_at = True, t
        self.live = [r for r in self.live if not r.done]


class Pipeline:
    def __init__(self, log):
        self.log, self.req, self.finished_at = log, None, None

    def poll(self, now):
        if self.req is None:
            self.req = self.log.submit(np.arange(4))
        if self.req.done:
            self.finished_at = now
            return True
        return False


class Log:
    def __init__(self, engine):
        self.engine, self.requests, self.prompts = engine, [], []

    def submit(self, prompt, **kw):
        r = self.engine.submit(prompt, **kw)
        self.requests.append(r)
        self.prompts.append(prompt)
        return r


def arrival(due, i):
    return Arrival(due=due, phase="window", index=i, slo_class="vrag",
                   deadline_s=10.0, query_len=4, max_new=3, k_docs=1,
                   complexity=0.5, path_seed=i, token_seed=i)


def run(step_s):
    clock = Clock()
    eng = Engine(clock, step_s)
    t0 = clock.t

    def start(a, due):
        log = Log(eng)
        return Pipeline(log), log

    loop = OpenLoop([arrival(0.0, 0), arrival(0.05, 1)], t_zero=t0,
                    window_s=1.0, grace_s=10.0, start=start,
                    busy=lambda: bool(eng.live), step=eng.step, read_counters=dict,
                    clock=clock.now, sleep=clock.sleep)
    res = loop.run()
    return runner.plain_records(res, spec.load_adapter("generation_engine")), res


def test_request_due_during_a_stall_is_timed_from_its_due_time():
    recs, res = run(step_s=0.5)
    late = recs[1]
    assert late["released_at"] - late["due"] >= 0.45       # released after the stall
    req = late["requests"][0]
    (row,) = e2e.stage_rows(late, res.ended_at)
    assert row["ttft"] == req["first_token_at"] - late["due"]
    assert row["ttft"] >= req["first_token_at"] - req["submitted_at"] + 0.45
    out = e2e.compute(recs, res.t_zero, res.window_s, res.ended_at)
    assert out["counts"]["release_late_max_ms"] >= 450


def test_no_stall_no_lateness():
    recs, res = run(step_s=0.001)
    out = e2e.compute(recs, res.t_zero, res.window_s, res.ended_at)
    assert out["counts"]["release_late_max_ms"] < 2
    assert out["counts"]["pipelines_finished"] == 2
    assert out["metrics"]["slo_attain_pct"] == out["counts"]["slo_attain_pct"] == 100.0


def test_later_stage_is_due_when_the_stage_before_it_finished():
    rec = {"due": 1.0, "requests": [
        {"first_token_at": 1.5, "finished_at": 2.0, "submitted_at": 1.0, "token_gaps": []},
        {"first_token_at": 2.7, "finished_at": 3.0, "submitted_at": 2.2, "token_gaps": []}]}
    rows = e2e.stage_rows(rec, ended_at=9.0)
    assert [r["due"] for r in rows] == [1.0, 2.0]
    assert np.allclose([r["ttft"] for r in rows], [0.5, 0.7])


def test_an_unfinished_pipeline_is_named():
    rec = {"phase": "window", "index": 7, "slo_class": "planrag", "due": 12.0,
           "deadline_s": 5.0, "max_new": 96, "released_at": 12.0, "finished_at": None,
           "requests": [
               {"first_token_at": 12.5, "finished_at": 13.0, "submitted_at": 12.0,
                "token_gaps": [0.1], "out_tokens": [1, 2], "done": True,
                "truncated": False, "max_new": 6},
               {"first_token_at": 13.5, "finished_at": None, "submitted_at": 13.0,
                "token_gaps": [0.1] * 9, "out_tokens": [3] * 10, "done": False,
                "truncated": False, "max_new": 96}]}
    out = e2e.compute([rec], t_zero=10.0, window_s=5.0, ended_at=20.0)
    assert out["counts"]["unfinished"] == [{
        "index": 7, "class": "planrag", "due_s": 2.0, "max_new": 96, "requests": 2,
        "requests_done": 1, "tokens_out": 12, "budgets": [6, 96]}]
    assert out["metrics"]["slo_attain_pct"] == 0.0


def test_a_request_that_never_produced_counts_its_whole_wait():
    rec = {"due": 1.0, "requests": [
        {"first_token_at": None, "finished_at": None, "submitted_at": 1.0, "token_gaps": []}]}
    (row,) = e2e.stage_rows(rec, ended_at=61.0)
    assert row["ttft"] == 60.0 and not row["produced"]
