"""What decides ``correct``, driven through a whole run at toy size on the
CPU: a sound run passes, the control (the reference one precision step
below bfloat16) fails the same limit, and each fault a serving cell can
have, planted under the timed path, turns ``correct`` false.

The toy limit (fixtures/tiny_config.json, 0.04) sits between the readings
of five seeds on this CPU: the program's widest gap at most 0.0101, the
float8 control's at least 0.090 (the int8 control's at least 0.017, a
weaker separation at this size; on the chip both are read)."""
import time

import pytest

from bench.harness import check, runner, spec

SEED = 2**31 + 9


def run(cell, tmp_path, **kw):
    return runner.run_cell(cell, SEED, 3.0, False, time.monotonic(), str(tmp_path),
                           log=lambda _m: None, **kw)


def test_sound_run_is_correct_and_the_control_is_not(tiny_cell, tmp_path):
    r = run(tiny_cell, tmp_path, controls=("int8", "fp8"))
    limit = r["checks"]["gap_max"]["limit"]
    assert r["correct"] is True
    assert r["checks"]["gap_max"]["value"] <= limit
    assert r["checks"]["tokens_compared"]["value"] >= tiny_cell.config["check"]["min_tokens"] * 0.5
    assert r["counts"]["control_fp8_gap_max"] > limit
    assert r["counts"]["control_int8_gap_max"] > r["checks"]["gap_max"]["value"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in tiny_cell.end_to_end}


def _planted(monkeypatch, cell, fault):
    adapter = spec.load_adapter(cell.config["adapter"], cell.root)
    build = adapter.build

    def faulty(config, weights, dims):
        e = build(config, weights, dims)
        fault(e)
        return e

    monkeypatch.setattr(adapter, "build", faulty)


def _state_unchanged(e):
    """Every step program hands back the pools it was given: no K/V lands."""
    for name in ("_ragged_step_jit", "_decode_dispatch_jit"):
        fn = getattr(e, name)

        def same_state(params, k, v, ks, vs, *rest, fn=fn):
            return (fn(params, k, v, ks, vs, *rest)[0], k, v, ks, vs)

        setattr(e, name, same_state)


def _token_altered(e):
    """The sampler's tokens are changed where they are produced."""
    sample = e.runner._sample_jit
    vocab = e.cfg.vocab_size
    e.runner._sample_jit = lambda *a: (sample(*a) + 1) % vocab


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_planted_fault_is_not_correct(tiny_cell, tmp_path, monkeypatch, fault):
    _planted(monkeypatch, tiny_cell, fault)
    r = run(tiny_cell, tmp_path)
    assert r["correct"] is False
    assert r["checks"]["gap_max"]["value"] > r["checks"]["gap_max"]["limit"]


def _records(lengths, marked=()):
    reqs = [{"out_tokens": [1] * n, "done": True, "truncated": False,
             "check_first": i in marked, "id": i} for i, n in enumerate(lengths)]
    return [{"phase": "window", "requests": reqs}]


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**33 + 1])
def test_sample_takes_the_longest_then_the_seeds_order(seed):
    recs = _records([5, 40, 7, 9, 11, 3])
    got = [r["id"] for r in check.sample(recs, seed, 60, 6)]
    again = [r["id"] for r in check.sample(recs, seed, 60, 6)]
    assert got[0] == 1 and got == again
    assert sum(len(r["out_tokens"]) for r in check.sample(recs, seed, 60, 6)) >= 60


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**33 + 1])
def test_sample_always_holds_a_marked_request(seed):
    """A request marked ``check_first`` is compared even where the longest
    alone fills the sample's tokens or its count."""
    recs = _records([5, 40, 7, 9, 11, 3], marked=(4, 5))
    for min_tokens, max_requests in ((30, 6), (400, 2), (400, 6)):
        ids = [r["id"] for r in check.sample(recs, seed, min_tokens, max_requests)]
        assert ids[0] == 1 and ids[1] in (4, 5) and len(set(ids)) == len(ids)
    unmarked = _records([5, 40, 7, 9, 11, 3])
    assert [r["id"] for r in check.sample(unmarked, seed, 30, 6)] == [1]
