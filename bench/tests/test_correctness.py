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

from bench.harness import runner, spec

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
