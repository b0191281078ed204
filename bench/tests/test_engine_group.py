"""What decides ``correct`` for the two-replica cell, driven through whole
runs at toy size on four virtual CPU devices (``group_cases.py``, in a
process of its own): a sound run passes with a document shared between the
replicas through the host tier, a request that read it among those compared,
and nothing compiled in the window; the
float8 control fails the limit; and each fault that this cell can have,
planted under the timed path, turns ``correct`` false: a step that returns
its state unchanged, a token altered where it is produced, the model axis's
reduction left out, and blocks from the other replica altered. The sample
is the cell's in kind (``min_tokens`` and ``max_requests`` give about three
requests, as the cell's 256 and 6 do), so the last fault is caught only
because a request that took the other replica's blocks is always compared."""
import json
import os
import subprocess
import sys

import pytest

from .conftest import ROOT

FAULTS = ["state_unchanged", "token_altered", "model_exchange_left_out",
          "replica_exchange_altered"]


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.group_cases", "sound", *FAULTS],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-4000:]
    out = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    return {r["case"]: r for r in out}


def test_sound_run_is_correct_and_the_control_is_not(cases):
    r = cases["sound"]
    limit = r["checks"]["gap_max"]["limit"]
    assert r["correct"] is True and r["checks"]["gap_max"]["value"] <= limit
    assert r["counts"]["control_fp8_gap_max"] > limit
    assert r["counts"]["pipelines_finished"] == r["counts"]["pipelines"]
    assert r["counts"]["cross_replica_host_hits_at_end"] > 0
    assert r["counts"]["check_first_compared"] == 1
    assert r["counts"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(cases, fault):
    r = cases[fault]
    assert r["correct"] is False
    assert r["checks"]["gap_max"]["value"] > r["checks"]["gap_max"]["limit"]
