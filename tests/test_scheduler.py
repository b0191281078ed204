"""core.scheduler coverage: EDF-slack queue ordering (least-slack-first,
arrival-order tie-breaks), the engine's admission + prefill-budget hooks
honoring the policy ordering, and the eviction-aware ``resident_first``
policy (residency-probe binding + engine admission preference)."""
import numpy as np

from repro.configs import get_arch, smoke_variant
from repro.core.scheduler import EDFSlack, QueuePolicy, ResidentFirst, make_policy
from repro.core.simcluster import Task
from repro.serving.engine import GenerationEngine


def _task(priority, enqueued_at):
    return Task(req=None, comp_name="gen", features={}, enqueued_at=enqueued_at,
                priority=priority)


def _cfg():
    return smoke_variant(get_arch("smollm-135m"))


# ------------------------------------------------------------- policy unit


def test_edf_slack_pops_least_slack_first():
    q = [_task(3.0, 0.0), _task(0.2, 1.0), _task(1.5, 2.0)]
    pol = EDFSlack()
    assert [pol.pop(q).priority for _ in range(3)] == [0.2, 1.5, 3.0]
    assert pol.pop(q) is None


def test_edf_slack_breaks_ties_by_arrival():
    q = [_task(1.0, 5.0), _task(1.0, 1.0), _task(1.0, 3.0)]
    pol = EDFSlack()
    assert [pol.pop(q).enqueued_at for _ in range(3)] == [1.0, 3.0, 5.0]


def test_fifo_pops_in_arrival_order():
    q = [_task(3.0, 0.0), _task(0.1, 1.0)]
    pol = QueuePolicy()
    assert pol.pop(q).enqueued_at == 0.0  # ignores priority entirely
    assert pol.pop(q).enqueued_at == 1.0


def test_order_is_non_destructive():
    q = [_task(3.0, 0.0), _task(0.2, 1.0)]
    ordered = EDFSlack().order(q)
    assert [t.priority for t in ordered] == [0.2, 3.0]
    assert len(q) == 2  # original queue untouched


def test_make_policy_resolves_names_and_instances():
    assert make_policy("edf_slack").name == "edf_slack"
    assert make_policy("fifo").name == "fifo"
    assert make_policy("resident_first").name == "resident_first"
    pol = EDFSlack()
    assert make_policy(pol) is pol  # engine accepts a policy object directly


def test_resident_first_orders_by_residency_then_slack():
    """Most-resident first; among equal residency, least slack; without a
    bound probe the policy degrades to plain EDF-slack."""
    a, b, c = _task(3.0, 0.0), _task(0.2, 1.0), _task(1.5, 2.0)
    pol = ResidentFirst()
    # no probe bound: residency is 0 for everyone -> EDF order
    assert [t.priority for t in pol.order([a, b, c])] == [0.2, 1.5, 3.0]
    pol.bind_residency(lambda t: {3.0: 0.9, 0.2: 0.0, 1.5: 0.9}[t.priority])
    # a and c are resident (ties broken by slack: c first), b is cold
    assert [t.priority for t in pol.order([a, b, c])] == [1.5, 3.0, 0.2]


def test_engine_never_mutates_caller_policy_object():
    """Binding the residency probe must happen on a per-engine copy: a
    caller-supplied policy instance stays unbound and reusable (e.g. for a
    simcluster dispatch queue, whose Tasks the engine probe can't score)."""
    pol = ResidentFirst()
    eng = GenerationEngine(_cfg(), max_batch=1, max_seq=64, scheduler=pol)
    assert eng.scheduler is not pol
    assert pol._residency_fn is None          # caller's object untouched
    assert eng.scheduler._residency_fn is not None


def test_resident_first_engine_prefers_warm_prompt():
    """With the only slot occupied, a queued request whose context blocks are
    warm in the cache must be admitted before an earlier-queued cold one —
    admitting it costs almost no fresh blocks and zero prefill."""
    eng = GenerationEngine(_cfg(), max_batch=1, max_seq=128,
                           scheduler="resident_first")
    ctx = np.arange(64) % 90
    warm = eng.submit(np.concatenate([ctx, [5]]), max_new=2)
    eng.run_until_done()  # ctx blocks published, released to the warm LRU
    assert warm.done
    filler = eng.submit(np.arange(8) % 90 + 200, max_new=8)
    eng.step()  # filler occupies the only slot
    r_cold = eng.submit(np.arange(32) % 90 + 400, max_new=2)
    r_warm = eng.submit(np.concatenate([ctx, [6]]), max_new=2)
    eng.run_until_done()
    assert filler.done and r_cold.done and r_warm.done
    assert r_warm.first_token_at < r_cold.first_token_at
    assert r_warm.shared_prefix_tokens == 64  # it really was resident


# ------------------------------------------------- engine scheduling hooks


def test_prefill_budget_grants_follow_policy_order():
    """With one chunk of budget per step, the least-slack mid-prefill request
    must receive every grant until it finishes prefilling."""
    eng = GenerationEngine(
        _cfg(), max_batch=2, max_seq=128, prefill_chunk_size=16,
        token_budget=16, scheduler="edf_slack",
    )
    # disjoint first blocks so prefix-deferral never couples the two
    r_lax = eng.submit(np.arange(64) % 40, max_new=2, priority=5.0)
    r_urgent = eng.submit(np.arange(64) % 40 + 41, max_new=2, priority=0.5)
    eng.step()
    assert r_urgent.prefill_pos == 16, "least slack gets the step's budget"
    assert r_lax.prefill_pos == 0, "higher slack waits"
    while r_urgent.first_token_at is None:
        eng.step()
    assert r_lax.first_token_at is None, "urgent request finished prefill first"
    eng.run_until_done()
    assert r_lax.done and r_urgent.done


def test_admission_follows_policy_order():
    """A later-submitted lower-slack request must be admitted before an
    earlier higher-slack one under EDF (and after it under FIFO) — in both
    the paged planned loop and the dense backend's sequential admission."""
    cases = (("edf_slack", "paged", "urgent"), ("fifo", "paged", "lax"),
             ("edf_slack", "dense", "urgent"))
    for scheduler, backend, first in cases:
        eng = GenerationEngine(
            _cfg(), max_batch=1, max_seq=128, scheduler=scheduler,
            backend=backend,
        )
        filler = eng.submit(np.arange(8) % 90, max_new=6, priority=0.0)
        eng.step()  # filler occupies the only slot
        r_lax = eng.submit(np.arange(12) % 90, max_new=2, priority=9.0)
        r_urgent = eng.submit(np.arange(12) % 90 + 30, max_new=2, priority=0.1)
        eng.run_until_done()
        assert filler.done and r_lax.done and r_urgent.done
        winner = r_urgent if first == "urgent" else r_lax
        loser = r_lax if first == "urgent" else r_urgent
        assert winner.first_token_at < loser.first_token_at, scheduler
