"""Device runtime: executes ``StepPlan``s as jitted steps, double-buffered.

The runner is the device half of the control-plane split. Its contract:

* **Same programs, same numerics.** It runs the engine's OWN compiled step
  programs (``_fused_step_jit`` / ``_decode_paged_jit``) unchanged, so the
  logits — and therefore greedy tokens — are bit-identical to the
  sequential oracle. Around them sit two tiny extra jits: a prev-token
  substitution (decode rows feed the previous plan's sampled token straight
  from device memory, no host roundtrip) and the sampler.

* **Deferred materialization.** ``dispatch`` only ENQUEUES work: with
  JAX's async dispatch the call returns as soon as the computation is
  queued, holding the sampled-token array as a device future. The engine
  materializes (``np.asarray``) one plan behind, so plan N+1 is built on
  the host while step N runs on the device.

* **Host-gap accounting.** The wall time the device sat idle between the
  completion of one step and the dispatch of the next is the quantity the
  whole refactor exists to shrink; the runner estimates it (ready-probe at
  build start + blocking materializes). The probe misses idle time it does
  not observe; a profiler trace, with the runner's ``engine.dispatch.*``
  and ``engine.materialize.wait`` spans beside the device ops, is the
  authority.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.control_plane import StepPlan
from repro.serving.sampler import sample_tokens


def _substitute(tokens, prev, prev_slots):
    """Replace column 0 of rows with ``prev_slots[b] >= 0`` by the previous
    plan's device-resident sampled token for that row."""
    idx = jnp.maximum(prev_slots, 0)
    col0 = jnp.where(prev_slots >= 0, prev[idx], tokens[:, 0])
    return tokens.at[:, 0].set(col0)


def _substitute_packed(tokens, prev, prev_slots, decode_idx):
    """Ragged-layout substitution: a decode row's single token lives at flat
    index ``decode_idx[b]``; rows with ``prev_slots[b] >= 0`` take the
    previous plan's device-resident sampled token. Non-substituting rows
    scatter out of range and are dropped."""
    T = tokens.shape[0]
    idx = jnp.where(prev_slots >= 0, decode_idx, T)
    vals = prev[jnp.maximum(prev_slots, 0)]
    return tokens.at[idx].set(vals, mode="drop")


def _is_ready(arr) -> bool:
    """True when a device array's computation has finished (best effort:
    backends without ``is_ready`` report ready, degrading the gap metric to
    the blocking-materialize measurements, never the correctness path)."""
    try:
        return bool(arr.is_ready())
    except AttributeError:
        return True


# the plan arrays each kind of step uploads
_INPUTS = {
    "ragged": ("tokens", "prev_slots", "decode_idx", "tables", "row_of", "slots",
               "positions", "p_end", "s_start", "last_idx", "temps"),
    "fused": ("tokens", "prev_slots", "tables", "starts", "n_valid", "positions",
              "p_end", "s_start", "temps"),
    "decode": ("tokens", "prev_slots", "tables", "starts", "temps"),
}


class PlanExec:
    """A dispatched plan: the device future of its sampled tokens."""

    __slots__ = ("plan", "tokens", "dispatched_at", "ready_at", "_host")

    def __init__(self, plan: StepPlan, tokens, dispatched_at: float):
        self.plan = plan
        self.tokens = tokens          # (B,) device array, possibly in flight
        self.dispatched_at = dispatched_at
        self.ready_at: Optional[float] = None
        self._host: Optional[np.ndarray] = None


class DeviceRunner:
    def __init__(self, engine):
        self.eng = engine
        self.last_plan_id = -1
        self._last: Optional[PlanExec] = None         # prev-token source
        self._outstanding: Optional[PlanExec] = None  # newest unmaterialized
        self._idle_mark: Optional[float] = None       # when idleness observed
        self.host_gap_s = 0.0
        self.n_gaps = 0          # dispatches that followed an earlier one
        self.n_dispatched = 0
        # online per-valid-token step time (EMA over materialized plans);
        # the cost-model preemption's recompute estimate consumes it
        self.token_time_ema: Optional[float] = None
        self._subst_jit = jax.jit(_substitute)
        self._subst_packed_jit = jax.jit(_substitute_packed)
        self._sample_jit = jax.jit(sample_tokens)

    # --------------------------------------------------------------- probes
    def probe_idle(self) -> None:
        """Called at plan-build start: if the outstanding step already
        finished, the device is idle from NOW until the next dispatch."""
        if (self._outstanding is not None and self._idle_mark is None
                and _is_ready(self._outstanding.tokens)):
            self._idle_mark = time.perf_counter()

    # ------------------------------------------------------------- dispatch
    def dispatch(self, plan: StepPlan) -> PlanExec:
        eng = self.eng
        span = eng.telemetry.span
        with span("engine.dispatch"):
            now = time.perf_counter()
            self._account_gap(now)
            with span("engine.dispatch.inputs"):
                # the plan's host arrays become device arrays, by kind
                dev = {f: jnp.asarray(getattr(plan, f))
                       for f in _INPUTS[plan.kind]}
            with span("engine.dispatch.launch"):
                eng._key, sk = jax.random.split(eng._key)
                toks = self._launch(plan.kind, dev, sk)
            ex = PlanExec(plan, toks, now)
            self._last = ex
            self._outstanding = ex
            self.last_plan_id = plan.plan_id
            self.n_dispatched += 1
            return ex

    def _account_gap(self, now: float) -> None:
        if self._outstanding is not None and self._idle_mark is None:
            # late probe: the step may have finished mid-build; counting the
            # gap from now underestimates, never inflates, the idle time
            if _is_ready(self._outstanding.tokens):
                self._idle_mark = now
        if self._idle_mark is not None:
            self.host_gap_s += max(now - self._idle_mark, 0.0)
            self.n_gaps += 1
        elif self._outstanding is not None:
            self.n_gaps += 1  # device still busy: zero gap
        self._idle_mark = None

    def _launch(self, kind: str, dev: dict, sk):
        """The prev-token substitution, the step program and the sampler
        (with key ``sk``): returns the sampled tokens' device future."""
        eng = self.eng
        prev = (self._last.tokens if self._last is not None
                else jnp.zeros((eng.max_batch,), jnp.int32))
        state = (eng.params, eng.kv.k, eng.kv.v, eng.kv.k_scale, eng.kv.v_scale,
                 dev["tables"])
        if kind == "ragged":
            toks_in = self._subst_packed_jit(dev["tokens"], prev,
                                             dev["prev_slots"], dev["decode_idx"])
            logits, *out = eng._ragged_step_jit(
                *state, toks_in, dev["row_of"], dev["slots"], dev["positions"],
                dev["p_end"], dev["s_start"], dev["last_idx"])
        elif kind == "fused":
            toks_in = self._subst_jit(dev["tokens"], prev, dev["prev_slots"])
            logits, *out = eng._fused_step_jit(
                *state, toks_in, dev["starts"], dev["n_valid"],
                dev["positions"], dev["p_end"], dev["s_start"])
        else:
            toks_in = self._subst_jit(dev["tokens"], prev, dev["prev_slots"])
            logits, *out = eng._decode_dispatch_jit(*state, toks_in,
                                                    dev["starts"])
        eng._set_pools(*out)
        return self._sample_jit(sk, logits, dev["temps"])

    # ---------------------------------------------------------- materialize
    def materialize(self, ex: PlanExec) -> np.ndarray:
        """Block until ``ex``'s sampled tokens are on the host (idempotent).
        When ``ex`` is the newest dispatched work, the device is idle from
        here until the next dispatch — start the gap clock."""
        if ex._host is None:
            with self.eng.telemetry.span("engine.materialize.wait"):
                ex._host = np.asarray(ex.tokens)
            t = time.perf_counter()
            ex.ready_at = t
            if self._outstanding is ex:
                self._outstanding = None
                self._idle_mark = t
            if ex.plan.n_tokens > 0:
                per = max(t - ex.dispatched_at, 1e-9) / ex.plan.n_tokens
                self.token_time_ema = (
                    per if self.token_time_ema is None
                    else 0.8 * self.token_time_ema + 0.2 * per
                )
        return ex._host

    # ---------------------------------------------------------------- stats
    def summary(self) -> dict:
        return {
            "host_gap_s": self.host_gap_s,
            "host_gap_mean_s": self.host_gap_s / self.n_gaps if self.n_gaps else 0.0,
            "dispatches": self.n_dispatched,
        }
