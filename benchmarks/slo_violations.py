"""Paper Fig. 11: SLO violation rate vs offered load, per pipeline class.

Default mode drives the REAL paged engine: a seeded open-loop trace
(``core.workload``) of mixed RAG pipelines — including multi-turn sessions
and plan-RAG's data-dependent stage counts — replays through
``apps.OpenLoopDriver``; every engine submit's priority is its predicted
slack against the class deadline (EDF-slack admission), and the report is
the per-SLO-class violation rate at each offered load.

SLO methodology (paper sec. 4.1): each class's deadline is ``slo_scale`` (2x)
the class's mean end-to-end latency measured on a calibration trace at low
load, so deadlines encode "how much slower than unloaded is acceptable"
rather than absolute wall-clock guesses. The trace clock is virtual
(one engine step = ``DT`` trace-seconds), making runs deterministic across
hosts: a violation means the request *spanned too many engine steps*, the
machine-independent notion of queueing delay.

``--sim`` runs the legacy discrete-event-simulator comparison (Patchwork vs
monolithic/ray-like baselines) instead.
"""
from __future__ import annotations

from _report import parse_cli, print_table

DT = 0.02           # trace-seconds per engine step (virtual clock)
SLO_SCALE = 2.0     # deadline = SLO_SCALE x calibrated low-load mean e2e
CALIBRATION_RATE = 2.0
APP_MIX = ("vrag", "crag", "srag", "planrag")


def _build_engine():
    from repro.configs import get_arch, smoke_variant
    from repro.serving.engine import GenerationEngine

    cfg = smoke_variant(get_arch("smollm-135m"))
    return GenerationEngine(
        cfg, max_batch=4, max_seq=256, prefill_chunk_size=32,
        token_budget=64, scheduler="edf_slack", host_blocks=128,
    )


def _run_trace(classes, rate, duration, *, arrival="poisson",
               session_fraction=0.3, seed=0):
    from repro.apps import OpenLoopDriver, VirtualClock, make_app
    from repro.core.workload import WorkloadSpec, generate

    eng = _build_engine()
    apps = {c.name: make_app(c.name, engine=eng) for c in classes}
    spec = WorkloadSpec(rate_rps=rate, duration_s=duration, arrival=arrival,
                        classes=tuple(classes),
                        session_fraction=session_fraction, think_time_s=0.3)
    drv = OpenLoopDriver(eng, apps, generate(spec, seed=seed),
                         clock=VirtualClock(dt=DT), seed=seed)
    drv.run()
    return drv


def _calibrate(classes, duration, seed=0):
    """Low-load pass -> per-class deadline = SLO_SCALE x mean e2e latency."""
    from repro.core.workload import SLOClass

    drv = _run_trace(classes, CALIBRATION_RATE, duration,
                     session_fraction=0.0, seed=seed)
    summ = drv.violation_summary()
    out = []
    for c in classes:
        mean = summ.get(c.name, {}).get("mean_latency_s", c.deadline_s)
        out.append(SLOClass(c.name, deadline_s=SLO_SCALE * mean,
                            weight=c.weight, max_new=c.max_new,
                            k_docs=c.k_docs))
    return out


def main(fast: bool = False, arrival: str = "poisson", seed: int = 0):
    from repro.core.workload import DEFAULT_CLASSES

    classes = [c for c in DEFAULT_CLASSES if c.name in APP_MIX]
    if fast:
        classes = classes[:2]            # vrag + crag keep the smoke tight
        rates, duration, cal_dur = [10.0], 1.0, 1.0
    else:
        rates, duration, cal_dur = [5.0, 15.0, 30.0], 4.0, 4.0
    classes = _calibrate(classes, cal_dur, seed=seed)
    print("calibrated deadlines (trace-s): "
          + ", ".join(f"{c.name}={c.deadline_s:.3f}" for c in classes))

    rows = []
    for rate in rates:
        drv = _run_trace(classes, rate, duration, arrival=arrival,
                         seed=seed + 1)
        summ = drv.violation_summary()
        st = drv.engine.stats()
        for c in classes:
            s = summ.get(c.name)
            if s is None:
                continue
            rows.append({
                "class": c.name, "rate_rps": rate,
                "completed": int(s["completed"]),
                "violation_pct": 100.0 * s["violation_rate"],
                "mean_e2e_s": s["mean_latency_s"],
                "deadline_s": c.deadline_s,
            })
        sess = st.get("session_hit_tokens", 0) + st.get("session_shared_tokens", 0)
        print(f"rate={rate:g}: {len(drv.records)} completed, "
              f"{sess} session-reused tokens")
    print_table(rows, ("class", "rate_rps", "completed", "violation_pct",
                       "mean_e2e_s", "deadline_s"))

    if fast:  # CI smoke contract: the real engine completed work and the
        # headline metric is a finite number
        total = sum(r["completed"] for r in rows)
        assert total > 0, "smoke run completed no requests"
        for r in rows:
            v = r["violation_pct"]
            assert 0.0 <= v <= 100.0, f"violation rate not finite: {v}"
        print(f"smoke OK: {total} requests, finite per-class violation rates")
    return rows


def main_sim(fast: bool = False):
    """Legacy simulator comparison: Patchwork vs monolithic/ray-like."""
    from benchmarks.common import APP_NAMES, ENGINES, low_load_mean_latency, run_app

    rates = [8, 16, 24, 32, 40] if not fast else [16, 32]
    print("app,engine,rate_rps,slo_violation_pct")
    out = {}
    for app in APP_NAMES:
        slo = 2.0 * low_load_mean_latency(app)
        for ename, engine in ENGINES.items():
            for rate in rates:
                m, _ = run_app(app, engine, rate, duration=20.0, slo_s=slo)
                v = m.slo_violation_rate * 100
                out[(app, ename, rate)] = v
                print(f"{app},{ename},{rate},{v:.1f}")
    # headline: max reduction vs best baseline
    print("\napp,max_slo_reduction_pct_points")
    for app in APP_NAMES:
        best = 0.0
        for rate in rates:
            pw = out[(app, "patchwork", rate)]
            base = min(out[(app, "monolithic", rate)], out[(app, "ray_like", rate)])
            best = max(best, base - pw)
        print(f"{app},{best:.1f}")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace + assertions: fast smoke run for CI")
    ap.add_argument("--sim", action="store_true",
                    help="legacy simulator comparison instead of the real engine")
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "diurnal", "bursty"))
    ap.add_argument("--seed", type=int, default=0)
    args = parse_cli(ap)
    if args.sim:
        main_sim(fast=args.smoke)
    else:
        main(fast=args.smoke, arrival=args.arrival, seed=args.seed)
