"""One run of one cell: set-up, warm-up traffic, the measured window, the
grace period, the metrics, and the correctness comparison.

``run_cell`` does not look for a chip; ``bench/run.py`` does that before
calling it, so tests can drive a whole run at a tiny size on the CPU.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import time
from typing import Callable, Dict, List

from bench.harness import check, e2e, spec, trace, traffic
from bench.harness.loop import OpenLoop

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class CompileCounter:
    """Compilations (and loads from the persistent cache) while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0

        def on_event(event, *args, **kw):
            if self.armed and event in COMPILE_EVENTS:
                self.count += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)


def plain_records(res, adapter) -> List[dict]:
    """The loop's records as plain data, holding no program object."""
    out = []
    for rec in res.records:
        a = rec.arrival
        reqs = []
        for req, prompt in zip(rec.log.requests, rec.log.prompts):
            v = adapter.request_view(req)
            v["segments"] = adapter.prompt_segments(prompt)
            reqs.append(v)
        out.append({"phase": a.phase, "index": a.index, "slo_class": a.slo_class,
                    "due": rec.due, "deadline_s": a.deadline_s, "max_new": a.max_new,
                    "released_at": rec.released_at, "finished_at": rec.finished_at,
                    "requests": reqs})
    return out


def _span_factory(enabled: bool) -> Callable:
    if not enabled:
        return lambda _name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(f"bench:{name}")


class Tracer:
    """Records ``trace_s`` seconds in the middle of the window, with every
    dispatched step plan in that stretch."""

    def __init__(self, adapter, engine, span, out_dir: str, t_zero: float,
                 window_s: float, trace_s: float):
        self.adapter, self.engine, self.span, self.out_dir = adapter, engine, span, out_dir
        mid = t_zero + window_s / 2
        self.t_on, self.t_off = mid - trace_s / 2, mid + trace_s / 2
        self.state = "before"
        self.plans: List[dict] = []
        self._ctx = None
        adapter.trace_hooks(engine, span, self.on_plan)

    def on_plan(self, plan) -> None:
        if self.state == "on":
            self.plans.append(self.adapter.plan_view(plan))

    def tick(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.t_on:
            self.adapter.sync(self.engine)
            jax.profiler.start_trace(self.out_dir)
            self._ctx = self.span("traced")
            self._ctx.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.t_off:
            self.adapter.sync(self.engine)
            self._ctx.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def finish(self) -> None:
        if self.state == "on":
            self.tick(float("inf"))


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_proc: float, work_dir: str, log: Callable[[str], None] = print,
             controls=(), keep_trace: bool = False) -> Dict:
    """Run ``cell`` once and return its result (the JSON line's object).
    ``controls`` also reads those controls' gaps, and ``keep_trace`` leaves a
    traced run's profile in ``work_dir/trace`` (calibration only)."""
    import jax

    cfg, tf = cell.config, cell.traffic
    ad = cell.adapter
    ref = spec.load_reference(cfg["reference"])
    dims = ref.Dims.from_config(cfg["model"])
    t0 = time.monotonic()
    # an adapter whose system serves from weights split over several devices
    # has them made there, so that no device holds a second, whole copy
    place = getattr(ad, "weight_shardings", None)
    shardings = None if place is None else place(cfg, dims, jax.eval_shape(
        lambda: ref.make_weights(dims, seed, cfg["dtype"])))
    w = ref.make_weights(dims, seed, cfg["dtype"], shardings=shardings)
    jax.block_until_ready(w)
    t_w = time.monotonic()
    engine = ad.build(cfg, w, dims)
    n_warm = ad.warm(engine)
    t_b = time.monotonic()
    log(f"set-up: weights {t_w - t0:.3f} s, engine and {n_warm} step programs "
        f"{t_b - t_w:.3f} s")

    source = cell.requests.Source(engine, tf, seed, dims.vocab)
    arrivals = traffic.schedule(tf, seed, seconds, cell.arrival)
    t_p = time.monotonic()
    pre = source.prewarm()
    log(f"set-up: {pre} in {time.monotonic() - t_p:.3f} s")
    t_zero = time.monotonic() + float(tf["warmup_s"])
    span = _span_factory(traced)
    compiles = CompileCounter()
    tracer = None
    if traced:
        tdir = os.path.join(work_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        tracer = Tracer(ad, engine, span, tdir, t_zero, seconds, float(tf["trace_s"]))
    t_end = t_zero + seconds

    def on_tick(now):
        compiles.armed = t_zero <= now < t_end
        if tracer is not None:
            tracer.tick(now)

    loop = OpenLoop(arrivals, t_zero=t_zero, window_s=seconds,
                    grace_s=float(tf["grace_s"]), start=source.start,
                    busy=lambda: ad.busy(engine), step=lambda: ad.step(engine),
                    read_counters=lambda: ad.counters(engine),
                    span=span, on_tick=on_tick)
    res = loop.run()
    compiles.armed = False
    if tracer is not None:
        tracer.finish()
    ad.sync(engine)
    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[: cell.chips])
    records = plain_records(res, ad)
    ended_at, counters = res.ended_at, (res.counters_at_start, res.counters_at_end)
    steps_run = res.steps
    plans = tracer.plans if tracer is not None else []
    # the program's state goes before the reference runs
    del res, loop, source, tracer, engine
    gc.collect()
    freed = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devs[: cell.chips])

    out = e2e.compute(records, t_zero, seconds, ended_at)
    counts = out["counts"]
    counts.update(setup_s=t_zero - t_proc, compiles_in_window=compiles.count,
                  loop_steps=steps_run, weights_s=t_w - t0, engine_s=t_b - t_w,
                  bytes_in_use_for_check=freed, **pre,
                  **{f"{k}_at_start": v for k, v in counters[0].items()},
                  **{f"{k}_at_end": v for k, v in counters[1].items()})
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    ctx = {"records": records, "counters": counters, "dims": dims, "config": cfg,
           "reference": ref, "plans": plans, "reduced": None, "peaks": None}
    result = {"attempted": counts["pipelines"],
              "failed": counts["pipelines"] - counts["pipelines_finished"]
              + counts["truncated_requests"]}
    if traced:
        red = trace.reduce(trace.load_xplane(trace.newest_xplane(tdir)),
                           kernels=cfg["kernels"])
        ctx["reduced"] = red
        ctx["peaks"] = spec.load_peaks(devs[0].device_kind, cell.root)
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], cell.root).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = trace.breakdown(red)
        if not keep_trace:
            shutil.rmtree(tdir, ignore_errors=True)
        counts["traced_steps"] = len(plans)
    else:
        values = dict(out["metrics"], setup_s=t_zero - t_proc)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    t_c = time.monotonic()
    lim = cfg["check"]
    items = check.sample(records, seed, int(lim["min_tokens"]), int(lim["max_requests"]))
    got = check.gaps(ref, dims, w, items, controls=controls)
    counts["check_s"] = time.monotonic() - t_c
    counts["check_requests"] = len(items)
    counts["check_first_compared"] = sum(bool(r.get("check_first")) for r in items)
    ok = got["tokens"] >= 1 and got["gap_max"] <= float(lim["gap_limit"])
    result.update(correct=bool(ok), metrics=metrics, device=device, counts=counts)
    counts.update({k: v for k, v in got.items() if k.startswith("control_")})
    result["checks"] = {
        "gap_max": {"value": got["gap_max"], "limit": float(lim["gap_limit"])},
        "tokens_compared": {"value": got["tokens"], "limit": 1},
    }
    return _finite(result)


def _finite(x):
    """JSON has no NaN: a number that could not be computed becomes null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x
