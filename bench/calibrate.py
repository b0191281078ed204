"""Calibration on the chip: what sets a cell's rate, deadlines and limit.

    python bench/calibrate.py sweep --workload W --rates 0.5,1,2 --seconds 30 \
        --low-rate 0.2 --low-seconds 80 --seeds 11,12,13
    python bench/calibrate.py check --workload W --seeds 1,2,3 --seconds 15
    python bench/calibrate.py record-trace --workload W --seed 5 --seconds 20

``sweep`` first runs the cell's traffic at ``--low-rate`` with loose
deadlines and every class at the same weight (so that each has several
pipelines), and sets each class's deadline to twice its mean latency there
(the paper's section 4.1); then it runs each rate with those deadlines and
reports attainment, tails and whether the queue grew over the window. Each
run of a sweep takes the next of ``--seeds`` and also reads the controls'
gaps, so a sweep gives the limit's readings too; ``--write-mix`` writes
the mix with the rate and deadlines that ``choose`` sets from it.
``check`` runs the cell once per seed, each with a short window at the
cell's own load, and reads the program's widest gap against the reference
beside the control's (the reference one precision step lower): the
readings a limit is set from.
``record-trace`` keeps one traced run's profile and writes a summary of its
planes, lines and events. All runs share one process, and every result is
one JSON line in ``--out``.

Each mode runs the cell exactly as ``bench/run.py`` does (``run_cell``);
only the traffic's rate and deadlines (``sweep``) and the control reading
(``check``) differ.
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "check", "record-trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--low-rate", type=float, default=0.0)
    ap.add_argument("--low-seconds", type=float, default=0.0)
    ap.add_argument("--grace", type=float, default=0.0,
                    help="grace period of the sweep's rates (0: the mix's)")
    ap.add_argument("--write-mix", default="",
                    help="after a sweep, write the mix with the rate and deadlines "
                         "it chose (``choose``) to this path")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"))
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import run as bench_run
    from bench.harness import runner, spec

    cell = spec.find_cell(args.workload, ROOT)
    cell.adapter.import_program(ROOT)
    import jax

    if jax.devices()[0].platform == "cpu":
        print("calibration runs on the chip only", file=sys.stderr)
        return 2
    bench_run.enable_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    sink = out / f"calibrate_{args.mode}_{cell.name}.jsonl"
    work = str(ROOT / ".bench_work")
    Path(work).mkdir(exist_ok=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with open(sink, "a") as f:
            f.write(line + "\n")

    def one(c, seed, seconds=None, **kw):
        t = time.monotonic()
        r = runner.run_cell(c, seed, seconds or args.seconds, kw.pop("traced", False),
                            t, work, log=bench_run.log, **kw)
        r["wall_s"] = time.monotonic() - t
        return r

    seeds = [int(x) for x in args.seeds.split(",") if x] or [args.seed]
    controls = ("int8", "fp8")
    if args.mode == "sweep":
        low = copy.deepcopy(cell)
        low.traffic["rate_per_s"] = args.low_rate
        for c in low.traffic["classes"].values():
            c["deadline_s"], c["weight"] = 1e6, 1
        r = one(low, seeds[0], seconds=args.low_seconds, controls=controls)
        per = r["counts"]["per_class"]
        # a class with no pipeline finished at low load keeps the mix's deadline
        deadlines = {c: 2.0 * per[c]["mean_e2e_s"] for c in per
                     if per[c]["mean_e2e_s"] is not None}
        emit({"mode": "low", "rate": args.low_rate, "seed": seeds[0],
              "deadlines": deadlines, **r})
        rates = [float(x) for x in args.rates.split(",") if x]
        swept = []
        for i, rate in enumerate(rates):
            c = copy.deepcopy(cell)
            c.traffic["rate_per_s"] = rate
            if args.grace:
                c.traffic["grace_s"] = args.grace
            for name, d in deadlines.items():
                c.traffic["classes"][name]["deadline_s"] = d
            seed = seeds[(i + 1) % len(seeds)]
            rec = {"mode": "rate", "rate": rate, "seed": seed, "deadlines": deadlines,
                   **one(c, seed, controls=controls)}
            swept.append(rec)
            emit(rec)
        if args.write_mix:
            mix = choose(cell.traffic, r["counts"], swept)
            Path(args.write_mix).write_text(json.dumps(mix, indent=2) + "\n")
            emit({"mode": "chosen", "rate_per_s": mix["rate_per_s"],
                  "classes": mix["classes"]})
    elif args.mode == "check":
        for seed in seeds:
            emit({"mode": "check", "seed": seed, **one(cell, seed, controls=controls)})
    else:
        r = one(cell, args.seed, traced=True, keep_trace=True)
        emit({"mode": "trace", "seed": args.seed, **r})
        summarize_trace(ROOT / ".bench_work" / "trace", out / f"trace_{cell.name}")
    return 0


KNEE_ATTAIN_PCT = 90.0


def sustained(run: dict) -> bool:
    """A swept rate the system keeps up with: at least 90% of the window's
    pipelines met their class deadline, every one of them finished, and the
    queue of requests not yet admitted grew by at most two over the
    window."""
    c = run["counts"]
    att = c["slo_attain_pct"]
    return (att is not None and att >= KNEE_ATTAIN_PCT
            and c["pipelines_finished"] == c["pipelines"]
            and c["waiting_at_end"] <= c["waiting_at_start"] + 2)


def choose(traffic: dict, low_counts: dict, swept: list) -> dict:
    """The mix with the numbers a sweep sets: each class's deadline twice
    its mean latency at low load (to 0.1 s), and the rate at four fifths of
    the knee, the highest swept rate sustained (``sustained``; down to a
    multiple of 0.05 pipelines/s). A class the low-load run did not finish keeps its
    deadline; where no rate was sustained the knee is the lowest swept."""
    mix = copy.deepcopy(traffic)
    for name, per in low_counts["per_class"].items():
        if per.get("mean_e2e_s") is not None and name in mix["classes"]:
            mix["classes"][name]["deadline_s"] = round(2.0 * per["mean_e2e_s"], 1)
    ok = [s["rate"] for s in swept if sustained(s)]
    knee = max(ok) if ok else min(s["rate"] for s in swept)
    mix["rate_per_s"] = max(round(int(0.8 * knee / 0.05 + 1e-9) * 0.05, 2), 0.05)
    return mix


def summarize_trace(trace_dir: Path, dest: Path) -> None:
    """Planes, lines and the costliest event names of a recorded profile,
    plus every event of its first half second as a JSON fixture."""
    from jax.profiler import ProfileData

    from bench.harness import trace

    path = trace.newest_xplane(str(trace_dir))
    pd = ProfileData.from_file(path)
    summary = []
    for pl in pd.planes:
        for ln in pl.lines:
            tot, cnt, stats = {}, {}, {}
            for ev in ln.events:
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns / 1e9
                cnt[ev.name] = cnt.get(ev.name, 0) + 1
                if ev.name not in stats and len(stats) < 8:
                    stats[ev.name] = [str(s)[:200] for s in list(ev.stats)[:12]]
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:40]
            summary.append({"plane": pl.name, "line": ln.name, "events": sum(cnt.values()),
                            "top": [[k, v, cnt[k]] for k, v in top], "stats": stats})
    dest.with_suffix(".summary.json").write_text(json.dumps(summary, indent=1))
    events = trace.load_xplane(path)
    win = [e for e in events if e.name == trace.WINDOW_SPAN]
    lo = win[0].start if win else min(e.start for e in events)
    keep = [e for e in events if lo <= e.start < lo + 500_000_000
            and (trace.is_device(e.plane) or e.name.startswith("bench:"))]
    dest.with_suffix(".events.json").write_text(json.dumps(
        [[e.plane, e.line, e.name, e.start, e.end] for e in keep]))


if __name__ == "__main__":
    sys.exit(main())
