"""Run one cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload qwen3b.rag_hot --seed 7 --seconds 40 --trace 0

``--trace 0`` measures the cell's end-to-end metrics with tracing off;
``--trace 1`` records a few seconds of the window with the profiler and
reports the cell's per-layer metrics instead. Either way the last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, and ``checks`` last), and the last lines of
standard error give each compared number beside its limit.

Without an accelerator, with fewer chips than the cell asks for, or without
the program beside the benchmark, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()   # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK_DIR = ROOT / ".bench_work"          # traces, removed after each run
CACHE_DIR = ROOT / ".jax_cache"          # persistent compilation cache


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.harness import runner, spec

    try:
        cell = spec.find_cell(args.workload, ROOT)
    except (spec.SpecError, OSError, ValueError) as e:
        log(f"FAIL: {e}")
        return 2
    try:
        cell.adapter.import_program(ROOT)
    except ImportError as e:
        log(f"FAIL: the program is not beside the benchmark ({e})")
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        log("FAIL: JAX found no accelerator")
        return 2
    if len(devs) < cell.chips:
        log(f"FAIL: the cell asks for {cell.chips} chips, JAX found {len(devs)}")
        return 2
    cache = enable_compile_cache()
    log(f"jax {jax.__version__}, {devs[0].device_kind} x {len(devs)}, "
        f"cell {cell.name}, seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}, compile cache {cache}")
    WORK_DIR.mkdir(exist_ok=True)
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             T_PROC, str(WORK_DIR), log=log)
    log("counts " + json.dumps(result.get("counts", {})))
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    log(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
