"""Benchmark entrypoint: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV summary lines at the end; each
section also prints its own detailed CSV. --full runs longer sweeps.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for the default fast mode (uniform bench CLI)")
    ap.add_argument("--only", default=None)
    from benchmarks._report import parse_cli

    args = parse_cli(ap, argv)
    fast = not args.full or args.smoke

    from benchmarks import (
        ablations,
        colocation,
        component_breakdown,
        controller_latency,
        loc_table,
        lp_scalability,
        retrieval_knob,
        roofline,
        slo_violations,
        streaming_load,
        throughput,
    )

    sections = [
        ("fig3_fig10_component_breakdown", component_breakdown.main),
        ("fig4_retrieval_knob", retrieval_knob.main),
        ("fig5_streaming_load", streaming_load.main),
        ("fig9_throughput", throughput.main),
        ("fig11_slo_violations", slo_violations.main),
        ("fig12_lp_scalability", lp_scalability.main),
        ("fig13_controller_latency", controller_latency.main),
        ("fig14_ablations", ablations.main),
        ("table2_loc", loc_table.main),
        ("table3_colocation", colocation.main),
        ("roofline", roofline.main),
    ]
    summary = []
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} " + "=" * max(50 - len(name), 3))
        t0 = time.perf_counter()
        try:
            fn(fast=fast)
            status = "ok"
        except Exception as e:  # noqa: BLE001
            status = f"FAIL:{type(e).__name__}:{e}"
            print(f"[bench] {name} failed: {e}")
        dt = (time.perf_counter() - t0) * 1e6
        summary.append((name, dt, status))

    print("\n=== summary (name,us_per_call,derived) ===")
    for name, us, status in summary:
        print(f"{name},{us:.0f},{status}")
    if any("FAIL" in s for _, _, s in summary):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
