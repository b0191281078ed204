"""Shared benchmark reporting + CLI helpers.

Deduplicates the latency-table code the serving benchmarks used to copy from
each other, and gives every benchmark entry point a uniform ``--smoke`` flag
(tiny model / few requests) so CI can execute them all without letting the
entry points rot.
"""
from __future__ import annotations

import argparse
import math
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence


def ensure_import_paths() -> None:
    """Make every benchmark entry point importable both ways.

    Benchmarks run as ``python -m benchmarks.X`` (CI) and as direct scripts
    (``python benchmarks/X.py``). This inserts the three roots they need —
    ``src/`` for ``repro``, this directory for bare ``_report``-style
    imports, and the repo root for ``benchmarks.common``-style imports — so
    individual files no longer carry try/except dual-import boilerplate:
    ``benchmarks/__init__.py`` calls this for module mode, and importing
    ``_report`` (always a benchmark's first local import) covers script mode.
    """
    here = os.path.dirname(os.path.abspath(__file__))
    for p in (os.path.join(here, "..", "src"), here, os.path.join(here, "..")):
        p = os.path.abspath(p)
        if p not in (os.path.abspath(q) for q in sys.path):
            sys.path.insert(0, p)


ensure_import_paths()

LAT_KEYS = ("ttft_p50", "ttft_p95", "tpot_p50", "tpot_p95", "gap_p95", "e2e_p95")


def parse_cli(ap: argparse.ArgumentParser,
              argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    """Every benchmark's entry: parse its arguments and turn on JAX's
    persistent compilation cache (``repro.launch.compile_cache``)."""
    from repro.launch.compile_cache import enable_compile_cache

    args = ap.parse_args(argv)
    enable_compile_cache()
    return args


def smoke_flag(description: str = "", argv: Optional[Sequence[str]] = None) -> bool:
    """Uniform benchmark CLI: ``--smoke`` runs the tiny configuration (CI
    executes every benchmark this way)."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny model / few requests: fast smoke run for CI",
    )
    return parse_cli(ap, argv).smoke


def latency_row(summary: Dict[str, float], keys: Sequence[str] = LAT_KEYS) -> Dict[str, float]:
    """Project an engine ``latency_summary()`` onto the standard columns."""
    return {k: float(summary.get(k, float("nan"))) for k in keys}


def _fmt(v, width: int) -> str:
    if isinstance(v, str):
        return f"{v:>{width}}"
    if isinstance(v, int):
        return f"{v:>{width}d}"
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return f"{'-':>{width}}"
    return f"{v:>{width}.4f}"


def print_table(rows: Iterable[Dict], cols: Sequence[str], width: int = 12) -> None:
    """Aligned fixed-width table over dict rows (missing keys print '-')."""
    print(" ".join(f"{c:>{width}}" for c in cols))
    for r in rows:
        print(" ".join(_fmt(r.get(c), width) for c in cols))


def print_latency_ms(rows: Iterable[Dict], label_key: str,
                     keys: Sequence[str] = LAT_KEYS, width: int = 10) -> None:
    """Latency percentile table in milliseconds, one row per engine/mode."""
    print(f"\nlatency (ms):")
    print(f"{label_key:>12} " + " ".join(f"{k:>{width}}" for k in keys))
    for r in rows:
        vals = []
        for k in keys:
            v = r.get(k, float("nan"))
            vals.append(
                f"{'-':>{width}}" if (v is None or math.isnan(v))
                else f"{v * 1e3:>{width}.2f}"
            )
        print(f"{str(r.get(label_key, '')):>12} " + " ".join(vals))
