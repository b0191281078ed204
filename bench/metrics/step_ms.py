"""Mean device time of one dispatched step program on one chip (the packed
fused step and the decode step, matched by the XLA module names the
configuration lists), from the traced window. A step partitioned over
several devices counts its time on each of them once."""
from bench.harness import trace

LAYER = "step programs (serving/engine.py)"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx["reduced"] is None:
        return None
    ts = trace.module_times(ctx["reduced"], ctx["config"]["step_modules"].values())
    return 1e3 * sum(ts) / len(ts) if ts else None
