"""Mean device time of one dispatched step program (the packed fused step
and the decode step, matched by the XLA module names the configuration
lists), from the traced window."""
from bench.harness import trace

LAYER = "step programs (serving/engine.py)"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx["reduced"] is None:
        return None
    ts = trace.module_times(ctx["reduced"], ctx["config"]["step_modules"].values())
    return 1e3 * sum(ts) / len(ts) if ts else None
