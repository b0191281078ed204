"""Model FLOPs of the traced steps over their device time at the chip's
bf16 peak: the whole step's share of the chip. The FLOPs of each traced
step come from the configuration's own reference (``step_flops``); the
time from the step programs' XLA modules in the trace, summed over every
device that ran them, so on several devices it is a share of one chip.
Every traced step plan has to match one launch of a step program, and
every plan has to have a count: otherwise nothing is read."""
from bench.harness import trace

LAYER = "step programs (serving/engine.py)"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    red, plans = ctx["reduced"], ctx["plans"]
    if red is None or not plans:
        return None
    fragments = ctx["config"]["step_modules"].values()
    if len(trace.launches(red, fragments)) != len(plans):
        return None
    flops = [ctx["reference"].step_flops(ctx["dims"], p) for p in plans]
    if any(f is None for f in flops):
        return None
    ts = trace.module_times(red, fragments)
    return 100.0 * sum(flops) / (sum(ts) * ctx["peaks"]["bf16_flops_per_s"])
