"""Model FLOPs of the traced steps over their device time at the chip's
bf16 peak: the whole step's share of the chip. Computed tokens through
every layer, attention over each token's own context, and the head where a
token is sampled (``harness/work.py``); time from the step programs' XLA
modules in the trace."""
from bench.harness import trace, work

LAYER = "step programs (serving/engine.py)"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx["reduced"] is None or not ctx["plans"]:
        return None
    ts = trace.module_times(ctx["reduced"], ctx["config"]["step_modules"].values())
    plans = [p for p in ctx["plans"] if p["kind"] in ("ragged", "decode")]
    if not ts or len(ts) != len(plans):
        return None
    flops = sum(work.step_model_flops(ctx["dims"], p) for p in plans)
    return 100.0 * flops / (sum(ts) * ctx["peaks"]["bf16_flops_per_s"])
