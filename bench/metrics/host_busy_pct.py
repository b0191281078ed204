"""Share of the engine's step time that the host spends on its own work
(plan build, dispatch, copies, emission, delivery) and not blocked on the
device for sampled tokens: 100 * (1 - time in the program's
``engine.materialize.wait`` spans / time in its ``engine.step`` spans),
from the traced window of the profile. At 100% the host, not the device,
sets the pace."""
from bench.harness import program_spans

LAYER = "device runner (serving/device_runner.py)"
UNIT = "%"
MOVES = "out_tok_s"


def read(ctx):
    spans = program_spans.totals(ctx)
    step = spans.get("engine.step", (0, 0))[1]
    if step <= 0:
        return None
    wait = spans.get("engine.materialize.wait", (0, 0))[1]
    return 100.0 * (1.0 - wait / step)
