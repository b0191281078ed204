"""Mean host time the control plane takes to build one step plan
(admission, capacity, grants, batch assembly): the program's own
``engine.plan`` spans around ``ControlPlane.build_plan``, from the traced
window of the profile."""
from bench.harness import program_spans

LAYER = "control plane (serving/control_plane.py)"
UNIT = "ms"
MOVES = "out_tok_s"


def read(ctx):
    n, ns = program_spans.totals(ctx).get("engine.plan", (0, 0))
    return ns / n / 1e6 if n else None
