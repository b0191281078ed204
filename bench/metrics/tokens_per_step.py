"""Tokens computed per engine step over the window: the growth of the
program's counters ``prefill_tokens + tokens_out`` over the growth of
``steps``. The control plane's batching: how much work one step carries."""

LAYER = "control plane (serving/control_plane.py)"
UNIT = "tokens"
MOVES = "out_tok_s"


def read(ctx):
    a, b = ctx["counters"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    done = (b["prefill_tokens"] + b["tokens_out"]) - (a["prefill_tokens"] + a["tokens_out"])
    return done / steps
