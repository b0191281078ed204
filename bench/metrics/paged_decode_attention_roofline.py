"""``paged_decode_attention`` (the decode step's attention kernel): the
least time its traced calls could take at the chip's peaks, over the time
they took. Work from each traced decode step's per-row contexts
(``harness/work.py``); dead table columns not counted."""
from bench.harness import work

LAYER = "kernels (kernels/decode_attention.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
KERNEL = "paged_decode_attention"


def read(ctx):
    red = ctx["reduced"]
    if red is None:
        return None
    spent = red.kernels.get(KERNEL, 0.0)
    plans = [p for p in ctx["plans"] if p["kind"] == "decode"]
    if spent <= 0 or not plans:
        return None
    d, kv, act = ctx["dims"], ctx["config"]["kv_bytes"], ctx["config"]["act_bytes"]
    least = sum(work.min_time(*work.decode_kernel_work(d, kv, act, p["ctx"]), ctx["peaks"])
                for p in plans)
    return 100.0 * least / spent
