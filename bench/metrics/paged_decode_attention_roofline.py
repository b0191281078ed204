"""``paged_decode_attention`` (the decode step's attention kernel): the
least time its traced calls could take at the chip's peaks, over the time
they took (``work.roofline_pct``). The work of each traced decode step
comes from the configuration's own reference (``kernel_work``); dead table
columns not counted."""
from bench.harness import work

LAYER = "kernels (kernels/decode_attention.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
KERNEL = "paged_decode_attention"


def read(ctx):
    return work.roofline_pct(ctx, KERNEL)
