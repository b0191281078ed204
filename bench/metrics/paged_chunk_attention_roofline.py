"""``paged_chunk_attention`` (the packed fused step's attention kernel):
the least time its traced calls could take at the chip's peaks, over the
time they took (``work.roofline_pct``). The work of each traced step that
runs it comes from the configuration's own reference (``kernel_work``):
every row's attended slots read once, dead table columns and padding not
counted."""
from bench.harness import work

LAYER = "kernels (kernels/decode_attention.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
KERNEL = "paged_chunk_attention"


def read(ctx):
    return work.roofline_pct(ctx, KERNEL)
