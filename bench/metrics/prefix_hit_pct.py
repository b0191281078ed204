"""Share of the prompt tokens admitted in the window that came from cached
blocks (device prefix sharing or the host tier) instead of being computed:
the growth of ``prefix_hit_tokens + host_hit_tokens`` over that plus the
growth of ``prefill_tokens``."""

LAYER = "cache (serving/paged_cache.py)"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    a, b = ctx["counters"]
    hit = (b["prefix_hit_tokens"] + b["host_hit_tokens"]) - (
        a["prefix_hit_tokens"] + a["host_hit_tokens"])
    computed = b["prefill_tokens"] - a["prefill_tokens"]
    if hit + computed <= 0:
        return None
    return 100.0 * hit / (hit + computed)
