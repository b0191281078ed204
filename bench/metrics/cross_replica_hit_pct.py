"""Share of the prompt tokens admitted in the window that one replica took
from blocks another replica wrote to the shared host tier: the growth of
``cross_replica_host_hits`` (host blocks promoted on a replica other than
the one that wrote them) times the block size, over the growth of the
prompt tokens computed or hit (``prefill_tokens + prefix_hit_tokens +
host_hit_tokens``)."""

LAYER = "cache (serving/host_tier.py)"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    a, b = ctx["counters"]
    if "cross_replica_host_hits" not in b:
        return None
    cross = b["cross_replica_host_hits"] - a["cross_replica_host_hits"]
    keys = ("prefill_tokens", "prefix_hit_tokens", "host_hit_tokens")
    prompt = sum(b[k] for k in keys) - sum(a[k] for k in keys)
    if prompt <= 0:
        return None
    return 100.0 * cross * int(ctx["config"]["engine"]["block_size"]) / prompt
