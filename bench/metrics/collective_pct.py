"""Share of the devices' operation time spent in collectives (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and their
asynchronous start and done halves): their self time over the self time of
every operation, summed over the devices, in the traced window. What the
mesh's partitioning of the step programs costs in exchanges between chips;
on one chip there is none to read."""

LAYER = "mesh partitioning (serving/sharded_pool.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def read(ctx):
    red = ctx["reduced"]
    if red is None:
        return None
    total = sum(red.ops.values())
    coll = sum(t for label, t in red.ops.items() if label.startswith(COLLECTIVES))
    if coll <= 0 or total <= 0:
        return None
    return 100.0 * coll / total
