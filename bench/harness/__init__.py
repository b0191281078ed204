"""The benchmark's yardstick: traffic, the open-loop loop, the reduction of
traces and counters to metrics, the work counts, and the correctness
comparison. Nothing here is specific to one configuration, traffic mix or
per-layer metric; those are data files and readers found by name."""
