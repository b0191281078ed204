"""Share of the time the engine had work in flight in which no operation
ran on the device: 1 - busy union / in-flight time, from the profiler trace
(in flight: the traced window less the loop's waits for arrivals)."""

LAYER = "device (TPU v5e)"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    red = ctx["reduced"]
    if red is None or red.in_flight_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_in_flight_s / red.in_flight_s)
