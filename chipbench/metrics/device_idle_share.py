"""Share of the traced window in which no operation ran on the device, on
average over the cell's chips (from the profiler's ``XLA Ops`` lines)."""


def read(r):
    if not r.trace or r.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
