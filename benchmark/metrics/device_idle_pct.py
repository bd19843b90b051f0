"""Device: share of the traced part in which no operation ran on the device,
from the profiler trace through ``trace_reduce``."""


def read(run):
    t = run.trace
    if not t or not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
