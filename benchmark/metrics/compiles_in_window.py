"""kernels.py: first calls (trace + compile or a load from the store) inside
the measured window. A count; 0 is what a warmed-up cell reads."""


def read(run):
    if "kernel.firstCalls" not in run.counters_after:
        return None
    return run.counter_delta("kernel.firstCalls")
