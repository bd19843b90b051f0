"""exec/ window: launches of the window kernel per completed query of the
window, from the program's counter. q67 has one window over one partition."""


def read(run):
    done = sum(1 for r in run.requests if r[2])
    if "window.calls" not in run.counters_after or not done:
        return None
    return run.counter_delta("window.calls") / done
