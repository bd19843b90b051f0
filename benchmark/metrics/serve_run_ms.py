"""serve/ front end: mean run time per served query over the window, from the
server's own timer and counter (host clock inside the program)."""


def read(run):
    n = run.counter_delta("serve.queries")
    return run.counter_delta("serve.queryRunNs") / n / 1e6 if n > 0 else None
