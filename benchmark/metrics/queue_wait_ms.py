"""sched/ admission: mean wait for a permit per served query over the window."""


def read(run):
    n = run.counter_delta("serve.queries")
    return run.counter_delta("serve.queryWaitNs") / n / 1e6 if n > 0 else None
