"""sql and plan: values of ``IN (subquery)`` results brought to the host and
inlined into the main query as literals, per completed query of the window,
from the program's counter. 0 where every such predicate ran as a semi join
on the device. A program without the counter (the parent) reports nothing."""


def counter_per_query(run, name: str) -> float | None:
    """The window's rise of one of the program's counters over its completed
    queries, or None where the program has no such counter."""
    done = sum(1 for r in run.requests if r[2])
    if name not in run.counters_after or not done:
        return None
    return run.counter_delta(name) / done


def read(run):
    return counter_per_query(run, "subquery.hostValues")
