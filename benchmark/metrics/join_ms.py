"""exec/ joins: device milliseconds of the join kernels' jit modules
(``jit__join_*``: bounds, pairs, cross pairs, null extension) per traced
query. ``trace_reduce`` keeps the ten costliest modules; where none of them
is a join's, or the program names its join kernels otherwise (the parent's
are ``jit_fn``), there is nothing to read."""

PREFIX = "jit__join_"


def join_seconds(run) -> float | None:
    """Device seconds of the ``jit__join_*`` modules in the traced part, or
    None where the trace holds none of them."""
    t = run.trace
    if not t or not run.traced_requests:
        return None
    found = [s for n, s in t.get("modules", []) if n.startswith(PREFIX)]
    return sum(found) if found else None


def read(run):
    s = join_seconds(run)
    return None if s is None else 1e3 * s / len(run.traced_requests)
