"""exec/ window: device milliseconds of the window kernel's jit module
(``jit__window``) per traced query. ``trace_reduce`` keeps the ten costliest
modules; where the window is not among them, or the program has no module of
that name, there is nothing to read."""

MODULE = "jit__window"


def module_seconds(run, names) -> float | None:
    """Device seconds of the named jit modules in the traced part, or None
    where the trace holds none of them."""
    t = run.trace
    if not t or not run.traced_requests:
        return None
    found = [s for n, s in t.get("modules", []) if n in names]
    return sum(found) if found else None


def read(run):
    s = module_seconds(run, (MODULE,))
    return None if s is None else 1e3 * s / len(run.traced_requests)
