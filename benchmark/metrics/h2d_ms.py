"""exec/ transitions: PhaseLedger ``h2d`` + ``pad``, mean per query of the
window. Summed over the threads that upload, so it can exceed the wall."""


def read(run):
    if not run.ledgers:
        return None
    total = sum(led.get("h2d", 0) + led.get("pad", 0) for led in run.ledgers)
    return total / len(run.ledgers) / 1e6
