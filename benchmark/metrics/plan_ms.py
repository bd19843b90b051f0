"""sql/ + plan/: PhaseLedger ``parse_plan``, mean per query of the window."""


def read(run):
    if not run.ledgers:
        return None
    return sum(led.get("parse_plan", 0) for led in run.ledgers) / len(run.ledgers) / 1e6
