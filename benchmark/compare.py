"""The comparison that decides ``correct``: rows the timed calls returned
against rows the plain reference computed from the same files.

Rows are compared in the order the query states (all three queries end in an
ORDER BY or return one row). Two numbers come out, each with a limit of its
own in the configuration's file:

``rows_wrong``      answers with a missing or extra row, or a value that is
                    not a float and differs (keys, counts, dates): limit 0.
``float_rel_gap``   the widest |got − want| / max(|want|, 1) over every float
                    of every compared answer.
"""
from __future__ import annotations

import math
from datetime import date, datetime


def _plain(v):
    if isinstance(v, datetime):
        return v.date().isoformat()
    if isinstance(v, date):
        return v.isoformat()
    if hasattr(v, "item"):  # numpy scalar
        return v.item()
    return v


def answer_gap(names, rows, want_names, want_rows) -> tuple:
    """(wrong, gap) of one answer: ``wrong`` is True where the rows differ in
    anything but floats; ``gap`` is the widest relative float gap (inf where a
    float is missing or not finite on one side only)."""
    names = [n.lower() for n in names]
    if sorted(names) != sorted(want_names) or len(rows) != len(want_rows):
        return True, 0.0
    at = [names.index(n) for n in want_names]
    gap = 0.0
    for got, want in zip(rows, want_rows):
        for j, w in zip(at, want):
            g, w = _plain(got[j]), _plain(w)
            if isinstance(w, float):
                if not isinstance(g, float):
                    return True, gap
                if math.isnan(g) or math.isnan(w) or math.isinf(g) or math.isinf(w):
                    if not (g == w or (math.isnan(g) and math.isnan(w))):
                        gap = math.inf
                    continue
                gap = max(gap, abs(g - w) / max(abs(w), 1.0))
            elif g != w or isinstance(g, bool) != isinstance(w, bool):
                return True, gap
    return False, gap


def compare(answers, references) -> dict:
    """``answers``: (key, names, rows) per compared answer; ``references``:
    key → (names, rows). Returns the numbers compared."""
    wrong, gap = 0, 0.0
    for key, names, rows in answers:
        want_names, want_rows = references[key]
        w, g = answer_gap(names, rows, want_names, want_rows)
        wrong += bool(w)
        gap = max(gap, g)
    return {"answers_compared": len(answers), "rows_wrong": wrong, "float_rel_gap": gap}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, compared): each number beside its limit. No answer compared
    is not correct: a run that finished nothing proved nothing."""
    compared = {
        "rows_wrong": {"value": numbers["rows_wrong"], "limit": limits["rows_wrong"]},
        "float_rel_gap": {
            "value": numbers["float_rel_gap"], "limit": limits["float_rel_gap"],
        },
        "answers_compared": {"value": numbers["answers_compared"], "at_least": 1},
    }
    ok = (
        numbers["answers_compared"] >= 1
        and numbers["rows_wrong"] <= limits["rows_wrong"]
        and numbers["float_rel_gap"] <= limits["float_rel_gap"]
    )
    return ok, compared
