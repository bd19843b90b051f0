"""``IN (subquery)`` as a left-semi join — Catalyst's
``RewritePredicateSubquery`` for the one shape where the rewrite is exact.

A ``Filter`` whose condition has ``InSubquery(c, plan)`` as a top-level AND
conjunct keeps a row only where the predicate is TRUE; NULL (a null probe,
or no match against a result that holds a null) and FALSE both drop it.
That is a left-semi join on ``c = item``: null keys never match on either
side, duplicates in the subquery's result change nothing, an empty result
keeps no row. The subquery then runs inside the main plan, on the device,
and nothing of its result comes to the host.

Everything else stays an ``InSubquery`` for ``TpuSession._resolve_subqueries``
(the shapes and their reasons are listed in ``expr/subquery.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

from ..expr import Alias, Expression, UnresolvedAttribute
from ..expr.base import bind
from ..expr.predicates import And
from ..expr.subquery import InSubquery
from ..types import DoubleType, FloatType, IntegralType
from . import logical as L

#: name of the subquery's one column on the join's build side (a semi join
#: emits its left side only, so two rewrites of one filter may share it). A
#: fixed name: schema names are part of every kernel's cache key
KEY_NAME = "__in_subquery_key"

_NUMERIC = (IntegralType, FloatType, DoubleType)


def _conjuncts(e: Expression) -> List[Expression]:
    if isinstance(e, And):
        return _conjuncts(e.l) + _conjuncts(e.r)
    return [e]


def _joinable(probe: Expression, child: L.LogicalPlan, sub: L.LogicalPlan) -> bool:
    """Whether ``probe = item`` is an equi-join the planner matches exactly:
    one type on both sides, or two numeric types (``_coerce_join_keys``
    widens those as Catalyst does)."""
    try:
        a = bind(probe, child.schema).data_type
    except Exception:
        return False
    b = sub.schema.fields[0].data_type
    return a == b or (isinstance(a, _NUMERIC) and isinstance(b, _NUMERIC))


def rewrite_in_subqueries(lp: L.LogicalPlan) -> Tuple[L.LogicalPlan, int]:
    """``(plan, predicates rewritten)``: every ``Filter`` conjunct of the
    form ``c IN (subquery)`` becomes a left-semi join under the filter's
    other conjuncts, in the subqueries' own plans too."""
    count = 0

    def walk(node):
        nonlocal count
        kw = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, L.LogicalPlan):
                nv = walk(v)
            elif isinstance(v, list) and v and isinstance(v[0], L.LogicalPlan):
                nv = [walk(c) for c in v]
                if all(a is b for a, b in zip(nv, v)):
                    nv = v
            else:
                continue
            if nv is not v:
                kw[f.name] = nv
        if kw:
            node = dataclasses.replace(node, **kw)
        if not isinstance(node, L.Filter):
            return node
        semi, rest = [], []
        for cj in _conjuncts(node.condition):
            if isinstance(cj, InSubquery):
                if len(cj.plan.schema.fields) != 1:
                    raise ValueError(
                        "IN-subquery must return one column, got "
                        f"{len(cj.plan.schema.fields)}"
                    )
                if _joinable(cj.c, node.child, cj.plan):
                    semi.append(cj)
                    continue
            rest.append(cj)
        if not semi:
            return node
        out = node.child
        if rest:
            out = L.Filter(functools.reduce(And, rest), out)
        for cj in semi:
            sub = walk(cj.plan)
            item = UnresolvedAttribute(sub.schema.names[0])
            build = L.Project([Alias(item, KEY_NAME)], sub)
            out = L.Join(
                out, build, "left_semi", [cj.c], [UnresolvedAttribute(KEY_NAME)]
            )
            count += 1
        return out

    return walk(lp), count
