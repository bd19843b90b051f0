"""plan/logical.py: a node's schema is computed once (ISSUE 25).

Every ``schema`` used to recompute its child's, twice per expression in
``Project``, so the cost was a product over the plan's depth: TPC-DS q39's
plan asked ``Join.schema`` 32.9 million times. Counts calls; times nothing.
"""
import dataclasses

import pyarrow as pa

from spark_rapids_tpu.expr import Alias, UnresolvedAttribute
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.types import Schema

DEPTH, WIDTH = 30, 8


def _chain():
    table = pa.table({f"c{i}": [1, 2] for i in range(WIDTH)})
    plan = L.LocalRelation(table, Schema.from_arrow(table.schema))
    for _ in range(DEPTH):
        plan = L.Project(
            [
                Alias(UnresolvedAttribute(f"c{i}"), f"c{i}")
                for i in range(WIDTH)
            ],
            plan,
        )
    return plan


def test_schema_of_a_deep_plan_binds_each_expression_once(monkeypatch):
    calls = []
    bind = L._bound
    monkeypatch.setattr(
        L, "_bound", lambda e, schema: calls.append(e) or bind(e, schema)
    )
    plan = _chain()
    assert plan.schema.names == [f"c{i}" for i in range(WIDTH)]
    assert len(calls) == DEPTH * WIDTH
    plan.schema, plan.child.schema  # asked again: nothing is computed again
    assert len(calls) == DEPTH * WIDTH


def test_a_rebuilt_node_computes_its_own_schema():
    plan = _chain()
    before = plan.schema
    narrower = dataclasses.replace(plan, exprs=plan.exprs[:3])
    assert narrower.schema.names == ["c0", "c1", "c2"]
    assert plan.schema is before
