"""Logical → CPU physical planning.

Produces the "Spark plan" that the override pass (overrides.py) then rewrites
onto the device — mirroring how the reference receives Catalyst physical
plans. Aggregations are split into partial → hash exchange → final exactly
like Spark's physical aggregation strategy (which the reference inherits);
global sorts currently plan as coalesce-to-one + local sort (range
partitioning lands with the exchange work).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from .. import config as cfg
from ..config import TpuConf
from ..expr import Alias, Expression, UnresolvedAttribute, bind, output_name
from ..expr.aggregates import AggregateFunction, is_aggregate
from ..expr.base import BoundReference
from ..exec.cpu import (
    CpuCoalescePartitionsExec,
    CpuExpandExec,
    CpuFilterExec,
    CpuHashAggregateExec,
    CpuLimitExec,
    CpuProjectExec,
    CpuScanExec,
    CpuShuffleExchangeExec,
    CpuSortExec,
    CpuTakeOrderedAndProjectExec,
    CpuUnionExec,
)
from ..plan import logical as L
from ..plan import partitioning as P
from ..plan.physical import Exec
from ..types import Schema


def plan_physical(lp: L.LogicalPlan, conf: TpuConf) -> Exec:
    if isinstance(lp, L.LocalRelation):
        return CpuScanExec(lp.table, lp.schema, lp.num_partitions, lp.source)
    if isinstance(lp, L.FileScan):
        from ..io.files import CpuFileScanExec

        return CpuFileScanExec(lp.paths, lp.file_format, lp.schema, lp.options, conf)
    if isinstance(lp, L.Range):
        from ..exec.cpu import CpuRangeExec

        return CpuRangeExec(lp.start, lp.end, lp.step, lp.num_partitions)
    if isinstance(lp, L.Project):
        return CpuProjectExec(lp.exprs, plan_physical(lp.child, conf))
    if isinstance(lp, L.Filter):
        child = lp.child
        if isinstance(child, L.FileScan):
            # predicate pushdown: conjuncts of col-vs-literal comparisons go
            # to the scan for row-group + partition-value pruning (reference:
            # GpuParquetFileFilterHandler; the Filter stays — stats pruning
            # is conservative)
            preds = _extract_pushdown(lp.condition)
            if preds:
                opts = dict(child.options)
                opts["__predicates"] = tuple(preds)
                child = dataclasses.replace(child, options=opts)
        return CpuFilterExec(lp.condition, plan_physical(child, conf))
    if isinstance(lp, L.Aggregate):
        return _plan_aggregate(lp, conf)
    if isinstance(lp, L.MapInPandas):
        from ..exec.cpu_pandas import CpuMapInPandasExec

        return CpuMapInPandasExec(lp.fn, lp.schema, plan_physical(lp.child, conf))
    if isinstance(lp, L.FlatMapGroupsInPandas):
        from ..exec.cpu_pandas import CpuFlatMapGroupsInPandasExec

        child = plan_physical(lp.child, conf)
        if _num_partitions_hint(child) != 1:
            if lp.grouping:
                # whole groups per partition (the reference plans its python
                # exec behind a hash exchange on the grouping keys too)
                child = CpuShuffleExchangeExec(
                    P.HashPartitioning(
                        cfg.SHUFFLE_PARTITIONS.get(conf),
                        [UnresolvedAttribute(n) for n in lp.grouping],
                    ),
                    child,
                )
            else:
                # groupBy().applyInPandas: the whole frame is one group
                child = CpuCoalescePartitionsExec(child)
        return CpuFlatMapGroupsInPandasExec(lp.grouping, lp.fn, lp.schema, child)
    if isinstance(lp, L.FlatMapCoGroupsInPandas):
        from ..exec.cpu_pandas import CpuFlatMapCoGroupsInPandasExec

        left = plan_physical(lp.left, conf)
        right = plan_physical(lp.right, conf)
        if (
            _num_partitions_hint(left) != 1
            or _num_partitions_hint(right) != 1
        ):
            # co-partition both sides on their keys with the same arity so
            # matching key groups meet in the same partition pair. Mismatched
            # key dtypes hash differently (murmur3 of int32 5 != int64 5);
            # the PARTITIONING keys are cast to the common type — the frames
            # the user's fn sees keep their own types (Catalyst coerces join
            # keys the same way; see _coerce_join_keys)
            from ..expr.cast import Cast
            from ..types import numeric_promote

            lkeys: list = [UnresolvedAttribute(n) for n in lp.left_keys]
            rkeys: list = [UnresolvedAttribute(n) for n in lp.right_keys]
            for i, (ln, rn) in enumerate(zip(lp.left_keys, lp.right_keys)):
                ta = lp.left.schema[ln].data_type
                tb = lp.right.schema[rn].data_type
                if type(ta) is type(tb):
                    continue
                try:
                    common = numeric_promote(ta, tb)
                except Exception:
                    raise ValueError(
                        f"cogroup keys {ln}:{ta.simple_string} and "
                        f"{rn}:{tb.simple_string} are incompatible"
                    )
                if type(ta) is not type(common):
                    lkeys[i] = Cast(lkeys[i], common)
                if type(tb) is not type(common):
                    rkeys[i] = Cast(rkeys[i], common)
            nparts = cfg.SHUFFLE_PARTITIONS.get(conf)
            left = CpuShuffleExchangeExec(
                P.HashPartitioning(nparts, lkeys), left
            )
            right = CpuShuffleExchangeExec(
                P.HashPartitioning(nparts, rkeys), right
            )
        return CpuFlatMapCoGroupsInPandasExec(
            lp.left_keys, lp.right_keys, lp.fn, lp.schema, left, right
        )
    if isinstance(lp, L.AggregateInPandas):
        from ..exec.cpu_pandas import CpuAggregateInPandasExec

        child = plan_physical(lp.child, conf)
        if _num_partitions_hint(child) != 1:
            if lp.grouping:
                child = CpuShuffleExchangeExec(
                    P.HashPartitioning(
                        cfg.SHUFFLE_PARTITIONS.get(conf),
                        [UnresolvedAttribute(n) for n in lp.grouping],
                    ),
                    child,
                )
            else:
                child = CpuCoalescePartitionsExec(child)
        return CpuAggregateInPandasExec(lp.grouping, lp.udfs, lp.schema, child)
    if isinstance(lp, L.Sort):
        child = plan_physical(lp.child, conf)
        if lp.is_global and _num_partitions_hint(child) != 1:
            # Distributed total sort: range-partition on the sort keys, then
            # sort each partition locally; partition order == global order
            # (Spark's SortExec + range exchange; GpuRangePartitioning).
            nparts = cfg.SHUFFLE_PARTITIONS.get(conf)
            if nparts > 1:
                child = CpuShuffleExchangeExec(
                    P.RangePartitioning(nparts, lp.order), child
                )
            else:
                child = CpuCoalescePartitionsExec(child)
        return CpuSortExec(lp.order, child)
    if isinstance(lp, L.Limit):
        # Limit over a global Sort plans as TopN (Spark's
        # TakeOrderedAndProject strategy; reference limit.scala)
        if isinstance(lp.child, L.Sort) and lp.child.is_global:
            return CpuTakeOrderedAndProjectExec(
                lp.n, lp.child.order, plan_physical(lp.child.child, conf)
            )
        return CpuLimitExec(lp.n, plan_physical(lp.child, conf))
    if isinstance(lp, L.Expand):
        return CpuExpandExec(lp.projections, lp.names, plan_physical(lp.child, conf))
    if isinstance(lp, L.Generate):
        from ..exec.cpu import CpuGenerateExec

        return CpuGenerateExec(
            lp.generator, lp.out_names, plan_physical(lp.child, conf)
        )
    if isinstance(lp, L.WriteFiles):
        from ..io.writer import CpuWriteFilesExec

        return CpuWriteFilesExec(
            plan_physical(lp.child, conf),
            lp.path,
            lp.file_format,
            lp.partition_by,
            lp.options,
        )
    if isinstance(lp, L.Union):
        return CpuUnionExec([plan_physical(p, conf) for p in lp.plans])
    if isinstance(lp, L.Repartition):
        child = plan_physical(lp.child, conf)
        if lp.exprs:
            part = P.HashPartitioning(lp.num_partitions, lp.exprs)
        else:
            part = P.RoundRobinPartitioning(lp.num_partitions)
        return CpuShuffleExchangeExec(part, child)
    if isinstance(lp, L.Join):
        return _plan_join(lp, conf)
    if isinstance(lp, L.Hint):
        return plan_physical(lp.child, conf)
    if isinstance(lp, L.Window):
        from ..exec.cpu_window import CpuWindowExec

        child = plan_physical(lp.child, conf)
        spec = lp.window_cols[0][1].spec
        if spec.partition_by:
            child = CpuShuffleExchangeExec(
                P.HashPartitioning(
                    cfg.SHUFFLE_PARTITIONS.get(conf), list(spec.partition_by)
                ),
                child,
            )
        elif _num_partitions_hint(child) != 1:
            child = CpuCoalescePartitionsExec(child)
        return CpuWindowExec(lp.window_cols, child)
    raise NotImplementedError(f"no physical plan for {type(lp).__name__}")


def _extract_pushdown(e: Expression):
    """Conjuncts shaped ``col <op> literal`` → (name, op, value) triples."""
    from ..expr import predicates as prd
    from ..expr.base import Literal, UnresolvedAttribute

    ops = {
        prd.GreaterThan: ">",
        prd.GreaterThanOrEqual: ">=",
        prd.LessThan: "<",
        prd.LessThanOrEqual: "<=",
        prd.EqualTo: "=",
    }
    flip = {">": "<", ">=": "<=", "<": ">", "<=": ">=", "=": "="}
    out = []

    def walk(x):
        if isinstance(x, prd.And):
            for c in x.children():
                walk(c)
            return
        op = ops.get(type(x))
        if not op:
            return
        l, r = x.children()
        if isinstance(l, UnresolvedAttribute) and isinstance(r, Literal):
            if r.value is not None:
                out.append((l.name, op, r.value))
        elif isinstance(r, UnresolvedAttribute) and isinstance(l, Literal):
            if l.value is not None:
                out.append((r.name, flip[op], l.value))

    walk(e)
    return out


def _estimate_size(lp: L.LogicalPlan) -> Optional[int]:
    """Best-effort logical size estimate in bytes (Spark's statistics
    sizeInBytes analogue) used only for broadcast-side selection."""
    if isinstance(lp, L.LocalRelation):
        return lp.table.nbytes
    if isinstance(lp, L.FileScan):
        import os

        try:
            return sum(os.path.getsize(p) for p in lp.paths)
        except OSError:
            return None
    if isinstance(lp, (L.Project, L.Filter, L.Sort, L.Limit, L.Hint, L.Repartition)):
        return _estimate_size(lp.children()[0])
    if isinstance(lp, L.Union):
        sizes = [_estimate_size(p) for p in lp.plans]
        return None if any(s is None for s in sizes) else sum(sizes)
    if isinstance(lp, L.Range):
        return 8 * max(0, (lp.end - lp.start) // (lp.step or 1))
    return None  # aggregates/joins: unknown → never auto-broadcast


def _has_broadcast_hint(lp: L.LogicalPlan) -> bool:
    """Hint detection looking through unary nodes (Spark propagates hints
    up through unary operators)."""
    if isinstance(lp, L.Hint):
        return lp.name == "broadcast" or _has_broadcast_hint(lp.child)
    if isinstance(lp, (L.Project, L.Filter, L.Sort, L.Limit, L.Repartition)):
        return _has_broadcast_hint(lp.children()[0])
    return False


def _num_partitions_hint(e: Exec) -> int:
    from ..exec.cpu import CpuRangeExec
    from ..exec.cpu_join import CpuCartesianProductExec

    from ..io.files import CpuFileScanExec

    if isinstance(e, (CpuScanExec, CpuRangeExec, CpuFileScanExec)):
        return e.num_partitions
    if isinstance(e, CpuShuffleExchangeExec):
        return e.num_partitions
    if isinstance(e, (CpuCoalescePartitionsExec, CpuLimitExec)):
        return 1
    if isinstance(e, CpuCartesianProductExec):
        # pairwise fan-out: n_left × n_right tasks
        return _num_partitions_hint(e.children[0]) * _num_partitions_hint(
            e.children[1]
        )
    if isinstance(e, CpuUnionExec):
        # union CONCATENATES its children's partitions — reporting only the
        # first child's count made aggregates over a union of
        # single-partition inputs skip their merge exchange and aggregate
        # each branch separately (wrong results)
        return sum(_num_partitions_hint(c) for c in e.children)
    if e.children:
        return _num_partitions_hint(e.children[0])
    return 1


def _extract_aggs(
    e: Expression, agg_fns: List[AggregateFunction]
) -> Expression:
    """Replace AggregateFunction nodes with placeholders indexing agg_fns."""
    if isinstance(e, AggregateFunction):
        try:
            i = agg_fns.index(e)
        except ValueError:
            i = len(agg_fns)
            agg_fns.append(e)
        return _AggResultRef(i, e)
    if not e.children():
        return e
    from ..expr.base import map_child_exprs

    return map_child_exprs(e, lambda c: _extract_aggs(c, agg_fns))


@dataclasses.dataclass(frozen=True)
class _AggResultRef(Expression):
    """Placeholder resolved to a BoundReference over [keys ++ agg results]."""

    index: int
    fn: AggregateFunction

    @property
    def data_type(self):
        return self.fn.data_type

    @property
    def nullable(self):
        return self.fn.nullable


def _finalize_result_expr(e: Expression, num_keys: int, key_exprs) -> Expression:
    """Rewrite grouping-expr occurrences and agg placeholders to bound refs
    over the virtual post-aggregation schema [key0..k, agg0..m]."""
    if isinstance(e, _AggResultRef):
        return BoundReference(num_keys + e.index, e.fn.data_type, e.fn.nullable)
    for i, k in enumerate(key_exprs):
        # grouping exprs may arrive Alias-wrapped (SQL compiler emits
        # Alias(expr, "__g0") keys); the result expr references the BARE
        # expr — match through the alias or the ordinal binds to the CHILD
        # schema and reads the wrong post-aggregation column
        kc = k.child if isinstance(k, Alias) else k
        if e == k or e == kc:
            return BoundReference(i, kc.data_type, kc.nullable)
    if not e.children():
        return e
    from ..expr.base import map_child_exprs

    return map_child_exprs(e, lambda c: _finalize_result_expr(c, num_keys, key_exprs))


def _merge_regular_agg(
    e: AggregateFunction,
    name: str,
    inner_out: List[Expression],
    child: Expression,
    sum_type,
) -> Expression:
    """Split a non-distinct aggregate into an inner partial (appended to
    ``inner_out``) and the outer merge expression returned. ``child`` is
    the expression the partial aggregates over (the original child for the
    one-distinct shape; an Expand-projected column for multi-distinct)."""
    import dataclasses as _dc

    from ..expr import Literal
    from ..expr.aggregates import (
        Average,
        Count,
        First,
        Last,
        Max,
        Min,
        Sum,
        _CentralMoment,
    )
    from ..expr.cast import Cast
    from ..expr.conditional import Coalesce
    from ..expr.arithmetic import Divide
    from ..types import DOUBLE, LONG

    if isinstance(e, (Min, Max, First, Last)):
        inner_out.append(Alias(_dc.replace(e, child=child), name))
        return _dc.replace(e, child=UnresolvedAttribute(name))
    if isinstance(e, Sum):
        # re-summing widens again (decimal p+10): cast back
        inner_out.append(Alias(_dc.replace(e, child=child), name))
        return Cast(Sum(UnresolvedAttribute(name)), sum_type)
    if isinstance(e, Count):
        inner_out.append(Alias(_dc.replace(e, child=child), name))
        return Coalesce((Sum(UnresolvedAttribute(name)), Literal(0, LONG)))
    if isinstance(e, Average):
        sname, cname = name + "s", name + "c"
        inner_out.append(Alias(Sum(Cast(child, DOUBLE)), sname))
        inner_out.append(Alias(Count(child), cname))
        return Divide(
            Sum(UnresolvedAttribute(sname)),
            Cast(Sum(UnresolvedAttribute(cname)), DOUBLE),
        )
    if isinstance(e, _CentralMoment):
        # (count, Σx, Σx²) partials re-sum; the result expression
        # mirrors _CentralMoment.evaluate term for term
        from ..expr.arithmetic import Multiply, Subtract
        from ..expr.conditional import If
        from ..expr.math import Sqrt
        from ..expr.predicates import GreaterThan, LessThan

        cname, sname, ssn = name + "c", name + "s", name + "ss"
        xd = Cast(child, DOUBLE)
        inner_out.append(Alias(Count(child), cname))
        inner_out.append(Alias(Sum(xd), sname))
        inner_out.append(Alias(Sum(Multiply(xd, xd)), ssn))
        nD = Cast(
            Coalesce((Sum(UnresolvedAttribute(cname)), Literal(0, LONG))),
            DOUBLE,
        )
        sS = Sum(UnresolvedAttribute(sname))
        m2 = Subtract(
            Sum(UnresolvedAttribute(ssn)), Multiply(sS, Divide(sS, nD))
        )
        div = Subtract(nD, Literal(1.0, DOUBLE)) if e.sample else nD
        var = If(
            GreaterThan(div, Literal(0.0, DOUBLE)),
            Divide(m2, div),
            Literal(float("nan"), DOUBLE),
        )
        var = If(
            GreaterThan(nD, Literal(0.0, DOUBLE)),
            var,
            Literal(None, DOUBLE),
        )
        var = If(LessThan(var, Literal(0.0, DOUBLE)), Literal(0.0, DOUBLE), var)
        return Sqrt(var) if e.sqrt else var
    from ..expr.aggregates import CollectList, CollectSet, MergeLists, MergeSets

    if isinstance(e, CollectList):
        # partial collect per inner group, merged at the outer aggregate
        # (Spark's Collect merge phase; MergeLists/Sets are CPU-executed)
        inner_out.append(Alias(_dc.replace(e, child=child), name))
        merge_cls = MergeSets if isinstance(e, CollectSet) else MergeLists
        return merge_cls(UnresolvedAttribute(name))
    raise NotImplementedError(
        f"{type(e).__name__} combined with DISTINCT aggregates"
    )


def _rewrite_distinct(lp: L.Aggregate) -> L.Aggregate:
    """Plan DISTINCT aggregates as two stacked aggregations — Spark's
    AggUtils.planAggregateWithOneDistinct shape (reference relies on it:
    distinct arrives at the plugin already rewritten):

        Aggregate(keys, [sum(y), count(DISTINCT x)])
        ⇒ inner:  Aggregate(keys ++ [x], partial non-distinct aggs)
          outer:  Aggregate(keys, re-aggregate partials + agg over x)

    Multiple DISTINCT column sets take the Expand-based rewrite
    (_rewrite_multi_distinct)."""
    import dataclasses as _dc

    from ..expr.base import map_child_exprs

    # the single distinct child
    dchildren = []

    def find(e):
        if isinstance(e, AggregateFunction) and getattr(e, "distinct", False):
            if e.child not in dchildren:
                dchildren.append(e.child)
        for c in e.children():
            find(c)

    for e in lp.aggregates:
        find(e)
    if len(dchildren) > 1:
        return _rewrite_multi_distinct(lp, dchildren)
    first_child = dchildren[0]

    key_names = [f"__k{i}" for i in range(len(lp.grouping))]
    inner_out: List[Expression] = [
        Alias(g, n) for g, n in zip(lp.grouping, key_names)
    ]
    inner_out.append(Alias(first_child, "__dk"))
    nd_count = [0]

    def replace_agg(e: Expression) -> Expression:
        if isinstance(e, AggregateFunction):
            if getattr(e, "distinct", False):
                return _dc.replace(e, child=UnresolvedAttribute("__dk"), distinct=False)
            name = f"__nd{nd_count[0]}"
            nd_count[0] += 1
            sum_type = bind(e, lp.child.schema).data_type
            return _merge_regular_agg(e, name, inner_out, e.child, sum_type)
        if not e.children():
            return e
        return map_child_exprs(e, replace_agg)

    outer_out: List[Expression] = []
    for e in lp.aggregates:
        name = output_name(e)
        target = e.child if isinstance(e, Alias) else e
        mapped = None
        for i, g in enumerate(lp.grouping):
            # grouping items may be Alias-wrapped (SQL compiler) — match
            # through the alias like _finalize_result_expr does
            gc = g.child if isinstance(g, Alias) else g
            if target == g or target == gc:
                mapped = UnresolvedAttribute(key_names[i])
                break
        if mapped is None:
            mapped = replace_agg(target)
        outer_out.append(Alias(mapped, name))

    inner = L.Aggregate(list(lp.grouping) + [first_child], inner_out, lp.child)
    outer_grouping = [UnresolvedAttribute(n) for n in key_names]
    return L.Aggregate(outer_grouping, outer_out, inner)


def _rewrite_multi_distinct(
    lp: L.Aggregate, dchildren: List[Expression]
) -> L.Aggregate:
    """Multiple DISTINCT column sets — Spark's RewriteDistinctAggregates:
    fan each input row out through an Expand, one projection per distinct
    group (gid=i carries only that group's child value) plus a gid=0
    projection carrying the regular aggregates' inputs, then aggregate
    twice:

        inner: group by keys ++ [d1..dm, gid]   (dedupes each distinct set)
        outer: group by keys; distinct agg i over if(gid=i, di, null),
               regular aggs re-aggregate their gid=0 partials

    (Catalyst's RewriteDistinctAggregates rule; the reference receives this
    plan shape from Spark and runs it through GpuExpandExec —
    GpuExpandExec.scala.)"""
    import dataclasses as _dc

    from ..expr import Literal
    from ..expr.base import map_child_exprs
    from ..expr.conditional import If
    from ..expr.predicates import EqualTo
    from ..types import INT

    child_schema = lp.child.schema
    m = len(dchildren)

    # regular (non-distinct) aggregate children, deduped; each becomes an
    # Expand column live only in the gid=0 projection (count(*)'s literal
    # too, so expanded duplicate rows are not double-counted)
    reg_children: List[Expression] = []

    def collect_regular(e):
        if isinstance(e, AggregateFunction) and not getattr(e, "distinct", False):
            if e.child not in reg_children:
                reg_children.append(e.child)
        for c in e.children():
            collect_regular(c)

    for e in lp.aggregates:
        collect_regular(e)

    key_names = [f"__k{i}" for i in range(len(lp.grouping))]
    d_names = [f"__d{i}" for i in range(m)]
    r_names = [f"__r{j}" for j in range(len(reg_children))]
    gid_name = "__gid"
    out_names = key_names + d_names + r_names + [gid_name]

    def null_of(expr):
        return Literal(None, bind(expr, child_schema).data_type)

    projections: List[List[Expression]] = []
    proj0: List[Expression] = [
        Alias(g, n) for g, n in zip(lp.grouping, key_names)
    ]
    proj0 += [Alias(null_of(d), n) for d, n in zip(dchildren, d_names)]
    proj0 += [Alias(c, n) for c, n in zip(reg_children, r_names)]
    proj0.append(Alias(Literal(0, INT), gid_name))
    projections.append(proj0)
    for i, d in enumerate(dchildren):
        proj: List[Expression] = [
            Alias(g, n) for g, n in zip(lp.grouping, key_names)
        ]
        proj += [
            Alias(dj if j == i else null_of(dj), n)
            for j, (dj, n) in enumerate(zip(dchildren, d_names))
        ]
        proj += [Alias(null_of(c), n) for c, n in zip(reg_children, r_names)]
        proj.append(Alias(Literal(i + 1, INT), gid_name))
        projections.append(proj)

    expand = L.Expand(projections, out_names, lp.child)

    inner_grouping = [
        UnresolvedAttribute(n) for n in key_names + d_names + [gid_name]
    ]
    inner_out: List[Expression] = [
        Alias(UnresolvedAttribute(n), n)
        for n in key_names + d_names + [gid_name]
    ]
    nd_count = [0]

    def replace_agg(e: Expression) -> Expression:
        if isinstance(e, AggregateFunction):
            if getattr(e, "distinct", False):
                i = dchildren.index(e.child)
                guarded = If(
                    EqualTo(UnresolvedAttribute(gid_name), Literal(i + 1, INT)),
                    UnresolvedAttribute(d_names[i]),
                    null_of(e.child),
                )
                return _dc.replace(e, child=guarded, distinct=False)
            name = f"__nd{nd_count[0]}"
            nd_count[0] += 1
            sum_type = bind(e, child_schema).data_type
            j = reg_children.index(e.child)
            from ..expr.aggregates import First, Last

            if isinstance(e, (First, Last)):
                # gid!=0 inner groups carry all-null partials (their __r
                # column is the Expand-projected null); a null-blind merge
                # could pick one, so the outer merge must skip null
                # partials — there is exactly one gid=0 partial per key
                inner_out.append(
                    Alias(
                        _dc.replace(e, child=UnresolvedAttribute(r_names[j])),
                        name,
                    )
                )
                return _dc.replace(
                    e, child=UnresolvedAttribute(name), ignore_nulls=True
                )
            return _merge_regular_agg(
                e, name, inner_out, UnresolvedAttribute(r_names[j]), sum_type
            )
        if not e.children():
            return e
        return map_child_exprs(e, replace_agg)

    outer_out: List[Expression] = []
    for e in lp.aggregates:
        name = output_name(e)
        target = e.child if isinstance(e, Alias) else e
        mapped = None
        for i, g in enumerate(lp.grouping):
            # grouping items may be Alias-wrapped (SQL compiler) — match
            # through the alias like _finalize_result_expr does
            gc = g.child if isinstance(g, Alias) else g
            if target == g or target == gc:
                mapped = UnresolvedAttribute(key_names[i])
                break
        if mapped is None:
            mapped = replace_agg(target)
        outer_out.append(Alias(mapped, name))

    inner = L.Aggregate(inner_grouping, inner_out, expand)
    outer_grouping = [UnresolvedAttribute(n) for n in key_names]
    return L.Aggregate(outer_grouping, outer_out, inner)


def _plan_aggregate(lp: L.Aggregate, conf: TpuConf) -> Exec:
    from ..expr.aggregates import contains_distinct

    if any(contains_distinct(e) for e in lp.aggregates):
        lp = _rewrite_distinct(lp)
    child = plan_physical(lp.child, conf)
    child_schema = child.output
    bound_grouping = [bind(g, child_schema) for g in lp.grouping]
    # resolve aggregate list, splitting agg fns from result expressions
    agg_fns: List[AggregateFunction] = []
    result_exprs: List[Expression] = []
    result_names: List[str] = []
    for e in lp.aggregates:
        name = output_name(e)
        inner = e.child if isinstance(e, Alias) else e
        bound = bind(inner, child_schema)
        rewritten = _extract_aggs(bound, agg_fns)
        result_exprs.append(
            _finalize_result_expr(rewritten, len(bound_grouping), bound_grouping)
        )
        result_names.append(name)
    partial_grouping = [
        Alias(g, f"key{i}") for i, g in enumerate(bound_grouping)
    ]
    if _num_partitions_hint(child) == 1:
        # single upstream partition: one complete-mode pass — no partial/
        # exchange/final chain (Spark's partial-merge pair is pure overhead
        # here, and every extra operator costs a device round trip)
        return CpuHashAggregateExec(
            "complete", partial_grouping, agg_fns, result_exprs, result_names, child
        )
    nparts = cfg.SHUFFLE_PARTITIONS.get(conf)
    from ..expr.aggregates import CollectList, MergeLists

    if any(isinstance(f, (CollectList, MergeLists)) for f in agg_fns):
        # collect_list/set has no fixed-width merge buffer: exchange the RAW
        # rows by the grouping keys, then one complete aggregate per
        # partition — result identical to Spark's partial+merge, and the
        # device kernel only ever builds final list planes (the reference's
        # GpuCollectList merges device lists; this engine trades that merge
        # for a row exchange)
        if bound_grouping:
            pre = CpuShuffleExchangeExec(
                P.HashPartitioning(nparts, list(bound_grouping)), child
            )
        else:
            pre = CpuCoalescePartitionsExec(child)
        return CpuHashAggregateExec(
            "complete", partial_grouping, agg_fns, result_exprs, result_names, pre
        )
    partial = CpuHashAggregateExec(
        "partial", partial_grouping, agg_fns, None, None, child
    )
    if bound_grouping:
        exchange = CpuShuffleExchangeExec(
            P.HashPartitioning(
                nparts,
                [UnresolvedAttribute(f"key{i}") for i in range(len(bound_grouping))],
            ),
            partial,
        )
    else:
        exchange = CpuCoalescePartitionsExec(partial)
    final_grouping = [
        Alias(UnresolvedAttribute(f"key{i}"), f"key{i}")
        for i in range(len(bound_grouping))
    ]
    return CpuHashAggregateExec(
        "final", final_grouping, agg_fns, result_exprs, result_names, exchange
    )


def _coerce_join_keys(lp: L.Join) -> L.Join:
    """Catalyst coerces mismatched equi-join key types at analysis (casts
    the narrower side); without it, hash partitioning and word-encoded
    matchers see different representations of equal values and silently
    drop matches. Integral pairs widen to the wider side; integral/float
    pairs promote to double."""
    if not lp.left_keys:
        return lp
    import dataclasses as _dc

    from ..expr.cast import Cast
    from ..types import (
        DOUBLE,
        DoubleType,
        FloatType,
        IntegralType,
    )

    lk, rk = list(lp.left_keys), list(lp.right_keys)
    changed = False
    for i, (a, b) in enumerate(zip(lk, rk)):
        try:
            ta = bind(a, lp.left.schema).data_type
            tb = bind(b, lp.right.schema).data_type
        except Exception:
            continue
        if type(ta) is type(tb):
            continue
        if isinstance(ta, IntegralType) and isinstance(tb, IntegralType):
            wide = ta if ta.np_dtype.itemsize >= tb.np_dtype.itemsize else tb
            if type(ta) is not type(wide):
                lk[i] = Cast(a, wide)
                changed = True
            if type(tb) is not type(wide):
                rk[i] = Cast(b, wide)
                changed = True
            continue
        num = (IntegralType, FloatType, DoubleType)
        if isinstance(ta, num) and isinstance(tb, num):
            if not isinstance(ta, DoubleType):
                lk[i] = Cast(a, DOUBLE)
                changed = True
            if not isinstance(tb, DoubleType):
                rk[i] = Cast(b, DOUBLE)
                changed = True
    if not changed:
        return lp
    return _dc.replace(lp, left_keys=lk, right_keys=rk)


def _plan_join(lp: L.Join, conf: TpuConf) -> Exec:
    from ..exec.cpu_join import (
        CpuBroadcastExchangeExec,
        CpuBroadcastHashJoinExec,
        CpuNestedLoopJoinExec,
        CpuShuffledHashJoinExec,
    )

    lp = _coerce_join_keys(lp)
    nparts = cfg.SHUFFLE_PARTITIONS.get(conf)
    if lp.left_keys:
        jt = lp.join_type
        # Build-side selection (hint, or estimated size under the threshold).
        # build-right supports every type: right/full ride the broadcast
        # exec's global build-matched tracking, which emits the
        # unmatched-build tail exactly once across stream partitions.
        # build-left is realized by swapping sides + a column-reordering
        # projection.
        threshold = cfg.AUTO_BROADCAST_THRESHOLD.get(conf)
        l_hint, r_hint = _has_broadcast_hint(lp.left), _has_broadcast_hint(lp.right)

        def fits(sz):
            return threshold >= 0 and sz is not None and sz <= threshold

        # right/full on build-right ride the broadcast exec's global
        # build-matched tracking (exactly-once unmatched-build tail)
        bc_right_ok = jt in (
            "inner", "left", "left_semi", "left_anti", "right", "full",
        )
        bc_left_ok = jt in ("inner", "right", "left", "full") and not lp.using
        want_right = bc_right_ok and (r_hint or fits(_estimate_size(lp.right)))
        want_left = bc_left_ok and (l_hint or fits(_estimate_size(lp.left)))
        if want_left and (not want_right or (l_hint and not r_hint)):
            names = lp.schema.names
            if len(set(names)) == len(names):  # unambiguous re-projection
                swapped = L.Join(
                    lp.right,
                    lp.left,
                    {"inner": "inner", "right": "left", "left": "right",
                     "full": "full"}[jt],
                    lp.right_keys,
                    lp.left_keys,
                    lp.residual,
                    False,
                )
                return plan_physical(
                    L.Project([UnresolvedAttribute(n) for n in names], swapped),
                    conf,
                )
        if want_right:
            drop = [output_name(k) for k in lp.right_keys] if lp.using else None
            return CpuBroadcastHashJoinExec(
                jt,
                lp.left_keys,
                lp.right_keys,
                lp.residual,
                plan_physical(lp.left, conf),
                CpuBroadcastExchangeExec(plan_physical(lp.right, conf)),
                drop,
            )
    left = plan_physical(lp.left, conf)
    right = plan_physical(lp.right, conf)
    if lp.left_keys:
        drop = [output_name(k) for k in lp.right_keys] if lp.using else None
        lex = CpuShuffleExchangeExec(P.HashPartitioning(nparts, lp.left_keys), left)
        rex = CpuShuffleExchangeExec(P.HashPartitioning(nparts, lp.right_keys), right)
        return CpuShuffledHashJoinExec(
            lp.join_type, lp.left_keys, lp.right_keys, lp.residual, lex, rex, drop
        )
    if lp.join_type in ("cross", "inner"):
        # pairwise-partition cartesian product (GpuCartesianProductExec:349);
        # outer/semi shapes need global matched-set bookkeeping → NLJ below
        from ..exec.cpu_join import CpuCartesianProductExec

        return CpuCartesianProductExec(lp.residual, left, right)
    return CpuNestedLoopJoinExec(
        lp.join_type,
        lp.residual,
        CpuCoalescePartitionsExec(left),
        CpuCoalescePartitionsExec(right),
    )


# ── kernel pre-compilation pass ─────────────────────────────────────────────
#
# The reference never compiles at query time: cuDF ships pre-built kernels.
# The TPU engine's first touch of each operator pays an XLA compile instead,
# and those compiles SERIALIZE down the pull-based operator chain (cold
# set-up on the chip: PERF.md section 7). This pass
# walks the final (device) exec tree right after planning, derives the exact
# batch geometry of the shape-predictable scan-side chains, and warms every
# distinct kernel through kernels.precompile — concurrently where the
# backend allows, serialized on XLA:CPU (the known concurrent-compile
# SIGSEGV), always warm-starting the persistent on-disk XLA cache so later
# processes skip the compile entirely.

# (id(table), lo, rows) -> (table ref, {col index -> padded width}).
# The entry PINS the table so the id() key stays valid — the same reason
# the H2D upload cache pins its source (exec/tpu.py); without the pin a
# freed table's recycled id could serve stale widths.
_STR_WIDTH_CACHE: dict = {}


def _slice_str_widths(table, schema, max_str: int, lo: int, rows: int):
    """{col index → padded width} for rows [lo, lo+rows) of an in-memory
    scan — the widths ``host_to_device`` will bucket for THAT chunk (it
    buckets per chunk, not per table, so a partition-local max is the one
    the real batch gets). None when a column cannot be shaped (over the
    width ceiling — the real upload raises anyway)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    from ..columnar.device import bucket_width
    from ..types import StringType

    key = (id(table), lo, rows)
    cached = _STR_WIDTH_CACHE.get(key)
    if cached is not None and cached[0] is table:
        return cached[1]
    widths: dict = {}
    for i, f in enumerate(schema):
        if not isinstance(f.data_type, StringType):
            continue
        try:
            col = table.column(f.name).slice(lo, rows)
            ml = pc.max(pc.binary_length(col.cast(pa.binary()))).as_py() or 0
        except Exception:
            return None
        if ml > max_str:
            return None
        widths[i] = bucket_width(max(int(ml), 1))
    if len(_STR_WIDTH_CACHE) > 512:
        _STR_WIDTH_CACHE.clear()
    _STR_WIDTH_CACHE[key] = (table, widths)
    return widths


def _h2d_hints(node, conf: TpuConf) -> Optional[list]:
    """[(capacity, {col index → string width})] geometry variants a
    HostToDeviceExec over an in-memory scan will produce — mirrors the
    exec's re-chunking and host_to_device's per-chunk capacity/width
    bucketing exactly, so a warmed binary is the one the real batches hit."""
    from ..columnar.device import bucket_capacity
    from ..exec.cpu import CpuScanExec
    from ..exec.tpu import _row_bytes
    from ..types import StringType

    child = node.children[0]
    if not isinstance(child, CpuScanExec):
        return None  # file scans: batch geometry depends on file contents
    n = child.table.num_rows
    if n == 0:
        return None
    schema = node.output
    max_rows = max(1, cfg.BATCH_SIZE_BYTES.get(conf) // _row_bytes(schema))
    max_str = cfg.STRING_MAX_BYTES.get(conf)
    has_strings = any(isinstance(f.data_type, StringType) for f in schema)
    per = max(1, -(-n // child.num_partitions))
    hints: dict = {}  # (cap, width tuple) -> (cap, widths)
    for p in range(child.num_partitions):
        lo = min(p * per, n)
        rows = min(lo + per, n) - lo
        if rows <= 0:
            continue
        if rows > max_rows and has_strings:
            # the exec re-chunks this partition; sub-chunk string widths
            # bucket per chunk and are not worth mirroring — skip it
            continue
        widths = _slice_str_widths(child.table, schema, max_str, lo, rows)
        if widths is None:
            continue
        for cap_rows in (
            [rows]
            if rows <= max_rows
            else [max_rows] + ([rows % max_rows] if rows % max_rows else [])
        ):
            cap = bucket_capacity(cap_rows)
            hints.setdefault(
                (cap, tuple(sorted(widths.items()))), (cap, widths)
            )
    return list(hints.values()) or None


def _project_out_hints(exprs, out_schema, hints) -> Optional[list]:
    """Propagate geometry through a projection: capacity is preserved;
    string widths survive only for passthrough (BoundReference) columns —
    a computed string's width is data-dependent and stays unknown, which
    makes any consumer needing it skip its warm (abstract_batch → None)."""
    if not hints:
        return None
    from ..expr.base import Alias, BoundReference
    from ..types import StringType

    out = []
    for cap, widths in hints:
        ow: dict = {}
        for j, (e, f) in enumerate(zip(exprs, out_schema)):
            if not isinstance(f.data_type, StringType):
                continue
            t = e.child if isinstance(e, Alias) else e
            if isinstance(t, BoundReference) and t.ordinal in widths:
                ow[j] = widths[t.ordinal]
        out.append((cap, ow))
    return out


def precompile_plan(plan: Exec, conf: TpuConf) -> dict:
    """Walk the planned exec tree, collect every distinct kernel whose input
    geometry is statically derivable (H2D over in-memory scans → coalesce →
    filter/project chains, plus the fused update-aggregate above them), and
    compile them ahead of execution on the kernels.precompile pool. Returns
    the pool's stats plus the number of kernel specs collected; never
    raises — pre-compilation is an optimization, first touch keeps its own
    error handling."""
    from .. import kernels as K
    from ..columnar.device import abstract_batch
    from ..exec import task as task_mod
    from ..exec import tpu as T
    from .fusion import StageExec

    specs: list = []
    seen: set = set()

    def add(kernel, args) -> None:
        if kernel is None or not hasattr(kernel, "warm"):
            return
        key = (id(kernel), K._args_sig(args))
        if key in seen:
            return
        seen.add(key)
        specs.append((kernel, args))

    def warm_batch_kernel(node, hints) -> None:
        if not hints or node._needs_task:
            return
        for cap, widths in hints:
            ab = abstract_batch(node.children[0].output, cap, widths)
            if ab is not None:
                add(node._fn, (ab, task_mod.abstract_zero_vals()))

    def derive(node) -> Optional[list]:
        if isinstance(node, T.HostToDeviceExec):
            return _h2d_hints(node, conf)
        if isinstance(node, T.TpuCoalesceBatchesExec):
            # pass-through: single-batch partitions (the common in-memory
            # scan shape) cross coalesce untouched; multi-batch concats
            # land on a different capacity and simply miss the warm
            return derive(node.children[0])
        if isinstance(node, T.TpuFilterExec):
            hints = derive(node.children[0])
            warm_batch_kernel(node, hints)
            return hints  # compact() preserves capacity and schema
        if isinstance(node, T.TpuProjectExec):
            hints = derive(node.children[0])
            warm_batch_kernel(node, hints)
            return _project_out_hints(node.exprs, node.output, hints)
        if isinstance(node, StageExec):
            # one warm per input geometry compiles the WHOLE fused stage;
            # output hints fold through the steps exactly as the unfused
            # chain would have propagated them
            hints = derive(node.children[0])
            warm_batch_kernel(node, hints)
            for step in node.fused:
                if step[0] == "project":
                    hints = _project_out_hints(step[1], step[2], hints)
                # filter steps: compact() preserves capacity and schema
            return hints
        if isinstance(node, T.TpuHashAggregateExec):
            child, pre_filter = node._fused_child()
            hints = derive(child)
            if hints and node.mode in ("partial", "complete"):
                try:
                    kernel = node._make_kernel(
                        child.output, pre_filter, cfg.HAS_NANS.get(conf)
                    )
                except Exception:
                    kernel = None
                for cap, widths in hints:
                    ab = abstract_batch(child.output, cap, widths)
                    if ab is not None:
                        add(kernel, (ab,))
            return None  # output group count is data-dependent
        if isinstance(node, T.TpuShuffleExchangeExec):
            # mirror the exchange's filter fusion so a filter kernel that
            # will never run standalone is not warmed
            child = node.children[0]
            if (
                isinstance(child, T.TpuFilterExec)
                and not child._needs_task
                and not T._expr_has_error_site(child.condition)
            ):
                try:
                    kind = node._scatter_fns(node.num_partitions)[0]
                except Exception:
                    kind = None
                if kind in ("hash", "range"):
                    derive(child.children[0])
                    return None
            derive(child)
            return None
        for c in node.children:
            derive(c)
        return None

    empty = {"warmed": 0, "skipped": 0, "failed": 0, "kernels": 0}
    try:
        derive(plan)
    except Exception:
        return empty
    if not specs:
        return empty
    try:
        stats = K.precompile(specs, cfg.PRECOMPILE_PARALLELISM.get(conf))
    except Exception:
        return empty
    stats["kernels"] = len(specs)
    return stats
