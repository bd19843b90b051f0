"""Plan-rewrite layer: replace CPU execs with TPU execs where supported.

Reference: GpuOverrides.scala (rule registries + apply pipeline :2998-3098),
RapidsMeta.scala (tagging with ``willNotWorkOnGpu`` reason bookkeeping),
TypeChecks.scala (per-exec/expr type gating), GpuTransitionOverrides.scala
(transition insertion). The same architecture, compacted:

* every exec and every expression class has a **rule** with an auto-derived
  config kill switch (``spark.rapids.sql.exec.<Name>`` /
  ``spark.rapids.sql.expression.<Name>``) — the reference's
  "every rule can be disabled" invariant,
* a tagging walk collects human-readable reasons per node
  (``willNotWorkOnGpu``), surfaced via ``spark.rapids.sql.explain``,
* a conversion walk replaces supported subtrees and a transition pass inserts
  HostToDevice/DeviceToHost at engine boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

from .. import config as cfg
from ..config import TpuConf
from ..expr import Expression
from ..expr import aggregates as agg
from ..expr import arithmetic as ar
from ..expr import conditional as cond
from ..expr import bitwise as bw
from ..expr import datetime as dtx
from ..expr import math as mx
from ..expr import nullexprs as nx
from ..expr import predicates as pred
from ..expr import strings as st
from ..expr import subquery as sq
from ..expr.base import Alias, BoundReference, Literal, UnresolvedAttribute
from ..expr.cast import Cast, can_cast_on_device
from ..exec import cpu as C
from ..exec import tpu as T
from ..types import (
    DataType,
    DecimalType,
    NullType,
    Schema,
    StringType,
)
from .physical import Exec


# ── TypeSig algebra (TypeChecks.scala:129-367) ─────────────────────────────


class TypeSig:
    """Which data types a rule's inputs may have — the reference's
    type-signature algebra, compacted to a set of type classes combinable
    with ``+``. Rules carry a sig; the tagging walk rejects mismatches with
    a reason naming the offending type, exactly like ``ExprChecks.tag``."""

    def __init__(self, *classes, note: str = ""):
        self.classes = frozenset(classes)
        self.note = note

    def __add__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(*(self.classes | other.classes), note=self.note or other.note)

    def supports(self, dt: DataType) -> bool:
        return isinstance(dt, tuple(self.classes)) if self.classes else True

    def describe(self) -> str:
        names = sorted(c.__name__.replace("Type", "") for c in self.classes)
        return "+".join(names) if names else "any"


def _mk_sigs():
    from ..types import (
        ArrayType,
        BooleanType,
        ByteType,
        DateType,
        DoubleType,
        FloatType,
        IntegerType,
        LongType,
        MapType,
        NullType,
        ShortType,
        StructType,
        TimestampType,
    )

    integral = TypeSig(ByteType, ShortType, IntegerType, LongType)
    fp = TypeSig(FloatType, DoubleType)
    numeric = integral + fp + TypeSig(DecimalType)
    temporal = TypeSig(DateType, TimestampType)
    basic = numeric + temporal + TypeSig(BooleanType, StringType, NullType)
    nested = TypeSig(ArrayType, StructType, MapType)
    return {
        "integral": integral,
        "numeric": numeric,
        "orderable": basic,
        "basic": basic,
        "all": basic + nested,
    }


SIGS = _mk_sigs()


# ── expression rules ───────────────────────────────────────────────────────


class ExprRule:
    def __init__(
        self,
        cls,
        name: str,
        check: Optional[Callable] = None,
        sig: Optional[TypeSig] = None,
    ):
        self.cls = cls
        self.name = name
        self.conf_key = f"spark.rapids.sql.expression.{name}"
        self.check = check  # (expr, conf) -> Optional[str] (reason if bad)
        self.sig = sig  # TypeSig over the expression's child types


def _cast_check(e: Cast, conf: TpuConf) -> Optional[str]:
    if not can_cast_on_device(e.c.data_type, e.to, conf):
        return f"cast {e.c.data_type} -> {e.to} is not supported on device (config-gated)"
    return None


def _contains_ansi_cast(e: Expression) -> bool:
    if isinstance(e, Cast) and e.ansi:
        return True
    return any(_contains_ansi_cast(c) for c in e.children())


# string min/max runs on device via the lexicographic arg-scan
# (ops/aggregate._seg_arglexmin); the TypeSig excludes complex types


def _float_agg_check(e, conf: TpuConf) -> Optional[str]:
    """variableFloatAgg gate (reference RapidsConf.scala): float sums/avgs
    are evaluation-order dependent; when disabled they stay on CPU so the
    row-order result is Spark's."""
    from ..types import DoubleType, FloatType

    if isinstance(e.child.data_type, (FloatType, DoubleType)) and not conf.is_enabled(
        cfg.VARIABLE_FLOAT_AGG
    ):
        return (
            "float/double sum/avg varies with evaluation order; disabled by "
            f"{cfg.VARIABLE_FLOAT_AGG.key}"
        )
    return None


_EXPR_RULES: dict[type, ExprRule] = {}


def _expr(cls, name=None, check=None, sig=None):
    r = ExprRule(cls, name or cls.__name__, check, sig)
    _EXPR_RULES[cls] = r


for _cls in (
    BoundReference,
    Literal,
    Alias,
    UnresolvedAttribute,
    ar.Add,
    ar.Subtract,
    ar.Multiply,
    ar.Divide,
    ar.IntegralDivide,
    ar.Remainder,
    ar.Pmod,
    ar.UnaryMinus,
    ar.UnaryPositive,
    ar.Abs,
    pred.EqualTo,
    pred.EqualNullSafe,
    pred.LessThan,
    pred.LessThanOrEqual,
    pred.GreaterThan,
    pred.GreaterThanOrEqual,
    pred.And,
    pred.Or,
    pred.Not,
    pred.IsNull,
    pred.IsNotNull,
    pred.IsNaN,
    pred.In,
    sq.InSet,
    cond.If,
    cond.CaseWhen,
    cond.Coalesce,
    agg.Count,
    agg.First,
    agg.Last,
):
    _expr(_cls)
_expr(agg.Sum, check=_float_agg_check, sig=SIGS["numeric"])
_expr(agg.Average, check=_float_agg_check, sig=SIGS["numeric"])
_expr(Cast, check=_cast_check)
_expr(agg.Min, sig=SIGS["orderable"])
_expr(agg.Max, sig=SIGS["orderable"])
for _cls in (agg.StddevSamp, agg.StddevPop, agg.VarianceSamp, agg.VariancePop,
             agg.CovarPop, agg.CovarSamp, agg.Corr):
    _expr(_cls)


def _collect_check(e, conf: TpuConf) -> Optional[str]:
    from ..types import is_complex

    if is_complex(e.child.data_type):
        return "collect over nested element types is not supported on device"
    return None


_expr(agg.CollectList, check=_collect_check)
_expr(agg.CollectSet, check=_collect_check)


def _merge_lists_check(e, conf: TpuConf) -> Optional[str]:
    return (
        "merging partial collect arrays (collect alongside DISTINCT "
        "aggregates) runs on the CPU engine"
    )


_expr(agg.MergeLists, check=_merge_lists_check)
_expr(agg.MergeSets, check=_merge_lists_check)


# string rules — device paths that need a scalar pattern are gated exactly
# like the reference (GpuOverrides requires Literal for like/contains/replace
# search operands: GpuOverrides.scala string rules)
def _lit_check(attr: str, what: str):
    def check(e, conf: TpuConf) -> Optional[str]:
        if not st.is_string_literal(getattr(e, attr)):
            return f"{what} must be a string literal for the device path"
        return None

    return check


def _pad_check(e, conf: TpuConf) -> Optional[str]:
    p = e.pad
    if not st.is_string_literal(p):
        return "pad must be a string literal for the device path"
    if len(p.value.encode("utf-8")) != 1:
        return "device pad requires a single-byte pad string"
    if not isinstance(e.length, Literal):
        return "pad length must be a literal for the device path"
    return None


def _locate_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.substr):
        return "locate substring must be a string literal for the device path"
    if not isinstance(e.start, Literal):
        return "locate start must be a literal for the device path"
    return None


def _like_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.pattern):
        return "LIKE pattern must be a string literal for the device path"
    try:
        st.like_tokens(e.pattern.value, e.escape)
    except ValueError as ex:
        return str(ex)
    return None


def _repeat_check(e, conf: TpuConf) -> Optional[str]:
    if not isinstance(e.times, Literal):
        return "repeat count must be a literal for the device path"
    return None


def _replace_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.search) or not st.is_string_literal(e.replacement):
        return "replace search/replacement must be string literals for the device path"
    return None


def _trim_check(e, conf: TpuConf) -> Optional[str]:
    if e.trim_str is not None and not st.is_string_literal(e.trim_str):
        return "trim character set must be a string literal for the device path"
    return None


for _cls in (
    st.Length,
    st.Upper,
    st.Lower,
    st.InitCap,
    st.Reverse,
    st.Ascii,
    st.Substring,
    st.Concat,
):
    _expr(_cls)
_expr(st.StartsWith, check=_lit_check("pattern", "startswith pattern"))
_expr(st.EndsWith, check=_lit_check("pattern", "endswith pattern"))
_expr(st.Contains, check=_lit_check("pattern", "contains pattern"))
_expr(st.Like, check=_like_check)
_expr(st.StringReplace, check=_replace_check)
_expr(st.StringRepeat, check=_repeat_check)
_expr(st.StringLocate, check=_locate_check)


def _substring_index_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.delim):
        return "substring_index delimiter must be a string literal for the device path"
    if not isinstance(e.count, Literal):
        return "substring_index count must be a literal for the device path"
    return None


_expr(st.SubstringIndex, check=_substring_index_check)
_expr(st.StringLPad, check=_pad_check)
_expr(st.StringRPad, check=_pad_check)
_expr(st.StringTrim, check=_trim_check)
_expr(st.StringTrimLeft, check=_trim_check)
_expr(st.StringTrimRight, check=_trim_check)

def _interval_check(e, conf: TpuConf) -> Optional[str]:
    """Literal-interval gate, the reference's GpuTimeAdd/GpuDateAddInterval
    restriction (GpuOverrides.scala:1348,1369)."""
    from ..types import CalendarIntervalType

    itv = e.interval
    if not (isinstance(itv, Literal) and isinstance(itv.data_type, CalendarIntervalType)):
        return "interval operand must be a literal CalendarInterval for the device path"
    if isinstance(e, dtx.DateAddInterval) and itv.value[2] != 0:
        return "date + interval with a sub-day component is an error in Spark"
    return None


_expr(dtx.TimeAdd, check=_interval_check)
_expr(dtx.DateAddInterval, check=_interval_check)

for _cls in (
    dtx.Year,
    dtx.Month,
    dtx.DayOfMonth,
    dtx.Quarter,
    dtx.DayOfWeek,
    dtx.WeekDay,
    dtx.WeekOfYear,
    dtx.DayOfYear,
    dtx.LastDay,
    dtx.DateAdd,
    dtx.DateSub,
    dtx.DateDiff,
    dtx.AddMonths,
    dtx.Hour,
    dtx.Minute,
    dtx.Second,
    dtx.UnixTimestamp,
):
    _expr(_cls)

for _cls in (
    mx.Sqrt, mx.Cbrt, mx.Exp, mx.Expm1, mx.Sin, mx.Cos, mx.Tan,
    mx.Asin, mx.Acos, mx.Atan, mx.Sinh, mx.Cosh, mx.Tanh,
    mx.Asinh, mx.Acosh, mx.Atanh, mx.Cot,
    mx.ToDegrees, mx.ToRadians, mx.Rint, mx.Signum,
    mx.Log, mx.Log10, mx.Log2, mx.Log1p, mx.Logarithm,
    mx.Pow, mx.Atan2, mx.Hypot, mx.Floor, mx.Ceil,
    nx.NaNvl, nx.Nvl2, nx.AtLeastNNonNulls,
):
    _expr(_cls)
for _cls in (
    bw.BitwiseAnd, bw.BitwiseOr, bw.BitwiseXor, bw.BitwiseNot,
    bw.ShiftLeft, bw.ShiftRight, bw.ShiftRightUnsigned,
):
    _expr(_cls, sig=SIGS["integral"])


def _round_check(e, conf: TpuConf) -> Optional[str]:
    from ..types import IntegralType as _IT

    if not isinstance(e.scale, Literal):
        return "round scale must be a literal for the device path"
    if not isinstance(e.child.data_type, _IT) and not cfg.INCOMPATIBLE_OPS.get(conf):
        # reference gates float round the same way: "may round slightly
        # differently" under isIncompatEnabled (GpuOverrides.scala:2036-2077)
        return (
            "round on floating point may round slightly differently than "
            "Spark's java BigDecimal semantics; enable "
            "spark.rapids.sql.incompatibleOps.enabled"
        )
    return None


def _greatest_check(e, conf: TpuConf) -> Optional[str]:
    if any(isinstance(x.data_type, StringType) for x in e.exprs):
        return "greatest/least over strings is CPU-only"
    return None


_expr(mx.Round, check=_round_check)
_expr(mx.BRound, check=_round_check)
_expr(nx.Greatest, check=_greatest_check)
_expr(nx.Least, check=_greatest_check)


# ── window expressions (GpuWindowExpression gating) ────────────────────────
def _window_check(e, conf: TpuConf) -> Optional[str]:
    from ..expr import windows as W

    fn = e.function
    fr = e.spec.resolved_frame()
    if isinstance(
        fn,
        (W.Rank, W.DenseRank, W.RowNumber, W.PercentRank, W.CumeDist, W.NTile),
    ):
        if not e.spec.order_by:
            return "ranking window functions require ORDER BY"
        return None
    if isinstance(fn, (W.Lead, W.Lag)):
        return None
    if isinstance(fn, (agg.Sum, agg.Count, agg.Min, agg.Max, agg.Average)):
        sentinels = (W.UNBOUNDED_PRECEDING, W.CURRENT_ROW, W.UNBOUNDED_FOLLOWING)
        if fr.frame_type == "range" and not (
            fr.lower in sentinels and fr.upper in sentinels
        ):
            # numeric RANGE frames: value-space binary searches over ONE
            # numeric/temporal order key (Spark's own restriction)
            if len(e.spec.order_by) != 1:
                return "numeric RANGE frames require exactly one ORDER BY key"
            ot = e.spec.order_by[0].child.data_type
            from ..types import is_numeric

            # decimal keys compare unscaled with scale-adjusted bounds
            # (exec/tpu_window.py); strings and other non-numeric keys
            # have no value-space offset semantics
            if isinstance(ot, StringType) or not (
                is_numeric(ot) or ot.__class__.__name__ in ("DateType", "TimestampType")
            ):
                return f"numeric RANGE frame over {ot.simple_string} is CPU-only"
        return None
    return f"window function {type(fn).__name__} has no device implementation"


from ..expr import windows as _W  # noqa: E402

_expr(_W.WindowExpression, check=_window_check)
for _cls in (_W.RowNumber, _W.Rank, _W.DenseRank, _W.Lead, _W.Lag,
             _W.PercentRank, _W.CumeDist, _W.NTile):
    _expr(_cls)


# ── hash / task-context expressions (HashFunctions.scala, GpuSparkPartitionID,
#    GpuMonotonicallyIncreasingID, GpuInputFileBlock, GpuRand) ───────────────
from ..expr import misc as msc  # noqa: E402


def _rand_check(e, conf: TpuConf) -> Optional[str]:
    if not cfg.INCOMPATIBLE_OPS.get(conf):
        return (
            "rand() on device is not bit-identical to Spark's XORShiftRandom "
            "stream; enable spark.rapids.sql.incompatibleOps.enabled"
        )
    return None


for _cls in (
    msc.Murmur3Hash,
    msc.Md5,
    msc.SparkPartitionID,
    msc.MonotonicallyIncreasingID,
    msc.InputFileName,
    msc.InputFileBlockStart,
    msc.InputFileBlockLength,
    msc.NormalizeNaNAndZero,
):
    _expr(_cls)
_expr(msc.Rand, check=_rand_check)


# ── complex-type expressions (complexTypeCreator/Extractors,
#    collectionOperations.scala) ──────────────────────────────────────────
from ..expr import complex as cx  # noqa: E402


def _complex_child_check(e, conf: TpuConf) -> Optional[str]:
    dt = e.child.data_type
    if not _device_type_ok(dt):
        return f"{dt.simple_string} exceeds the device nesting support"
    return None


for _cls in (cx.CreateArray, cx.CreateNamedStruct):
    _expr(_cls)
_expr(cx.Size, check=_complex_child_check)
_expr(cx.GetStructField, check=_complex_child_check)
_expr(cx.GetArrayItem, check=_complex_child_check)
_expr(cx.ElementAt, check=_complex_child_check)
_expr(cx.GetMapValue, check=_complex_child_check)
_expr(cx.ArrayContains, check=_complex_child_check)
_expr(cx.Explode, check=_complex_child_check)


# ── string long tail + datetime patterns (stringFunctions.scala,
#    datetimeExpressions.scala) ───────────────────────────────────────────
from ..expr import strings_ext as sx  # noqa: E402
from ..expr import datetime_fmt as df  # noqa: E402


def _translate_check(e, conf: TpuConf) -> Optional[str]:
    if not sx.translate_args_ascii(e):
        return "translate on device requires ASCII literal from/to arguments"
    return None


def _cpu_regex_check(what: str):
    def check(e, conf: TpuConf) -> Optional[str]:
        return (
            f"{what} executes on the CPU engine (the reference leans on "
            "cuDF's device regex/JSON engines — no XLA analogue)"
        )

    return check


def _fmt_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.fmt):
        return "datetime pattern must be a string literal"
    # parsers scan fixed offsets, so unpadded single-letter tokens are
    # format-only (ToUnixTimestamp/ParseToDate reject them)
    if not df.pattern_supported(e.fmt.value):
        return (
            f"datetime pattern {e.fmt.value!r} is outside the device-"
            "supported token subset (yyyy MM dd HH mm ss + literals; "
            "y M d H m s when formatting)"
        )
    return None


_expr(sx.ConcatWs)
_expr(sx.StringTranslate, check=_translate_check)
def _split_check(e, conf: TpuConf) -> Optional[str]:
    from ..expr.strings_ext import split_device_pattern

    if not st.is_string_literal(e.pattern):
        return "split pattern must be a string literal for the device path"
    if split_device_pattern(e.pattern.value) is None:
        return (
            "only literal / plain char-class split patterns run on device "
            "(full regex is CPU-only, like the reference's "
            "GpuStringSplitMeta gate)"
        )
    return None


_expr(sx.StringSplit, check=_split_check)
_expr(sx.RLike, check=_cpu_regex_check("rlike"))
_expr(sx.RegExpReplace, check=_cpu_regex_check("regexp_replace"))
_expr(sx.RegExpExtract, check=_cpu_regex_check("regexp_extract"))
def _get_json_check(e, conf: TpuConf) -> Optional[str]:
    if not st.is_string_literal(e.path):
        return "get_json_object path must be a string literal"
    if not cfg.GET_JSON_OBJECT_DEVICE.get(conf):
        return (
            "device get_json_object returns raw value spans (no Jackson "
            "re-serialization / unescaping, like the reference's cudf "
            f"kernel); enable {cfg.GET_JSON_OBJECT_DEVICE.key} to accept "
            "the divergence (docs/compatibility.md)"
        )
    return None


_expr(sx.GetJsonObject, check=_get_json_check)
_expr(df.DateFormatClass, check=_fmt_check)
_expr(df.FromUnixTime, check=_fmt_check)
_expr(df.ToUnixTimestamp, check=_fmt_check)
_expr(df.ParseToDate, check=_fmt_check)


# ── UDFs (GpuUserDefinedFunction / GpuArrowEvalPythonExec seam) ───────────
from ..expr import udf as _udf  # noqa: E402

_expr(_udf.JaxUdf)
_expr(
    _udf.PythonUdf,
    check=lambda e, conf: (
        "python row UDFs execute on the CPU engine (register a jax_udf for "
        "device execution — it fuses into the XLA program)"
    ),
)


def expr_rules() -> dict[type, ExprRule]:
    return dict(_EXPR_RULES)


def _check_expr_tree(e: Expression, conf: TpuConf, reasons: List[str]) -> bool:
    ok = True
    rule = _EXPR_RULES.get(type(e))
    if rule is None:
        reasons.append(f"expression {type(e).__name__} has no device implementation")
        ok = False
    else:
        if not conf.rule_enabled(rule.conf_key):
            reasons.append(f"expression {rule.name} disabled by {rule.conf_key}")
            ok = False
        else:
            if rule.sig is not None:
                for c in e.children():
                    try:
                        dt = c.data_type
                    except TypeError:
                        continue  # unresolved — bound later
                    if not rule.sig.supports(dt):
                        reasons.append(
                            f"{rule.name} input type {dt.simple_string} is "
                            f"outside its device signature "
                            f"({rule.sig.describe()})"
                        )
                        ok = False
            if ok and rule.check is not None:
                why = rule.check(e, conf)
                if why:
                    reasons.append(why)
                    ok = False
    for c in e.children():
        ok = _check_expr_tree(c, conf, reasons) and ok
    return ok


# ── type gating (TypeChecks analogue) ──────────────────────────────────────


def _device_type_ok(dt: DataType) -> bool:
    """Types with a device layout: primitives/strings/decimal64, plus ONE
    level of array/struct/map nesting over them (deeper nesting has no
    padded-plane encoding yet — those plans stay on CPU)."""
    from ..types import ArrayType, MapType, StructType, is_complex

    def scalar_ok(t: DataType) -> bool:
        return not is_complex(t)

    if isinstance(dt, ArrayType):
        return scalar_ok(dt.element_type)
    if isinstance(dt, MapType):
        return scalar_ok(dt.key_type) and scalar_ok(dt.value_type)
    if isinstance(dt, StructType):
        return all(scalar_ok(f.data_type) for f in dt.fields)
    return True


def _check_schema(schema: Schema, conf: TpuConf, reasons: List[str], where: str) -> bool:
    ok = True
    for f in schema:
        dt = f.data_type
        if isinstance(dt, DecimalType) and not conf.is_enabled(cfg.DECIMAL_ENABLED):
            reasons.append(f"{where}: decimal disabled by {cfg.DECIMAL_ENABLED.key}")
            ok = False
        if not _device_type_ok(dt):
            reasons.append(
                f"{where}: {dt.simple_string} exceeds the device nesting support"
            )
            ok = False
        # every other supported type maps to the device layout
    return ok


def _no_complex_keys(exprs, what: str):
    """Exec-level check: complex types cannot be sort/group/join/partition
    keys on device (no radix-word encoding — reference gates these the same
    way via TypeSig key signatures)."""
    from ..types import is_complex

    def check(e, conf: TpuConf) -> Optional[str]:
        for k in exprs(e):
            if is_complex(k.data_type):
                return f"{what} of type {k.data_type.simple_string} is not supported on device"
        return None

    return check


# ── exec rules ─────────────────────────────────────────────────────────────


class ExecRule:
    def __init__(self, cls, name: str, convert, exprs_of, note: str = "", check=None):
        self.cls = cls
        self.name = name
        self.conf_key = f"spark.rapids.sql.exec.{name}"
        self.convert = convert  # (cpu_exec, children) -> Exec
        self.exprs_of = exprs_of  # (cpu_exec) -> list[Expression]
        self.check = check  # (cpu_exec, conf) -> Optional[str]


_EXEC_RULES: dict[type, ExecRule] = {}


def _rule(cls, name, convert, exprs_of, check=None):
    _EXEC_RULES[cls] = ExecRule(cls, name, convert, exprs_of, check=check)


def _conv_project(e: C.CpuProjectExec, ch):
    return T.TpuProjectExec(e.exprs, ch[0], schema=e.output)


def _conv_filter(e: C.CpuFilterExec, ch):
    return T.TpuFilterExec(e.condition, ch[0])


def _conv_agg(e: C.CpuHashAggregateExec, ch):
    t = T.TpuHashAggregateExec(
        e.mode, e.grouping, e.agg_fns, e.result_exprs, e.result_names, ch[0]
    )
    t._schema = e.output
    return t


def _conv_sort(e: C.CpuSortExec, ch):
    return T.TpuSortExec(e.order, ch[0])


def _conv_exchange(e: C.CpuShuffleExchangeExec, ch):
    return T.TpuShuffleExchangeExec(e.partitioning, ch[0])


def _conv_union(e: C.CpuUnionExec, ch):
    return T.TpuUnionExec(ch)


def _conv_coalesce(e: C.CpuCoalescePartitionsExec, ch):
    return T.TpuCoalescePartitionsExec(ch[0])


def _conv_limit(e: C.CpuLimitExec, ch):
    return T.TpuLimitExec(e.n, ch[0])


def _conv_topn(e: C.CpuTakeOrderedAndProjectExec, ch):
    return T.TpuTakeOrderedAndProjectExec(e.n, e.order, ch[0])


def _conv_expand(e: C.CpuExpandExec, ch):
    return T.TpuExpandExec(e.projections, e.output.names, ch[0])


_rule(C.CpuProjectExec, "ProjectExec", _conv_project, lambda e: e.exprs)
_rule(C.CpuFilterExec, "FilterExec", _conv_filter, lambda e: [e.condition])
_rule(
    C.CpuHashAggregateExec,
    "HashAggregateExec",
    _conv_agg,
    lambda e: e.grouping + list(e.agg_fns) + (e.result_exprs or []),
    check=_no_complex_keys(lambda e: e.grouping, "grouping key"),
)
_rule(
    C.CpuSortExec,
    "SortExec",
    _conv_sort,
    lambda e: [o.child for o in e.order],
    check=_no_complex_keys(lambda e: [o.child for o in e.order], "sort key"),
)
_rule(
    C.CpuShuffleExchangeExec,
    "ShuffleExchangeExec",
    _conv_exchange,
    lambda e: e.partitioning.exprs(),
    check=_no_complex_keys(lambda e: e.partitioning.exprs(), "partition key"),
)
_rule(C.CpuUnionExec, "UnionExec", _conv_union, lambda e: [])
_rule(
    C.CpuCoalescePartitionsExec,
    "CoalescePartitionsExec",
    _conv_coalesce,
    lambda e: [],
)
_rule(C.CpuLimitExec, "CollectLimitExec", _conv_limit, lambda e: [])


def _conv_range(e: C.CpuRangeExec, ch):
    return T.TpuRangeExec(e)


_rule(C.CpuRangeExec, "RangeExec", _conv_range, lambda e: [])
_rule(
    C.CpuTakeOrderedAndProjectExec,
    "TakeOrderedAndProjectExec",
    _conv_topn,
    lambda e: [o.child for o in e.order],
    check=_no_complex_keys(lambda e: [o.child for o in e.order], "sort key"),
)
_rule(
    C.CpuExpandExec,
    "ExpandExec",
    _conv_expand,
    lambda e: [x for proj in e.projections for x in proj],
)


def _conv_join(e, ch):
    from ..exec.tpu_join import TpuShuffledHashJoinExec

    return TpuShuffledHashJoinExec(
        e.join_type,
        e.left_keys,
        e.right_keys,
        e.residual,
        ch[0],
        ch[1],
        e.drop_right_keys,
    )


def _join_exprs_of(e):
    out = list(e.left_keys) + list(e.right_keys)
    if e.residual is not None:
        out.append(e.residual)
    return out


from ..exec.cpu_join import CpuShuffledHashJoinExec as _CpuSHJ  # noqa: E402
from ..exec.cpu_join import (  # noqa: E402
    CpuBroadcastExchangeExec as _CpuBE,
    CpuBroadcastHashJoinExec as _CpuBHJ,
    CpuNestedLoopJoinExec as _CpuNLJ,
)

_join_key_check = _no_complex_keys(
    lambda e: list(e.left_keys) + list(e.right_keys), "join key"
)
_rule(_CpuSHJ, "ShuffledHashJoinExec", _conv_join, _join_exprs_of, check=_join_key_check)


def _conv_bhj(e, ch):
    from ..exec.tpu_join import TpuBroadcastHashJoinExec

    return TpuBroadcastHashJoinExec(
        e.join_type,
        e.left_keys,
        e.right_keys,
        e.residual,
        ch[0],
        ch[1],
        e.drop_right_keys,
    )


def _conv_bexchange(e, ch):
    from ..exec.tpu_join import TpuBroadcastExchangeExec

    return TpuBroadcastExchangeExec(ch[0])


def _conv_nlj(e, ch):
    from ..exec.tpu_join import TpuBroadcastNestedLoopJoinExec

    return TpuBroadcastNestedLoopJoinExec(e.join_type, e.condition, ch[0], ch[1])


def _conv_cartesian(e, ch):
    from ..exec.tpu_join import TpuCartesianProductExec

    return TpuCartesianProductExec("inner", e.condition, ch[0], ch[1])


from ..exec.cpu_join import CpuCartesianProductExec as _CpuCart  # noqa: E402

_rule(
    _CpuCart,
    "CartesianProductExec",
    _conv_cartesian,
    lambda e: [e.condition] if e.condition is not None else [],
)

_rule(_CpuBE, "BroadcastExchangeExec", _conv_bexchange, lambda e: [])
_rule(_CpuBHJ, "BroadcastHashJoinExec", _conv_bhj, _join_exprs_of, check=_join_key_check)
_rule(
    _CpuNLJ,
    "BroadcastNestedLoopJoinExec",
    _conv_nlj,
    lambda e: [e.condition] if e.condition is not None else [],
)


def _conv_window(e, ch):
    from ..exec.tpu_window import TpuWindowExec

    return TpuWindowExec(e.window_cols, ch[0])


def _window_exprs_of(e):
    out = []
    for _, we in e.window_cols:
        out.append(we)
    out.extend(e.spec.partition_by)
    out.extend(o.child for o in e.spec.order_by)
    return out


from ..exec.cpu_window import CpuWindowExec as _CpuWin  # noqa: E402

_rule(
    _CpuWin,
    "WindowExec",
    _conv_window,
    _window_exprs_of,
    check=_no_complex_keys(
        lambda e: list(e.spec.partition_by) + [o.child for o in e.spec.order_by],
        "window key",
    ),
)


def _conv_generate(e: C.CpuGenerateExec, ch):
    return T.TpuGenerateExec(e, ch[0])


_rule(
    C.CpuGenerateExec,
    "GenerateExec",
    _conv_generate,
    lambda e: [e.generator],
)


def exec_rules() -> dict[type, ExecRule]:
    return dict(_EXEC_RULES)


# ── the override pass ──────────────────────────────────────────────────────


@dataclasses.dataclass
class ExplainEntry:
    node: str
    on_device: bool
    reasons: List[str]


class TpuOverrides:
    """GpuOverrides + GpuTransitionOverrides, applied to a CPU physical plan.

    ``breaker`` (resilience/breaker.py) is the session's CPU-fallback
    circuit breaker: op signatures whose device kernels failed repeatedly
    at RUNTIME are marked CPU-fallback here at the next planning pass,
    with the reason in the explain output — the same surface a plan-time
    fallback uses."""

    def __init__(self, conf: TpuConf, breaker=None):
        self.conf = conf
        self.breaker = breaker
        self.explain: List[ExplainEntry] = []
        # cost-model source: the hardcoded per-op weights, or — when
        # spark.rapids.tpu.cbo.measuredWeights holds and the persisted
        # calibration table (obs/calibration.py) has measured device
        # costs — measured ns/row normalized into the same integer-weight
        # currency. With the conf off or the table absent/empty this is
        # EXACTLY the hardcoded dict: planning stays bit-identical.
        self._cbo_weights = self._CBO_WEIGHTS
        self._cbo_source = "default"
        if cfg.CBO_MEASURED_WEIGHTS.get(conf):
            from ..obs.calibration import load_weights

            measured = load_weights(cfg.CBO_CALIBRATION_FILE.get(conf))
            if measured:
                self._cbo_weights = measured
                self._cbo_source = "measured"
        # calibrated engine routing: with measured per-op ns/row present,
        # predict each device island's device-vs-host time and route
        # sub-threshold islands (tiny input, full dispatch+transfer tax —
        # the q6/q15 shape) back to the CPU engine. No calibration data or
        # conf off: planning is unchanged.
        self._routing_cal = None
        if cfg.ROUTING_ENABLED.get(conf):
            from ..obs import calibration as obs_cal

            cal = obs_cal.get(cfg.CBO_CALIBRATION_FILE.get(conf))
            if cal.snapshot():
                self._routing_cal = cal

    def apply(self, plan: Exec) -> Exec:
        if not self.conf.is_enabled(cfg.SQL_ENABLED):
            return plan
        converted = self._convert(plan)
        if self.conf.is_enabled(cfg.CBO_ENABLED):
            converted = self._cost_optimize(converted)
        if self._routing_cal is not None:
            converted = self._route(converted)
        if converted.is_device:
            # the query root funnels to the driver anyway (collect); merging
            # partitions ON DEVICE first lets the D2H window concatenate
            # small result batches into one transfer — each device→host pull
            # is a host sync that stalls dispatch
            converted = T.TpuCoalescePartitionsExec(converted)
        out = self._insert_transitions(converted, want_device=False)
        self._maybe_log()
        return out

    # cost-based un-conversion (CostBasedOptimizer.scala:29-310) ───────────
    # DefaultCostModel stand-in: per-node compute weights; a contiguous
    # device island pays two transitions, so islands whose total weight is
    # below the threshold go back to the CPU engine.
    _CBO_WEIGHTS = {
        "TpuProjectExec": 1,
        "TpuFilterExec": 1,
        "TpuLimitExec": 1,
        "TpuCoalescePartitionsExec": 0,
    }
    _CBO_TRANSITION_COST = 3

    def _island_weight(self, plan: Exec) -> int:
        """Total weight of the contiguous device region rooted here (host
        children are the island's boundaries). Weights come from the
        active cost table: hardcoded, or measured (calibration) when the
        conf selected it — unknown ops default heavy either way (a node
        nobody measured is assumed worth keeping on device)."""
        w = self._cbo_weights.get(type(plan).__name__, 10)
        for c in plan.children:
            if c.is_device:
                w += self._island_weight(c)
        return w

    def _unconvert_island(
        self,
        plan: Exec,
        weight: Optional[int] = None,
        reason: Optional[str] = None,
        again: Optional[Callable] = None,
    ) -> Exec:
        """Put a device island back on the CPU engine via each node's
        ``_cpu_original`` seam. ``reason`` is the explain message (default:
        the CBO island-weight wording, with the numeric detail only at the
        root where ``weight`` is passed); ``again`` is the pass to resume on
        the island's host children (default: CBO cost analysis — the
        routing pass hands itself in)."""
        if again is None:
            again = self._cost_optimize
        if not plan.is_device:
            return again(plan)
        kids = [
            self._unconvert_island(c, reason=reason, again=again)
            for c in plan.children
        ]
        orig = getattr(plan, "_cpu_original", None)
        if orig is None:
            return plan.with_new_children(kids)
        if reason is None:
            detail = (
                f" ({self._cbo_source} weights: island {weight} < "
                f"transition cost {self._CBO_TRANSITION_COST})"
                if weight is not None
                else ""
            )
            node_reason = (
                "cost-based optimizer: island too small to pay "
                f"transitions{detail}"
            )
        else:
            node_reason = reason
        self.explain.append(
            ExplainEntry(orig.node_string(), False, [node_reason])
        )
        return orig.with_new_children(kids)

    def _keep_island(self, plan: Exec, again: Optional[Callable] = None) -> Exec:
        """Inside a kept island: never re-evaluate interior sub-islands (the
        transition boundary wouldn't move, only device work would be lost);
        resume cost analysis below the island's host boundaries."""
        if again is None:
            again = self._cost_optimize
        kids = [
            self._keep_island(c, again) if c.is_device else again(c)
            for c in plan.children
        ]
        return plan.with_new_children(kids)

    def _cost_optimize(self, plan: Exec) -> Exec:
        if plan.is_device:
            w = self._island_weight(plan)
            if w < self._CBO_TRANSITION_COST:
                return self._unconvert_island(plan, w)
            return self._keep_island(plan)
        return plan.with_new_children(
            [self._cost_optimize(c) for c in plan.children]
        )

    # calibrated engine routing ────────────────────────────────────────────
    # The CBO above reasons in unitless weights; this pass reasons in
    # *nanoseconds*. With a measured cost table (obs/calibration.py) it
    # predicts each device island's wall time on both engines — per-op
    # ns/row times the island's estimated input rows, plus the fixed
    # per-launch dispatch and H2D/D2H transfer taxes the ledger measured —
    # and sends the island to whichever engine is predicted faster. The
    # q6/q15 shape (one tiny filter+agg over a small scan) loses more to
    # dispatch+transfer than the device saves in compute; the prediction
    # makes that decision auditable instead of folkloric.

    #: plumbing nodes with no per-row ns of their own — they ride along
    #: with whatever engine the island lands on
    _ROUTING_FREE = frozenset(
        {"TpuCoalescePartitionsExec", "TpuCoalesceBatchesExec"}
    )

    def _route(self, plan: Exec) -> Exec:
        if plan.is_device:
            reason = self._route_verdict(plan)
            if reason is not None:
                return self._unconvert_island(
                    plan, reason=reason, again=self._route
                )
            return self._keep_island(plan, again=self._route)
        return plan.with_new_children(
            [self._route(c) for c in plan.children]
        )

    def _route_verdict(self, plan: Exec) -> Optional[str]:
        """Predicted-time comparison for the island rooted at ``plan``.
        Returns the explain reason when the HOST engine is predicted
        faster (island should be unconverted), None to stay on device.
        Conservative by construction: any node either engine has no
        measurement for, or an island with no estimable input rows, stays
        on device — routing only ever acts on numbers it actually has."""
        from ..sched.estimate import _leaf_bytes_rows, _walk as _est_walk

        cal = self._routing_cal
        island: List[Exec] = []
        boundary_rows = 0

        def collect(n: Exec) -> None:
            island.append(n)
            for c in n.children:
                if c.is_device:
                    collect(c)

        collect(plan)
        # input rows: what the host boundaries feed the island. Leaf
        # sources *inside* the island (TpuRangeExec) count too.
        for n in island:
            lb = _leaf_bytes_rows(n)
            if lb is not None:
                boundary_rows += lb[1]
            for c in n.children:
                if not c.is_device:
                    boundary_rows += sum(
                        r
                        for leaf in _est_walk(c)
                        for (_b, r) in [_leaf_bytes_rows(leaf) or (0, 0)]
                    )
        if boundary_rows <= 0:
            return None
        device_ns = 0.0
        host_ns = 0.0
        launches = 0
        op_detail = []
        for n in island:
            tpu_name = type(n).__name__
            if tpu_name in self._ROUTING_FREE:
                continue
            orig = getattr(n, "_cpu_original", None)
            if orig is None:
                return None  # no CPU form to route to
            cpu_name = type(orig).__name__
            d = cal.ns_per_row(tpu_name, device=True)
            h = cal.ns_per_row(cpu_name, device=False)
            if d is None or h is None:
                return None  # unmeasured op: keep on device
            device_ns += d * boundary_rows
            host_ns += h * boundary_rows
            launches += 1
            op_detail.append(f"{tpu_name} {d:g}ns/row vs {cpu_name} {h:g}ns/row")
        if not launches:
            return None
        device_ns += (
            launches * cfg.ROUTING_LAUNCH_OVERHEAD_NS.get(self.conf)
            + cfg.ROUTING_TRANSFER_OVERHEAD_NS.get(self.conf)
        )
        if device_ns <= host_ns:
            return None
        return (
            "calibrated routing: predicted device "
            f"{device_ns / 1e6:.3f}ms > host {host_ns / 1e6:.3f}ms "
            f"for ~{boundary_rows} rows over {launches} launches "
            f"({'; '.join(op_detail)})"
        )

    # conversion walk (meta.tagForGpu + convertIfNeeded)
    def _convert(self, plan: Exec) -> Exec:
        children = [self._convert(c) for c in plan.children]
        rule = _EXEC_RULES.get(type(plan))
        reasons: List[str] = []
        if rule is None:
            if not isinstance(plan, (T.HostToDeviceExec, T.DeviceToHostExec)):
                reasons.append(
                    f"exec {type(plan).__name__} has no device implementation"
                )
            self.explain.append(
                ExplainEntry(plan.node_string(), False, reasons)
            )
            return plan.with_new_children(children)
        breaker_reason = (
            self.breaker.check(rule.name) if self.breaker is not None else None
        )
        if not self.conf.rule_enabled(rule.conf_key):
            reasons.append(f"disabled by {rule.conf_key}")
        elif breaker_reason:
            reasons.append(breaker_reason)
        else:
            _check_schema(plan.output, self.conf, reasons, rule.name)
            if rule.check is not None:
                why = rule.check(plan, self.conf)
                if why:
                    reasons.append(why)
            for e in rule.exprs_of(plan):
                _check_expr_tree(e, self.conf, reasons)
            if not isinstance(plan, (C.CpuProjectExec, C.CpuFilterExec)):
                # the ANSI error channel is wired through the project/filter
                # kernels only; ANSI casts elsewhere fall back so errors
                # still raise (CPU eval raises inline)
                for e in rule.exprs_of(plan):
                    if _contains_ansi_cast(e):
                        reasons.append(
                            "ANSI-mode cast outside project/filter runs on "
                            "CPU (device error channel not wired here)"
                        )
                        break
        if reasons:
            self.explain.append(ExplainEntry(plan.node_string(), False, reasons))
            return plan.with_new_children(children)
        self.explain.append(ExplainEntry(plan.node_string(), True, []))
        converted = rule.convert(plan, children)
        converted._cpu_original = plan  # CBO un-conversion seam
        return converted

    # transition insertion (GpuTransitionOverrides)
    #
    # (helper lives at module level: _node_has_input_file_expr)
    def _insert_transitions(
        self, plan: Exec, want_device: bool, under_input_file: bool = False
    ) -> Exec:
        # input_file_name()/_block_*() read per-batch task state, so the
        # scan→expression path must keep per-file batches: the coalesce
        # disable propagates DOWN from the expression-bearing node and
        # resets at exchanges (batches above a shuffle are mixed-file
        # already — Spark reports "" there). Scoped like the reference's
        # GpuTransitionOverrides input-file handling (:84-170), not
        # plan-wide: transitions on other branches keep coalescing.
        local = _node_has_input_file_expr(plan)
        is_exchange = isinstance(
            plan, (T.TpuShuffleExchangeExec, C.CpuShuffleExchangeExec)
        )
        child_flag = False if is_exchange else (under_input_file or local)
        new_children = [
            self._insert_transitions(
                c, want_device=plan.is_device, under_input_file=child_flag
            )
            for c in plan.children
        ]
        plan = plan.with_new_children(new_children)
        if plan.is_device and not want_device:
            return T.DeviceToHostExec(plan)
        if not plan.is_device and want_device:
            h2d = T.HostToDeviceExec(plan)
            if under_input_file or local:
                return h2d
            # post-transition coalesce (GpuTransitionOverrides:84-91 +
            # GpuCoalesceBatches): a many-small-file scan otherwise pushes
            # one tiny batch per file through every downstream kernel
            return T.TpuCoalesceBatchesExec(
                h2d, T.CoalesceGoal(cfg.BATCH_SIZE_BYTES.get(self.conf))
            )
        return plan

    def _maybe_log(self):
        mode = cfg.EXPLAIN.get(self.conf).upper()
        if mode == "NONE":
            return
        import sys

        for e in self.explain:
            if e.on_device and mode != "ALL":
                continue
            marker = "will run on device" if e.on_device else "cannot run on device"
            print(f"! {e.node}: {marker}", file=sys.stderr)
            for r in e.reasons:
                print(f"    because {r}", file=sys.stderr)

    def fallback_execs(self) -> List[str]:
        return [e.node for e in self.explain if not e.on_device]


def _node_has_input_file_expr(node: Exec) -> bool:
    """Whether THIS node's own expressions read the input-file task state
    (input_file_name / input_file_block_start / input_file_block_length) —
    the GpuTransitionOverrides condition that disables batch coalescing so
    file boundaries survive to the expression."""
    targets = (msc.InputFileName, msc.InputFileBlockStart, msc.InputFileBlockLength)

    def expr_has(e) -> bool:
        if isinstance(e, targets):
            return True
        try:
            kids = e.children()
        except Exception:
            return False
        return any(expr_has(c) for c in kids)

    def scan_value(v) -> bool:
        if isinstance(v, Expression):
            return expr_has(v)
        if isinstance(v, (list, tuple)):
            return any(scan_value(x) for x in v)
        return False

    for k, v in vars(node).items():
        if k == "_children":
            continue
        if scan_value(v):
            return True
    return False
