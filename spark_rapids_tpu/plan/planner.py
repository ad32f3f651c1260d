"""The plan-rewriting engine: tagging, conversion, fallback, explain.

TPU re-design of the reference's L4 layer:
- per-node meta wrappers carrying will-not-work reasons
  (ref: RapidsMeta.scala:162 willNotWorkOnGpu, :197 canThisBeReplaced);
- a replacement-rule registry with auto-registered per-exec and
  per-expression conf kill-switches
  (ref: GpuOverrides.scala:679-748 expr/exec rules,
  RapidsMeta.scala:35-46 DataFromReplacementRule.confKey);
- explain output listing every node kept off the accelerator and why
  (ref: GpuOverrides.scala:3113-3122, the plugin's single most important
  observability feature);
- per-subtree CPU fallback with explicit transition execs at the
  boundary (ref: GpuTransitionOverrides.scala inserts
  HostColumnarToGpu/GpuBringBackToHost the same way).
"""

from __future__ import annotations

import copy
from typing import Iterator, Optional

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import SQL_ENABLED, get_conf, register
from spark_rapids_tpu.columnar.arrow import schema_to_arrow, to_arrow
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.exprs import arithmetic as A
from spark_rapids_tpu.exprs import base as B
from spark_rapids_tpu.exprs import predicates as P
from spark_rapids_tpu.exprs import decimal as DEC
from spark_rapids_tpu.exprs.hashing import Md5, Murmur3Hash
from spark_rapids_tpu.plan import logical as L

# ---------------------------------------------------------------------- #
# Supported-expression registry (ref: GpuOverrides.scala expr rules)
# ---------------------------------------------------------------------- #

from spark_rapids_tpu.plan import typesig as TS

SUPPORTED_EXPRS: dict[type, object] = {}
#: declarative input-type signatures per expression rule
#: (ref: TypeChecks.scala — tagging checks declarations, not op code)
EXPR_SIGS: dict[type, TS.ExprSig] = {}


def register_expr(cls: type, sig: TS.ExprSig = None) -> None:
    key = f"spark.rapids.tpu.sql.expression.{cls.__name__}"
    entry = register(key, True,
                     f"Enable TPU execution of expression {cls.__name__}.")
    SUPPORTED_EXPRS[cls] = entry
    if sig is not None:
        EXPR_SIGS[cls] = sig


from spark_rapids_tpu.exprs import bitwise as BW  # noqa: E402
from spark_rapids_tpu.exprs import datetime as DT  # noqa: E402
from spark_rapids_tpu.exprs import math as M  # noqa: E402
from spark_rapids_tpu.exprs import strings as S  # noqa: E402
from spark_rapids_tpu.exprs.cast import Cast  # noqa: E402

_PASSTHROUGH = TS.ExprSig(TS.ALL)
_ARITH = TS.ExprSig(
    TS.NUMERIC + TS.NULLSIG,
    "decimal arithmetic falls back (unscaled-value math would be wrong)")
_COMPARE = TS.ExprSig(TS.ORDERABLE)
_LOGIC = TS.ExprSig(TS.BOOLEAN + TS.NULLSIG)
_MATH = TS.ExprSig(TS.NUMERIC + TS.NULLSIG)
_BITS = TS.ExprSig(TS.INTEGRAL + TS.NULLSIG)
_DT = TS.ExprSig(TS.DATETIME + TS.INTEGRAL + TS.NULLSIG)
_STR = TS.ExprSig(TS.STRING + TS.INTEGRAL + TS.NULLSIG,
                  "needle/length parameters must be literals")
_COND = TS.ExprSig(TS.ORDERABLE)

for _sig, _classes in (
    (_PASSTHROUGH, (B.Alias, B.BoundReference, B.ColumnReference,
                    B.Literal)),
    (TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.NULLSIG,
                "decimal results wider than precision 18 fall back"),
     (A.Add, A.Subtract)),
    (_ARITH, (A.Multiply, A.Divide, A.IntegralDivide,
              A.Remainder, A.Pmod, A.UnaryMinus, A.UnaryPositive, A.Abs,
              A.Least, A.Greatest)),
    (_COMPARE, (P.EqualTo, P.LessThan, P.LessThanOrEqual, P.GreaterThan,
                P.GreaterThanOrEqual, P.EqualNullSafe, P.In)),
    (_LOGIC, (P.And, P.Or, P.Not)),
    (_PASSTHROUGH, (P.IsNull, P.IsNotNull, P.AtLeastNNonNulls)),
    (TS.ExprSig(TS.NUMERIC + TS.NULLSIG), (P.IsNaN,)),
    (_COND, (P.Coalesce, P.If, P.CaseWhen)),
    (TS.ExprSig(TS.COMMON_N), (Murmur3Hash,)),
    (TS.ExprSig(TS.STRING + TS.NULLSIG), (Md5,)),
    (TS.ExprSig(TS.DECIMAL + TS.NULLSIG),
     (DEC.PromotePrecision, DEC.CheckOverflow, DEC.UnscaledValue)),
    (TS.ExprSig(TS.INTEGRAL + TS.DECIMAL + TS.NULLSIG),
     (DEC.MakeDecimal,)),
    (_MATH, (M.Sqrt, M.Cbrt, M.Exp, M.Expm1, M.Sin, M.Cos, M.Tan, M.Cot,
             M.Asin, M.Acos, M.Atan, M.Sinh, M.Cosh, M.Tanh, M.Asinh,
             M.Acosh, M.Atanh, M.Rint, M.Signum, M.ToDegrees,
             M.ToRadians, M.Log, M.Log10, M.Log2, M.Log1p, M.Logarithm,
             M.Pow, M.Ceil, M.Floor, M.Round, M.BRound,
             M.KnownFloatingPointNormalized)),
    (TS.ExprSig(TS.TypeSig.of("float", "double") + TS.NULLSIG,
                "NaN semantics need floating inputs"),
     (M.NaNvl, M.NormalizeNaNAndZero)),
    (_BITS, (BW.BitwiseAnd, BW.BitwiseOr, BW.BitwiseXor, BW.BitwiseNot,
             BW.ShiftLeft, BW.ShiftRight, BW.ShiftRightUnsigned)),
    (_DT, (DT.Year, DT.Month, DT.DayOfMonth, DT.DayOfWeek, DT.WeekDay,
           DT.DayOfYear, DT.Quarter, DT.LastDay, DT.Hour, DT.Minute,
           DT.Second, DT.DateAdd, DT.DateSub, DT.AddMonths, DT.DateDiff,
           DT.UnixTimestampFromTs, DT.DateFormatClass, DT.TimeAdd,
           DT.TimeSub, DT.DateAddInterval)),
    (TS.ExprSig(TS.INTEGRAL + TS.NULLSIG,
                "epoch seconds input"), (DT.FromUnixTime,)),
    (_STR, (S.Length, S.Upper, S.Lower, S.StartsWith, S.EndsWith,
            S.Contains, S.Like, S.Substring, S.StringTrim,
            S.StringTrimLeft, S.StringTrimRight, S.Concat,
            S.StringReplace, S.RegExpReplace, S.StringLPad, S.StringRPad,
            S.StringLocate, S.SubstringIndex, S.InitCap, S.ConcatWs,
            S.StringSplit, S.SplitPart, S.GetJsonObject)),
    (TS.ExprSig(TS.ALL, "per-pair support matrix in check_supported"),
     (Cast,)),
):
    for _cls in _classes:
        register_expr(_cls, _sig)

from spark_rapids_tpu.exprs import collections as COLL  # noqa: E402

for _cls in (COLL.Size, COLL.GetArrayItem, COLL.ArrayContains):
    register_expr(_cls, TS.ExprSig(TS.ALL, "array input required"))

register_expr(COLL.CreateArray, TS.ExprSig(
    TS.NUMERIC + TS.BOOLEAN + TS.DATETIME + TS.NULLSIG,
    "fixed-width elements only"))

from spark_rapids_tpu.exprs import complex as CX  # noqa: E402

for _cls in (CX.GetStructField, CX.CreateNamedStruct, CX.GetMapValue,
             CX.ElementAt):
    register_expr(_cls, TS.ExprSig(
        TS.ALL + TS.NESTED, "struct/map input; fixed-width map "
        "key/value on device (check_supported)"))

# partition-context / nondeterministic expressions
from spark_rapids_tpu.exprs import nondeterministic as ND  # noqa: E402

register_expr(ND.SparkPartitionID, TS.ExprSig(TS.ALL, "no inputs"))
for _cls in (ND.InputFileName, ND.InputFileBlockStart,
             ND.InputFileBlockLength):
    register_expr(_cls, TS.ExprSig(
        TS.ALL, "rewritten to hidden scan columns above file scans; "
        "other positions fall back (Spark default values)"))
register_expr(ND.MonotonicallyIncreasingID,
              TS.ExprSig(TS.ALL, "no inputs"))
register_expr(ND.Rand, TS.ExprSig(TS.ALL, "no inputs"))

# columnar jax UDFs trace into the fused program like built-ins
# (OpaquePythonUDF deliberately stays unregistered -> CPU fallback)
from spark_rapids_tpu.udf.exprs import JaxScalarUDF  # noqa: E402

register_expr(JaxScalarUDF, TS.ExprSig(
    TS.NUMERIC + TS.BOOLEAN + TS.DATETIME + TS.NULLSIG,
    "user columnar function over fixed-width device arrays"))

# aggregate functions are checked by their own registry
from spark_rapids_tpu.exprs import aggregates as AG  # noqa: E402

SUPPORTED_AGGS = (AG.Sum, AG.Count, AG.CountStar, AG.Min, AG.Max,
                  AG.Average, AG.First, AG.Last, AG.CollectList,
                  AG.CollectSet, AG.PivotFirst)

#: per-aggregate input signatures (ref: TypeChecks on AggExprMeta)
AGG_SIGS: dict[type, TS.ExprSig] = {
    AG.CollectList: TS.ExprSig(
        TS.NUMERIC + TS.DATETIME + TS.BOOLEAN + TS.NULLSIG,
        "fixed-width elements only"),
    AG.CollectSet: TS.ExprSig(
        TS.NUMERIC + TS.DATETIME + TS.BOOLEAN + TS.NULLSIG,
        "fixed-width elements only"),
    AG.Sum: TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.NULLSIG),
    AG.Average: TS.ExprSig(TS.NUMERIC + TS.NULLSIG,
                           "decimal avg needs scale-aware division"),
    AG.Count: TS.ExprSig(TS.ALL),
    AG.CountStar: TS.ExprSig(TS.ALL),
    AG.Min: TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.DATETIME
                       + TS.BOOLEAN + TS.NULLSIG,
                       "string min/max falls back"),
    AG.Max: TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.DATETIME
                       + TS.BOOLEAN + TS.NULLSIG,
                       "string min/max falls back"),
    AG.First: TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.DATETIME
                         + TS.BOOLEAN + TS.NULLSIG),
    AG.Last: TS.ExprSig(TS.NUMERIC + TS.DECIMAL + TS.DATETIME
                        + TS.BOOLEAN + TS.NULLSIG),
    AG.PivotFirst: TS.ExprSig(
        TS.NUMERIC + TS.DECIMAL + TS.DATETIME + TS.BOOLEAN + TS.NULLSIG,
        "expanded into one masked First per pivot value"),
}


def _check_agg(fn, conf, reasons: set[str]) -> None:
    sig = AGG_SIGS.get(type(fn))
    if sig is None or fn.child is None:
        return
    try:
        dt = fn.child.dtype
    except Exception:
        return
    if not sig.inputs.supports(dt):
        reasons.add(
            f"aggregate {fn.name} does not support input type "
            f"{dt.name} on TPU (supported: {sig.inputs.describe()})")
    # data-dependent capability checks (the AggExprMeta.tagAggForGpu
    # hook): a raise becomes a fallback reason
    check = getattr(fn, "check_supported", None)
    if check is not None:
        try:
            check()
        except TypeError as exc:
            reasons.add(str(exc))

# per-exec kill switches (ref: spark.rapids.sql.exec.*)
_EXEC_CONFS = {
    cls: register(f"spark.rapids.tpu.sql.exec.{cls.__name__}", True,
                  f"Enable TPU execution of {cls.__name__}.")
    for cls in (L.InMemoryRelation, L.ParquetRelation, L.CsvRelation,
                L.OrcRelation, L.RangeRel, L.Project, L.Filter,
                L.Aggregate, L.Sort, L.Limit, L.Join, L.Union, L.Window,
                L.Expand, L.Generate, L.MapInArrow, L.GroupedPandas,
                L.CoGroupedPandas, L.Cached)
}


def _check_expr(e: B.Expression, conf, reasons: set[str]) -> None:
    entry = SUPPORTED_EXPRS.get(type(e))
    if entry is None:
        reasons.add(f"expression {type(e).__name__} is not supported on TPU")
    elif not conf.get(entry):
        reasons.add(
            f"expression {type(e).__name__} disabled by {entry.key}")
    # declarative input-type signature (ref: TypeChecks.tagExprForGpu)
    TS.check_inputs(e, EXPR_SIGS.get(type(e)), reasons)
    # expressions with data-dependent support (Cast matrix, Like
    # patterns) expose check_supported(); a raise becomes a reason
    check = getattr(e, "check_supported", None)
    if check is not None:
        try:
            check()
        except TypeError as exc:
            reasons.add(str(exc))
    for c in e.children:
        _check_expr(c, conf, reasons)


# ---------------------------------------------------------------------- #
# Meta wrapper
# ---------------------------------------------------------------------- #

class PlanMeta:
    """Wrapper tree over a logical plan carrying tagging state
    (ref: RapidsMeta.scala SparkPlanMeta)."""

    def __init__(self, plan: L.LogicalPlan, conf):
        self.plan = plan
        self.conf = conf
        self.children = [PlanMeta(c, conf) for c in plan.children]
        self.reasons: set[str] = set()

    @property
    def can_replace(self) -> bool:
        return not self.reasons

    def will_not_work(self, reason: str) -> None:
        self.reasons.add(reason)

    def _forbid_partition_aware(self, e, where: str) -> None:
        """Partition-context expressions (Rand, MID, ...) only get their
        context in the fused Project/Filter/Expand/Generate pipeline;
        anywhere else they would silently evaluate with partition 0 /
        offset 0 per batch, so route those plans to the CPU engine."""
        from spark_rapids_tpu.exprs.nondeterministic import (
            tree_is_partition_aware,
        )

        if tree_is_partition_aware(e):
            self.will_not_work(
                f"nondeterministic expression as {where} is only "
                "supported in project/filter on TPU")

    def tag(self) -> None:
        conf = self.conf
        entry = _EXEC_CONFS.get(type(self.plan))
        if entry is None:
            self.will_not_work(
                f"operator {self.plan.name} is not supported on TPU")
        elif not conf.get(entry):
            self.will_not_work(f"disabled by {entry.key}")
        if not self.children and not _schema_device_representable(
                self.plan.schema):
            # a LEAF producing unrepresentable columns can never
            # upload (list<string>, map<string,*>, ...): CPU source
            self.will_not_work(
                "source output type has no device layout")
        self._tag_exprs()
        for c in self.children:
            c.tag()

    def _forbid_ansi_risky(self, e, where: str) -> None:
        """ANSI error flags are captured only by the FUSED
        project/filter/expand/generate pipelines; an overflow-capable
        expression in any other position would silently keep legacy
        semantics while the CPU engine raises — route those plans to
        the CPU engine instead (the reference's partial-ANSI fallback
        posture)."""
        from spark_rapids_tpu.exprs.base import ansi_enabled

        if not ansi_enabled():
            return
        if _tree_has_ansi_risk(e):
            self.will_not_work(
                f"ANSI-checked expression as {where} only runs on TPU "
                "inside project/filter — CPU fallback")

    def _tag_exprs(self) -> None:
        p = self.plan
        conf = self.conf
        if isinstance(p, L.Project):
            for e in p.exprs:
                _check_expr(e, conf, self.reasons)
        elif isinstance(p, L.Expand):
            for proj in p.projections:
                for e in proj:
                    _check_expr(e, conf, self.reasons)
        elif isinstance(p, L.Generate):
            _check_expr(p.generator.child, conf, self.reasons)
            try:
                p.generator.check_supported()
            except TypeError as exc:
                self.will_not_work(str(exc))
        elif isinstance(p, L.Filter):
            _check_expr(p.condition, conf, self.reasons)
        elif isinstance(p, L.Aggregate):
            for g in p.groups:
                _check_expr(g, conf, self.reasons)
                self._forbid_partition_aware(g, "grouping key")
                self._forbid_ansi_risky(g, "grouping key")
            for na in p.aggs:
                for e in na.fn.inputs():
                    self._forbid_partition_aware(e, "aggregate input")
                if not isinstance(na.fn, SUPPORTED_AGGS):
                    self.will_not_work(
                        f"aggregate {na.fn.name} is not supported on TPU")
                else:
                    _check_agg(na.fn, conf, self.reasons)
                for e in na.fn.inputs():
                    _check_expr(e, conf, self.reasons)
                    self._forbid_ansi_risky(e, "aggregate input")
        elif isinstance(p, L.Sort):
            for k in p.keys:
                _check_expr(k.expr, conf, self.reasons)
                self._forbid_partition_aware(k.expr, "sort key")
                self._forbid_ansi_risky(k.expr, "sort key")
        elif isinstance(p, L.Window):
            for we, _name in p.window_exprs:
                for e in we.children:
                    _check_expr(e, conf, self.reasons)
                    self._forbid_partition_aware(e, "window input")
                    self._forbid_ansi_risky(e, "window input")
                try:
                    we.check_supported()
                except TypeError as exc:
                    self.will_not_work(str(exc))
        elif isinstance(p, L.Join):
            for e in list(p.left_keys) + list(p.right_keys):
                _check_expr(e, conf, self.reasons)
                self._forbid_partition_aware(e, "join key")
                self._forbid_ansi_risky(e, "join key")
            if p.condition is not None:
                if p.join_type != "inner":
                    self.will_not_work(
                        "non-inner join with residual condition")
                else:
                    _check_expr(p.condition, conf, self.reasons)
                    self._forbid_ansi_risky(p.condition,
                                            "join condition")
            if not p.left_keys and p.join_type not in ("cross", "inner"):
                # keyless inner joins run as conditional nested loops
                # (constant-key cross); keyless outer joins fall back
                self.will_not_work("non-equi join without keys")

    # -- explain -------------------------------------------------------- #

    def explain(self, indent: int = 0) -> str:
        mark = "*" if self.can_replace else "!"
        s = "  " * indent + f"{mark} {self.plan.node_desc()}"
        if self.reasons:
            s += "  <-- cannot run on TPU because " + "; ".join(
                sorted(self.reasons))
        s += "\n"
        for c in self.children:
            s += c.explain(indent + 1)
        return s


# ---------------------------------------------------------------------- #
# Conversion (ref: RapidsMeta convertIfNeeded)
# ---------------------------------------------------------------------- #

class CpuFallbackExec(TpuExec):
    """Runs one logical node on the CPU engine; exec children are
    materialized to Arrow at the boundary (the device->host transition,
    ref: GpuBringBackToHost + ColumnarToRow) and the result re-enters the
    device path through ArrowSourceExec slicing on the parent side."""

    def __init__(self, plan: L.LogicalPlan, *children: TpuExec):
        super().__init__(*children)
        self.plan = plan

    @property
    def schema(self) -> T.Schema:
        return self.plan.schema

    def node_desc(self) -> str:
        return f"CpuFallbackExec [{self.plan.node_desc()}]"

    def cpu_table(self) -> pa.Table:
        from spark_rapids_tpu.cpu.engine import execute_cpu

        new_children = []
        for c in self.children:
            if isinstance(c, CpuFallbackExec):
                # fuse adjacent CPU nodes: no device round-trip
                new_children.append(L.InMemoryRelation(c.cpu_table()))
            else:
                new_children.append(L.InMemoryRelation(collect_exec(c)))
        plan = copy.copy(self.plan)
        plan.children = new_children
        return execute_cpu(plan)

    #: logical nodes whose CPU evaluation is per-row: they can run on one
    #: batch at a time, so the fallback boundary streams batch-wise
    #: instead of materializing the whole child as a single Arrow table
    #: (the reference's fallback is row-iterator streaming throughout)
    _STREAMABLE = (L.Filter, L.Project, L.Generate)

    def _execute_streaming(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.columnar.arrow import to_arrow
        from spark_rapids_tpu.cpu.engine import execute_cpu
        from spark_rapids_tpu.columnar.arrow import from_arrow

        for b in self.children[0].execute():
            tbl = to_arrow(b)
            plan = copy.copy(self.plan)
            plan.children = [L.InMemoryRelation(tbl)]
            out = execute_cpu(plan)
            yield self._count_output(from_arrow(out))

    def execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.io.scan import ArrowSourceExec

        if isinstance(self.plan, self._STREAMABLE) \
                and len(self.children) == 1 \
                and not isinstance(self.children[0], CpuFallbackExec):
            # adjacent CPU nodes keep the fusing cpu_table() path — the
            # streaming boundary would bounce each batch through the
            # device (from_arrow -> to_arrow) for nothing
            yield from self._execute_streaming()
            return
        src = ArrowSourceExec(self.cpu_table(), self.schema)
        for b in src.execute():
            yield self._count_output(b)


def convert_meta(meta: PlanMeta) -> TpuExec:
    p = meta.plan
    if not meta.can_replace:
        kids = [convert_meta(c) for c in meta.children]
        _maybe_push_filter(p, kids)
        return CpuFallbackExec(p, *kids)
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.basic import (
        TpuFilterExec,
        TpuProjectExec,
        TpuRangeExec,
        TpuUnionExec,
    )
    from spark_rapids_tpu.execs.join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.execs.limit import TpuGlobalLimitExec
    from spark_rapids_tpu.execs.sort import TpuSortExec
    from spark_rapids_tpu.io.scan import (
        ArrowSourceExec,
        CsvScanExec,
        ParquetScanExec,
    )

    kids = [convert_meta(c) for c in meta.children]
    if isinstance(p, L.InMemoryRelation):
        return ArrowSourceExec(p.table, p.schema)
    if isinstance(p, L.ParquetRelation):
        return ParquetScanExec(p.paths, p.schema, p.columns,
                               partition_values=p.partition_values,
                               partition_fields=p.partition_fields)
    if isinstance(p, L.OrcRelation):
        from spark_rapids_tpu.io.scan import OrcScanExec

        return OrcScanExec(p.paths, p.schema, p.columns,
                           partition_values=p.partition_values,
                           partition_fields=p.partition_fields)
    if isinstance(p, L.CsvRelation):
        return CsvScanExec(p.paths, p.schema,
                           partition_values=p.partition_values,
                           partition_fields=p.partition_fields)
    if isinstance(p, L.RangeRel):
        return TpuRangeExec(p.start, p.end, p.step)
    if isinstance(p, L.Cached):
        from spark_rapids_tpu.execs.cache import TpuCacheExec

        return TpuCacheExec(p.slot, kids[0])
    if isinstance(p, L.Project):
        return TpuProjectExec(p.exprs, kids[0])
    if isinstance(p, L.Filter):
        _maybe_push_filter(p, kids)
        if _can_elide_device_filter(p, kids):
            # the host prefilter applies the FULL condition exactly
            # (exact mode raises instead of silently disabling), so the
            # device Filter would re-verify already-filtered rows — on
            # a per-program-cost link that is a whole program execution
            # per batch for nothing
            kids[0].exact_prefilter = True
            refs = getattr(p, "upload_refs", None)
            if refs is not None:
                # columns referenced ONLY by the (now host-applied)
                # condition ship as zero-byte all-NULL placeholders
                scan = kids[0]
                keep = {scan.schema.fields[i].name for i in refs}
                part = {f.name for f in getattr(
                    scan, "partition_fields", [])}
                drop = {f.name for f in scan.schema.fields} - keep - part
                if drop:
                    scan.null_upload_cols = drop
            return kids[0]
        return TpuFilterExec(p.condition, kids[0])
    if isinstance(p, L.Expand):
        from spark_rapids_tpu.execs.expand import TpuExpandExec

        return TpuExpandExec(p.projections, p.schema, kids[0])
    if isinstance(p, L.Generate):
        from spark_rapids_tpu.execs.generate import TpuGenerateExec

        return TpuGenerateExec(p.generator, p.schema, kids[0])
    if isinstance(p, L.MapInArrow):
        from spark_rapids_tpu.execs.python_exec import (
            TpuMapInArrowExec,
            TpuMapInPandasExec,
        )

        if getattr(p, "pandas", False):
            return TpuMapInPandasExec(p.fn, p.schema, kids[0])
        return TpuMapInArrowExec(p.fn, p.schema, kids[0])
    if isinstance(p, L.CoGroupedPandas):
        from spark_rapids_tpu.execs.exchange import (
            SHUFFLE_PARTITIONS,
            TpuShuffleExchangeExec,
        )
        from spark_rapids_tpu.execs.python_exec import (
            TpuFlatMapCoGroupsInPandasExec,
        )
        from spark_rapids_tpu.ops.partition import HashPartitioning

        n = get_conf().get(SHUFFLE_PARTITIONS)
        sides = []
        for kid, keys in ((kids[0], p.left_key_names),
                          (kids[1], p.right_key_names)):
            kexprs = [B.ColumnReference(k) for k in keys]
            sides.append(TpuShuffleExchangeExec(
                HashPartitioning(kexprs, n), kid))
        return TpuFlatMapCoGroupsInPandasExec(
            p.left_key_names, p.right_key_names, p.fn, p.schema,
            sides[0], sides[1])
    if isinstance(p, L.GroupedPandas):
        from spark_rapids_tpu.execs.exchange import (
            SHUFFLE_PARTITIONS,
            TpuShuffleExchangeExec,
        )
        from spark_rapids_tpu.execs.python_exec import (
            TpuAggregateInPandasExec,
            TpuFlatMapGroupsInPandasExec,
            TpuWindowInPandasExec,
        )
        from spark_rapids_tpu.ops.partition import HashPartitioning

        source = kids[0]
        keys = [B.ColumnReference(k) for k in p.key_names]
        if source.num_partitions > 1 and p.key_names \
                and _hash_satisfies(source, [
                    B.BoundReference(
                        source.schema.index_of(k),
                        source.schema.field(k).dtype,
                        source.schema.field(k).nullable, k)
                    for k in p.key_names]) is None:
            n = get_conf().get(SHUFFLE_PARTITIONS)
            source = TpuShuffleExchangeExec(
                HashPartitioning(keys, n), source)
        elif source.num_partitions > 1 and not p.key_names:
            from spark_rapids_tpu.execs.coalesce import (
                TpuCoalescePartitionsExec,
            )

            source = TpuCoalescePartitionsExec(source)
        if p.kind == "flatmap":
            return TpuFlatMapGroupsInPandasExec(
                p.key_names, p.payload, p.schema, source)
        if p.kind == "agg":
            return TpuAggregateInPandasExec(
                p.key_names, p.payload, p.schema, source)
        return TpuWindowInPandasExec(
            p.key_names, p.payload, p.schema, source)
    if isinstance(p, L.Aggregate):
        return _plan_aggregate(p, kids[0])
    if isinstance(p, L.Sort):
        return _plan_sort(p, kids[0])
    if isinstance(p, L.Window):
        from spark_rapids_tpu.execs.window import TpuWindowExec

        part_by = p.window_exprs[0][0].spec.partition_by
        if part_by:
            # tier-2: the exchange on the partition keys and the window
            # per shard as fused SPMD programs (SURVEY.md §5.8).  The
            # partition keys alone decide: a child hashed on more keys
            # (an aggregate on its group keys) does not satisfy it
            from spark_rapids_tpu.shuffle.transport import get_transport

            transport = get_transport()
            if transport.kind == "collective" \
                    and transport.supports_schema(kids[0].schema):
                from spark_rapids_tpu.execs.collective import (
                    TpuCollectiveWindowExec,
                    stage_bucket_rounds,
                )

                return TpuCollectiveWindowExec(
                    p.window_exprs, kids[0], transport.mesh,
                    bucket_rounds=stage_bucket_rounds())
        if part_by and kids[0].num_partitions > 1:
            # out-of-core: hash exchange on the partition keys makes
            # window groups partition-local, each reduce partition
            # windows independently (ref: GpuWindowExec's required
            # child distribution = ClusteredDistribution(partitionBy));
            # EnsureRequirements: an already-satisfying distribution
            # (e.g. a final aggregate keyed the same) skips the shuffle
            from spark_rapids_tpu.execs.exchange import (
                SHUFFLE_PARTITIONS,
                TpuShuffleExchangeExec,
            )
            from spark_rapids_tpu.ops.partition import HashPartitioning

            source = kids[0]
            if _hash_satisfies(source, list(part_by)) is None:
                n = get_conf().get(SHUFFLE_PARTITIONS)
                source = TpuShuffleExchangeExec(
                    HashPartitioning(list(part_by), n), source)
            w = TpuWindowExec(p.window_exprs, source)
            w.partitioned = True
            return w
        return TpuWindowExec(p.window_exprs, kids[0])
    if isinstance(p, L.Limit):
        topn = _maybe_topn(p, kids)
        if topn is not None:
            return topn
        if kids[0].num_partitions > 1:
            # collect-limit shape: prune each partition locally before
            # the single-partition drain (ref: GpuCollectLimitExec)
            from spark_rapids_tpu.execs.limit import TpuCollectLimitExec

            return TpuCollectLimitExec(p.n, kids[0])
        return TpuGlobalLimitExec(p.n, kids[0])
    if isinstance(p, L.Union):
        return TpuUnionExec(*kids)
    if isinstance(p, L.Join):
        return _plan_join(p, kids)
    raise AssertionError(f"tagged-replaceable node unconvertible: {p.name}")


ELIDE_DEVICE_FILTER = register(
    "spark.rapids.tpu.sql.scan.elideDeviceFilter", True,
    "Drop the device Filter above a Parquet scan when the host "
    "prefilter provably applies the full condition (deterministic, "
    "non-ANSI, prefilter enabled): the prefilter then runs in EXACT "
    "mode — any host evaluation failure raises instead of shipping "
    "unfiltered rows.")


def _can_elide_device_filter(p: L.LogicalPlan,
                             kids: list[TpuExec]) -> bool:
    from spark_rapids_tpu.exprs.base import ansi_enabled
    from spark_rapids_tpu.exprs.nondeterministic import (
        tree_is_partition_aware,
    )
    from spark_rapids_tpu.io.scan import HOST_PREFILTER, ParquetScanExec

    conf = get_conf()
    if not (conf.get(ELIDE_DEVICE_FILTER) and conf.get(HOST_PREFILTER)):
        return False
    if not (kids and type(kids[0]) in (ParquetScanExec,)
            and kids[0].pushed_filter is p.condition):
        return False
    if ansi_enabled() or tree_is_partition_aware(p.condition):
        return False
    # count-only scans never run the row-wise prefilter: the condition
    # must read at least one column so rows flow as tables
    refs = [e for e in _walk_expr(p.condition)
            if isinstance(e, (B.BoundReference, B.ColumnReference))]
    if not refs:
        return False
    # only elide when the compiled pyarrow prefilter subset covers the
    # whole condition: a condition only the DEVICE expression engine
    # supports must keep its device Filter (before elision the host
    # prefilter would just disable itself; with elision the exact-mode
    # prefilter would hard-fail the query instead)
    from spark_rapids_tpu.io.pa_filter import compile_filter

    return compile_filter(p.condition) is not None


def _walk_expr(e):
    yield e
    for c in getattr(e, "children", ()):
        yield from _walk_expr(c)


def _annotate_filter_upload(root: L.LogicalPlan) -> None:
    """Column-pruning-through-Filter analysis (the interplay of Spark's
    ColumnPruning and PushDownPredicates): for every Filter sitting
    directly on a file relation, record which relation ordinals any
    operator ABOVE the filter reads.  If the device filter is later
    elided (exact host prefilter), columns referenced ONLY by the
    filter condition need not cross the host->device wire at all —
    the scan ships them as zero-byte all-NULL placeholders, keeping
    the schema (and every bound ordinal above) intact.

    Conservative by construction: the walk ends at the nearest
    'bounding' ancestor whose output drops the relation's columns
    (Project/Aggregate/semi-anti-join's dropped side); any node kind
    outside the modeled set, or reaching the root with the columns
    still in the output, yields no annotation (upload everything)."""
    from spark_rapids_tpu.exprs import aggregates as AG
    from spark_rapids_tpu.plan.logical import OrcRelation, ParquetRelation

    def collect(e, pos: int, n: int, req: set) -> None:
        for x in _walk_expr(e):
            if isinstance(x, B.BoundReference) \
                    and pos <= x.ordinal < pos + n:
                req.add(x.ordinal - pos)
            elif isinstance(x, AG.AggregateFunction):
                for c in x.inputs():
                    collect(c, pos, n, req)

    def required_above(path: list, f: L.Filter):
        """`path` is [(ancestor, child_slot), ...] from root to the
        filter's parent; slots disambiguate self-joins where both
        children are the same object."""
        n = len(f.schema.fields)
        pos = 0
        req: set = set()
        for anc, ci in reversed(path):
            if isinstance(anc, L.Filter):
                collect(anc.condition, pos, n, req)
            elif isinstance(anc, L.Sort):
                for k in anc.keys:
                    collect(k.expr, pos, n, req)
            elif isinstance(anc, L.Limit):
                pass
            elif isinstance(anc, L.Project):
                for e in anc.exprs:
                    collect(e, pos, n, req)
                return req  # bounding: output drops pass-through cols
            elif isinstance(anc, L.Aggregate):
                for g in anc.groups:
                    collect(g, pos, n, req)
                for na in anc.aggs:
                    for e in na.fn.inputs():
                        collect(e, pos, n, req)
                return req  # bounding
            elif isinstance(anc, L.Window):
                for we, _name in anc.window_exprs:
                    for e in we.children:
                        collect(e, pos, n, req)
                # output = child ++ window cols: position unchanged
            elif isinstance(anc, L.Join):
                n_left = len(anc.children[0].schema.fields)
                if ci == 0:
                    for k in anc.left_keys:
                        collect(k, pos, n, req)
                    if anc.condition is not None:
                        collect(anc.condition, pos, n, req)
                    # output keeps the left side first (or alone, for
                    # semi/anti): position unchanged
                else:
                    for k in anc.right_keys:
                        collect(k, pos, n, req)
                    if anc.condition is not None:
                        collect(anc.condition, pos + n_left, n, req)
                    if anc.join_type in ("left_semi", "left_anti"):
                        # the right side never reaches the output (the
                        # condition above still reads it)
                        return req
                    pos += n_left
            else:
                return None  # unmodeled shape: no pruning
        return None  # columns reach the final output

    # plans are DAGs (DataFrame reuse, self-joins): gather EVERY path
    # to each filter-over-relation and union the requirements — a
    # column any consumer path reads must upload
    targets: dict[int, tuple[L.Filter, list]] = {}
    budget = [4096]  # visit cap: degenerate shared DAGs bail out

    def visit(node: L.LogicalPlan, path: list) -> None:
        budget[0] -= 1
        if budget[0] < 0:
            return
        for i, c in enumerate(node.children):
            visit(c, path + [(node, i)])
        if isinstance(node, L.Filter) and isinstance(
                node.children[0], (ParquetRelation, OrcRelation)):
            targets.setdefault(id(node), (node, []))[1].append(path)

    visit(root, [])
    if budget[0] < 0:
        return
    for node, paths in targets.values():
        reqs = [required_above(p, node) for p in paths]
        node.upload_refs = (None if any(r is None for r in reqs)
                            else set().union(*reqs))


def _maybe_push_filter(p: L.LogicalPlan, kids: list[TpuExec]) -> None:
    """Attach a scan-adjacent Filter's condition to the Parquet scan for
    row-group/partition pruning (ref: GpuParquetScan.scala:263-306).
    Pure IO optimization on the fresh exec instance — the Filter still
    evaluates exactly, whichever engine it runs on."""
    from spark_rapids_tpu.io.scan import ParquetScanExec

    if isinstance(p, L.Filter) and kids \
            and isinstance(kids[0], ParquetScanExec):
        kids[0].pushed_filter = p.condition


TOPN_MAX_ROWS = register(
    "spark.rapids.tpu.sql.topn.maxRows", 1 << 14,
    "LIMIT values up to this use the streaming top-n rewrite of "
    "ORDER BY + LIMIT (GpuTopN / TakeOrderedAndProject analog) instead "
    "of a full global sort.")


def _maybe_topn(p: "L.Limit", kids: list[TpuExec]) -> Optional[TpuExec]:
    """LIMIT over a just-planned global Sort with a fixed-width primary
    key -> streaming top-n (per-batch candidate pruning; the full
    multi-key sort runs only over the candidates)."""
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.execs.sort import TpuSortExec, TpuTopNExec
    from spark_rapids_tpu.ops.partition import RangePartitioning

    sort = kids[0]
    if not (isinstance(sort, TpuSortExec)
            and 0 < p.n <= get_conf().get(TOPN_MAX_ROWS)
            and sort.keys):
        return None
    child = sort.children[0]
    if sort.scope == "partition" and isinstance(
            child, TpuShuffleExchangeExec) and isinstance(
            child.partitioning, RangePartitioning):
        # distributed ORDER BY shape (range exchange + per-partition
        # sort): top-n needs no exchange at all — consume the
        # pre-exchange child directly
        child = child.children[0]
    elif sort.scope != "global":
        return None
    primary = sort.keys[0].expr.dtype
    if not isinstance(primary, (T.ByteType, T.ShortType, T.IntegerType,
                                T.LongType, T.FloatType, T.DoubleType,
                                T.DateType, T.TimestampType,
                                T.BooleanType)):
        return None
    return TpuTopNExec(p.n, sort.keys, child)


BROADCAST_THRESHOLD = register(
    "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes", 10 << 20,
    "Maximum estimated build-side size for a join to use the broadcast "
    "strategy (the spark.sql.autoBroadcastJoinThreshold analog); -1 "
    "disables broadcast joins.")


def broadcast_candidates(join_type: str, lbytes, rbytes,
                         thr: int) -> list[tuple[str, int]]:
    """Legal (build_side, bytes) pairs for a broadcast hash join — ONE
    legality table shared by static planning and the adaptive join's
    runtime re-decision (which feeds measured instead of estimated
    bytes)."""
    out: list[tuple[str, int]] = []
    if thr < 0 or join_type == "full_outer":
        return out
    if join_type in ("inner", "cross", "left_outer", "left_semi",
                     "left_anti") and rbytes is not None and rbytes <= thr:
        out.append(("right", rbytes))
    if join_type in ("inner", "cross", "right_outer") \
            and lbytes is not None and lbytes <= thr:
        out.append(("left", lbytes))
    return out


def _plan_join(p: L.Join, kids: list[TpuExec]) -> TpuExec:
    """Physical join strategy (the role GpuOverrides plays when Spark has
    already chosen; here the planner chooses, like Spark's
    JoinSelection): broadcast the small side when an estimate proves it
    fits; otherwise co-hash-partition both sides for a partition-wise
    join; otherwise a single wide local join."""
    from spark_rapids_tpu.execs.exchange import (
        SHUFFLE_PARTITIONS,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.execs.join import (
        TpuBroadcastHashJoinExec,
        TpuShuffledHashJoinExec,
    )
    from spark_rapids_tpu.ops.partition import HashPartitioning

    conf = get_conf()
    thr = conf.get(BROADCAST_THRESHOLD)
    jt = p.join_type
    lbytes = p.children[0].estimated_bytes()
    rbytes = p.children[1].estimated_bytes()

    candidates = broadcast_candidates(jt, lbytes, rbytes, thr)
    if candidates:
        side = min(candidates, key=lambda c: c[1])[0]
        return TpuBroadcastHashJoinExec(
            p.left_keys, p.right_keys, jt, kids[0], kids[1],
            condition=p.condition, build_side=side,
            null_safe=p.null_safe)

    # partition-wise shuffled join: only for real equi-keys with equal
    # key dtypes on both sides (hash-parity requires identical physical
    # hashing) and a genuinely partitioned input
    key_dtypes_match = p.left_keys and all(
        lk.dtype == rk.dtype
        for lk, rk in zip(p.left_keys, p.right_keys))

    # tier-2 lowering: with the collective transport active, the whole
    # exchange+exchange+join pipeline becomes fused SPMD programs over
    # the mesh — the route-everything-through-shuffle architecture of
    # GpuShuffleExchangeExec applied to joins (SURVEY.md §5.8)
    if key_dtypes_match and p.condition is None:
        from spark_rapids_tpu.execs.collective import (
            TpuCollectiveHashJoinExec,
            stage_bucket_rounds,
        )
        from spark_rapids_tpu.shuffle.transport import get_transport

        transport = get_transport()
        if (transport.kind == "collective"
                and jt in TpuCollectiveHashJoinExec.SUPPORTED_TYPES
                and transport.supports_schema(kids[0].schema)
                and transport.supports_schema(kids[1].schema)):
            # stage boundary decided HERE at plan time and pinned
            # into the exec
            return TpuCollectiveHashJoinExec(
                p.left_keys, p.right_keys, jt, kids[0], kids[1],
                transport.mesh,
                bucket_rounds=stage_bucket_rounds(conf),
                null_safe=p.null_safe)
    if key_dtypes_match and (kids[0].num_partitions > 1
                             or kids[1].num_partitions > 1):
        # EnsureRequirements: a child already hash-partitioned on these
        # keys (e.g. a final aggregate over an exchange) is not
        # re-shuffled
        lsat = _hash_satisfies(kids[0], p.left_keys)
        rsat = _hash_satisfies(kids[1], p.right_keys)
        if lsat is not None:
            n = lsat.num_partitions
            if rsat is not None and rsat.num_partitions != n:
                rsat = None  # mismatched widths: re-shuffle right
        elif rsat is not None:
            n = rsat.num_partitions
        else:
            n = conf.get(SHUFFLE_PARTITIONS)
        lex = kids[0] if lsat is not None else TpuShuffleExchangeExec(
            HashPartitioning(p.left_keys, n), kids[0])
        rex = kids[1] if rsat is not None else TpuShuffleExchangeExec(
            HashPartitioning(p.right_keys, n), kids[1])
        from spark_rapids_tpu.execs.adaptive import (
            ADAPTIVE_ENABLED,
            TpuAdaptiveJoinExec,
        )

        if conf.get(ADAPTIVE_ENABLED) and lsat is None and rsat is None:
            # both sides are fresh exchanges: defer shuffled-vs-broadcast
            # and reduce-partition grouping to measured map-output sizes
            # (reused child distributions can't re-group: their
            # partitioning is fixed by the producing stage)
            return TpuAdaptiveJoinExec(
                p.left_keys, p.right_keys, jt, lex, rex,
                condition=p.condition, null_safe=p.null_safe)
        return TpuShuffledHashJoinExec(
            p.left_keys, p.right_keys, jt, lex, rex,
            condition=p.condition, partition_wise=True,
            null_safe=p.null_safe)

    return TpuShuffledHashJoinExec(
        p.left_keys, p.right_keys, jt, kids[0], kids[1],
        condition=p.condition, null_safe=p.null_safe)


def _hash_satisfies(exec_: TpuExec, keys):
    """The child's output HashPartitioning when it already distributes by
    exactly these key expressions (value-identical hashing), else None."""
    from spark_rapids_tpu.execs.jit_cache import expr_key
    from spark_rapids_tpu.ops.partition import HashPartitioning

    part = exec_.output_partitioning
    if not isinstance(part, HashPartitioning) \
            or len(part.exprs) != len(keys):
        return None
    for pe, jk in zip(part.exprs, keys):
        if isinstance(pe, B.BoundReference) \
                and isinstance(jk, B.BoundReference):
            if pe.ordinal != jk.ordinal or pe.dtype != jk.dtype:
                return None
        elif expr_key(pe) != expr_key(jk):
            return None
    return part


RANGE_SORT = register(
    "spark.rapids.tpu.sql.sort.rangeExchange", True,
    "Plan multi-partition ORDER BY as a range-partitioned exchange plus "
    "per-partition sorts (the Spark physical shape, ref: "
    "GpuRangePartitioning.scala); disabled, the sort runs as one "
    "wide out-of-core operator.")


def _plan_sort(p: L.Sort, child_exec: TpuExec) -> TpuExec:
    """Distributed ORDER BY (ref: Spark planning SortExec under a
    RangePartitioning exchange): sample-bounded range exchange, then
    each reduce partition sorts independently; partition index order
    equals total order.  Single-partition children sort locally (with
    the out-of-core sample-split path above the size threshold)."""
    from spark_rapids_tpu.execs.exchange import (
        SHUFFLE_PARTITIONS,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.execs.sort import TpuSortExec
    from spark_rapids_tpu.ops.partition import RangePartitioning

    conf = get_conf()
    # tier-2: distributed ORDER BY as a fused range-routed all_to_all
    # plus per-shard local sorts (SURVEY.md §5.8)
    from spark_rapids_tpu.shuffle.transport import get_transport

    transport = get_transport()
    if transport.kind == "collective" \
            and transport.supports_schema(child_exec.schema):
        from spark_rapids_tpu.execs.collective import (
            TpuCollectiveSortExec,
            stage_bucket_rounds,
        )

        # stage boundary decided at plan time (docs/spmd.md)
        return TpuCollectiveSortExec(
            p.keys, child_exec, transport.mesh,
            bucket_rounds=stage_bucket_rounds(conf))
    if child_exec.num_partitions > 1 and conf.get(RANGE_SORT):
        n = conf.get(SHUFFLE_PARTITIONS)
        ex = TpuShuffleExchangeExec(
            RangePartitioning(p.keys, n), child_exec)
        return TpuSortExec(p.keys, ex, scope="partition")
    return TpuSortExec(p.keys, child_exec)


def _plan_aggregate(p: L.Aggregate, child_exec: TpuExec) -> TpuExec:
    """Multi-partition input: partial agg (narrow) -> hash exchange on
    the group keys -> final agg (narrow over key-disjoint partitions) —
    the Spark/reference physical shape (aggregate.scala mode handling
    around ShuffleExchange).  Grand aggregates skip the shuffle manager:
    their "exchange" has a single destination, so the partials are pulled
    straight into the final aggregate through a coalesce-partitions exec
    (prefetching worker pool) with no partitioned-block storage at all.
    Single-partition input: one complete aggregation, no shuffle."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.coalesce import TpuCoalescePartitionsExec
    from spark_rapids_tpu.execs.exchange import (
        SHUFFLE_PARTITIONS,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.ops.partition import HashPartitioning

    has_collect = any(isinstance(na.fn, AG.CollectList)
                      for na in p.aggs)
    if has_collect:
        # ragged results need the dedicated two-phase dense-list exec;
        # mixed collect+scalar aggregate lists still fall back
        if not all(isinstance(na.fn, AG.CollectList) for na in p.aggs):
            return CpuFallbackExec(p, child_exec)
        from spark_rapids_tpu.execs.collect_agg import TpuCollectAggExec

        if child_exec.num_partitions > 1:
            if p.groups:
                # hash exchange on the group keys makes partitions
                # KEY-DISJOINT: each reduce partition collects
                # independently, outputs union (ref: the reference's
                # shuffle-then-aggregate shape for GpuCollectList);
                # a child already distributed by the keys skips it
                source = child_exec
                if _hash_satisfies(source, list(p.groups)) is None:
                    n = get_conf().get(SHUFFLE_PARTITIONS)
                    source = TpuShuffleExchangeExec(
                        HashPartitioning(p.groups, n), source)
                agg = TpuCollectAggExec(p.groups, p.aggs, source)
                agg.partitioned = True
                return agg
            child_exec = TpuCoalescePartitionsExec(child_exec)
        return TpuCollectAggExec(p.groups, p.aggs, child_exec)
    if p.groups:
        # tier-2 lowering: with the collective transport active, the
        # whole partial->exchange->final pipeline becomes ONE fused
        # all_to_all SPMD program over the mesh (SURVEY.md §5.8)
        from spark_rapids_tpu.shuffle.transport import get_transport

        transport = get_transport()
        if transport.kind == "collective" \
                and transport.supports_schema(child_exec.schema):
            from spark_rapids_tpu.execs.collective import (
                TpuCollectiveHashAggregateExec,
                stage_bucket_rounds,
            )

            # stage boundary decided at plan time (docs/spmd.md)
            return TpuCollectiveHashAggregateExec(
                p.groups, p.aggs, child_exec, transport.mesh,
                bucket_rounds=stage_bucket_rounds())
    if child_exec.num_partitions <= 1:
        return TpuHashAggregateExec(p.groups, p.aggs, child_exec)
    partial = TpuHashAggregateExec(p.groups, p.aggs, child_exec,
                                   mode="partial")
    if p.groups:
        n = get_conf().get(SHUFFLE_PARTITIONS)
        keys = [B.BoundReference(i, f.dtype, f.nullable, f.name)
                for i, f in enumerate(
                    partial.schema.fields[: len(p.groups)])]
        source: TpuExec = TpuShuffleExchangeExec(
            HashPartitioning(keys, n), partial)
    else:
        source = TpuCoalescePartitionsExec(partial)
    return TpuHashAggregateExec(p.groups, p.aggs, source, mode="final",
                                input_schema=child_exec.schema)


def _tree_has_ansi_risk(e) -> bool:
    """True when the tree contains an expression whose ANSI error
    checks only fire inside fused pipelines (integral
    Add/Subtract/Multiply, division family, Cast)."""
    from spark_rapids_tpu.exprs.cast import Cast as _Cast

    stack = [e]
    while stack:
        x = stack.pop()
        if isinstance(x, _Cast):
            return True
        if isinstance(x, (A.Add, A.Subtract, A.Multiply, A.Divide,
                          A.IntegralDivide, A.Remainder, A.Pmod)):
            return True
        stack.extend(x.children)
    return False


# ---------------------------------------------------------------------- #
# Entry points
# ---------------------------------------------------------------------- #

def _rewrite_split_extracts(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Prepass: split(s, d)[i] (GetArrayItem over StringSplit with a
    plain literal delimiter and non-negative literal index) fuses into
    the device SplitPart kernel — the dominant consumption pattern of
    GpuStringSplit; other split uses stay and fall back to the CPU
    engine via StringSplit.check_supported."""

    def xform(e):
        kids = [xform(c) for c in e.children]
        if kids != list(e.children):
            e = e.with_children(kids)
        if isinstance(e, COLL.GetArrayItem) \
                and isinstance(e.child, S.StringSplit) \
                and isinstance(e.index, B.Literal) \
                and e.index.value is not None \
                and int(e.index.value) >= 0:
            sp = e.child
            if isinstance(sp.delim, B.Literal) and sp.delim.value \
                    and not any(ch in S.StringSplit._META
                                for ch in sp.delim.value) \
                    and sp.limit == -1:
                return S.SplitPart(sp.child, sp.delim,
                                   int(e.index.value))
        return e

    def walk(p: L.LogicalPlan) -> None:
        if isinstance(p, L.Project):
            p.exprs = [xform(e) for e in p.exprs]
        elif isinstance(p, L.Filter):
            p.condition = xform(p.condition)
        for c in p.children:
            walk(c)

    walk(plan)
    return plan


def _rewrite_input_file_exprs(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Prepass: InputFileName/BlockStart/BlockLength become hidden
    per-file constant columns appended by the scan (the reference's
    ColumnarPartitionReaderWithPartitionValues mechanism), provided the
    path from the expression down to a file relation crosses only
    Project/Filter nodes.  Anything else is left in place: the
    expression's check_supported then routes the subtree to the CPU
    engine, which evaluates Spark's no-file-context defaults."""
    import copy as _copy
    import os

    from spark_rapids_tpu.exprs.nondeterministic import InputFileName

    def tree_has(e) -> bool:
        stack = [e]
        while stack:
            x = stack.pop()
            if isinstance(x, InputFileName):
                return True
            stack.extend(x.children)
        return False

    def node_exprs(p):
        if isinstance(p, L.Project):
            return p.exprs
        if isinstance(p, L.Filter):
            return [p.condition]
        return []

    from spark_rapids_tpu.exprs.nondeterministic import (
        InputFileBlockLength,
        InputFileBlockStart,
    )

    def augment_relation(rel: L.LogicalPlan) -> L.LogicalPlan:
        rel2 = _copy.copy(rel)
        hidden = [T.Field(InputFileName.HIDDEN, T.STRING, False),
                  T.Field(InputFileBlockStart.HIDDEN, T.LONG, False),
                  T.Field(InputFileBlockLength.HIDDEN, T.LONG, False)]
        pvs = []
        for i, path in enumerate(rel.paths):
            pv = dict(rel.partition_values[i]
                      if i < len(rel.partition_values) else {})
            try:
                size = os.path.getsize(path)
            except OSError:
                size = -1
            pv[InputFileName.HIDDEN] = path
            pv[InputFileBlockStart.HIDDEN] = 0
            pv[InputFileBlockLength.HIDDEN] = size
            pvs.append(pv)
        rel2.partition_values = pvs
        rel2.partition_fields = list(rel.partition_fields) + hidden
        rel2._schema = T.Schema(list(rel.schema.fields) + hidden)
        return rel2

    def augment_chain(p: L.LogicalPlan):
        """Rebuild the Project/Filter chain below `p` over an augmented
        relation; returns the new child or None (unsupported shape)."""
        if isinstance(p, (L.ParquetRelation, L.OrcRelation,
                          L.CsvRelation)):
            return augment_relation(p)
        if isinstance(p, L.Project):
            child = augment_chain(p.children[0])
            if child is None:
                return None
            from spark_rapids_tpu.exprs.base import ColumnReference

            exprs = list(p.exprs) + [
                ColumnReference(f.name)
                for f in child.schema.fields[-3:]]
            return L.Project(exprs, child)
        if isinstance(p, L.Filter):
            child = augment_chain(p.children[0])
            if child is None:
                return None
            return L.Filter(p.condition, child)
        return None

    def replace_exprs(e, schema):
        from spark_rapids_tpu.exprs.base import Alias, ColumnReference

        if isinstance(e, InputFileName):
            return Alias(ColumnReference(e.HIDDEN), e.name)
        kids = [replace_exprs(c, schema) for c in e.children]
        return e.with_children(kids) if e.children else e

    def walk(p: L.LogicalPlan) -> L.LogicalPlan:
        new_children = [walk(c) for c in p.children]
        if new_children != p.children:
            p = _copy.copy(p)
            p.children = new_children
        if not any(tree_has(e) for e in node_exprs(p)):
            return p
        child = augment_chain(p.children[0])
        if child is None:
            return p  # leave for check_supported -> CPU fallback
        if isinstance(p, L.Project):
            return L.Project([replace_exprs(e, child.schema)
                              for e in p.exprs], child)
        # Filter: rewrite the condition, then strip the hidden columns
        # so the output schema is unchanged
        cond = replace_exprs(p.condition, child.schema)
        filtered = L.Filter(cond, child)
        keep = [B.BoundReference(i, f.dtype, f.nullable, f.name)
                for i, f in enumerate(p.children[0].schema.fields)]
        return L.Project(keep, filtered)

    return walk(plan)


def _rewrite_scalar_subqueries(plan: L.LogicalPlan,
                               conf) -> L.LogicalPlan:
    """Prepass: run each ScalarSubquery's subplan once and splice its
    value in as a Literal (ref: GpuScalarSubquery's driver-side eager
    evaluation).  Non-mutating: nodes with rewritten expressions are
    shallow-copied."""
    from spark_rapids_tpu.exprs.base import Literal
    from spark_rapids_tpu.exprs.subquery import (
        ScalarSubquery,
        subquery_value,
    )

    new_children = [_rewrite_scalar_subqueries(c, conf)
                    for c in plan.children]

    def rw(e):
        if isinstance(e, ScalarSubquery):
            return Literal.of(subquery_value(e.plan, conf), e.dtype)
        return e

    def has_sq(e) -> bool:
        if isinstance(e, ScalarSubquery):
            return True
        return any(has_sq(c) for c in e.children)

    replaced = False
    out = copy.copy(plan)
    out.children = new_children
    if isinstance(plan, L.Project) and any(has_sq(e) for e in plan.exprs):
        out.exprs = [e.transform_up(rw) for e in plan.exprs]
        replaced = True
    elif isinstance(plan, L.Filter) and has_sq(plan.condition):
        out.condition = plan.condition.transform_up(rw)
        replaced = True
    if not replaced and new_children == plan.children:
        return plan
    return out


def plan_query(plan: L.LogicalPlan, conf=None) -> tuple[TpuExec, PlanMeta]:
    from spark_rapids_tpu import trace as _trace

    conf = conf or get_conf()
    with _trace.span("query.tag"):
        plan = _rewrite_split_extracts(plan)
        plan = _rewrite_input_file_exprs(plan)
        plan = _rewrite_scalar_subqueries(plan, conf)
        _annotate_filter_upload(plan)
        meta = PlanMeta(plan, conf)
        if conf.get(SQL_ENABLED):
            meta.tag()
            from spark_rapids_tpu.plan.cost import optimize_costs

            optimize_costs(meta)
            _demote_unrepresentable_boundaries(meta)
        else:
            meta.will_not_work(f"disabled by {SQL_ENABLED.key}")
    with _trace.span("query.lower"):
        root = convert_meta(meta)
        # runtime join filters must inject BEFORE the encoded-scan
        # marking: the build wrapper changes which exec is a scan's
        # direct parent (plan/runtime_filter.py)
        from spark_rapids_tpu.plan.runtime_filter import (
            inject_runtime_filters,
        )

        inject_runtime_filters(root, conf)
        # coalesce insertion runs BEFORE the encoded-scan marking so
        # the marking can look through the inserted execs
        root = _plan_coalesce(root, conf)
        _mark_encoded_scans(root)
        _plan_pipeline(root, conf)
        _plan_fusion(root)
    return root, meta


def _plan_coalesce(root: TpuExec, conf) -> TpuExec:
    """Insert TpuCoalesceBatchesExec below the operators whose programs
    benefit from dense inputs (spark.rapids.tpu.sql.coalesce.enabled;
    docs/occupancy.md): the bottom link of every fusable chain, hash
    aggregates, hash joins and sorts.  Consecutive small batches from
    the producer below (scans, caches, exchanges, CPU fallbacks) then
    reach the expensive operator concatenated up to the coalesce
    targets.  Off (the default), the plan is untouched — bit-for-bit
    the pre-coalesce engine.  The insertion points are recorded on the
    root (`_coalesce_report`) for DataFrame.explain()."""
    from spark_rapids_tpu.execs.coalesce import (
        TpuCoalesceBatchesExec,
        coalesce_enabled,
    )

    if not coalesce_enabled(conf):
        root._coalesce_report = []
        return root
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.base import FusableExec
    from spark_rapids_tpu.execs.join import _HashJoinBase
    from spark_rapids_tpu.execs.sort import _SortMixin

    def wants_dense_input(node: TpuExec, child: TpuExec) -> bool:
        if isinstance(child, FusableExec):
            # never split a fusable chain (or an aggregate's absorbed
            # chain): the coalesce lands below the chain's BOTTOM link
            # instead, where the chain sources its batches
            return False
        return isinstance(node, (FusableExec, TpuHashAggregateExec,
                                 _HashJoinBase, _SortMixin))

    lines: list[str] = []
    for node in list(root._walk()):
        for i, c in enumerate(list(node.children)):
            if isinstance(c, (TpuCoalesceBatchesExec, CpuFallbackExec)) \
                    or not wants_dense_input(node, c):
                continue
            node.children[i] = TpuCoalesceBatchesExec(c)
            lines.append(f"{node.name} <- coalesce({c.name})")
    root._coalesce_report = lines
    return root


def _mark_encoded_scans(root: TpuExec) -> None:
    """Mark scans whose DIRECT parent fuses the wire decode into its own
    program (fusable chains, hash-aggregate update): those scans emit
    wire-form EncodedBatches, collapsing decode+transform(+update) to
    one program execution per batch (each execution has a fixed
    dispatch cost)."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.base import FusableExec
    from spark_rapids_tpu.execs.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.io.scan import ParquetScanExec

    from spark_rapids_tpu.execs.base import fusion_enabled

    if not fusion_enabled():
        # unfused baseline (spark.rapids.tpu.sql.fusion.enabled=false):
        # scans upload eagerly-decoded batches and every exec runs its
        # own program — the dispatch-soup configuration the fusion
        # smoke's on/off digest + dispatch-count gates compare against
        return
    for node in root._walk():
        for c in node.children:
            # look through a planner-inserted coalesce: the decode no
            # longer fuses into `node`'s program (the coalesce decodes
            # eagerly before concatenating), but the compressed wire
            # upload is preserved and the decode program is cached
            scan = c.children[0] \
                if isinstance(c, TpuCoalesceBatchesExec) else c
            if not isinstance(scan, ParquetScanExec):
                continue
            if isinstance(node, FusableExec) or (
                    isinstance(node, TpuHashAggregateExec)
                    and node.mode != "final"):
                scan.emit_encoded = True


def _plan_pipeline(root: TpuExec, conf) -> None:
    """Choose the software-pipeline stage insertion points for this plan
    (spark.rapids.tpu.sql.pipeline.*; parallel/pipeline.py): every
    Parquet/ORC scan gets its scan->decode and decode->upload stages,
    and the plan root gets the last-exec->fetch stage that collect_exec
    applies — so compute for batch k+1 dispatches while batch k's
    result is fetched D2H.  The chosen list is recorded on the root for
    DataFrame.explain()'s "Pipeline:" section."""
    from spark_rapids_tpu.io.scan import ParquetScanExec
    from spark_rapids_tpu.parallel.pipeline import stage_depth

    depth = stage_depth(conf)
    stages: list[str] = []
    if depth:
        for node in root._walk():
            if isinstance(node, ParquetScanExec):
                node._pipeline_depth = depth
                stages.append(
                    f"{node.name}: scan->decode + decode->upload "
                    f"stages (depth={depth})")
        if not isinstance(root, CpuFallbackExec):
            root._pipeline_fetch = depth
            stages.append(
                f"{root.name}: last-exec->fetch stage (depth={depth})")
    root._pipeline_stages = stages


def _plan_fusion(root: TpuExec) -> None:
    """Record which per-batch chains fuse into single XLA programs —
    and why others don't — for DataFrame.explain()'s "Fusion:" section
    (mirrors the "Pipeline:"/"RuntimeFilters:" sections; the list is
    stored on the root and rendered by eventlog.render_plan_report so
    the persisted plan matches the in-process view).  Pure
    description: it reads the same fusion_chain()/_absorbed_chain()
    decisions the drivers execute, so the report can never say one
    thing while the engine compiles another (docs/fusion.md)."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.base import (
        FusableExec,
        fusion_enabled,
        record_fused_chain,
    )
    from spark_rapids_tpu.execs.jit_cache import donation_enabled
    from spark_rapids_tpu.exprs.base import ansi_enabled

    lines: list[str] = []
    if not fusion_enabled():
        from spark_rapids_tpu.execs.base import _fusion_conf

        root._fusion_report = [
            f"disabled by {_fusion_conf().key}: every exec "
            "dispatches its own per-batch program"]
        return
    donate = donation_enabled()

    def decode_part() -> str:
        return "wire decode fused" + (", inputs donated" if donate
                                      else "")

    absorbed_heads: set[int] = set()
    for node in root._walk():
        if isinstance(node, TpuHashAggregateExec):
            ch = node._absorbed_chain()
            src = node._source_node()
            decode = getattr(src, "emit_encoded", False)
            if ch is not None:
                chain, src, _keys = ch
                absorbed_heads.update(id(e) for e in chain)
                names = "<-".join(e.name for e in reversed(chain))
                parts = [f"update + {len(chain)} exec(s)"]
                if decode:
                    parts.append(decode_part())
                lines.append(
                    f"{node.name}[{node.mode}] absorbs {names}: one "
                    f"program [{', '.join(parts)}] over {src.name}")
                record_fused_chain()
            elif decode:
                # no fusable chain below, but the scan's wire decode
                # still fuses into the update program
                lines.append(
                    f"{node.name}[{node.mode}]: one program "
                    f"[update + {decode_part()}] over {src.name}")
                record_fused_chain()
            elif node.mode != "final" and isinstance(
                    node.children[0], FusableExec):
                why = "ANSI error polling" if ansi_enabled() else \
                    "partition-aware or uncacheable chain"
                lines.append(
                    f"{node.name}[{node.mode}]: child chain NOT "
                    f"absorbed ({why}) — the chain still fuses on "
                    "its own")
    seen: set[int] = set()
    for node in root._walk():
        if not isinstance(node, FusableExec) or id(node) in seen \
                or id(node) in absorbed_heads:
            continue
        chain, src, aware, keys = node.fusion_chain()
        seen.update(id(e) for e in chain)
        decode = getattr(src, "emit_encoded", False) and not aware
        if len(chain) > 1 or decode:
            names = "<-".join(e.name for e in reversed(chain))
            parts = [f"{len(chain)} exec(s)"]
            if decode:
                parts.append(decode_part())
            lines.append(f"{names}: one program "
                         f"[{', '.join(parts)}] over {src.name}")
            record_fused_chain()
            if aware:
                lines[-1] += " (partition-aware: encoded inputs " \
                             "decode eagerly)"
            if not all(k is not None for k in keys):
                lines[-1] += " (uncacheable key: compiled per " \
                             "instance)"
    root._fusion_report = lines


def _schema_device_representable(schema: T.Schema) -> bool:
    """Can a batch of this schema live in device columns?  list<string>
    / list<decimal> exist logically (CPU-engine results) but have no
    dense device layout; map key/value must be fixed-width (the twin
    dense matrices hold physical scalars)."""

    fixed = (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
             T.LongType, T.FloatType, T.DoubleType, T.DateType,
             T.TimestampType)

    def ok(dt: T.DataType) -> bool:
        if isinstance(dt, T.ListType):
            # dense element matrix holds physical scalars only
            return isinstance(dt.element, fixed)
        if isinstance(dt, T.StructType):
            return all(ok(f.dtype) for f in dt.fields)
        if isinstance(dt, T.MapType):
            return isinstance(dt.key, fixed) and isinstance(dt.value,
                                                            fixed)
        return True

    return all(ok(f.dtype) for f in schema.fields)


def _demote_unrepresentable_boundaries(meta: PlanMeta) -> None:
    """A TPU node above a CPU child whose output cannot be uploaded
    would crash at the transition — push the CPU region up until every
    host->device boundary carries representable types (iterates because
    each demotion creates a new boundary one level up)."""
    changed = True
    while changed:
        changed = False

        def walk(m: PlanMeta) -> None:
            nonlocal changed
            for c in m.children:
                if m.can_replace and not c.can_replace \
                        and not _schema_device_representable(
                            c.plan.schema):
                    m.will_not_work(
                        "child output type has no device layout "
                        "(list of string/decimal) — runs on CPU")
                    changed = True
                walk(c)

        walk(meta)


def collect_exec(exec_: TpuExec) -> pa.Table:
    """Drain an exec to a host Arrow table (the D2H plan root): the
    materialized form of :func:`stream_exec` — ONE drain loop serves
    both the classic collect and the serving tier's streaming fetch,
    so the drain protocol (prefetch wiring, traced fetches, iterator/
    exec close invariants) can never diverge between them."""
    tables = list(stream_exec(exec_))
    if not tables:
        return schema_to_arrow(exec_.schema).empty_table()
    return pa.concat_tables(tables)


def stream_exec(exec_: TpuExec, stage: str = "result.fetch"):
    """Drain an exec INCREMENTALLY: one host Arrow table per device
    batch (already cast to the output schema), yielded as produced —
    the serving tier's streaming result fetch (docs/serving.md) and
    the single drain loop under :func:`collect_exec`.

    With the software pipeline on, the plan runs on a prefetch
    producer thread whose bounded queue (`pipeline.depth`) holds the
    in-flight result batches — a slow consumer blocks the producer at
    the queue, so backpressure is the stage depth, not unbounded
    buffering; fetch(k) overlaps compute(k+1) exactly as the classic
    collect's last-exec->fetch stage did.  Closing the generator early
    aborts the stage and closes the exec tree (partial drains release
    shuffle blocks).  A fully-CPU root yields its host table directly
    (also the only path for types with no device layout,
    e.g. list<string>)."""
    from spark_rapids_tpu import trace as _trace
    from spark_rapids_tpu.serving.cancel import check_point

    if isinstance(exec_, CpuFallbackExec):
        try:
            yield exec_.cpu_table().cast(schema_to_arrow(exec_.schema))
        finally:
            exec_.close()
        return
    aschema = schema_to_arrow(exec_.schema)
    try:
        it = exec_.execute()
        fetch_depth = getattr(exec_, "_pipeline_fetch", 0)
        if fetch_depth:
            from spark_rapids_tpu.parallel.pipeline import prefetch

            it = prefetch(it, depth=fetch_depth, stage=stage)
        try:
            for b in it:
                # the result-fetch cancellation checkpoint: a
                # cancelled query raises HERE on the consumer thread;
                # the finallys below close the prefetch stage (abort +
                # join) and the exec tree (shuffle blocks, spillables)
                check_point()
                if _trace.TRACER.enabled:
                    with _trace.span("query.fetch.batch"):
                        t = to_arrow(b)
                else:
                    t = to_arrow(b)
                yield t.cast(aschema)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()
    finally:
        exec_.close()  # release shuffle blocks even on partial drains
