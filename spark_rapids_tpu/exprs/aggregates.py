"""Declarative aggregate functions.

TPU counterpart of the reference's GpuAggregateFunction hierarchy
(ref: sql-plugin/.../org/apache/spark/sql/rapids/AggregateFunctions.scala,
704 LoC: Sum/Count/Min/Max/Average/First/Last/Pivot) which decomposes
every SQL aggregate into *update* expressions (per input batch),
*merge* expressions (combining partial results, e.g. post-shuffle), and
a *finalize* projection (e.g. avg = sum / count).  The same decomposition
drives three placements here: single-batch complete aggregation,
multi-batch streaming re-merge, and distributed partial->exchange->final
plans (aggregate.scala:240's mode handling).

Each function maps its update/merge phases onto the segmented-reduce
kernel ops in ops.groupby (AggSpec)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import BoundReference, Expression
from spark_rapids_tpu.ops.groupby import AggSpec, agg_output_dtype


@dataclasses.dataclass(repr=False)
class AggregateFunction:
    """Base: child input expression(s) + phase decomposition."""

    child: Optional[Expression]

    @property
    def name(self) -> str:
        return type(self).__name__.lower()

    def bind(self, schema: T.Schema) -> "AggregateFunction":
        """Resolve the child expression against the pre-aggregation input
        schema (required before dtype/partial_dtypes are meaningful)."""
        if self.child is None:
            return self
        from spark_rapids_tpu.exprs.base import bind_references

        return type(self)(bind_references(self.child, schema))

    def inputs(self) -> list[Expression]:
        """Expressions projected out of the child batch before update."""
        return [self.child] if self.child is not None else []

    def n_partials(self) -> int:
        return 1

    def update_ops(self) -> list[str]:
        """AggSpec ops over this function's input columns (one per
        partial)."""
        raise NotImplementedError

    def merge_ops(self) -> list[str]:
        """AggSpec ops over this function's partial columns."""
        raise NotImplementedError

    def partial_dtypes(self) -> list[T.DataType]:
        ops = self.update_ops()
        in_dt = self.child.dtype if self.child is not None else None
        return [agg_output_dtype(AggSpec(op, 0), in_dt) for op in ops]

    def finalize_expr(self, partial_refs: list[Expression]) -> Expression:
        """Expression over the partial columns producing the SQL result."""
        return partial_refs[0]

    @property
    def dtype(self) -> T.DataType:
        return self.partial_dtypes()[0]

    @property
    def nullable(self) -> bool:
        return True


class Sum(AggregateFunction):
    """sum(expr).  Over a decimal(p, s) it is Spark's decimal(min(p +
    10, 38), s), summed exactly (`ops/int128.py`): a partial that
    overflowed carries a mark through the merge and the answer is
    NULL."""

    def update_ops(self):
        return ["sum"]

    def merge_ops(self):
        return ["sum"]

    def finalize_expr(self, partial_refs):
        if isinstance(self.dtype, T.DecimalType):
            from spark_rapids_tpu.exprs.decimal import DecimalSumResult

            return DecimalSumResult(partial_refs[0])
        return partial_refs[0]


class Count(AggregateFunction):
    """count(expr): counts non-null rows; count(*) via CountStar."""

    def update_ops(self):
        return ["count"]

    def merge_ops(self):
        return ["sum"]

    @property
    def dtype(self) -> T.DataType:
        return T.LONG

    @property
    def nullable(self) -> bool:
        return False

    def finalize_expr(self, partial_refs):
        from spark_rapids_tpu.exprs.predicates import Coalesce
        from spark_rapids_tpu.exprs.base import Literal

        # the merge phase SUMs counts; over an empty grand aggregate that
        # sum is NULL but SQL count() must be 0
        return Coalesce(partial_refs[0], Literal.of(0))


class CountDistinct(AggregateFunction):
    """count(DISTINCT x) marker: never executes directly — the session
    frontend rewrites it into a two-level aggregate (group-by-x dedupe
    then count), the single-distinct specialization of Spark's
    RewriteDistinctAggregates rule."""

    @property
    def name(self) -> str:
        return "count_distinct"


class CountStar(AggregateFunction):
    def __init__(self):
        super().__init__(None)

    def update_ops(self):
        return ["count_star"]

    def merge_ops(self):
        return ["sum"]

    def partial_dtypes(self):
        return [T.LONG]

    @property
    def dtype(self) -> T.DataType:
        return T.LONG

    @property
    def nullable(self) -> bool:
        return False

    def finalize_expr(self, partial_refs):
        from spark_rapids_tpu.exprs.predicates import Coalesce
        from spark_rapids_tpu.exprs.base import Literal

        # merge-sum of counts is NULL only for an empty global aggregate
        return Coalesce(partial_refs[0], Literal.of(0))


class Min(AggregateFunction):
    def update_ops(self):
        return ["min"]

    def merge_ops(self):
        return ["min"]


class Max(AggregateFunction):
    def update_ops(self):
        return ["max"]

    def merge_ops(self):
        return ["max"]


@dataclasses.dataclass(repr=False)
class First(AggregateFunction):
    """first(expr[, ignoreNulls]) — Spark defaults ignoreNulls to FALSE:
    a group whose first value is NULL returns NULL (ref: GpuFirst,
    AggregateFunctions.scala).  Deterministic only after an explicit
    sort, as in Spark."""

    ignore_nulls: bool = False

    def bind(self, schema: T.Schema) -> "First":
        from spark_rapids_tpu.exprs.base import bind_references

        return type(self)(bind_references(self.child, schema),
                          self.ignore_nulls)

    def _op(self) -> str:
        base = type(self).__name__.lower()
        return base if self.ignore_nulls else f"{base}_any"

    def update_ops(self):
        return [self._op()]

    def merge_ops(self):
        return [self._op()]


class Last(First):
    pass


class Average(AggregateFunction):
    """avg = sum / count, decomposed exactly like the reference's
    GpuAverage (AggregateFunctions.scala): partials [sum, count],
    merge [sum, sum], finalize sum/count (NULL when count == 0 — Divide
    by zero yields NULL, matching Spark's null-safe average).  Over a
    decimal(p, s) the sum is Spark's decimal(p + 10, s) and the answer
    decimal(p + 4, s + 4), as `DecimalAverage` computes it."""

    def n_partials(self) -> int:
        return 2

    def update_ops(self):
        return ["sum", "count"]

    def merge_ops(self):
        return ["sum", "sum"]

    def _decimal_input(self):
        dt = self.child.dtype if self.child is not None else None
        return dt if isinstance(dt, T.DecimalType) else None

    def partial_dtypes(self):
        dec = self._decimal_input()
        if dec is not None:
            return [agg_output_dtype(AggSpec("sum", 0), dec), T.LONG]
        return [T.DOUBLE, T.LONG]

    @property
    def dtype(self) -> T.DataType:
        dec = self._decimal_input()
        if dec is not None:
            from spark_rapids_tpu.exprs.decimal import average_type

            return average_type(dec)
        return T.DOUBLE

    def finalize_expr(self, partial_refs):
        if self._decimal_input() is not None:
            from spark_rapids_tpu.exprs.decimal import DecimalAverage

            return DecimalAverage(partial_refs[0], partial_refs[1],
                                  self.dtype)
        from spark_rapids_tpu.exprs.arithmetic import Divide

        return Divide(partial_refs[0], partial_refs[1])


@dataclasses.dataclass
class NamedAgg:
    """An aggregate function with its output column name."""

    fn: AggregateFunction
    out_name: str

    def output_field(self) -> T.Field:
        return T.Field(self.out_name, self.fn.dtype, self.fn.nullable)


class CollectList(AggregateFunction):
    """collect_list(expr): non-null inputs gathered into an array per
    group (ref: AggregateFunctions.scala GpuCollectList; element order
    is unspecified, as in Spark).  Executes on a dedicated two-phase
    dense-list exec (ops/collect.py); multi-partition plans fall back."""

    collect_kind = "list"

    def update_ops(self):
        return ["collect"]

    def merge_ops(self):
        return ["collect"]

    @property
    def dtype(self) -> T.DataType:
        cdt = self.child.dtype
        if isinstance(cdt, T.ListType):
            # nested arrays have no logical type in this engine —
            # a query-construction error, not a fallback (documented
            # divergence: the reference supports array<array<T>>)
            raise TypeError(
                f"{self.name} over an array column is not supported "
                "by this engine (no nested array type)")
        return T.ListType(cdt)

    @property
    def nullable(self) -> bool:
        return False  # empty group -> empty list, never NULL

    def check_supported(self) -> None:
        dt = self.child.dtype
        if isinstance(dt, (T.StringType, T.DecimalType)):
            raise TypeError(
                f"{self.name} over {dt.name} input runs on the CPU "
                "engine (device lists hold fixed-width elements only)")


class CollectSet(CollectList):
    """collect_set(expr): distinct non-null inputs per group (total
    order equality: NaN == NaN dedups)."""

    collect_kind = "set"


@dataclasses.dataclass(repr=False)
class PivotFirst(AggregateFunction):
    """pivot aggregate marker (ref: GpuPivotFirst,
    AggregateFunctions.scala): first value of `child` per group per
    pivot value.  Expanded at aggregate construction into one masked
    First per pivot value — PivotFirst(p, v, [a, b]) becomes
    first(if(p = a, v, null) ignore nulls), first(if(p = b, ...)) —
    the exact per-slot semantics the reference's array-building kernel
    computes, laid out straight into the output columns."""

    pivot: Expression = None  # type: ignore[assignment]
    pivot_values: tuple = ()

    @property
    def name(self) -> str:
        return (f"pivot_first({self.pivot.name}, {self.child.name}, "
                f"{list(self.pivot_values)})")

    def inputs(self):
        return (self.child, self.pivot)

    def bind(self, schema):
        from spark_rapids_tpu.exprs.base import bind_references

        return PivotFirst(bind_references(self.child, schema),
                          bind_references(self.pivot, schema),
                          tuple(self.pivot_values))

    def expand(self, out_name: str) -> list["NamedAgg"]:
        """The masked-First expansion (one output column per value)."""
        return expand_pivot_aggs(
            self.pivot, self.pivot_values,
            [NamedAgg(First(self.child, ignore_nulls=True), out_name)],
            single=out_name == "__pivot")


def expand_pivot_aggs(pcol, values, named: list["NamedAgg"],
                      single: bool) -> list["NamedAgg"]:
    """Masked-aggregate pivot expansion shared by PivotFirst and
    GroupedData.pivot(): F(v) becomes F(if(p <=> val, v, null)) per
    pivot value.  A None pivot value matches NULL keys (null-safe);
    First/Last flip to ignore_nulls so masked-out rows never win the
    slot (the reference's PivotFirst updates only on a pivot match)."""
    import dataclasses as _dc

    from spark_rapids_tpu.exprs.base import Literal
    from spark_rapids_tpu.exprs.predicates import EqualTo, If, IsNull

    out = []
    for v in values:
        for na in named:
            ins = na.fn.inputs()
            if len(ins) != 1:
                raise ValueError(
                    f"pivot over {na.fn.name} is not supported")
            child = ins[0]
            try:
                null_dt = child.dtype  # bound children know their type
            except RuntimeError:
                null_dt = None  # unbound: NULL literal widens in If
            cond = IsNull(pcol) if v is None \
                else EqualTo(pcol, Literal.of(v))
            masked = If(cond, child, Literal.of(None, null_dt))
            fn2 = _dc.replace(na.fn, child=masked)
            if isinstance(fn2, First) and not fn2.ignore_nulls:
                # non-null-ignoring First/Last would treat masked-out
                # rows as candidate values — Spark's pivot only
                # considers matching rows
                fn2 = _dc.replace(fn2, ignore_nulls=True)
            name = str(v) if single else f"{v}_{na.out_name}"
            out.append(NamedAgg(fn2, name))
    return out
