"""Expression tree base classes.

TPU re-design of the reference's GpuExpression contract
(ref: sql-plugin/.../GpuExpressions.scala:110-134 `columnarEval` returning a
GpuColumnVector or scalar) and reference binding
(ref: GpuBoundAttribute.scala, used at basicPhysicalOperators.scala:114).

Key difference from the reference: `eval` here runs *inside a JAX trace* —
the whole expression tree of an operator (or a fused pipeline of operators)
becomes one XLA program, so there is no per-expression kernel-launch cost
to optimize and literals can simply broadcast (XLA folds them).  Every
`eval` returns a Column/StringColumn of the batch's capacity; SQL NULLs
travel in the validity array.
"""

from __future__ import annotations

import contextlib
import dataclasses
import decimal
import threading
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn


@dataclasses.dataclass
class EvalContext:
    """Evaluation context handed down an expression tree: the input batch
    plus its live-row mask (rows past num_rows must stay NULL).

    `partition_index` / `row_offset` serve PartitionAware expressions
    (Rand, MonotonicallyIncreasingID, ...); they are device scalars when
    the fused pipeline threads them in (so programs stay shared across
    partitions) and plain 0 everywhere partition context is
    meaningless (sort keys, join keys, aggregates — where Spark forbids
    nondeterministic expressions too)."""

    batch: ColumnarBatch
    row_mask: jax.Array
    partition_index: object = 0  # int or jax i32 scalar
    row_offset: object = 0  # int or jax i64 scalar

    @staticmethod
    def for_batch(batch: ColumnarBatch) -> "EvalContext":
        pi = getattr(_PINFO, "v", None) or (0, 0)
        return EvalContext(batch, batch.row_mask(), pi[0], pi[1])


_PINFO = threading.local()


@contextlib.contextmanager
def partition_info(partition_index, row_offset):
    """Scope PartitionAware context for expression evaluation: the fused
    pipeline sets TRACED device scalars here while tracing, so compiled
    programs stay shared across partitions."""
    prev = getattr(_PINFO, "v", None)
    _PINFO.v = (partition_index, row_offset)
    try:
        yield
    finally:
        _PINFO.v = prev


# ------------------------------------------------------------------ #
# ANSI mode (ref: GpuCast.scala:166 ANSI cast matrix + the ANSI
# arithmetic overflow gating in arithmetic.scala).  XLA programs can't
# raise, so error conditions trace as per-row flags collected into one
# int32 error-code scalar the EXEC polls after the program runs —
# the host-side throw the reference gets synchronously from cudf.
# ------------------------------------------------------------------ #

def _register_ansi_conf():
    from spark_rapids_tpu.config import register

    return register(
        "spark.rapids.tpu.sql.ansi.enabled", False,
        "ANSI SQL mode (the spark.sql.ansi.enabled analog): overflow "
        "in Add/Subtract/Multiply and invalid/overflowing casts RAISE "
        "instead of wrapping/NULLing (ref: GpuCast.scala:166 ANSI "
        "matrix; CheckOverflow).")


ANSI_ENABLED = _register_ansi_conf()


class AnsiError(RuntimeError):
    """org.apache.spark.SparkArithmeticException analog."""


def ansi_enabled() -> bool:
    from spark_rapids_tpu.config import get_conf

    return get_conf().get(ANSI_ENABLED)


_ANSI_CAPTURE = threading.local()
_ANSI_MESSAGES: dict[int, str] = {}
_ANSI_CODES: dict[str, int] = {}
_ansi_lock = threading.Lock()


def ansi_code(message: str) -> int:
    """Stable small int code for an error message (trace-time)."""
    with _ansi_lock:
        code = _ANSI_CODES.get(message)
        if code is None:
            code = _ANSI_CODES[message] = len(_ANSI_CODES) + 1
            _ANSI_MESSAGES[code] = message
        return code


@contextlib.contextmanager
def ansi_capture():
    """Scope an ANSI flag accumulator around a traced pipeline; yields
    the list the trace appends (code, any-flag scalar) pairs into."""
    flags: list = []
    prev = getattr(_ANSI_CAPTURE, "v", None)
    _ANSI_CAPTURE.v = flags
    try:
        yield flags
    finally:
        _ANSI_CAPTURE.v = prev


def ansi_active() -> bool:
    """True while a capture is open (the pipeline only opens one when
    ANSI mode is on, so expressions check this, not the conf)."""
    return getattr(_ANSI_CAPTURE, "v", None) is not None


def ansi_report(flag, message: str) -> None:
    """Record a per-row error condition (traced bool array)."""
    cap = getattr(_ANSI_CAPTURE, "v", None)
    if cap is not None:
        cap.append((ansi_code(message), jnp.any(flag)))


def fold_ansi_flags(flags: list) -> jax.Array:
    """(code, flag) pairs -> one int32 scalar (0 = no error)."""
    err = jnp.int32(0)
    for code, f in flags:
        err = jnp.maximum(err, jnp.where(f, jnp.int32(code),
                                         jnp.int32(0)))
    return err


def raise_if_ansi_error(err) -> None:
    code = int(err)
    if code:
        raise AnsiError(
            _ANSI_MESSAGES.get(code, f"ANSI error {code}")
            + ". If necessary set spark.rapids.tpu.sql.ansi.enabled "
            "to false to bypass this error.")


class Expression:
    """Base expression. Subclasses define `dtype`, `nullable` and `eval`.

    `children` is derived automatically from dataclass fields that hold
    Expressions (or tuples of Expressions), in field order, so eval()'s
    named fields (self.left, self.child, ...) can never go stale against
    the child list during tree rewrites.  Variadic/irregular nodes
    (CaseWhen's branch pairs) override both `children` and
    `with_children`.
    """

    @property
    def children(self) -> tuple["Expression", ...]:
        if not dataclasses.is_dataclass(self):
            return ()
        out: list[Expression] = []
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, Expression):
                out.append(v)
            elif isinstance(v, tuple) and v and all(
                    isinstance(x, Expression) for x in v):
                out.extend(v)
        return tuple(out)

    @property
    def dtype(self) -> T.DataType:
        raise NotImplementedError(type(self).__name__)

    @property
    def nullable(self) -> bool:
        return True

    def eval(self, ctx: EvalContext) -> AnyColumn:
        raise NotImplementedError(type(self).__name__)

    # -- tree utilities -------------------------------------------------- #

    def with_children(self, children: Sequence["Expression"]) -> "Expression":
        """Rebuild this node with new children (for binding/rewrites)."""
        children = list(children)
        if not children:
            return self
        assert dataclasses.is_dataclass(self), type(self).__name__
        updates: dict[str, Any] = {}
        i = 0
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, Expression):
                updates[f.name] = children[i]
                i += 1
            elif isinstance(v, tuple) and v and all(
                    isinstance(x, Expression) for x in v):
                updates[f.name] = tuple(children[i:i + len(v)])
                i += len(v)
        assert i == len(children), f"arity mismatch in {type(self).__name__}"
        return dataclasses.replace(self, **updates)

    def transform_up(self, fn) -> "Expression":
        node = self
        if self.children:
            node = self.with_children(
                [c.transform_up(fn) for c in self.children])
        return fn(node)

    @property
    def name(self) -> str:
        return type(self).__name__

    def __repr__(self) -> str:
        if self.children:
            return f"{self.name}({', '.join(map(repr, self.children))})"
        return self.name

    # convenience builders (mirrors the Column DSL of DataFrame frontends)
    def __add__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Add

        return Add(_expr(self), _expr(other))

    def __sub__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Subtract

        return Subtract(_expr(self), _expr(other))

    def __mul__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Multiply

        return Multiply(_expr(self), _expr(other))

    def __truediv__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Divide

        return Divide(_expr(self), _expr(other))

    def __mod__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Remainder

        return Remainder(_expr(self), _expr(other))

    def __neg__(self):
        from spark_rapids_tpu.exprs.arithmetic import UnaryMinus

        return UnaryMinus(_expr(self))

    def __and__(self, other):
        from spark_rapids_tpu.exprs.predicates import And

        return And(_expr(self), _expr(other))

    def __or__(self, other):
        from spark_rapids_tpu.exprs.predicates import Or

        return Or(_expr(self), _expr(other))

    def __invert__(self):
        from spark_rapids_tpu.exprs.predicates import Not

        return Not(_expr(self))

    def _cmp(self, other, cls):
        return cls(_expr(self), _expr(other))

    def __lt__(self, other):
        from spark_rapids_tpu.exprs.predicates import LessThan

        return self._cmp(other, LessThan)

    def __le__(self, other):
        from spark_rapids_tpu.exprs.predicates import LessThanOrEqual

        return self._cmp(other, LessThanOrEqual)

    def __gt__(self, other):
        from spark_rapids_tpu.exprs.predicates import GreaterThan

        return self._cmp(other, GreaterThan)

    def __ge__(self, other):
        from spark_rapids_tpu.exprs.predicates import GreaterThanOrEqual

        return self._cmp(other, GreaterThanOrEqual)

    def eq(self, other):
        from spark_rapids_tpu.exprs.predicates import EqualTo

        return self._cmp(other, EqualTo)

    def ne(self, other):
        from spark_rapids_tpu.exprs.predicates import EqualTo, Not

        return Not(EqualTo(_expr(self), _expr(other)))

    def get_field(self, name: str):
        """struct field access: col("s").get_field("x")."""
        from spark_rapids_tpu.exprs.complex import GetStructField

        return GetStructField(self, name)

    def element_at(self, key):
        """element_at(array, 1-based index) / element_at(map, key)."""
        from spark_rapids_tpu.exprs.complex import ElementAt

        return ElementAt(self, _expr(key))

    def get_map_value(self, key):
        from spark_rapids_tpu.exprs.complex import GetMapValue

        return GetMapValue(self, _expr(key))

    def is_null(self):
        from spark_rapids_tpu.exprs.predicates import IsNull

        return IsNull(self)

    def is_not_null(self):
        from spark_rapids_tpu.exprs.predicates import IsNotNull

        return IsNotNull(self)

    def cast(self, dtype: T.DataType):
        from spark_rapids_tpu.exprs.cast import Cast

        return Cast(self, dtype)

    def alias(self, name: str):
        return Alias(self, name)


def _expr(v) -> Expression:
    return v if isinstance(v, Expression) else Literal.of(v)


def lit(v) -> "Literal":
    return Literal.of(v)


@dataclasses.dataclass(repr=False)
class ColumnReference(Expression):
    """Unresolved reference by column name; resolved against a schema into
    a BoundReference before execution (analysis step)."""

    col_name: str
    _dtype: Optional[T.DataType] = None
    _nullable: bool = True

    @property
    def dtype(self) -> T.DataType:
        if self._dtype is None:
            raise RuntimeError(f"unresolved reference {self.col_name}")
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.col_name

    def eval(self, ctx: EvalContext) -> AnyColumn:
        raise RuntimeError(
            f"unbound reference {self.col_name}; bind_references first")


@dataclasses.dataclass(repr=False)
class BoundReference(Expression):
    """Reference bound to an input-batch ordinal (ref: the reference's
    GpuBoundReference in GpuBoundAttribute.scala)."""

    ordinal: int
    _dtype: T.DataType = dataclasses.field(default_factory=lambda: T.LONG)
    _nullable: bool = True
    col_name: str = ""

    @property
    def dtype(self) -> T.DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.col_name or f"input[{self.ordinal}]"

    def eval(self, ctx: EvalContext) -> AnyColumn:
        col = ctx.batch.columns[self.ordinal]
        # mask out padding rows so downstream reductions can trust validity
        return col.with_validity(col.validity & ctx.row_mask)


@dataclasses.dataclass(repr=False)
class Literal(Expression):
    """A scalar literal, broadcast to the batch capacity at eval
    (ref: literals.scala GpuLiteral/GpuScalar)."""

    value: Any
    _dtype: T.DataType = dataclasses.field(default_factory=lambda: T.LONG)

    @property
    def dtype(self) -> T.DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    @property
    def name(self) -> str:
        return repr(self.value)

    @staticmethod
    def of(v, dtype: Optional[T.DataType] = None) -> "Literal":
        if dtype is None:
            if v is None:
                dtype = T.NULL
            elif isinstance(v, bool):
                dtype = T.BOOLEAN
            elif isinstance(v, (int, np.integer)):
                dtype = T.LONG
            elif isinstance(v, (float, np.floating)):
                dtype = T.DOUBLE
            elif isinstance(v, str):
                dtype = T.STRING
            elif isinstance(v, decimal.Decimal):
                # a Spark decimal literal: its own digits and scale
                dtype = T.decimal_of_literal(v)
            else:
                raise TypeError(f"cannot infer literal type of {v!r}")
        return Literal(v, dtype)

    def eval(self, ctx: EvalContext) -> AnyColumn:
        cap = ctx.batch.capacity
        if isinstance(self._dtype, T.StringType):
            b = (self.value or "").encode("utf-8")
            w = max(len(b), 1)
            chars = jnp.broadcast_to(
                jnp.asarray(np.frombuffer(b.ljust(w, b"\0"), np.uint8)),
                (cap, w))
            lengths = jnp.full(cap, len(b), jnp.int32)
            valid = jnp.full(cap, self.value is not None) & ctx.row_mask
            return StringColumn(chars, lengths, valid)
        phys = T.to_numpy_dtype(self._dtype)
        v = self.value if self.value is not None else 0
        valid = jnp.full(cap, self.value is not None) & ctx.row_mask
        if isinstance(self._dtype, T.DecimalType):
            # the unscaled value, in the column's one or two words
            unscaled = int(decimal.Decimal(v).scaleb(self._dtype.scale))
            if self._dtype.wide:
                from spark_rapids_tpu.ops.int128 import literal_words

                data = jnp.broadcast_to(
                    jnp.asarray(literal_words(unscaled), jnp.int64),
                    (cap, 2))
                return Column(data, valid, self._dtype)
            v = unscaled
        data = jnp.full(cap, v, dtype=phys)
        return Column(data, valid, self._dtype)


@dataclasses.dataclass(repr=False)
class Alias(Expression):
    child: Expression
    out_name: str

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        return self.out_name

    def eval(self, ctx: EvalContext) -> AnyColumn:
        return self.child.eval(ctx)


def bind_references(expr: Expression, schema: T.Schema) -> Expression:
    """Resolve ColumnReferences against `schema` into BoundReferences
    (ref: GpuBindReferences.bindGpuReferences)."""

    def rewrite(e: Expression) -> Expression:
        if isinstance(e, ColumnReference):
            idx = schema.index_of(e.col_name)
            f = schema.fields[idx]
            return BoundReference(idx, f.dtype, f.nullable, f.name)
        return e

    return expr.transform_up(rewrite)


# ---------------------------------------------------------------------- #
# Shared eval helpers
# ---------------------------------------------------------------------- #

def broadcast_validity(*cols: AnyColumn) -> jax.Array:
    v = cols[0].validity
    for c in cols[1:]:
        v = v & c.validity
    return v


def result_numeric_type(left: T.DataType, right: T.DataType,
                        div: bool = False) -> T.DataType:
    if div:
        return T.DOUBLE
    ct = T.common_type(left, right)
    if ct is None:
        raise TypeError(f"incompatible types {left} / {right}")
    return ct
