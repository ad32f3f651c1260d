"""Predicate and null-handling expressions.

TPU counterparts of the reference's predicates and null expressions
(ref: sql-plugin/.../org/apache/spark/sql/rapids/predicates.scala, 631 LoC;
com/nvidia/spark/rapids/nullExpressions.scala, conditionalExpressions.scala,
GpuInSet.scala) with Spark SQL three-valued logic: And/Or are Kleene
(false AND NULL = false, true OR NULL = true), comparisons propagate NULL,
EqualNullSafe/IsNull/IsNotNull/IsNaN never return NULL.

Floating-point comparisons implement Spark's total order for NaN:
NaN = NaN is true and NaN sorts greater than every other value.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.exprs.base import (
    EvalContext,
    Expression,
    broadcast_validity,
)


def _string_cmp(lc: StringColumn, rc: StringColumn):
    """Lexicographic byte comparison of two string columns.
    Returns (lt, eq) boolean arrays.  Widths may differ; compare on the
    common padded width (zero padding sorts first, which matches byte-wise
    UTF-8 ordering on the unpadded strings)."""
    w = max(lc.width, rc.width)
    lchars = jnp.pad(lc.chars, ((0, 0), (0, w - lc.width)))
    rchars = jnp.pad(rc.chars, ((0, 0), (0, w - rc.width)))
    diff = lchars.astype(jnp.int16) - rchars.astype(jnp.int16)
    nz = diff != 0
    any_nz = jnp.any(nz, axis=1)
    first_nz = jnp.argmax(nz, axis=1)
    first_diff = jnp.take_along_axis(diff, first_nz[:, None], axis=1)[:, 0]
    lt = any_nz & (first_diff < 0)
    eq_bytes = ~any_nz
    # zero padding makes "a" and "a\0" byte-equal; break ties on length so
    # embedded-NUL strings compare correctly (shorter prefix sorts first)
    lt = lt | (eq_bytes & (lc.lengths < rc.lengths))
    eq = eq_bytes & (lc.lengths == rc.lengths)
    return lt, eq


def _ordered_cmp(ld, rd):
    """(lt, eq) under Spark's total order: for floats, NaN == NaN and NaN
    is greater than everything else."""
    if jnp.issubdtype(ld.dtype, jnp.floating):
        lnan = jnp.isnan(ld)
        rnan = jnp.isnan(rd)
        eq = (ld == rd) | (lnan & rnan)
        lt = (ld < rd) | (~lnan & rnan)
        return lt, eq
    return ld < rd, ld == rd


@dataclasses.dataclass(repr=False)
class BinaryComparison(Expression):
    left: Expression
    right: Expression

    symbol = "?"

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    def check_supported(self) -> None:
        from spark_rapids_tpu.exprs import decimal as DEC

        try:
            pair = DEC.decimal_operands(self.left, self.right)
        except RuntimeError:
            return  # unbound; the planner re-checks after binding
        if pair is not None and not DEC.fits_128(*pair):
            raise TypeError(
                "decimals whose values do not rescale to one scale "
                "within precision 38 compare on the CPU")

    def _cmp_columns(self, lc: AnyColumn, rc: AnyColumn):
        if isinstance(lc, StringColumn) or isinstance(rc, StringColumn):
            return _string_cmp(lc, rc)
        from spark_rapids_tpu.exprs import decimal as DEC

        pair = DEC.decimal_pair_or_none(self.left, self.right)
        if pair is not None:
            return DEC.compare(lc, self.left, rc, self.right)
        ct = T.common_type(self.left.dtype, self.right.dtype) \
            or self.left.dtype
        phys = T.to_numpy_dtype(ct)
        return _ordered_cmp(lc.data.astype(phys), rc.data.astype(phys))

    def eval(self, ctx: EvalContext) -> AnyColumn:
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        valid = broadcast_validity(lc, rc)
        lt, eq = self._cmp_columns(lc, rc)
        return Column(self.compare_ordered(lt, eq), valid, T.BOOLEAN)

    def compare_ordered(self, lt, eq):
        raise NotImplementedError


class EqualTo(BinaryComparison):
    symbol = "="

    def compare_ordered(self, lt, eq):
        return eq


class LessThan(BinaryComparison):
    symbol = "<"

    def compare_ordered(self, lt, eq):
        return lt


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def compare_ordered(self, lt, eq):
        return lt | eq


class GreaterThan(BinaryComparison):
    symbol = ">"

    def compare_ordered(self, lt, eq):
        return ~(lt | eq)


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def compare_ordered(self, lt, eq):
        return ~lt


class EqualNullSafe(BinaryComparison):
    """<=>: never NULL; NULL <=> NULL is true."""

    symbol = "<=>"

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        _, eq = self._cmp_columns(lc, rc)
        both_null = ~lc.validity & ~rc.validity
        both_valid = lc.validity & rc.validity
        data = (both_null | (both_valid & eq)) & ctx.row_mask
        return Column(data, ctx.row_mask, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class And(Expression):
    left: Expression
    right: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    def eval(self, ctx: EvalContext) -> AnyColumn:
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        lval = lc.data.astype(bool)
        rval = rc.data.astype(bool)
        false_wins = (lc.validity & ~lval) | (rc.validity & ~rval)
        valid = (lc.validity & rc.validity) | false_wins
        return Column(lval & rval & lc.validity & rc.validity,
                      valid, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class Or(Expression):
    left: Expression
    right: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    def eval(self, ctx: EvalContext) -> AnyColumn:
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        lval = lc.data.astype(bool) & lc.validity
        rval = rc.data.astype(bool) & rc.validity
        true_wins = lval | rval
        valid = (lc.validity & rc.validity) | true_wins
        return Column(true_wins, valid, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class Not(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(~c.data.astype(bool), c.validity, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class IsNull(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(~c.validity & ctx.row_mask, ctx.row_mask, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class IsNotNull(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(c.validity & ctx.row_mask, ctx.row_mask, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class IsNaN(Expression):
    """Spark IsNaN: non-nullable; NULL input -> false."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(jnp.isnan(c.data) & c.validity, ctx.row_mask,
                      T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class In(Expression):
    """value IN (literals...) (ref: GpuInSet.scala). NULL semantics: if the
    value is NULL -> NULL; if no match and the list contains NULL -> NULL."""

    child: Expression
    values: tuple

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        has_null = any(v is None for v in self.values)
        vals = [v for v in self.values if v is not None]
        if isinstance(c, StringColumn):
            from spark_rapids_tpu.exprs.base import Literal

            match = jnp.zeros(c.capacity, bool)
            for v in vals:
                litcol = Literal.of(v, T.STRING).eval(ctx)
                _, eq = _string_cmp(c, litcol)
                match = match | eq
        else:
            phys = c.data.dtype
            match = jnp.zeros(c.data.shape[0], bool)
            for v in vals:
                match = match | (c.data == jnp.asarray(v, phys))
        valid = c.validity & (match | (~jnp.asarray(has_null)))
        return Column(match, valid, T.BOOLEAN)


@dataclasses.dataclass(repr=False)
class Coalesce(Expression):
    """First non-null value (ref: nullExpressions.scala GpuCoalesce)."""

    exprs: tuple[Expression, ...]

    def __init__(self, *exprs: Expression):
        self.exprs = tuple(exprs)

    def with_children(self, children):
        return Coalesce(*children)

    @property
    def dtype(self) -> T.DataType:
        from spark_rapids_tpu.exprs.arithmetic import _widen

        if isinstance(self.exprs[0].dtype, T.StringType):
            return T.STRING
        return _widen([e.dtype for e in self.exprs])

    def eval(self, ctx: EvalContext) -> AnyColumn:
        cols = [e.eval(ctx) for e in self.exprs]
        if isinstance(cols[0], StringColumn):
            w = max(c.width for c in cols)
            chars = jnp.zeros((cols[0].capacity, w), jnp.uint8)
            lengths = jnp.zeros(cols[0].capacity, jnp.int32)
            taken = jnp.zeros(cols[0].capacity, bool)
            for c in cols:
                pc = jnp.pad(c.chars, ((0, 0), (0, w - c.width)))
                use = c.validity & ~taken
                chars = jnp.where(use[:, None], pc, chars)
                lengths = jnp.where(use, c.lengths, lengths)
                taken = taken | c.validity
            return StringColumn(chars, lengths, taken)
        phys = T.to_numpy_dtype(self.dtype)
        data = jnp.zeros(cols[0].data.shape[0], phys)
        taken = jnp.zeros(cols[0].data.shape[0], bool)
        for c in cols:
            use = c.validity & ~taken
            data = jnp.where(use, c.data.astype(phys), data)
            taken = taken | c.validity
        return Column(data, taken, self.dtype)


@dataclasses.dataclass(repr=False)
class If(Expression):
    """if(cond, a, b) (ref: conditionalExpressions.scala GpuIf).
    Branch types widen to a common numeric type."""

    pred: Expression
    then: Expression
    otherwise: Expression

    @property
    def dtype(self) -> T.DataType:
        if isinstance(self.then.dtype, T.StringType) or isinstance(
                self.otherwise.dtype, T.StringType):
            return T.STRING
        from spark_rapids_tpu.exprs.arithmetic import _widen

        return _widen([self.then.dtype, self.otherwise.dtype])

    def eval(self, ctx: EvalContext) -> AnyColumn:
        p = self.pred.eval(ctx)
        a = self.then.eval(ctx)
        b = self.otherwise.eval(ctx)
        take_a = p.data.astype(bool) & p.validity
        if isinstance(a, StringColumn):
            w = max(a.width, b.width)
            ac = jnp.pad(a.chars, ((0, 0), (0, w - a.width)))
            bc = jnp.pad(b.chars, ((0, 0), (0, w - b.width)))
            return StringColumn(
                jnp.where(take_a[:, None], ac, bc),
                jnp.where(take_a, a.lengths, b.lengths),
                jnp.where(take_a, a.validity, b.validity))
        phys = T.to_numpy_dtype(self.dtype)
        return Column(
            jnp.where(take_a, a.data.astype(phys), b.data.astype(phys)),
            jnp.where(take_a, a.validity, b.validity),
            self.dtype)


@dataclasses.dataclass(repr=False)
class CaseWhen(Expression):
    """CASE WHEN ... (ref: conditionalExpressions.scala GpuCaseWhen)."""

    branches: tuple[tuple[Expression, Expression], ...]
    else_value: Expression

    @property
    def children(self):
        kids = []
        for c, v in self.branches:
            kids += [c, v]
        kids.append(self.else_value)
        return tuple(kids)

    def with_children(self, children):
        n = len(self.branches)
        branches = tuple(
            (children[2 * i], children[2 * i + 1]) for i in range(n))
        return CaseWhen(branches, children[2 * n])

    @property
    def dtype(self) -> T.DataType:
        vals = [v for _, v in self.branches] + [self.else_value]
        if any(isinstance(v.dtype, T.StringType) for v in vals):
            return T.STRING
        from spark_rapids_tpu.exprs.arithmetic import _widen

        return _widen([v.dtype for v in vals])

    def eval(self, ctx: EvalContext) -> AnyColumn:
        expr: Expression = self.else_value
        for cond, val in reversed(self.branches):
            expr = If(cond, val, expr)
        return expr.eval(ctx)


@dataclasses.dataclass(repr=False)
class AtLeastNNonNulls(Expression):
    n: int
    exprs: tuple[Expression, ...]

    def __init__(self, n: int, exprs: Sequence[Expression]):
        self.n = n
        self.exprs = tuple(exprs)

    @property
    def dtype(self) -> T.DataType:
        return T.BOOLEAN

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        cols = [e.eval(ctx) for e in self.exprs]
        count = None
        for c in cols:
            v = c.validity
            if not isinstance(c, StringColumn):
                if jnp.issubdtype(c.data.dtype, jnp.floating):
                    v = v & ~jnp.isnan(c.data)
            x = v.astype(jnp.int32)
            count = x if count is None else count + x
        return Column(count >= self.n, ctx.row_mask, T.BOOLEAN)
