"""Decimal precision-management expressions.

Analogs of the reference's decimal plumbing (ref:
sql-plugin/.../decimalExpressions.scala — GpuPromotePrecision,
GpuCheckOverflow): Spark's analyzer wraps decimal arithmetic as
CheckOverflow(op(PromotePrecision(cast l), PromotePrecision(cast r))),
and the physical layer works on UNSCALED integer values: one int64 up
to precision 18, two int64 words up to 38 (`ops/int128.py`), so:

- `PromotePrecision` rescales the unscaled value to the target scale
  (a multiply by a power of ten, exact);
- `CheckOverflow` re-asserts the declared precision after an
  operation: values whose magnitude reaches 10^precision become NULL
  (Spark's default nullOnOverflow=true; ANSI raise mode is a planner
  fallback, like the reference's ansiEnabled tagging).

The arithmetic itself (`exprs/arithmetic.py`) types its results as
Spark's `DecimalPrecision` does under the default conf
(`allowPrecisionLoss=true`, `literal.pickMinimumPrecision=true`,
non-ANSI) and applies the same check inside.  `DecimalSumResult` and
`DecimalAverage` finish the decimal sum and average of an aggregate.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column
from spark_rapids_tpu.exprs.base import EvalContext, Expression, Literal
from spark_rapids_tpu.ops import int128 as W

#: how an integral operand's int64 data is laid out: one word, scale 0
_ONE_WORD = T.DecimalType(T.DecimalType.NARROW_PRECISION, 0)

#: Spark's `DecimalType.forType` of each integral type
_FOR_TYPE = {T.ByteType: 3, T.ShortType: 5, T.IntegerType: 10,
             T.LongType: 20}


def operand_type(e: Expression):
    """The decimal type `e` takes as an operand beside a decimal: its
    own; an integer literal's fewest digits; an integral column's
    `DecimalType.forType`.  None for anything else (a float beside a
    decimal makes a double, which this engine leaves to the CPU)."""
    dt = e.dtype
    if isinstance(dt, T.DecimalType):
        return dt
    if isinstance(dt, T.NullType):
        return T.DecimalType(1, 0)  # a NULL operand: the answer is NULL
    if isinstance(dt, T.IntegralType):
        if isinstance(e, Literal) and e.value is not None:
            return T.decimal_of_literal(e.value)
        return T.DecimalType(_FOR_TYPE[type(dt)], 0)
    return None


def decimal_operands(left: Expression, right: Expression):
    """(left type, right type) where a decimal meets a decimal or an
    integral operand; None where neither side is a decimal; raises
    where a decimal meets anything else."""
    l, r = left.dtype, right.dtype
    if not (isinstance(l, T.DecimalType) or isinstance(r, T.DecimalType)):
        return None
    lt, rt = operand_type(left), operand_type(right)
    if lt is None or rt is None:
        raise TypeError(f"decimal beside {l if lt is None else r} falls "
                        "back (Spark computes it as a double)")
    return lt, rt


def decimal_pair_or_none(left: Expression, right: Expression):
    """`decimal_operands`, with None also where a decimal meets an
    operand Spark makes a double of (the result type is then DOUBLE,
    and `check_supported` refuses it)."""
    try:
        return decimal_operands(left, right)
    except TypeError:
        return None


def layout(e: Expression) -> T.DecimalType:
    """How `e`'s evaluated data lies: its decimal type, or one int64
    word at scale 0 for an integral operand."""
    dt = e.dtype
    return dt if isinstance(dt, T.DecimalType) else _ONE_WORD


def operand_data(col: Column, e: Expression):
    dt = e.dtype
    return col.data if isinstance(dt, T.DecimalType) \
        else col.data.astype(jnp.int64)


def compare(lc: Column, left: Expression, rc: Column,
            right: Expression) -> tuple:
    """(lt, eq) of two decimal operands (or a decimal and an integral
    one) by value: at one scale, in one int64 where both are one word
    at one scale, else in 128 bits (`fits_128` holds)."""
    lt_, rt_ = operand_type(left), operand_type(right)
    ld, rd = operand_data(lc, left), operand_data(rc, right)
    ll, rl = layout(left), layout(right)
    scale = max(lt_.scale, rt_.scale)
    if not ll.wide and not rl.wide and (
            max(lt_.precision - lt_.scale, rt_.precision - rt_.scale)
            + scale <= T.DecimalType.NARROW_PRECISION):
        if lt_.scale < scale:
            ld = ld * jnp.int64(10 ** (scale - lt_.scale))
        if rt_.scale < scale:
            rd = rd * jnp.int64(10 ** (scale - rt_.scale))
        return ld < rd, ld == rd
    at = T.DecimalType(T.DecimalType.MAX_PRECISION,
                       max(lt_.scale, rt_.scale))
    a = W.words(W.rescaled(ld, ll, at)[0])
    b = W.words(W.rescaled(rd, rl, at)[0])
    return W.less(a, b), W.equal(a, b)


def fits_128(a: T.DecimalType, b: T.DecimalType) -> bool:
    """Both rescale to the larger scale within 38 digits."""
    return (max(a.precision - a.scale, b.precision - b.scale)
            + max(a.scale, b.scale)) <= T.DecimalType.MAX_PRECISION


@dataclasses.dataclass(repr=False)
class PromotePrecision(Expression):
    """Rescale a decimal child to the target precision/scale (ref:
    decimalExpressions.scala GpuPromotePrecision)."""

    child: Expression
    target: T.DecimalType

    @property
    def dtype(self) -> T.DataType:
        return self.target

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    def check_supported(self) -> None:
        cdt = self.child.dtype
        if not isinstance(cdt, T.DecimalType):
            raise TypeError("PromotePrecision over non-decimal input")
        if self.target.scale < cdt.scale:
            raise TypeError(
                "PromotePrecision cannot reduce scale (would round)")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        assert isinstance(c, Column)
        cdt = self.child.dtype
        if cdt.wide or self.target.wide:
            W.note("rescale")
            data, ok = W.rescaled(c.data, cdt, self.target)
            return Column(data, c.validity & ok, self.target)
        diff = self.target.scale - cdt.scale
        data = c.data * jnp.int64(10 ** diff) if diff else c.data
        return Column(data, c.validity, self.target)


@dataclasses.dataclass(repr=False)
class CheckOverflow(Expression):
    """NULL out values exceeding the declared precision (ref:
    decimalExpressions.scala GpuCheckOverflow, nullOnOverflow=true)."""

    child: Expression
    target: T.DecimalType
    null_on_overflow: bool = True

    @property
    def dtype(self) -> T.DataType:
        return self.target

    @property
    def nullable(self) -> bool:
        return True

    def check_supported(self) -> None:
        if not self.null_on_overflow:
            raise TypeError(
                "ANSI overflow (exception mode) falls back, like the "
                "reference's ansiEnabled tagging")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        assert isinstance(c, Column)
        cdt = self.child.dtype
        assert isinstance(cdt, T.DecimalType)
        if cdt.wide or self.target.wide:
            W.note("rescale")
            data, ok = W.rescaled(c.data, cdt, self.target)
            W.note_overflow(c.validity, ok)
            return Column(data, c.validity & ok, self.target)
        diff = cdt.scale - self.target.scale
        data = c.data
        if diff > 0:
            # scale down with HALF_UP (away from zero) rounding —
            # Spark's toPrecision: round on |v|, restore the sign
            p = jnp.int64(10 ** diff)
            half = p // 2
            mag = (jnp.abs(data) + half) // p
            data = jnp.where(data < 0, -mag, mag)
            bound = jnp.int64(10 ** self.target.precision)
            ok = (data > -bound) & (data < bound)
        elif diff < 0:
            # guard BEFORE scaling up: int64 wraparound could land a
            # huge value back inside the bound and return a wrong
            # non-null result — the exact rows this exists to NULL
            mult = 10 ** (-diff)
            limit = jnp.int64((10 ** self.target.precision - 1) // mult)
            ok = (data >= -limit) & (data <= limit)
            data = data * jnp.int64(mult)
        else:
            bound = jnp.int64(10 ** self.target.precision)
            ok = (data > -bound) & (data < bound)
        return Column(data, c.validity & ok, self.target)


@dataclasses.dataclass(repr=False)
class UnscaledValue(Expression):
    """decimal -> LONG unscaled backing value (ref:
    decimalExpressions.scala GpuUnscaledValue) — zero-copy here: the
    device representation IS the unscaled int64."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.LONG

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        return f"unscaled({self.child.name})"

    def check_supported(self) -> None:
        if not isinstance(self.child.dtype, T.DecimalType):
            raise TypeError("UnscaledValue needs a decimal input")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(c.data.astype(jnp.int64), c.validity, T.LONG)


@dataclasses.dataclass(repr=False)
class MakeDecimal(Expression):
    """LONG unscaled -> decimal(p, s) (ref: GpuMakeDecimal): values
    beyond the declared precision become NULL (nullOnOverflow)."""

    child: Expression
    precision: int
    scale: int

    @property
    def dtype(self) -> T.DataType:
        return T.DecimalType(self.precision, self.scale)

    @property
    def nullable(self) -> bool:
        return True

    @property
    def name(self) -> str:
        return (f"make_decimal({self.child.name}, "
                f"{self.precision}, {self.scale})")

    @property
    def children(self) -> tuple:
        return (self.child,)

    def with_children(self, children):
        return MakeDecimal(children[0], self.precision, self.scale)

    def check_supported(self) -> None:
        from spark_rapids_tpu import types as _T

        if not isinstance(self.child.dtype, _T.IntegralType):
            raise TypeError("MakeDecimal needs an integral input")
        if self.precision > T.DecimalType.MAX_PRECISION:
            raise TypeError("decimal precision beyond int64 falls back")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        data = c.data.astype(jnp.int64)
        bound = jnp.int64(10 ** self.precision)
        ok = (data > -bound) & (data < bound)
        return Column(data, c.validity & ok, self.dtype)


@dataclasses.dataclass(repr=False)
class DecimalSumResult(Expression):
    """The answer of a decimal sum from its merged partial: NULL where
    the partial carries the overflow mark (`ops/int128.py`, POISON), as
    Spark's `Sum` answers NULL once its decimal buffer overflowed."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    @property
    def nullable(self) -> bool:
        return True

    @property
    def name(self) -> str:
        return self.child.name

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        ok = ~W.poisoned(c.data, self.dtype)
        W.note_overflow(c.validity, ok)
        return Column(c.data, c.validity & ok, self.dtype)


def divide_type(sum_type: T.DecimalType) -> T.DecimalType:
    """The type of Spark's `sum / count.cast(DecimalType.LongDecimal)`
    inside `Average` for decimals: `DecimalPrecision`'s divide rule
    over decimal(20, 0) with `allowPrecisionLoss`."""
    p1, s1, p2 = sum_type.precision, sum_type.scale, 20
    scale = max(6, s1 + p2 + 1)
    return T.adjust_precision_scale(p1 - s1 + scale, scale)


def average_type(input_type: T.DecimalType) -> T.DecimalType:
    """Spark's `Average` result for a decimal input: decimal(p+4, s+4),
    bounded at 38."""
    return T.DecimalType(min(input_type.precision + 4,
                             T.DecimalType.MAX_PRECISION),
                         min(input_type.scale + 4,
                             T.DecimalType.MAX_PRECISION))


@dataclasses.dataclass(repr=False)
class DecimalAverage(Expression):
    """Spark's `Average.evaluateExpression` for decimals, from the sum
    and count partials: the sum divided by the count at `divide_type`'s
    scale, rounded HALF_UP, then cast to the result type, rounded
    HALF_UP again; NULL for no rows, an overflowed sum, or a value past
    either precision.

    Spark divides to scale 38 first and rounds that to the divide
    type's scale; with a count under 10^23 no quotient has the run of
    nines that would make the two roundings differ, so one rounding at
    the divide type's scale is the same answer."""

    total: Expression
    count: Expression
    out: T.DecimalType

    @property
    def dtype(self) -> T.DataType:
        return self.out

    @property
    def nullable(self) -> bool:
        return True

    @property
    def name(self) -> str:
        return f"avg({self.total.name})"

    def eval(self, ctx: EvalContext) -> AnyColumn:
        s = self.total.eval(ctx)
        n = self.count.eval(ctx)
        sdt = self.total.dtype
        div = divide_type(sdt)
        W.note("avg_finish")
        valid = (s.validity & n.validity & (n.data > 0)
                 & ~W.poisoned(s.data, sdt))
        negative, mag = W.sign_magnitude(s.data, sdt)
        mag = W.scale_up(mag, div.scale - sdt.scale)
        d = jnp.where(valid, n.data, 1).astype(jnp.int64)
        d = W.to_unsigned(d)
        q, r = W.divmod_wide(mag, d)
        q = W.add_small(q, W.half_up(r, d))
        ok = W.fits(q, div.precision)
        data, held = W.held_to(negative,
                               W.rescale(q, self.out.scale - div.scale),
                               self.out)
        ok = ok & held
        W.note_overflow(valid, ok)
        return Column(data, valid & ok, self.out)
