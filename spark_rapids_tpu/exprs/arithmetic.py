"""Arithmetic expressions.

TPU counterparts of the reference's arithmetic expression library
(ref: sql-plugin/.../org/apache/spark/sql/rapids/arithmetic.scala, 676 LoC)
with Spark SQL semantics: NULL-propagating binary ops, NULL (not error) on
divide/modulo by zero in non-ANSI mode, Java-style truncating integer
division, and Spark's pmod definition (sign handling follows
`r = a % n; if (r < 0) (r + n) % n else r` with Java `%`).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column
from spark_rapids_tpu.exprs.base import (
    EvalContext,
    Expression,
    broadcast_validity,
    result_numeric_type,
)


def _java_divmod(ld, rd):
    """Java's truncate-toward-zero (quotient, remainder) for integer
    arrays.  jnp's // rounds toward -inf; Java truncates toward zero."""
    qi = ld // rd
    rem = ld - qi * rd
    fix = (rem != 0) & ((ld < 0) != (rd < 0))
    qtrunc = jnp.where(fix, qi + 1, qi)
    return qtrunc, ld - qtrunc * rd


def _java_mod(ld, rd):
    return _java_divmod(ld, rd)[1]


@dataclasses.dataclass(repr=False)
class BinaryArithmetic(Expression):
    left: Expression
    right: Expression

    symbol = "?"

    @property
    def dtype(self) -> T.DataType:
        return result_numeric_type(self.left.dtype, self.right.dtype)

    @property
    def nullable(self) -> bool:
        return self.left.nullable or self.right.nullable

    def _phys(self):
        return T.to_numpy_dtype(self.dtype)

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.exprs.base import ansi_active

        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        phys = self._phys()
        ld = lc.data.astype(phys)
        rd = rc.data.astype(phys)
        valid = broadcast_validity(lc, rc)
        pre_valid = valid
        data, valid = self.compute(ld, rd, valid)
        if ansi_active():
            self._ansi_check(ld, rd, data, pre_valid, phys)
        return Column(data, valid, self.dtype)

    def _ansi_check(self, ld, rd, data, valid, phys) -> None:
        """Per-op ANSI error detection (overflow / division by zero);
        `valid` is the PRE-compute row validity."""

    def compute(self, ld, rd, valid):
        raise NotImplementedError


class _DecimalAddSub(BinaryArithmetic):
    """Shared decimal path for +/-: Spark's result type (p = max
    integral digits + max scale + 1, s = max scale, then
    `adjustPrecisionScale`; ref: decimalExpressions.scala /
    DecimalPrecision), an integral operand taken as a decimal of scale
    0.  The operands rescale to the result scale and add exactly: in
    one int64 up to precision 18, in 128 bits past it.  Where the
    operands do not rescale within 38 digits (so the result would need
    rounding or could overflow) the expression falls back."""

    def _decimal_result(self, l: T.DecimalType,
                        r: T.DecimalType) -> T.DecimalType:
        s = max(l.scale, r.scale)
        p = max(l.precision - l.scale, r.precision - r.scale) + s + 1
        return T.adjust_precision_scale(p, s)

    @property
    def dtype(self) -> T.DataType:
        from spark_rapids_tpu.exprs import decimal as DEC

        pair = DEC.decimal_pair_or_none(self.left, self.right)
        if pair is not None:
            return self._decimal_result(*pair)
        return result_numeric_type(self.left.dtype, self.right.dtype)

    def check_supported(self) -> None:
        from spark_rapids_tpu.exprs import decimal as DEC

        try:
            pair = DEC.decimal_operands(self.left, self.right)
        except RuntimeError:
            return  # unbound; the planner re-checks after binding
        if pair is not None and not DEC.fits_128(*pair):
            raise TypeError(
                "decimal +/- whose operands do not rescale within "
                "precision 38 falls back (the sum could need rounding)")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.exprs import decimal as DEC
        from spark_rapids_tpu.ops import int128 as W

        out = self.dtype
        if not isinstance(out, T.DecimalType):
            return super().eval(ctx)
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        valid = broadcast_validity(lc, rc)
        ld = DEC.operand_data(lc, self.left)
        rd = DEC.operand_data(rc, self.right)
        lt, rt = DEC.layout(self.left), DEC.layout(self.right)
        if not out.wide:
            ls = out.scale - lt.scale
            rs = out.scale - rt.scale
            ld = ld * jnp.int64(10 ** ls) if ls else ld
            rd = rd * jnp.int64(10 ** rs) if rs else rd
            data, valid = self.compute(ld, rd, valid)
            return Column(data, valid, out)
        # the operands rescale within 38 digits (check_supported), so
        # neither the rescale nor the sum can overflow 128 bits
        W.note("add_sub")
        a = W.words(W.rescaled(ld, lt, out)[0])
        b = W.words(W.rescaled(rd, rt, out)[0])
        if isinstance(self, Subtract):
            b = W.neg(*b)
        lo, hi, _ = W.add(a, b)
        return Column(W.pack(lo, hi), valid, out)


def _overflow_message(phys) -> str:
    # java.lang.Math.addExact wording (what Spark surfaces)
    return "long overflow" if jnp.dtype(phys).itemsize == 8 \
        else "integer overflow"


class Add(_DecimalAddSub):
    symbol = "+"

    def compute(self, ld, rd, valid):
        return ld + rd, valid

    def _ansi_check(self, ld, rd, data, valid, phys) -> None:
        from spark_rapids_tpu.exprs.base import ansi_report

        if not jnp.issubdtype(phys, jnp.integer):
            return
        # two same-sign operands whose sum flips sign overflowed
        ovf = valid & ((ld >= 0) == (rd >= 0)) \
            & ((data >= 0) != (ld >= 0))
        ansi_report(ovf, _overflow_message(phys))


class Subtract(_DecimalAddSub):
    symbol = "-"

    def compute(self, ld, rd, valid):
        return ld - rd, valid

    def _ansi_check(self, ld, rd, data, valid, phys) -> None:
        from spark_rapids_tpu.exprs.base import ansi_report

        if not jnp.issubdtype(phys, jnp.integer):
            return
        # mixed-sign operands whose difference flips sign overflowed
        ovf = valid & ((ld >= 0) != (rd >= 0)) \
            & ((data >= 0) != (ld >= 0))
        ansi_report(ovf, _overflow_message(phys))


class Multiply(BinaryArithmetic):
    symbol = "*"

    @property
    def dtype(self) -> T.DataType:
        from spark_rapids_tpu.exprs import decimal as DEC

        pair = DEC.decimal_pair_or_none(self.left, self.right)
        if pair is not None:
            # Spark DecimalPrecision: scale adds, precision p1+p2+1,
            # then adjustPrecisionScale (allowPrecisionLoss)
            l, r = pair
            return T.adjust_precision_scale(l.precision + r.precision + 1,
                                            l.scale + r.scale)
        return result_numeric_type(self.left.dtype, self.right.dtype)

    def check_supported(self) -> None:
        from spark_rapids_tpu.exprs import decimal as DEC

        try:
            DEC.decimal_operands(self.left, self.right)
        except RuntimeError:
            return  # unbound; the planner re-checks after binding

    def eval(self, ctx: EvalContext) -> AnyColumn:
        """A decimal product is exact in 32-bit limbs, then HALF_UP to
        the result's scale and NULL past its precision (Spark's
        CheckOverflow around Multiply, non-ANSI)."""
        from spark_rapids_tpu.exprs import decimal as DEC
        from spark_rapids_tpu.ops import int128 as W

        out = self.dtype
        if not isinstance(out, T.DecimalType):
            return super().eval(ctx)
        lc = self.left.eval(ctx)
        rc = self.right.eval(ctx)
        valid = broadcast_validity(lc, rc)
        W.note("multiply")
        data, ok = W.multiply(DEC.operand_data(lc, self.left),
                              DEC.layout(self.left),
                              DEC.operand_data(rc, self.right),
                              DEC.layout(self.right), out)
        if ok is None:
            return Column(data, valid, out)
        W.note_overflow(valid, ok)
        return Column(data, valid & ok, out)

    def compute(self, ld, rd, valid):
        return ld * rd, valid

    def _ansi_check(self, ld, rd, data, valid, phys) -> None:
        from spark_rapids_tpu.exprs.base import ansi_report

        if not jnp.issubdtype(phys, jnp.integer):
            return
        # multiplicative overflow: the product no longer divides back
        # to the left operand (Math.multiplyExact's check), plus the
        # MIN_VALUE * -1 corner
        info = jnp.iinfo(phys)
        back = jnp.where(rd != 0, _java_divmod(data, jnp.where(
            rd != 0, rd, 1))[0], 0)
        ovf = valid & (rd != 0) & (back != ld)
        ovf = ovf | (valid & (ld == info.min) & (rd == -1))
        ansi_report(ovf, _overflow_message(phys))


class _DivByZeroAnsi(BinaryArithmetic):
    """Shared ANSI division-by-zero detection for the divide family
    (both-valid gating; Spark's right-only rule differs on the
    (NULL, 0) corner — documented engine behavior)."""

    def _ansi_check(self, ld, rd, data, valid, phys) -> None:
        from spark_rapids_tpu.exprs.base import ansi_report

        ansi_report(valid & (rd == 0), "Division by zero")


class Divide(_DivByZeroAnsi):
    """Double division; x/0 -> NULL per Spark non-ANSI Divide semantics."""

    symbol = "/"

    @property
    def nullable(self) -> bool:
        return True  # introduces NULL on zero divisor (Spark: always true)

    @property
    def dtype(self) -> T.DataType:
        return T.DOUBLE

    def compute(self, ld, rd, valid):
        zero = rd == 0.0
        safe = jnp.where(zero, 1.0, rd)
        return ld / safe, valid & ~zero



class IntegralDivide(_DivByZeroAnsi):
    """`div`: long division truncated toward zero; x div 0 -> NULL."""

    symbol = "div"

    @property
    def nullable(self) -> bool:
        return True

    @property
    def dtype(self) -> T.DataType:
        return T.LONG

    def compute(self, ld, rd, valid):
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        # integer arithmetic (no float round-trip: big longs lose precision)
        qi, _ = _java_divmod(ld, safe)
        return qi, valid & ~zero



class Remainder(_DivByZeroAnsi):
    """`%` with Java semantics (sign of dividend); x % 0 -> NULL."""

    symbol = "%"

    @property
    def nullable(self) -> bool:
        return True

    def compute(self, ld, rd, valid):
        if jnp.issubdtype(ld.dtype, jnp.floating):
            zero = rd == 0.0
            safe = jnp.where(zero, 1.0, rd)
            return jnp.fmod(ld, safe), valid & ~zero
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        return _java_mod(ld, safe), valid & ~zero



class Pmod(_DivByZeroAnsi):
    """Spark pmod: `r = a % n; if (r < 0) (r + n) % n else r` with Java `%`
    (ref: arithmetic.scala GpuPmod).  Note pmod(-7, -3) = -1, not 2."""

    symbol = "pmod"

    @property
    def nullable(self) -> bool:
        return True

    def compute(self, ld, rd, valid):
        if jnp.issubdtype(ld.dtype, jnp.floating):
            zero = rd == 0.0
            safe = jnp.where(zero, 1.0, rd)
            r = jnp.fmod(ld, safe)
            r = jnp.where(r < 0, jnp.fmod(r + safe, safe), r)
            return r, valid & ~zero
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        r = _java_mod(ld, safe)
        r = jnp.where(r < 0, _java_mod(r + safe, safe), r)
        return r, valid & ~zero



@dataclasses.dataclass(repr=False)
class UnaryMinus(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(-c.data, c.validity, self.dtype)


@dataclasses.dataclass(repr=False)
class UnaryPositive(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    def eval(self, ctx: EvalContext) -> AnyColumn:
        return self.child.eval(ctx)


@dataclasses.dataclass(repr=False)
class Abs(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        return Column(jnp.abs(c.data), c.validity, self.dtype)


def _widen(dtypes) -> T.DataType:
    out = dtypes[0]
    for dt in dtypes[1:]:
        ct = T.common_type(out, dt)
        if ct is None:
            raise TypeError(f"incompatible types {out} / {dt}")
        out = ct
    return out


@dataclasses.dataclass(repr=False)
class Least(Expression):
    """least(...) ignoring NULLs (ref: arithmetic.scala GpuLeast).

    Selection runs on integer *total-order keys* (the sort-key transform
    from ops.sort) rather than the float values themselves, which gets
    Spark's ordering contract for free: NaN counts as the greatest value
    (least(NaN, 1.0) = 1.0, greatest(NaN, 1.0) = NaN).  NULL slots are
    excluded by validity-aware selection, not sentinel keys, so extreme
    valid values (LONG_MAX, +/-inf) are handled exactly."""

    exprs: tuple[Expression, ...]


    def __init__(self, *exprs: Expression):
        self.exprs = tuple(exprs)

    def with_children(self, children):
        return type(self)(*children)

    @property
    def dtype(self) -> T.DataType:
        return _widen([e.dtype for e in self.exprs])

    def eval(self, ctx: EvalContext) -> AnyColumn:
        cols = [e.eval(ctx) for e in self.exprs]
        phys = T.to_numpy_dtype(self.dtype)
        is_float = jnp.issubdtype(phys, jnp.floating)
        acc_val = acc_key = acc_valid = None
        for c in cols:
            d = c.data.astype(phys)
            # floats: Spark total order with NaN largest, realized by
            # canonicalizing NaN to +inf plus an is-NaN tiebreak INSIDE
            # _take_new (a 64-bit bitcast to order bits would not
            # compile through the TPU X64 rewriter); exact f64
            # comparisons are preserved
            key = (jnp.where(jnp.isnan(d), jnp.inf, d), jnp.isnan(d)) \
                if is_float else d
            if acc_val is None:
                acc_val, acc_key, acc_valid = d, key, c.validity
            else:
                # validity-aware select: no NULL sentinel key, so a valid
                # LONG_MAX/LONG_MIN can never collide with a NULL slot
                take = c.validity & (~acc_valid
                                     | self._take_new(key, acc_key))
                acc_val = jnp.where(take, d, acc_val)
                if isinstance(key, tuple):
                    acc_key = tuple(jnp.where(take, k, a)
                                    for k, a in zip(key, acc_key))
                else:
                    acc_key = jnp.where(take, key, acc_key)
                acc_valid = acc_valid | c.validity
        return Column(acc_val, acc_valid, self.dtype)

    @staticmethod
    def _lt(a, b):
        """Total-order less-than over plain or (value, is_nan) keys."""
        if isinstance(a, tuple):
            (va, na), (vb, nb) = a, b
            return (va < vb) | ((va == vb) & ~na & nb)
        return a < b

    def _take_new(self, k, acc_k):
        return self._lt(k, acc_k)


class Greatest(Least):
    def _take_new(self, k, acc_k):
        return self._lt(acc_k, k)
