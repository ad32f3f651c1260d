"""Cast expression.

TPU counterpart of GpuCast.scala (1,296 LoC).  Non-ANSI Spark cast
semantics for the supported matrix:

- numeric -> narrower integral: bit truncation (Java semantics);
- float/double -> integral: truncate toward zero; NaN -> 0; +/-inf and
  out-of-range saturate to the target MIN/MAX (Java `(long) d`);
- numeric -> boolean: != 0; boolean -> numeric: 1/0;
- date -> timestamp: midnight UTC; timestamp -> date: floor to day;
- timestamp <-> long: seconds (Spark casts ts to epoch *seconds*);
- integral -> string: device-side digit expansion;
- string -> integral: device-side parse, NULL on malformed (non-ANSI);
- decimal -> wider decimal: int64 unscaled rescale (widening shapes
  only — scale and integral digits both non-decreasing);
- integral -> decimal (when every source digit fits) and
  decimal -> float/double.

Unsupported pairs raise at construction; the planner turns that into a
will-not-work reason and falls back (the reference gates the same way
through TypeSig checks, GpuCast.scala:166)."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.exprs.base import EvalContext, Expression

_INTEGRAL = (T.ByteType, T.ShortType, T.IntegerType, T.LongType)
_FLOATING = (T.FloatType, T.DoubleType)
_NUMERIC = _INTEGRAL + _FLOATING

#: max decimal digits for int64 -> string expansion
_MAX_DIGITS = 20


def cast_supported(src: T.DataType, dst: T.DataType) -> bool:
    if src == dst:
        return True
    ts, td = type(src), type(dst)
    if ts in _NUMERIC and td in _NUMERIC:
        return True
    if ts in _NUMERIC and td is T.BooleanType:
        return True
    if ts is T.BooleanType and td in _NUMERIC:
        return True
    if (ts, td) in ((T.DateType, T.TimestampType),
                    (T.TimestampType, T.DateType)):
        return True
    if ts is T.TimestampType and td is T.LongType:
        return True
    if ts is T.LongType and td is T.TimestampType:
        return True
    if ts in _INTEGRAL + (T.BooleanType,) and td is T.StringType:
        return True
    if ts is T.StringType and td in _INTEGRAL:
        return True
    if ts is T.DecimalType and td is T.DecimalType:
        # any pair: the value rescales HALF_UP and a value past the
        # target's precision is NULL (ANSI reports it)
        return True
    if ts in _INTEGRAL and td is T.DecimalType:
        # widening only: the target must hold every integral digit the
        # source type can produce
        return (dst.precision - dst.scale
                >= T.INTEGRAL_DECIMAL_DIGITS[ts])
    if ts is T.DecimalType and td in _FLOATING:
        # one int64 word over a power of ten; a two-word value has no
        # correctly rounded conversion here yet
        return not src.wide
    return False


@dataclasses.dataclass(repr=False)
class Cast(Expression):
    child: Expression
    to: T.DataType

    @property
    def dtype(self) -> T.DataType:
        return self.to

    def check_supported(self) -> None:
        """Raises for unsupported pairs.  Called after reference binding
        (construction may hold unresolved ColumnReferences); the planner
        turns the raise into a will-not-work reason -> CPU fallback."""
        if not cast_supported(self.child.dtype, self.to):
            raise TypeError(
                f"cast {self.child.dtype} -> {self.to} not supported")

    @property
    def name(self) -> str:
        return f"cast({self.child.name} as {self.to.name})"

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.exprs.base import ansi_active, ansi_report

        self.check_supported()
        src = self.child.dtype
        dst = self.to
        c = self.child.eval(ctx)
        if src == dst:
            return c
        ts, td = type(src), type(dst)
        if ts is T.StringType:
            out = _parse_integral(c, dst)
            if ansi_active():
                # ANSI: malformed input RAISES instead of NULLing
                # (ref: GpuCast ANSI matrix, GpuCast.scala:166)
                ansi_report(
                    c.validity & ~out.validity,
                    f"invalid input syntax for type {dst.name} "
                    "(ANSI cast)")
            return out
        if td is T.StringType:
            return _integral_to_string(c, src, ctx)
        d = c.data
        valid = c.validity
        if td is T.BooleanType:
            return Column(d != 0, valid, dst)
        if ts is T.BooleanType:
            return Column(d.astype(T.to_numpy_dtype(dst)), valid, dst)
        if ts is T.DecimalType and td is T.DecimalType:
            if (not src.wide and not dst.wide and dst.scale >= src.scale
                    and dst.precision - dst.scale
                    >= src.precision - src.scale):
                # widening within one int64: the shift cannot overflow
                shift = dst.scale - src.scale
                return Column(d.astype(jnp.int64) * (10 ** shift), valid,
                              dst)
            from spark_rapids_tpu.ops import int128 as W

            W.note("rescale")
            data, ok = W.rescaled(d, src, dst)
            W.note_overflow(valid, ok)
            if ansi_active():
                ansi_report(valid & ~ok,
                            f"value out of range for {dst.name} "
                            "(ANSI cast overflow)")
            return Column(data, valid & ok, dst)
        if ts in _INTEGRAL and td is T.DecimalType:
            # widening only (cast_supported): value * 10^scale fits
            if dst.wide:
                from spark_rapids_tpu.ops import int128 as W

                data, _ = W.rescaled(
                    d.astype(jnp.int64),
                    T.DecimalType(T.DecimalType.NARROW_PRECISION, 0), dst)
                return Column(data, valid, dst)
            return Column(d.astype(jnp.int64) * (10 ** dst.scale),
                          valid, dst)
        if ts is T.DecimalType and td in _FLOATING:
            out = d.astype(jnp.float64) / (10.0 ** src.scale)
            return Column(out.astype(T.to_numpy_dtype(dst)), valid, dst)
        if (ts, td) == (T.DateType, T.TimestampType):
            from spark_rapids_tpu.exprs.datetime import US_PER_DAY

            return Column(d.astype(jnp.int64) * US_PER_DAY, valid, dst)
        if (ts, td) == (T.TimestampType, T.DateType):
            from spark_rapids_tpu.exprs.datetime import US_PER_DAY

            us = d.astype(jnp.int64)
            return Column((us // US_PER_DAY).astype(jnp.int32), valid, dst)
        if ts is T.TimestampType and td is T.LongType:
            return Column(d.astype(jnp.int64) // 1_000_000, valid, dst)
        if ts is T.LongType and td is T.TimestampType:
            return Column(d.astype(jnp.int64) * 1_000_000, valid, dst)
        phys = T.to_numpy_dtype(dst)
        if ts in _FLOATING and td in _INTEGRAL:
            if ansi_active():
                f = d.astype(jnp.float64)
                info = jnp.iinfo(phys)
                t = jnp.trunc(f)
                bad = valid & (jnp.isnan(f)
                               | (t > float(info.max))
                               | (t < float(info.min)))
                ansi_report(
                    bad, f"value out of range for {dst.name} "
                    "(ANSI cast overflow)")
            return Column(saturating_float_to_integral(d, phys), valid, dst)
        out_data = d.astype(phys)
        if ansi_active() and ts in _INTEGRAL and td in _INTEGRAL \
                and jnp.dtype(phys).itemsize < d.dtype.itemsize:
            # narrowing truncation that loses value = ANSI overflow
            ansi_report(valid & (out_data.astype(d.dtype) != d),
                        f"value out of range for {dst.name} "
                        "(ANSI cast overflow)")
        return Column(out_data, valid, dst)


def saturating_float_to_integral(d, phys):
    """Java (long)(double) semantics: truncate toward zero, NaN -> 0,
    +/-inf and out-of-range saturate at target MIN/MAX.  Saturation is by
    threshold compare: float64 cannot represent INT64_MAX, so
    clip-then-astype would convert 2^63 out of range.  Shared by Cast and
    Ceil/Floor (whose double -> LONG results must saturate identically)."""
    f = d.astype(jnp.float64)
    info = jnp.iinfo(phys)
    hi_f = float(info.max) + 1.0  # exact power of two
    lo_f = float(info.min)
    t = jnp.trunc(jnp.where(jnp.isnan(f), 0.0, f))
    interior = (t > lo_f) & (t < hi_f)
    out = jnp.where(interior, t, 0.0).astype(phys)
    out = jnp.where(t >= hi_f, info.max, out)
    out = jnp.where(t <= lo_f, info.min, out)
    return out


def _integral_to_string(c: Column, src: T.DataType,
                        ctx: EvalContext) -> StringColumn:
    """Digit expansion on device: int64 -> fixed-width decimal bytes."""
    if isinstance(src, T.BooleanType):
        n = c.data.shape[0]
        true_b = jnp.asarray(
            [116, 114, 117, 101, 0], jnp.uint8)  # "true"
        false_b = jnp.asarray(
            [102, 97, 108, 115, 101], jnp.uint8)  # "false"
        b = c.data.astype(bool)
        chars = jnp.where(b[:, None], true_b[None, :], false_b[None, :])
        lengths = jnp.where(b, 4, 5).astype(jnp.int32)
        return StringColumn(chars, lengths, c.validity)
    v = c.data.astype(jnp.int64)
    neg = v < 0
    # abs via where (INT64_MIN-safe: uint arithmetic)
    u = jnp.where(neg, (-(v + 1)).astype(jnp.uint64) + 1,
                  v.astype(jnp.uint64))
    digits = []
    for i in range(_MAX_DIGITS):
        digits.append((u % 10).astype(jnp.uint8))
        u = u // 10
    digs = jnp.stack(digits[::-1], axis=1)  # most significant first
    ndig = jnp.maximum(
        _MAX_DIGITS - jnp.sum(jnp.cumsum(digs != 0, axis=1) == 0, axis=1),
        1).astype(jnp.int32)
    length = ndig + neg.astype(jnp.int32)
    width = _MAX_DIGITS + 1
    # layout: optional '-' then digits left-aligned
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]
    digit_idx = pos - neg.astype(jnp.int32)[:, None] \
        + (_MAX_DIGITS - ndig)[:, None]
    digit_idx_c = jnp.clip(digit_idx, 0, _MAX_DIGITS - 1)
    dig_chars = jnp.take_along_axis(digs, digit_idx_c, axis=1) + 48
    chars = jnp.where((pos == 0) & neg[:, None], 45, dig_chars)  # '-'
    in_range = pos < length[:, None]
    chars = jnp.where(in_range, chars, 0).astype(jnp.uint8)
    return StringColumn(chars, length, c.validity)


def _parse_integral(c: StringColumn, dst: T.DataType) -> Column:
    """String -> integral parse; NULL on malformed (non-ANSI Spark).
    Accepts optional sign + digits + surrounding ASCII whitespace (Spark
    trims UTF8 whitespace before parsing)."""
    chars = c.chars.astype(jnp.int32)
    lengths = c.lengths
    n, w = chars.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    in_str = pos < lengths[:, None]
    is_space = in_str & ((chars == 32) | ((chars >= 9) & (chars <= 13)))
    # leading/trailing whitespace bounds
    lead = jnp.sum(jnp.cumprod(is_space, axis=1), axis=1)
    rev_space = is_space[:, ::-1] | ~in_str[:, ::-1]
    trail_plus_pad = jnp.sum(jnp.cumprod(rev_space, axis=1), axis=1)
    end = w - trail_plus_pad
    start = lead.astype(jnp.int32)
    end = jnp.maximum(end.astype(jnp.int32), start)
    has_sign = in_str & (pos == start[:, None]) & (
        (chars == 45) | (chars == 43))
    sign_neg = jnp.any(has_sign & (chars == 45), axis=1)
    dstart = start + jnp.any(has_sign, axis=1).astype(jnp.int32)
    payload = (pos >= dstart[:, None]) & (pos < end[:, None])
    is_digit = (chars >= 48) & (chars <= 57)
    # Spark accepts a fractional tail and TRUNCATES toward zero
    # (cast('3.5' as int) = 3, cast('-3.5' as int) = -3): digits up to
    # an optional single '.', digits after it ignored for the value
    dot = payload & (chars == 46)
    any_dot = jnp.any(dot, axis=1)
    first_dot = jnp.where(any_dot,
                          jnp.argmax(dot, axis=1).astype(jnp.int32),
                          end)
    int_pos = payload & (pos < first_dot[:, None])
    frac_pos = payload & (pos > first_dot[:, None])
    n_digits = jnp.sum((int_pos | frac_pos) & is_digit, axis=1)
    ok = jnp.all(~int_pos | is_digit, axis=1) \
        & jnp.all(~frac_pos | is_digit, axis=1) \
        & (n_digits > 0)
    is_digit_pos = int_pos
    digit_vals = jnp.where(is_digit_pos & is_digit, chars - 48, 0)
    # Horner in uint64 magnitude with overflow detection (19-digit
    # values can exceed INT64_MAX and must become NULL, not wrap)
    acc = jnp.zeros((n,), jnp.uint64)
    overflow = jnp.zeros((n,), bool)
    safe_mul = jnp.uint64((2**64 - 1) // 10)
    for j in range(w):
        dj = digit_vals[:, j].astype(jnp.uint64)
        use = is_digit_pos[:, j]
        overflow = overflow | (use & (acc > safe_mul))
        nxt = acc * jnp.uint64(10)
        overflow = overflow | (use & (nxt > nxt + dj))  # add wrapped
        acc = jnp.where(use, nxt + dj, acc)
    bound = jnp.where(sign_neg, jnp.uint64(2**63), jnp.uint64(2**63 - 1))
    ok = ok & ~overflow & (acc <= bound)
    mag = acc.astype(jnp.int64)  # -2^63 wraps correctly under negation
    val = jnp.where(sign_neg, -mag, mag)
    phys = T.to_numpy_dtype(dst)
    if not isinstance(dst, T.LongType):
        info = jnp.iinfo(phys)
        ok = ok & (val >= info.min) & (val <= info.max)
    return Column(val.astype(phys), c.validity & ok, dst)
