"""Date/time expressions.

TPU counterparts of datetimeExpressions.scala (845 LoC).  DATE is int32
days since epoch; TIMESTAMP is int64 microseconds UTC (UTC-only, like
the reference: GpuOverrides.scala:439).  Civil-calendar field extraction
uses Howard Hinnant's civil_from_days algorithm — branch-free integer
arithmetic that XLA vectorizes cleanly (vs cudf's datetime kernels)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column
from spark_rapids_tpu.exprs.base import (
    EvalContext,
    Expression,
    broadcast_validity,
)

US_PER_DAY = 86_400_000_000
US_PER_HOUR = 3_600_000_000
US_PER_MINUTE = 60_000_000
US_PER_SECOND = 1_000_000


def civil_from_days(z: jax.Array):
    """days-since-epoch -> (year, month [1,12], day [1,31]).

    Hinnant's algorithm (public domain), int32-safe for the SQL date
    range."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097  # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)  # [0, 365]
    mp = (5 * doy + 2) // 153  # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1  # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)  # [1, 12]
    y = jnp.where(m <= 2, y + 1, y)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def days_from_civil(y: jax.Array, m: jax.Array, d: jax.Array) -> jax.Array:
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def _leap(y):
    return ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)


#: a host (numpy) constant: a jnp array at module level would initialise
#: a JAX backend on import, and whoever imports the package first would
#: own the chip
_DAYS_IN_MONTH = np.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30,
                             31], np.int32)


@dataclasses.dataclass(repr=False)
class _DateField(Expression):
    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.INT

    def _field(self, days: jax.Array) -> jax.Array:
        raise NotImplementedError

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        days = c.data.astype(jnp.int32)
        if isinstance(self.child.dtype, T.TimestampType):
            days = (c.data.astype(jnp.int64) // US_PER_DAY).astype(jnp.int32)
        return Column(self._field(days), c.validity, T.INT)


class Year(_DateField):
    def _field(self, days):
        y, _, _ = civil_from_days(days)
        return y


class Month(_DateField):
    def _field(self, days):
        _, m, _ = civil_from_days(days)
        return m


class DayOfMonth(_DateField):
    def _field(self, days):
        _, _, d = civil_from_days(days)
        return d


class Quarter(_DateField):
    def _field(self, days):
        _, m, _ = civil_from_days(days)
        return (m - 1) // 3 + 1


class DayOfWeek(_DateField):
    """Spark: Sunday=1 .. Saturday=7.  1970-01-01 was a Thursday."""

    def _field(self, days):
        return ((((days.astype(jnp.int64) + 4) % 7 + 7) % 7 + 1)
                .astype(jnp.int32))


class WeekDay(_DateField):
    """Spark weekday(): Monday=0 .. Sunday=6."""

    def _field(self, days):
        return (((days.astype(jnp.int64) + 3) % 7 + 7) % 7).astype(jnp.int32)


class DayOfYear(_DateField):
    def _field(self, days):
        y, _, _ = civil_from_days(days)
        jan1 = days_from_civil(y, jnp.full_like(y, 1), jnp.full_like(y, 1))
        return days - jan1 + 1


@dataclasses.dataclass(repr=False)
class LastDay(Expression):
    """Last day of the input date's month -> DATE."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.DATE

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        days = c.data.astype(jnp.int32)
        y, m, _ = civil_from_days(days)
        dim = jnp.take(_DAYS_IN_MONTH, m - 1)
        dim = jnp.where((m == 2) & _leap(y), 29, dim)
        return Column(days_from_civil(y, m, dim), c.validity, T.DATE)


@dataclasses.dataclass(repr=False)
class AddMonths(Expression):
    """add_months(date, n) — calendar month shift with end-of-month
    clamping (ref: GpuAddMonths, datetimeExpressions.scala): Jan 31 +
    1 month = Feb 28 (29 in leap years).  Proleptic Gregorian on
    device via Hinnant's civil conversions, so pre-1582 dates shift
    exactly like Python's datetime does — the month/year arm of the
    SQL frontend's date-column interval arithmetic lowers here."""

    child: Expression
    months: int

    @property
    def dtype(self) -> T.DataType:
        return T.DATE

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        return f"add_months({self.child.name}, {self.months})"

    @property
    def children(self) -> tuple:
        return (self.child,)

    def with_children(self, children):
        return AddMonths(children[0], self.months)

    def check_supported(self) -> None:
        if not isinstance(self.child.dtype, T.DateType):
            raise TypeError("AddMonths needs a date input")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        days = c.data.astype(jnp.int32)
        y, m, d = civil_from_days(days)
        mi = y.astype(jnp.int64) * 12 + (m - 1) + jnp.int64(self.months)
        # floor divmod keeps pre-year-1 months correct
        y2 = (jnp.where(mi >= 0, mi, mi - 11) // 12).astype(jnp.int32)
        m2 = (mi - y2.astype(jnp.int64) * 12).astype(jnp.int32) + 1
        dim = jnp.take(_DAYS_IN_MONTH, m2 - 1)
        dim = jnp.where((m2 == 2) & _leap(y2), 29, dim)
        d2 = jnp.minimum(d, dim)
        return Column(days_from_civil(y2, m2, d2), c.validity, T.DATE)


@dataclasses.dataclass(repr=False)
class _TimeField(Expression):
    child: Expression

    divisor = US_PER_HOUR
    modulus = 24

    @property
    def dtype(self) -> T.DataType:
        return T.INT

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        us = c.data.astype(jnp.int64)
        # floor-mod keeps pre-epoch timestamps correct
        day_us = ((us % US_PER_DAY) + US_PER_DAY) % US_PER_DAY
        out = (day_us // self.divisor) % self.modulus
        return Column(out.astype(jnp.int32), c.validity, T.INT)


class Hour(_TimeField):
    divisor = US_PER_HOUR
    modulus = 24


class Minute(_TimeField):
    divisor = US_PER_MINUTE
    modulus = 60


class Second(_TimeField):
    divisor = US_PER_SECOND
    modulus = 60


@dataclasses.dataclass(repr=False)
class DateAdd(Expression):
    """date_add(date, days) -> DATE (ref: GpuDateAdd)."""

    left: Expression
    right: Expression

    _sign = 1

    @property
    def dtype(self) -> T.DataType:
        return T.DATE

    def eval(self, ctx: EvalContext) -> AnyColumn:
        l = self.left.eval(ctx)
        r = self.right.eval(ctx)
        out = l.data.astype(jnp.int32) + \
            self._sign * r.data.astype(jnp.int32)
        return Column(out, broadcast_validity(l, r), T.DATE)


class DateSub(DateAdd):
    _sign = -1


@dataclasses.dataclass(repr=False)
class DateDiff(Expression):
    """datediff(end, start) -> INT days."""

    left: Expression
    right: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.INT

    def eval(self, ctx: EvalContext) -> AnyColumn:
        l = self.left.eval(ctx)
        r = self.right.eval(ctx)
        out = l.data.astype(jnp.int32) - r.data.astype(jnp.int32)
        return Column(out, broadcast_validity(l, r), T.INT)


@dataclasses.dataclass(repr=False)
class UnixTimestampFromTs(Expression):
    """to_unix_timestamp(timestamp) -> LONG seconds (floor)."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.LONG

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        us = c.data.astype(jnp.int64)
        return Column(us // US_PER_SECOND, c.validity, T.LONG)


_SUPPORTED_FORMATS = ("yyyy-MM-dd HH:mm:ss", "yyyy-MM-dd")


def _format_chars(days, sec_of_day, fmt: str, cap: int):
    """Device-side date formatting: civil fields -> a uint8 char matrix
    (one fixed-width program per supported format — the GpuOverrides
    regexp-style policy: refuse exotic formats at tagging instead of
    producing wrong output)."""
    from spark_rapids_tpu.columnar.column import pad_width

    y, m, d = civil_from_days(days)
    fields = {
        "yyyy": (y, 4), "MM": (m, 2), "dd": (d, 2),
        "HH": (sec_of_day // 3600, 2),
        "mm": ((sec_of_day // 60) % 60, 2),
        "ss": (sec_of_day % 60, 2),
    }
    out_len = len(fmt)
    width = pad_width(out_len)
    chars = jnp.zeros((cap, width), jnp.uint8)
    i = 0
    pos = 0
    while i < len(fmt):
        for token, (val, nd) in fields.items():
            if fmt.startswith(token, i):
                v = val.astype(jnp.int64)
                for k in range(nd):
                    digit = (v // (10 ** (nd - 1 - k))) % 10
                    chars = chars.at[:, pos + k].set(
                        (digit + ord("0")).astype(jnp.uint8))
                i += len(token)
                pos += nd
                break
        else:
            chars = chars.at[:, pos].set(jnp.uint8(ord(fmt[i])))
            i += 1
            pos += 1
    return chars, out_len


@dataclasses.dataclass(repr=False)
class FromUnixTime(Expression):
    """from_unixtime(seconds, fmt) -> formatted UTC string
    (ref: GpuFromUnixTime, datetimeExpressions.scala)."""

    child: Expression
    fmt: str = "yyyy-MM-dd HH:mm:ss"

    @property
    def dtype(self) -> T.DataType:
        return T.STRING

    def check_supported(self) -> None:
        if self.fmt not in _SUPPORTED_FORMATS:
            raise TypeError(
                f"from_unixtime format {self.fmt!r} not supported "
                f"(supported: {', '.join(_SUPPORTED_FORMATS)})")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.columnar.column import StringColumn

        c = self.child.eval(ctx)
        secs = c.data.astype(jnp.int64)
        days = secs // 86400  # jnp // floors, negatives included
        sod = secs - days * 86400
        chars, out_len = _format_chars(days, sod, self.fmt,
                                       ctx.batch.capacity)
        return StringColumn(
            chars, jnp.full((ctx.batch.capacity,), out_len, jnp.int32),
            c.validity & ctx.row_mask)


@dataclasses.dataclass(repr=False)
class DateFormatClass(Expression):
    """date_format(ts, fmt) -> formatted UTC string
    (ref: GpuDateFormatClass)."""

    child: Expression
    fmt: str = "yyyy-MM-dd"

    @property
    def dtype(self) -> T.DataType:
        return T.STRING

    def check_supported(self) -> None:
        if self.fmt not in _SUPPORTED_FORMATS:
            raise TypeError(
                f"date_format format {self.fmt!r} not supported "
                f"(supported: {', '.join(_SUPPORTED_FORMATS)})")
        if not isinstance(self.child.dtype,
                          (T.DateType, T.TimestampType)):
            raise TypeError("date_format needs a date/timestamp input")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.columnar.column import StringColumn

        c = self.child.eval(ctx)
        if isinstance(self.child.dtype, T.DateType):
            days = c.data.astype(jnp.int64)
            sod = jnp.zeros_like(days)
        else:
            us = c.data.astype(jnp.int64)
            days = us // US_PER_DAY  # floor division, negatives included
            sod = (us - days * US_PER_DAY) // US_PER_SECOND
        chars, out_len = _format_chars(days, sod, self.fmt,
                                       ctx.batch.capacity)
        return StringColumn(
            chars, jnp.full((ctx.batch.capacity,), out_len, jnp.int32),
            c.validity & ctx.row_mask)


@dataclasses.dataclass(repr=False)
class CalendarInterval:
    """A literal calendar interval (months, days, microseconds) — the
    Spark CalendarIntervalType value TimeAdd/DateAddInterval consume
    (ref: TimeSub/TimeAdd in datetimeExpressions.scala)."""

    months: int = 0
    days: int = 0
    microseconds: int = 0


@dataclasses.dataclass(repr=False)
class TimeAdd(Expression):
    """timestamp + interval (ref: GpuTimeAdd/GpuTimeSub,
    datetimeExpressions.scala).  Month components are calendar-
    dependent and fall back (matching the reference, which rejects
    intervals with months)."""

    child: Expression
    interval: CalendarInterval
    _sign = 1

    @property
    def dtype(self) -> T.DataType:
        return T.TIMESTAMP

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        iv = self.interval
        return (f"{self.child.name} + interval({iv.months}m "
                f"{iv.days}d {iv.microseconds}us)")

    @property
    def children(self) -> tuple:
        return (self.child,)

    def with_children(self, children):
        out = type(self)(children[0], self.interval)
        return out

    def check_supported(self) -> None:
        if not isinstance(self.child.dtype, T.TimestampType):
            raise TypeError("TimeAdd needs a timestamp input")
        if self.interval.months:
            raise TypeError(
                "interval months are calendar-dependent — CPU fallback "
                "(the reference rejects them too)")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        delta = (self.interval.days * US_PER_DAY
                 + self.interval.microseconds) * self._sign
        return Column(c.data.astype(jnp.int64) + jnp.int64(delta),
                      c.validity, T.TIMESTAMP)


class TimeSub(TimeAdd):
    _sign = -1


@dataclasses.dataclass(repr=False)
class DateAddInterval(Expression):
    """date + interval -> DATE (ref: GpuDateAddInterval,
    datetimeExpressions.scala: microseconds must be a whole number of
    days in practice; Spark truncates toward zero)."""

    child: Expression
    interval: CalendarInterval

    @property
    def dtype(self) -> T.DataType:
        return T.DATE

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        iv = self.interval
        return f"{self.child.name} + interval({iv.days}d)"

    @property
    def children(self) -> tuple:
        return (self.child,)

    def with_children(self, children):
        return DateAddInterval(children[0], self.interval)

    def check_supported(self) -> None:
        if not isinstance(self.child.dtype, T.DateType):
            raise TypeError("DateAddInterval needs a date input")
        if self.interval.months:
            raise TypeError("interval months fall back")

    def eval(self, ctx: EvalContext) -> AnyColumn:
        c = self.child.eval(ctx)
        days = self.interval.days + int(
            self.interval.microseconds / US_PER_DAY)
        return Column(c.data.astype(jnp.int32) + jnp.int32(days),
                      c.validity, T.DATE)
