"""128-bit decimal arithmetic on the device, for DECIMAL(p > 18).

A wide decimal column's `data` is a `(capacity, 2)` int64 array: the
unscaled value's low word (the bits of a uint64) and its high word,
signed (`types.py`).  Here a wide value is the pair `(lo, hi)` of
`(capacity,)` int64 arrays.  A chip emulates 64-bit integers as pairs
of 32-bit words, so whatever needs a carry or a product's high half is
done in 32-bit limbs held in uint64 lanes: the product of two limbs and
the sum of a few dozen of them fit one lane exactly.

What Spark computes with `java.math.BigDecimal` is done here exactly,
in as many limbs as the operands need, and then held to the result's
precision: a magnitude of 10^p or more is an overflow, which Spark
(non-ANSI) and this engine answer with NULL.  Rounding is HALF_UP, on
the magnitude, as `Decimal.changePrecision` rounds.

Sums accumulate in split form: each value becomes int64 terms of 32
bits (four for a wide value, two for a narrow one, the top one signed),
and each term is summed on its own, so a segment sum needs no carry
between rows and takes the int64 paths the group-by already has
(`sum_terms`, then `settle` once per group).  A
partial sum that overflowed is carried as POISON (-2^127, or the int64
minimum in a narrow column), a value no decimal of 38 digits can take,
so that a later merge still knows it overflowed and its group's answer
is NULL.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T

_M32 = 0xFFFFFFFF
_INT64_MIN = -(1 << 63)

#: the largest power of ten that one 32-bit limb holds
_CHUNK = 9


def _u(x):
    return lax.bitcast_convert_type(x, jnp.uint64)


def _s(x):
    return lax.bitcast_convert_type(x, jnp.int64)


def _c(v: int):
    return jnp.uint64(v)


def to_unsigned(x):
    """int64 bits as uint64."""
    return _u(x)


def half_up(r, d):
    """1 where a remainder `r` of a division by `d` (both uint64, d
    under 2^63) rounds the quotient up, HALF_UP."""
    return ((r << _c(1)) >= d).astype(jnp.uint64)


# -- layout --------------------------------------------------------- #


def words(data) -> tuple:
    """(lo, hi) of a wide column's `(capacity, 2)` data."""
    return data[:, 0], data[:, 1]


def pack(lo, hi):
    return jnp.stack([lo, hi], axis=-1)


def as_wide(data, dtype: T.DecimalType) -> tuple:
    """(lo, hi) of a decimal column's data, narrow or wide."""
    if dtype.wide:
        return words(data)
    x = data.astype(jnp.int64)
    return x, x >> 63


def as_data(lo, hi, dtype: T.DecimalType):
    """Column data of `dtype` from a value that fits it."""
    return pack(lo, hi) if dtype.wide else lo


def literal_words(value: int) -> tuple:
    """(lo, hi) of a Python integer, as int64 scalars."""
    v = value & ((1 << 128) - 1)
    lo, hi = v & ((1 << 64) - 1), v >> 64
    return (lo - (1 << 64) if lo >> 63 else lo,
            hi - (1 << 64) if hi >> 63 else hi)


# -- sign and magnitude --------------------------------------------- #


def neg(lo, hi) -> tuple:
    return -lo, jnp.where(lo == 0, -hi, ~hi)


def sign_magnitude(data, dtype: T.DecimalType) -> tuple:
    """(negative, magnitude limbs) of a decimal column's data: two
    limbs for a narrow column (|x| < 2^63), four for a wide one."""
    lo, hi = as_wide(data, dtype)
    negative = hi < 0
    nlo, nhi = neg(lo, hi)
    lo, hi = jnp.where(negative, nlo, lo), jnp.where(negative, nhi, hi)
    ul, uh = _u(lo), _u(hi)
    out = [ul & _c(_M32), ul >> _c(32), uh & _c(_M32), uh >> _c(32)]
    return negative, out[:4 if dtype.wide else 2]


def from_magnitude(negative, limbs: list) -> tuple:
    """(lo, hi) of a signed value from the low four limbs of its
    magnitude (the rest must have been checked to be zero)."""
    ls = list(limbs[:4]) + [jnp.zeros_like(limbs[0])] * (4 - len(limbs))
    lo = _s(ls[0] | (ls[1] << _c(32)))
    hi = _s(ls[2] | (ls[3] << _c(32)))
    nlo, nhi = neg(lo, hi)
    return jnp.where(negative, nlo, lo), jnp.where(negative, nhi, hi)


def _limbs_of(value: int) -> list:
    out = []
    while value:
        out.append(value & _M32)
        value >>= 32
    return out or [0]


def below(limbs: list, bound: int):
    """magnitude < `bound` (a Python int), lexicographic from the top
    limb."""
    b = _limbs_of(bound)
    n = max(len(limbs), len(b))
    zero = jnp.zeros_like(limbs[0])
    lt = jnp.zeros(limbs[0].shape, bool)
    eq = jnp.ones(limbs[0].shape, bool)
    for k in reversed(range(n)):
        x = limbs[k] if k < len(limbs) else zero
        bk = _c(b[k] if k < len(b) else 0)
        lt = lt | (eq & (x < bk))
        eq = eq & (x == bk)
    return lt


def fits(limbs: list, precision: int):
    """The magnitude has at most `precision` digits."""
    return below(limbs, 10 ** precision)


# -- limb arithmetic over magnitudes -------------------------------- #


def _carry(acc: list) -> list:
    """Limbs from column sums of limb-sized terms (each under 2^36)."""
    out, c = [], None
    for v in acc:
        if c is not None:
            v = v + c
        out.append(v & _c(_M32))
        c = v >> _c(32)
    out.append(c)
    return out


def mul(a: list, b: list) -> list:
    """Magnitude product, len(a) + len(b) limbs: schoolbook, every
    partial product exact in a uint64 lane."""
    acc = [None] * (len(a) + len(b))

    def add(k, v):
        acc[k] = v if acc[k] is None else acc[k] + v

    for i, x in enumerate(a):
        for j, y in enumerate(b):
            p = x * y
            add(i + j, p & _c(_M32))
            add(i + j + 1, p >> _c(32))
    zero = jnp.zeros_like(a[0])
    return _carry([zero if v is None else v for v in acc])[:len(a) + len(b)]


def mul_small(a: list, m: int) -> list:
    """Magnitude times a constant under 2^32, one limb longer."""
    acc, c = [], None
    for x in a:
        p = x * _c(m)
        if c is not None:
            p = p + c
        acc.append(p & _c(_M32))
        c = p >> _c(32)
    return acc + [c]


def scale_up(a: list, k: int) -> list:
    """Magnitude times 10^k."""
    while k > 0:
        step = min(k, _CHUNK)
        a = mul_small(a, 10 ** step)
        k -= step
    return a


def divmod_small(a: list, d: int) -> tuple:
    """(quotient limbs, remainder) of a magnitude by a constant under
    2^32: from the top limb down, each step's dividend under 2^64."""
    q = [None] * len(a)
    r = jnp.zeros_like(a[0])
    for k in reversed(range(len(a))):
        cur = (r << _c(32)) | a[k]
        q[k] = cur // _c(d)
        r = cur % _c(d)
    return q, r


def add_small(a: list, x) -> list:
    """Magnitude plus a small uint64 array (a rounding's one)."""
    out, c = [], x
    for v in a:
        v = v + c
        out.append(v & _c(_M32))
        c = v >> _c(32)
    return out


def round_down(a: list, k: int) -> list:
    """Magnitude over 10^k, rounded HALF_UP: the first digit dropped
    decides, since the digits below it add less than one of it."""
    if k <= 0:
        return a
    rest = k - 1
    while rest > 0:
        step = min(rest, _CHUNK)
        a, _ = divmod_small(a, 10 ** step)
        rest -= step
    a, digit = divmod_small(a, 10)
    return add_small(a, (digit >= _c(5)).astype(jnp.uint64))


def rescale(a: list, k: int) -> list:
    """Magnitude times 10^k, k of either sign (HALF_UP down)."""
    return scale_up(a, k) if k >= 0 else round_down(a, -k)


def divmod_wide(a: list, d) -> tuple:
    """(quotient limbs, remainder) of a magnitude by a uint64 array
    `d` (0 < d < 2^63): restoring binary division, one bit at a time,
    so that the remainder, doubled, still fits one lane."""
    q = [None] * len(a)
    r = jnp.zeros_like(a[0])
    for k in reversed(range(len(a))):
        limb = a[k]

        def step(i, state, limb=limb):
            r, qk = state
            bit = (limb >> (_c(31) - i.astype(jnp.uint64))) & _c(1)
            r = (r << _c(1)) | bit
            ge = r >= d
            return (jnp.where(ge, r - d, r),
                    (qk << _c(1)) | ge.astype(jnp.uint64))

        r, q[k] = lax.fori_loop(0, 32, step, (r, jnp.zeros_like(limb)))
    return q, r


# -- values --------------------------------------------------------- #


def add(a: tuple, b: tuple) -> tuple:
    """(lo, hi, overflowed) of a 128-bit sum."""
    lo = a[0] + b[0]
    hi = a[1] + b[1] + (_u(lo) < _u(a[0])).astype(jnp.int64)
    return lo, hi, ((a[1] ^ hi) & (b[1] ^ hi)) < 0


def less(a: tuple, b: tuple):
    return (a[1] < b[1]) | ((a[1] == b[1]) & (_u(a[0]) < _u(b[0])))


def equal(a: tuple, b: tuple):
    return (a[0] == b[0]) & (a[1] == b[1])


def held_to(negative, limbs: list, dtype: T.DecimalType) -> tuple:
    """(data of `dtype`, ok): a magnitude held to the type's precision;
    where it does not fit, `ok` is False and the data is zero."""
    ok = fits(limbs, dtype.precision)
    lo, hi = from_magnitude(negative, limbs)
    lo, hi = jnp.where(ok, lo, 0), jnp.where(ok, hi, 0)
    return as_data(lo, hi, dtype), ok


def rescaled(data, src: T.DecimalType, dst: T.DecimalType) -> tuple:
    """(data of `dst`, ok): a decimal column's values at `dst`'s scale
    (HALF_UP where the scale falls) and held to its precision."""
    negative, m = sign_magnitude(data, src)
    return held_to(negative, rescale(m, dst.scale - src.scale), dst)


def can_overflow(ldt: T.DecimalType, rdt: T.DecimalType,
                 out: T.DecimalType) -> bool:
    """Whether a product of the two types can pass `out`'s precision:
    not where the digits of both fit it at the operands' own scale."""
    return (ldt.precision + rdt.precision > out.precision
            or out.scale != ldt.scale + rdt.scale)


def multiply(ld, ldt: T.DecimalType, rd, rdt: T.DecimalType,
             out: T.DecimalType) -> tuple:
    """(data of `out`, ok) of a decimal product: exact, then HALF_UP to
    the result's scale, then held to its precision; `ok` is None where
    no product of the types can overflow (`can_overflow`)."""
    ln, lm = sign_magnitude(ld, ldt)
    rn, rm = sign_magnitude(rd, rdt)
    m = rescale(mul(lm, rm), out.scale - (ldt.scale + rdt.scale))
    if not can_overflow(ldt, rdt, out):
        lo, hi = from_magnitude(ln ^ rn, m)
        return as_data(lo, hi, out), None
    return held_to(ln ^ rn, m, out)


# -- sums ----------------------------------------------------------- #

def poisoned(data, dtype: T.DecimalType):
    """Rows that carry an overflowed partial sum."""
    if dtype.wide:
        lo, hi = words(data)
        return (hi == _INT64_MIN) & (lo == 0)
    return data == _INT64_MIN


def poison(data, dtype: T.DecimalType, where):
    """`data` with POISON at `where`."""
    if dtype.wide:
        lo, hi = words(data)
        return pack(jnp.where(where, 0, lo),
                    jnp.where(where, _INT64_MIN, hi))
    return jnp.where(where, _INT64_MIN, data)


def sum_terms(data, dtype: T.DecimalType, valid, marks: bool) -> list:
    """The int64 columns a decimal sum adds up row by row: the value's
    32-bit parts, the top one signed (two for a narrow column, four for
    a wide one), zero where not `valid`; and, where the rows may be
    partial sums (`marks`), 1 where a row is POISON.  Sums of up to
    2^31 rows of each stay exact in int64."""
    m = jnp.int64(_M32)
    keep = valid
    bad = None
    if marks:
        bad = valid & poisoned(data, dtype)
        keep = valid & ~bad
    if dtype.wide:
        lo, hi = words(data)
        lo, hi = jnp.where(keep, lo, 0), jnp.where(keep, hi, 0)
        terms = [lo & m, (lo >> 32) & m, hi & m, hi >> 32]
    else:
        x = jnp.where(keep, data.astype(jnp.int64), 0)
        terms = [x & m, x >> 32]
    return terms + ([bad.astype(jnp.int64)] if marks else [])


def terms_of(dtype: T.DecimalType, marks: bool) -> int:
    """How many columns `sum_terms` gives."""
    return (4 if dtype.wide else 2) + marks


def settle(sums: list, dtype: T.DecimalType, marks: bool) -> tuple:
    """(data of `dtype`, overflowed) from summed `sum_terms`: carries
    propagated once; an overflow (past 128 bits, past the precision, or
    a poisoned term) is written as POISON."""
    m = jnp.int64(_M32)
    vals = sums[:-1] if marks else sums
    parts, c = [], None
    for t in vals[:-1]:
        t = t if c is None else t + c
        parts.append(t & m)
        c = t >> 32
    top = vals[-1] + c
    if len(vals) == 4:
        lo = (parts[1] << 32) | parts[0]
        hi = (top << 32) | parts[2]
        over = (top < -(1 << 31)) | (top >= (1 << 31))
    else:  # two terms: top * 2^32 + the low part, under 2^95
        lo = (top << 32) | parts[0]
        hi = top >> 32
        over = jnp.zeros(top.shape, bool)
    negative = hi < 0
    nlo, nhi = neg(lo, hi)
    ul = _u(jnp.where(negative, nlo, lo))
    uh = _u(jnp.where(negative, nhi, hi))
    mag = [ul & _c(_M32), ul >> _c(32), uh & _c(_M32), uh >> _c(32)]
    over = over | ~fits(mag, dtype.precision)
    if marks:
        over = over | (sums[-1] > 0)
    return poison(as_data(lo, hi, dtype), dtype, over), over


# -- counting --------------------------------------------------------- #

_NOTES = threading.local()


def note(op: str) -> None:
    """Record, while a program is traced on this thread, one 128-bit
    operation over its rows (`multiply`, `add_sub`, `rescale`,
    `sum_update`, `merge`, `avg_finish`): the exec that compiles the
    program keeps the list, and its spans count rows by it."""
    notes = getattr(_NOTES, "ops", None)
    if notes is not None:
        notes.append(op)


class noting:
    """Context: collects `note`s made inside it (nestable)."""

    def __enter__(self):
        self._prev = getattr(_NOTES, "ops", None)
        _NOTES.ops = self.ops = []
        return self.ops

    def __exit__(self, *exc):
        inner = self.ops
        _NOTES.ops = self._prev
        if self._prev is not None:
            self._prev.extend(inner)
        return False


_OVERFLOWS = threading.local()


def note_overflow(valid, ok) -> None:
    """Record, while a program is traced inside `counting_overflows`,
    how many rows a precision check set to NULL: valid going in, out of
    range."""
    found = getattr(_OVERFLOWS, "found", None)
    if found is not None:
        found.append(jnp.sum((valid & ~ok).astype(jnp.int64)))


class counting_overflows:
    """Context: collects `note_overflow` counts (traced scalars)."""

    def __enter__(self):
        self._prev = getattr(_OVERFLOWS, "found", None)
        _OVERFLOWS.found = self.found = []
        return self.found

    def __exit__(self, *exc):
        _OVERFLOWS.found = self._prev
        return False
