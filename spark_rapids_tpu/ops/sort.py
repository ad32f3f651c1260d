"""Total-order sort keys and batch sorting.

TPU counterpart of cudf's `Table.orderBy` as used by GpuSortExec
(ref: sql-plugin/.../GpuSortExec.scala) — but instead of a comparator
kernel, every SQL sort key is mapped to one or more *integer key arrays*
whose ascending lexicographic order equals the SQL order, and
`lexsort_permutation` turns them into the permutation with no dynamic
shapes: least-significant-digit passes, each a stable two-operand sort of
one 32-bit digit and the running permutation.  A single many-operand
`jnp.lexsort` is the same order in one op, but the TPU compiler takes
minutes over it once the operands are 64-bit (the X64 rewriter doubles
them and the comparator grows with every key: 186 s for two keys at 1M
rows on a v5e), while one 32-bit pass inside a `lax.scan` is compiled
once however many keys there are.

Key transforms:
- integers: identity (descending = bitwise NOT, which is monotone-reversing
  and overflow-free, unlike negation at INT_MIN);
- floats: IEEE-754 total-order trick (sign-magnitude -> two's complement);
  NaN's canonical bit pattern sorts above +inf, matching Spark;
- strings: the fixed-width byte matrix is already lexicographic because
  padding bytes are zero; bytes become uint8 key columns (chunked into
  int32 words, 4 bytes per word, to cut lexsort key count 4x);
- NULLs: a leading null-flag key implements NULLS FIRST/LAST;
- dead padding rows always sort last via a most-significant live flag.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn


@dataclasses.dataclass(frozen=True)
class SortOrder:
    """One sort key: column (by ordinal at this layer), direction, null
    placement (Spark default: ascending, nulls first)."""

    ordinal: int
    descending: bool = False
    nulls_last: bool = False


def float_total_order_bits(x: jax.Array) -> jax.Array:
    """Map a FLOAT32 array to ints whose ascending order is IEEE total
    order (with canonical NaN > +inf, as Spark sorts NaN largest).
    float64 has no bitcast form on TPU (the X64 rewriter cannot compile
    64-bit bitcast-convert) — float64_order_keys is its counterpart."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    bits = jnp.where(jnp.isnan(x), jnp.int32(0x7FC00000), bits)
    return jnp.where(bits < 0, bits ^ jnp.int32(2**31 - 1), bits)


def float64_order_keys(x: jax.Array, descending: bool) -> list:
    """int32 keys, minor-first, whose ascending order is float64 total
    order: NaN canonical and largest, -0.0 strictly below 0.0 (the
    bit-order the CPU oracle sorts by).

    Sorting on the float64 value itself compiles for 91 s per size on a
    v5e and the TPU has no 64-bit bitcast, so the keys are built from
    what the TPU's float64 IS: a pair of float32 words (float32's
    exponent range, ~49 bits of mantissa — `x == nextafter(x)` holds
    for four fifths of random doubles there).  hi = float32(x) and
    lo = float32(x - hi) recover the pair exactly, and normalised pairs
    order lexicographically.  Every other backend has true doubles and
    takes their own bits."""
    if jax.default_backend() == "tpu":
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        # the tie on hi is broken by lo only for finite values: below
        # an infinite or NaN hi, lo is inf - inf
        lo = jnp.where(jnp.isfinite(hi), lo, jnp.float32(0))
        keys = [float_total_order_bits(lo), float_total_order_bits(hi)]
    else:
        bits = jax.lax.bitcast_convert_type(x, jnp.int64)
        bits = jnp.where(jnp.isnan(x), jnp.int64(0x7FF8000000000000), bits)
        keys = [jnp.where(bits < 0, bits ^ jnp.int64(2**63 - 1), bits)]
    return [~k for k in keys] if descending else keys


def _string_word_keys(col: StringColumn) -> list[jax.Array]:
    """Big-endian 4-byte words over the byte matrix: ascending word order
    == ascending byte-lexicographic order (zero padding sorts prefixes
    first)."""
    n, width = col.chars.shape
    c = col.chars.astype(jnp.uint32)
    words: list[jax.Array] = []
    for j in range(0, width, 4):

        def byte(off):
            if j + off < width:
                return c[:, j + off]
            return jnp.zeros((n,), jnp.uint32)

        words.append((byte(0) << 24) | (byte(1) << 16) | (byte(2) << 8)
                     | byte(3))
    return words


def column_sort_keys(col: AnyColumn, descending: bool,
                     nulls_last: bool) -> list[jax.Array]:
    """Minor-to-major key arrays for one SQL sort key.  Returned
    minor-first (callers feed lexsort_permutation, whose LAST key is
    primary).

    Value keys are neutralized to a constant under NULL: the slot data
    beneath a null is decoder garbage (fastpar leaves the previous
    value), and if it leaked into the key, NULL rows would order by
    garbage instead of falling through to the next SQL sort key — a
    divergence from Spark that only bites multi-key sorts."""
    if isinstance(col, StringColumn):
        vals = [jnp.where(col.validity, v, 0)
                for v in _string_word_keys(col)]
        if descending:
            vals = [~v for v in vals]
        vals = list(reversed(vals))  # minor-first
    elif isinstance(col.dtype, T.DoubleType):
        vals = float64_order_keys(
            jnp.where(col.validity, col.data, 0.0), descending)
    else:
        d = jnp.where(col.validity, col.data,
                      jnp.zeros((), col.data.dtype))
        if isinstance(col.dtype, T.FloatType):
            k = float_total_order_bits(d)
        elif col.dtype == T.BOOLEAN:
            k = d.astype(jnp.int32)
        else:
            k = d
        if descending:
            k = ~k
        vals = [k]
    null_flag = col.validity.astype(jnp.int32)  # 0 = null
    if nulls_last:
        null_flag = 1 - null_flag
    # null flag is more significant than the value keys
    return vals + [null_flag]


def _u32_digits(k: jax.Array) -> list[jax.Array]:
    """uint32 arrays, minor-first, whose ascending lexicographic order
    is `k`'s ascending order: signed values get their sign bit flipped,
    64-bit values split into two words."""
    dt = jnp.dtype(k.dtype)
    if dt == jnp.uint32:
        return [k]
    if dt == jnp.bool_:
        return [k.astype(jnp.uint32)]
    if jnp.issubdtype(dt, jnp.signedinteger) and dt.itemsize <= 4:
        return [k.astype(jnp.int32).astype(jnp.uint32)
                ^ jnp.uint32(0x80000000)]
    if dt == jnp.int64:
        lo = (k & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (k >> 32).astype(jnp.int32).astype(jnp.uint32)
        return [lo, hi ^ jnp.uint32(0x80000000)]
    raise TypeError(f"no sort digits for a {dt} key")


def _stable_pass(perm: jax.Array, key: jax.Array) -> jax.Array:
    """Reorder `perm` by `key` (indexed by original row), stably."""
    return jax.lax.sort((jnp.take(key, perm), perm), num_keys=1,
                        is_stable=True)[1]


def lexsort_permutation(keys: Sequence[jax.Array]) -> jax.Array:
    """`jnp.lexsort(keys)` (minor key first, stable, int32 result) as
    least-significant-digit passes: the keys become uint32 digits, and
    more than one digit is ONE `lax.scan` over the stacked digits, so
    the sort inside is compiled once.  Every sort the engine runs has
    this one signature (uint32 digit, int32 permutation): the TPU
    compiler takes 23 s over it at 1M rows, and 40-190 s over each
    other one it is given."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    digits = [d for k in keys for d in _u32_digits(k)]
    if len(digits) == 1:
        return _stable_pass(perm, digits[0])
    perm, _ = jax.lax.scan(
        lambda p, d: (_stable_pass(p, d), None), perm, jnp.stack(digits))
    return perm


def stable_argsort(key: jax.Array) -> jax.Array:
    """`jnp.argsort(key, stable=True)` for a 1-D integer or bool key,
    through the engine's one sort signature (int32 result)."""
    return lexsort_permutation([key])


def sort_permutation(batch: ColumnarBatch,
                     orders: Sequence[SortOrder],
                     live=None) -> jax.Array:
    """Stable permutation realizing the SQL ORDER BY; padding rows last.
    `live` overrides the default prefix liveness (masked-filter callers
    mark additional rows dead without compacting first)."""
    keys: list[jax.Array] = []
    for o in reversed(orders):  # minor keys first
        col = batch.columns[o.ordinal]
        keys.extend(column_sort_keys(col, o.descending, o.nulls_last))
    if live is None:
        live = batch.row_mask()
    keys.append(~live)  # live rows first
    return lexsort_permutation(keys)


def sort_batch(batch: ColumnarBatch,
               orders: Sequence[SortOrder]) -> ColumnarBatch:
    perm = sort_permutation(batch, orders)
    return batch.gather(perm, batch.num_rows)
