"""Spark-compatible Murmur3 hashing, vectorized for XLA.

Counterpart of the reference's HashFunctions.scala (GpuMurmur3Hash) whose
whole purpose is *bit-for-bit parity with Spark CPU hash partitioning*
(ref: sql-plugin/.../org/apache/spark/sql/rapids/HashFunctions.scala and
GpuHashPartitioning.scala).  Spark's hash is Murmur3 x86_32 with Spark's
own quirks (from `Murmur3_x86_32.hashUnsafeBytes` in spark-catalyst):

- ints/smaller + float + boolean + date hash as a single 4-byte block;
- longs + double + timestamp hash as two 4-byte blocks (low word first);
- strings hash their UTF-8 bytes: each aligned 4-byte little-endian block
  through mixK1/mixH1, then *each remaining tail byte individually*
  (sign-extended!) through mixK1/mixH1 — this differs from canonical
  murmur3's tail handling and is required for parity;
- NULL columns leave the running seed untouched;
- multi-column hash chains: seed of column i+1 = hash of column i;
  default initial seed is 42.

All arithmetic is uint32 with wrap-around, which XLA vectorizes cleanly on
the VPU; the string path is a static unroll over the fixed byte-matrix
width (W/4 block steps + W masked tail steps).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Union

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.exprs.base import EvalContext, Expression

# plain ints (weak-typed: uint32 math stays uint32) so kernels that
# import the mix functions don't capture device constants
_C1 = 0xCC9E2D51
_C2 = 0x1B873593

DEFAULT_SEED = 42


def _u32(x) -> jax.Array:
    return jnp.asarray(x).astype(jnp.uint32)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1: jax.Array) -> jax.Array:
    k1 = k1 * _C1
    k1 = _rotl(k1, 15)
    return k1 * _C2


def _mix_h1(h1: jax.Array, k1: jax.Array) -> jax.Array:
    h1 = h1 ^ k1
    h1 = _rotl(h1, 13)
    return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _fmix(h1: jax.Array, length: Union[int, jax.Array]) -> jax.Array:
    h1 = h1 ^ _u32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    h1 = h1 ^ (h1 >> 16)
    return h1


def hash_int32_block(word: jax.Array, seed: jax.Array) -> jax.Array:
    """Murmur3 of a single 4-byte value (Spark hashInt)."""
    h1 = _mix_h1(_u32(seed), _mix_k1(_u32(word)))
    return _fmix(h1, 4)


# --------------------------------------------------------------------- #
# Host (numpy) mirrors of the fixed-width block hashes.
#
# The runtime-filter subsystem (plan/runtime_filter.py) builds its Bloom
# bitset ON DEVICE from build-side join keys and probes it ON HOST
# against freshly decoded scan columns — before any byte crosses the
# host->device link.  Both sides must agree bit-for-bit, so the host
# probe mirrors the jax functions above in pure numpy uint32 arithmetic
# (numpy integer ops wrap exactly like XLA's).  Any edit to the device
# functions must be mirrored here; test_runtime_filter.py pins parity
# on randomized keys.
# --------------------------------------------------------------------- #


def np_hash_int32_block(word, seed):
    """numpy mirror of :func:`hash_int32_block`: uint32[n] hashes of
    int32-block values (int/short/byte/date/bool lanes)."""
    import numpy as np

    k1 = np.asarray(word).astype(np.uint32)
    k1 = k1 * np.uint32(_C1)
    k1 = (k1 << np.uint32(15)) | (k1 >> np.uint32(17))
    k1 = k1 * np.uint32(_C2)
    h1 = np.asarray(seed).astype(np.uint32) ^ k1
    h1 = (h1 << np.uint32(13)) | (h1 >> np.uint32(19))
    h1 = h1 * np.uint32(5) + np.uint32(0xE6546B64)
    return _np_fmix(h1, 4)


def np_hash_int64_blocks(value, seed):
    """numpy mirror of :func:`hash_int64_blocks`: uint32[n] hashes of
    8-byte values, low word first (long/timestamp lanes)."""
    import numpy as np

    v = np.asarray(value).astype(np.int64)
    low = (v & np.int64(0xFFFFFFFF)).astype(np.uint32)
    high = ((v >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    h1 = np.asarray(seed).astype(np.uint32)
    for k1 in (low, high):
        k1 = k1 * np.uint32(_C1)
        k1 = (k1 << np.uint32(15)) | (k1 >> np.uint32(17))
        k1 = k1 * np.uint32(_C2)
        h1 = h1 ^ k1
        h1 = (h1 << np.uint32(13)) | (h1 >> np.uint32(19))
        h1 = h1 * np.uint32(5) + np.uint32(0xE6546B64)
    return _np_fmix(h1, 8)


def _np_fmix(h1, length: int):
    import numpy as np

    h1 = h1 ^ np.uint32(length)
    h1 = h1 ^ (h1 >> np.uint32(16))
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> np.uint32(13))
    h1 = h1 * np.uint32(0xC2B2AE35)
    h1 = h1 ^ (h1 >> np.uint32(16))
    return h1


def hash_int64_blocks(value: jax.Array, seed: jax.Array) -> jax.Array:
    """Murmur3 of an 8-byte value, low 32-bit word first (Spark hashLong)."""
    v = value.astype(jnp.int64)
    low = _u32(v & jnp.int64(0xFFFFFFFF))
    high = _u32((v >> 32) & jnp.int64(0xFFFFFFFF))
    h1 = _mix_h1(_u32(seed), _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, 8)


def _float_to_bits(x: jax.Array) -> jax.Array:
    """Java floatToIntBits: canonical NaN 0x7fc00000, else raw IEEE bits."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(jnp.isnan(x), jnp.int32(0x7FC00000), bits)


def _double_to_bits(x: jax.Array) -> jax.Array:
    """Java doubleToLongBits: canonical NaN 0x7ff8000000000000."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)
    return jnp.where(jnp.isnan(x), jnp.int64(0x7FF8000000000000), bits)


def hash_string_bytes(chars: jax.Array, lengths: jax.Array,
                      seed: jax.Array) -> jax.Array:
    """Spark hashUnsafeBytes over a fixed-width (n, W) uint8 byte matrix.

    Aligned blocks are little-endian ints; tail bytes are processed one at
    a time *sign-extended* (Platform.getByte is a signed read).

    On a TPU backend this routes to the Pallas kernel
    (ops/pallas_kernels.py) — bit-identical, but walks the byte matrix
    once per VMEM-resident row block instead of ~1.25*W masked
    full-width passes.
    """
    n, width = chars.shape
    seeds = jnp.broadcast_to(_u32(seed), (n,))
    from spark_rapids_tpu.ops.pallas_kernels import (
        maybe_pallas_hash_string,
    )

    fast = maybe_pallas_hash_string(chars, lengths.astype(jnp.int32),
                                    seeds)
    if fast is not None:
        return fast
    return hash_string_bytes_jnp(chars, lengths, seeds)


def hash_string_bytes_jnp(chars: jax.Array, lengths: jax.Array,
                          seeds: jax.Array) -> jax.Array:
    """The jnp form of :func:`hash_string_bytes` (per-row uint32 seeds):
    what every non-TPU backend runs, and the reference the Pallas
    kernel is held bit-equal to on the chip."""
    n, width = chars.shape
    h1 = seeds
    lengths = lengths.astype(jnp.int32)
    aligned = lengths - (lengths % 4)
    c32 = chars.astype(jnp.uint32)
    nblocks = (width + 3) // 4
    for b in range(nblocks):
        j = b * 4

        def byte(off):
            if j + off < width:
                return c32[:, j + off]
            return jnp.zeros((n,), jnp.uint32)

        word = (byte(0) | (byte(1) << 8) | (byte(2) << 16) | (byte(3) << 24))
        in_block = jnp.int32(j + 4) <= aligned
        h1 = jnp.where(in_block, _mix_h1(h1, _mix_k1(word)), h1)
    # tail: each byte beyond the aligned prefix, sign-extended to int
    for j in range(width):
        is_tail = (jnp.int32(j) >= aligned) & (jnp.int32(j) < lengths)
        signed = chars[:, j].astype(jnp.int8).astype(jnp.int32)
        h1 = jnp.where(is_tail, _mix_h1(h1, _mix_k1(_u32(signed))), h1)
    return _fmix(h1, _u32(lengths))


def hash_column(col: AnyColumn, seed: jax.Array) -> jax.Array:
    """Hash one column into a running uint32 seed array; NULL rows keep
    the incoming seed (Spark semantics)."""
    if isinstance(col, StringColumn):
        h = hash_string_bytes(col.chars, col.lengths, seed)
        return jnp.where(col.validity, h, seed)
    dt = col.dtype
    if isinstance(dt, (T.BooleanType,)):
        h = hash_int32_block(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.ByteType, T.ShortType, T.IntegerType, T.DateType)):
        h = hash_int32_block(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.LongType, T.TimestampType, T.DecimalType)):
        h = hash_int64_blocks(col.data, seed)
    elif isinstance(dt, T.FloatType):
        # Spark normalizes -0.0f to 0.0f before hashing
        x = col.data.astype(jnp.float32)
        x = jnp.where(x == 0.0, jnp.float32(0.0), x)
        h = hash_int32_block(_float_to_bits(x), seed)
    elif isinstance(dt, T.DoubleType):
        x = col.data.astype(jnp.float64)
        x = jnp.where(x == 0.0, jnp.float64(0.0), x)
        h = hash_int64_blocks(_double_to_bits(x), seed)
    else:
        raise TypeError(f"murmur3 unsupported for {dt}")
    return jnp.where(col.validity, h, seed)


def hash_columns(cols: Sequence[AnyColumn], capacity: int,
                 seed: int = DEFAULT_SEED) -> jax.Array:
    """Chained multi-column Spark hash -> int32 array (Spark `hash(...)`)."""
    h = jnp.full((capacity,), seed, jnp.uint32)
    for c in cols:
        h = hash_column(c, h)
    return h.astype(jnp.int32)


@dataclasses.dataclass(repr=False)
class Murmur3Hash(Expression):
    """SQL hash(exprs...) (ref: HashFunctions.scala GpuMurmur3Hash)."""

    exprs: tuple[Expression, ...]
    seed: int = DEFAULT_SEED

    def __init__(self, *exprs: Expression, seed: int = DEFAULT_SEED):
        self.exprs = tuple(exprs)
        self.seed = seed

    def with_children(self, children):
        return type(self)(*children, seed=self.seed)

    @property
    def dtype(self) -> T.DataType:
        return T.INT

    @property
    def nullable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> AnyColumn:
        cols = [e.eval(ctx) for e in self.exprs]
        h = hash_columns(cols, ctx.batch.capacity, self.seed)
        return Column(h, ctx.row_mask, T.INT)


def partition_ids(cols: Sequence[AnyColumn], capacity: int,
                  num_partitions: int) -> jax.Array:
    """Spark hash-partitioning: pmod(hash(keys), numPartitions)
    (ref: GpuHashPartitioning.scala).  Returns int32 in [0, n)."""
    h = hash_columns(cols, capacity)
    m = h % jnp.int32(num_partitions)
    return jnp.where(m < 0, m + jnp.int32(num_partitions), m)


# --------------------------------------------------------------------- #
# MD5 (ref: HashFunctions.scala GpuMd5 -> cudf md5; Spark md5() returns
# the lowercase hex digest of the UTF-8 bytes)
# --------------------------------------------------------------------- #

_MD5_K = tuple(int(abs(__import__("math").sin(i + 1)) * (1 << 32))
               & 0xFFFFFFFF for i in range(64))
_MD5_S = (7, 12, 17, 22) * 4 + (5, 9, 14, 20) * 4 \
    + (4, 11, 16, 23) * 4 + (6, 10, 15, 21) * 4
_MD5_INIT = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476)
_HEX = tuple(b"0123456789abcdef")


def _rotl32(x: jax.Array, s: int) -> jax.Array:
    return (x << jnp.uint32(s)) | (x >> jnp.uint32(32 - s))


def md5_string_bytes(chars: jax.Array, lengths: jax.Array,
                     cap: int) -> tuple[jax.Array, jax.Array]:
    """Per-row MD5 over the fixed-width chars matrix.

    Rows of different byte lengths need different block counts; every
    row runs the full (static) block schedule but only folds a block
    into its state while the block index is below the row's own block
    count — branch-free lockstep on the VPU, the TPU shape of cudf's
    warp-per-row md5 kernel.  Returns (hex_chars[cap, 32],
    lengths[cap] == 32)."""
    w = int(chars.shape[1])
    msg_len = ((w + 9 + 63) // 64) * 64
    nblocks = msg_len // 64
    L = lengths.astype(jnp.int32)
    msg = jnp.concatenate(
        [chars, jnp.zeros((cap, msg_len - w), jnp.uint8)], axis=1)
    cols = jnp.arange(msg_len, dtype=jnp.int32)[None, :]
    msg = jnp.where(cols == L[:, None], jnp.uint8(0x80), msg)
    # per-row trailer: 64-bit little-endian BIT length at the end of
    # the row's LAST block
    row_blocks = (L + 9 + 63) // 64
    len_pos = row_blocks * 64 - 8
    bitlen = (L.astype(jnp.int64) * 8)
    for k in range(8):
        byte_k = ((bitlen >> (8 * k)) & 0xFF).astype(jnp.uint8)
        msg = jnp.where(cols == (len_pos + k)[:, None],
                        byte_k[:, None], msg)
    # bytes -> little-endian u32 words: (cap, nblocks, 16)
    bw = msg.reshape(cap, nblocks, 16, 4).astype(jnp.uint32)
    words = (bw[..., 0] | (bw[..., 1] << 8) | (bw[..., 2] << 16)
             | (bw[..., 3] << 24))

    a0 = jnp.full((cap,), _MD5_INIT[0], jnp.uint32)
    b0 = jnp.full((cap,), _MD5_INIT[1], jnp.uint32)
    c0 = jnp.full((cap,), _MD5_INIT[2], jnp.uint32)
    d0 = jnp.full((cap,), _MD5_INIT[3], jnp.uint32)
    # g-schedule per round is static; the BLOCK loop is a fori_loop so
    # the compiled graph is 64 rounds regardless of string width
    gidx = []
    for i in range(64):
        if i < 16:
            gidx.append(i)
        elif i < 32:
            gidx.append((5 * i + 1) % 16)
        elif i < 48:
            gidx.append((3 * i + 5) % 16)
        else:
            gidx.append((7 * i) % 16)

    def body(blk, state):
        a0, b0, c0, d0 = state
        active = blk < row_blocks
        m = jax.lax.dynamic_index_in_dim(words, blk, axis=1,
                                         keepdims=False)
        a, b, c, d = a0, b0, c0, d0
        for i in range(64):
            if i < 16:
                f = (b & c) | (~b & d)
            elif i < 32:
                f = (d & b) | (~d & c)
            elif i < 48:
                f = b ^ c ^ d
            else:
                f = c ^ (b | ~d)
            tmp = d
            d = c
            c = b
            rot = a + f + jnp.uint32(_MD5_K[i]) + m[:, gidx[i]]
            b = b + _rotl32(rot, _MD5_S[i])
            a = tmp
        return (jnp.where(active, a0 + a, a0),
                jnp.where(active, b0 + b, b0),
                jnp.where(active, c0 + c, c0),
                jnp.where(active, d0 + d, d0))

    a0, b0, c0, d0 = jax.lax.fori_loop(0, nblocks, body,
                                       (a0, b0, c0, d0))

    digest = jnp.stack([a0, b0, c0, d0], axis=1)  # (cap, 4) LE words
    dbytes = jnp.stack(
        [(digest >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)
         for k in range(4)], axis=2).reshape(cap, 16).astype(jnp.uint8)
    hex_lut = jnp.asarray(_HEX, jnp.uint8)
    hi = jnp.take(hex_lut, (dbytes >> 4).astype(jnp.int32))
    lo = jnp.take(hex_lut, (dbytes & 0xF).astype(jnp.int32))
    hex_chars = jnp.stack([hi, lo], axis=2).reshape(cap, 32)
    return hex_chars, jnp.full((cap,), 32, jnp.int32)


@dataclasses.dataclass(repr=False)
class Md5(Expression):
    """SQL md5(string) -> lowercase hex digest (ref:
    HashFunctions.scala GpuMd5)."""

    child: Expression

    @property
    def dtype(self) -> T.DataType:
        return T.STRING

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    def eval(self, ctx: EvalContext) -> AnyColumn:
        from spark_rapids_tpu.columnar.column import StringColumn

        col = self.child.eval(ctx)
        assert isinstance(col, StringColumn), "md5 over non-string"
        cap = ctx.batch.capacity
        hex_chars, lens = md5_string_bytes(col.chars, col.lengths, cap)
        valid = col.validity
        return StringColumn(hex_chars * valid[:, None].astype(jnp.uint8),
                            lens * valid.astype(jnp.int32), valid)
