"""Hand-written Pallas TPU kernels for the engine's hottest device ops.

This is the L0 native-kernel layer (SURVEY §1 L0): where the reference
ships CUDA kernels inside cudf (hashing, stream compaction), the TPU
analog is a Pallas kernel compiled for the VPU.  XLA already fuses most
of this engine's elementwise work well; Pallas earns its keep where the
access pattern defeats XLA's fusion heuristics — the Spark-parity
string hash is the canonical case: ~W/4 block-mix steps plus W masked
tail steps over an (N, W) byte matrix, which XLA lowers as ~1.25*W
full-width masked vector passes over HBM, while the kernel below walks
the byte matrix ONCE per VMEM-resident row block.

Kernels are bit-compatible with the jnp reference implementations in
exprs/hashing.py (the same mix functions are imported).  Routing is by
rule, never by failure: pallas.enabled=false, a non-TPU backend, or a
string column wider than _MAX_WIDTH takes the jnp path; everything else
on a TPU takes the kernel, and a kernel that fails to lower there is an
error the caller sees.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from spark_rapids_tpu.config import get_conf, register

PALLAS_ENABLED = register(
    "spark.rapids.tpu.sql.pallas.enabled", True,
    "Use hand-written Pallas TPU kernels for hot ops (string murmur3) "
    "instead of the XLA-fused jnp reference implementations.  Only "
    "takes effect on a TPU backend; other backends always use jnp.  "
    "Read at program-compile time: changing it mid-session does not "
    "affect pipelines already in the compile cache.")

_BLOCK_N = 1024  # rows per grid step: (8, 128) row tiles; W*1KB << VMEM
#: widest string column the kernel accepts: the per-grid-step working
#: set is ~5KB per byte of width (chars tile + widened u32 copy), so
#: wider columns would overrun the kernel's VMEM budget — they take
#: the jnp path instead
_MAX_WIDTH = 128


#: programs traced through the kernel route since process start — what
#: chip_smoke.py reads to show a query really reached the kernel
_ROUTED = 0


def routed_count() -> int:
    return _ROUTED


def pallas_available() -> bool:
    return bool(get_conf().get(PALLAS_ENABLED)) \
        and jax.default_backend() == "tpu"


def _hash_string_kernel(chars_ref, lengths_ref, seed_ref, out_ref):
    """One (B/128, 128, W) tile of Spark hashUnsafeBytes: aligned
    4-byte little-endian blocks through mixK1/mixH1, then each tail
    byte sign-extended — identical math to
    exprs.hashing.hash_string_bytes.

    Rows are laid out (group, byte, lane) — the byte index is a SUBLANE
    coordinate, so plane selection chars[:, j, :] is a cheap sublane
    slice and every mix step is a full (groups, 128) vector op on the
    VPU.  (Byte-in-lane layouts force a cross-lane relayout per plane —
    measured ~8.5MB of scoped VMEM on v5e.)"""
    from spark_rapids_tpu.exprs.hashing import _fmix, _mix_h1, _mix_k1

    chars = chars_ref[:]  # (G, W, 128) uint8, VMEM-resident
    lengths = lengths_ref[:].astype(jnp.int32)  # (G, 128)
    h1 = seed_ref[:].astype(jnp.uint32)  # (G, 128)
    s_rows, width, lanes = chars.shape
    four = jnp.asarray(4, jnp.int32)
    aligned = lengths - jnp.remainder(lengths, four)
    # widen THEN mask: Mosaic's u8 widening sign-extends bytes >= 128
    c32 = (chars.astype(jnp.int32)
           & jnp.asarray(0xFF, jnp.int32)).astype(jnp.uint32)
    nblocks = (width + 3) // 4
    # little-endian word assembly via MULTIPLIES: Mosaic miscompiles
    # vector shifts of byte-widened uint32 planes (verified on v5e),
    # while multiplies by 2^8k are exact
    scales = (jnp.asarray(0x100, jnp.uint32),
              jnp.asarray(0x10000, jnp.uint32),
              jnp.asarray(0x1000000, jnp.uint32))
    for b in range(nblocks):
        j = b * 4

        def byte(off):
            if j + off < width:
                return c32[:, j + off, :]
            return jnp.zeros((s_rows, lanes), jnp.uint32)

        word = (byte(0) + byte(1) * scales[0] + byte(2) * scales[1]
                + byte(3) * scales[2])
        in_block = jnp.asarray(j + 4, jnp.int32) <= aligned
        h1 = jnp.where(in_block, _mix_h1(h1, _mix_k1(word)), h1)
    c128 = jnp.asarray(128, jnp.int32)
    c256 = jnp.asarray(256, jnp.int32)
    for j in range(width):
        jj = jnp.asarray(j, jnp.int32)
        is_tail = (jj >= aligned) & (jj < lengths)
        b32 = c32[:, j, :].astype(jnp.int32)
        signed = jnp.where(b32 >= c128, b32 - c256, b32)
        h1 = jnp.where(is_tail,
                       _mix_h1(h1, _mix_k1(signed.astype(jnp.uint32))),
                       h1)
    out_ref[:] = _fmix(h1, lengths.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_hash_string(chars: jax.Array, lengths: jax.Array,
                       seeds: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Spark murmur3 of a fixed-width string column via a Pallas grid
    over row blocks.  chars (N, W) uint8; lengths/seeds (N,); -> (N,)
    uint32.  Caller guarantees N % _BLOCK_N == 0
    (maybe_pallas_hash_string pads off-multiple shapes up first)."""
    from jax.experimental import pallas as pl

    n, width = chars.shape
    sub = _BLOCK_N // 128
    grid = (n // _BLOCK_N,)

    def blk3(i):
        # under jax_enable_x64 a literal 0 would trace as i64, which
        # Mosaic's index-map legalization rejects — derive 0 from i
        return (i, i * 0, i * 0)

    def blk2(i):
        return (i, i * 0)

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        # the default VMEM budget (16MB) plus XLA's scoped overhead
        # overruns the 16MB space; the kernel's working set per grid
        # step is tiny, so cap it well below
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=2 * 1024 * 1024)
    out = pl.pallas_call(
        _hash_string_kernel,
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((sub, width, 128), blk3),
            pl.BlockSpec((sub, 128), blk2),
            pl.BlockSpec((sub, 128), blk2),
        ],
        out_specs=pl.BlockSpec((sub, 128), blk2),
        interpret=interpret,
        **kwargs,
    )(chars.reshape(n // 128, 128, width).transpose(0, 2, 1),
      lengths.reshape(n // 128, 128).astype(jnp.int32),
      seeds.reshape(n // 128, 128).astype(jnp.uint32))
    return out.reshape(n)


def maybe_pallas_hash_string(chars, lengths, seeds):
    """Route to the Pallas kernel when available and the shape fits;
    None means 'use the jnp reference path'.

    Off-multiple batches pad into WIDE kernel blocks: any capacity
    that is not a _BLOCK_N multiple — ragged scan tails and small
    partials below one block, and the 3*pow2/2 occupancy buckets above
    it (1536, 3·2^k for k < 10: capacity.policy=pow2x3,
    docs/occupancy.md) — pads its rows up to the next block multiple
    and slices the result back, instead of falling to the
    width-specialized jnp path.  Shapes are static (capacities come
    from pad_capacity), so the pad/slice fuse into the surrounding
    program; the win is program-count, not FLOPs — every distinct
    jnp-path shape used to mint its own ~1.25*W-pass lowering per
    (capacity, width), while the padded form shares the one
    grid-blocked kernel per width with every batch, including the
    multi-batch blocks a TpuCoalesceBatchesExec feeds in.  The grid
    covers ceil(n / _BLOCK_N) row blocks — sized to the live region of
    the padded matrix — and the pad tail is masked by construction:
    padding rows hash garbage nobody reads (length 0 -> fmix of an
    empty string); the slice drops them inside the same program."""
    global _ROUTED
    n, width = chars.shape
    if width > _MAX_WIDTH or not pallas_available():
        return None
    _ROUTED += 1
    if n % _BLOCK_N != 0:
        pad = -n % _BLOCK_N
        chars = jnp.concatenate(
            [chars, jnp.zeros((pad, width), chars.dtype)], axis=0)
        lengths = jnp.concatenate(
            [lengths, jnp.zeros((pad,), lengths.dtype)], axis=0)
        seeds = jnp.concatenate(
            [seeds, jnp.zeros((pad,), seeds.dtype)], axis=0)
        return pallas_hash_string(chars, lengths, seeds)[:n]
    return pallas_hash_string(chars, lengths, seeds)
