"""Wire-codec registry: the shared codec surface for the H2D wire,
the TCP shuffle tier, and the spill tiers.

The reference compresses shuffle slices ON DEVICE via nvcomp before
they touch the wire (RapidsShuffleManager + NvcompLZ4CompressionCodec;
conf spark.rapids.shuffle.compression.codec) and decompresses on the
GPU.  The TPU mirror splits the work across the link the same way but
with XLA-friendly primitives: the HOST compresses wire components
during scan-prefetch encode, and a jitted DEVICE program decompresses
them in HBM — so compressed bytes, not raw, cross the H2D link
(whether that link bounds any query on the chip is not measured yet,
ROADMAP D4).

Two codec kinds share one registry and one per-codec stats surface:

- ARRAY codecs (bitpack, delta, rle): host ``encode_array`` packs a
  1-D integer/bool component into smaller typed arrays + a static
  meta tuple; device ``decode_array`` reconstructs the exact original
  inside whatever jitted program reads the component (the scan decode,
  or a fused consumer program).  Everything is shift/mask/gather/
  cumsum — XLA-static shapes, no bitcasts, so the decode composes
  into the existing wire-decode program as one fused XLA program.
- BYTE codecs (none, zlib): host-side framed-bytes compression for
  the serde tier (TCP shuffle frames, disk/host spill files) — the
  stdlib stand-in for nvcomp's host path.

Every codec declares a ``decoder_program_key`` naming the program (or
host routine) that undoes it; tpulint REG007 hard-fails a registered
codec without one, or one missing from the round-trip test matrix.

Compression is LOSSLESS RE-ENCODING, never approximation: a codec
must round-trip bit-exactly or refuse (return None) — the chooser
additionally refuses when the measured ratio does not clear
``wireCompression.minRatio``, mirroring the ``_try_dict`` /
``_try_scaled`` pays-for-itself gates in columnar/transfer.py.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from spark_rapids_tpu.config import get_conf, register

WIRE_ENABLED = register(
    "spark.rapids.tpu.sql.wireCompression.enabled", False,
    "Compress wire components on the host during scan-prefetch encode "
    "and decompress them on device inside the jitted wire-decode "
    "program, so compressed bytes (not raw) cross the H2D link (the "
    "TPU mirror of the reference's nvcomp device-side shuffle "
    "compression, RapidsConf.scala:905).  Off is bit-for-bit "
    "identical to the uncompressed wire format.")

WIRE_CODECS = register(
    "spark.rapids.tpu.sql.wireCompression.codecs", "bitpack,delta,rle",
    "Comma-separated array codecs the per-column chooser may pick "
    "from, in no particular order (the chooser ranks by estimated "
    "ratio): bitpack (block frame-of-reference + sub-byte bitpacking "
    "for integers/dict-codes/dates/validity), delta (delta + zigzag + "
    "bitpack for sorted/clustered columns), rle (block run-length, "
    "expanded on device via cumsum/searchsorted gather).")

WIRE_MIN_RATIO = register(
    "spark.rapids.tpu.sql.wireCompression.minRatio", 1.3,
    "Minimum measured compression ratio (raw bytes / packed bytes) a "
    "codec must achieve on a component before it rides the wire "
    "compressed; below this the component ships raw (compression "
    "must pay for its decode gathers).",
    check=lambda v: v >= 1.0)

WIRE_BLOCK_ROWS = register(
    "spark.rapids.tpu.sql.wireCompression.blockRows", 256,
    "Frame-of-reference / delta block size in rows (power of two, "
    ">= 32 so packed lanes tile uint32 words exactly).  Smaller "
    "blocks track local value ranges tighter at more per-block "
    "reference overhead.",
    check=lambda v: v >= 32 and (v & (v - 1)) == 0)

#: components smaller than this ship raw — a packed scalar or a tiny
#: dictionary would spend a decode gather to save nothing measurable
MIN_COMPRESS_BYTES = 1024


class Codec:
    """One registered codec.  Array codecs implement ``estimate`` /
    ``encode_array`` / ``decode_array``; byte codecs implement
    ``compress_bytes`` / ``decompress_bytes``.  ``decoder_program_key``
    names the decode program (device) or routine (host) that undoes
    the encode — REG007 requires it and a round-trip test matrix row
    for every registered codec."""

    name: str = ""
    decoder_program_key: str = ""
    supports_arrays: bool = False
    supports_bytes: bool = False

    # -- array side (host pack -> device unpack) ------------------------ #

    def estimate(self, vals: np.ndarray,
                 block_rows: int) -> Optional[float]:
        """Cheap sampled ratio estimate (host), or None when the codec
        cannot apply.  Never exact — the chooser re-checks the real
        ratio after ``encode_array``."""
        return None

    def encode_array(self, vals: np.ndarray, block_rows: int
                     ) -> Optional[tuple[list[np.ndarray], tuple]]:
        """vals (1-D, int/uint/bool) -> (component arrays, static meta)
        or None when the codec does not apply.  The meta tuple must be
        hashable: it rides the wire plan and keys the compiled decode
        program."""
        raise NotImplementedError(self.name)

    def decode_array(self, arrays: Sequence, meta: tuple,
                     out_dtype: np.dtype):
        """TRACEABLE device decompress: the uploaded component arrays
        + meta -> the exact original 1-D array (dtype ``out_dtype``).
        Runs inside whatever jitted program reads the component."""
        raise NotImplementedError(self.name)

    # -- byte side (serde frames: shuffle + spill) ---------------------- #

    def compress_bytes(self, body: bytes) -> bytes:
        raise NotImplementedError(self.name)

    def decompress_bytes(self, body: bytes) -> bytes:
        raise NotImplementedError(self.name)


_REG_LOCK = threading.Lock()
_REGISTRY: dict[str, Codec] = {}


def register_codec(codec: Codec) -> Codec:
    if not codec.name:
        raise ValueError("codec must declare a name")
    with _REG_LOCK:
        _REGISTRY[codec.name] = codec
    return codec


def unregister_codec(name: str) -> None:
    """Test hook: remove a codec registered by a fixture."""
    with _REG_LOCK:
        _REGISTRY.pop(name, None)


def get_codec(name: str) -> Codec:
    with _REG_LOCK:
        c = _REGISTRY.get(name)
    if c is None:
        raise ValueError(f"unknown codec {name!r}")
    return c


def get_bytes_codec(name: str) -> Codec:
    c = get_codec(name)
    if not c.supports_bytes:
        raise ValueError(
            f"codec {name!r} has no byte-stream form (array-only)")
    return c


def registry_items() -> list[tuple[str, Codec]]:
    with _REG_LOCK:
        return sorted(_REGISTRY.items())


# ------------------------------------------------------------------ #
# Per-codec stats: THE shared observability surface (H2D wire,
# TCP shuffle and spill all report here)
# ------------------------------------------------------------------ #

_STATS_LOCK = threading.Lock()
_STATS: dict[str, dict] = {}


def _stat_entry(name: str) -> dict:
    e = _STATS.get(name)
    if e is None:
        e = _STATS[name] = {"compress_calls": 0, "decompress_calls": 0,
                            "raw_bytes": 0, "wire_bytes": 0}
    return e


def record_compress(name: str, raw: int, wire: int) -> None:
    with _STATS_LOCK:
        e = _stat_entry(name)
        e["compress_calls"] += 1
        e["raw_bytes"] += int(raw)
        e["wire_bytes"] += int(wire)


def record_decompress(name: str, count: int = 1) -> None:
    with _STATS_LOCK:
        _stat_entry(name)["decompress_calls"] += int(count)


def stats() -> dict:
    """{codec: {compress_calls, decompress_calls, raw_bytes,
    wire_bytes, ratio}} — one surface per codec regardless of which
    tier (H2D wire, shuffle frame, spill file) drove it."""
    with _STATS_LOCK:
        out = {}
        for name, e in sorted(_STATS.items()):
            d = dict(e)
            d["ratio"] = round(e["raw_bytes"] / e["wire_bytes"], 3) \
                if e["wire_bytes"] else 0.0
            out[name] = d
        return out


def reset_stats() -> None:
    with _STATS_LOCK:
        _STATS.clear()


# ------------------------------------------------------------------ #
# The chooser
# ------------------------------------------------------------------ #


def wire_codec_config(conf=None) -> Optional[tuple]:
    """(codec names, min_ratio, block_rows) when wire compression is
    enabled, else None — disabled is ONE conf read and the encode path
    is byte-identical to the uncompressed wire format."""
    conf = conf or get_conf()
    if not conf.get_bool(WIRE_ENABLED.key):
        return None
    names = tuple(n.strip() for n in
                  str(conf.get(WIRE_CODECS)).split(",") if n.strip())
    return names, float(conf.get(WIRE_MIN_RATIO)), \
        int(conf.get(WIRE_BLOCK_ROWS))


def choose_and_encode(vals: np.ndarray, names: Sequence[str],
                      min_ratio: float, block_rows: int
                      ) -> Optional[tuple[str, list[np.ndarray], tuple]]:
    """Pick the best-paying codec for one 1-D wire component, or None
    to ship raw.  Cheap sampled estimates rank the candidates
    (mirroring the _try_dict/_try_scaled entropy gates); the winner's
    REAL ratio is re-checked against ``min_ratio`` before committing —
    estimates may flatter, the wire never lies."""
    if vals.ndim != 1 or vals.dtype.kind not in "iub" \
            or vals.nbytes < MIN_COMPRESS_BYTES or len(vals) == 0:
        return None
    ranked = []
    for name in names:
        with _REG_LOCK:
            c = _REGISTRY.get(name)
        if c is None or not c.supports_arrays:
            continue
        est = c.estimate(vals, block_rows)
        if est is not None and est >= min_ratio:
            ranked.append((est, name, c))
    ranked.sort(key=lambda t: t[0], reverse=True)
    for _est, name, c in ranked:
        enc = c.encode_array(vals, block_rows)
        if enc is None:
            continue
        arrays, meta = enc
        wire = sum(int(a.nbytes) for a in arrays)
        if wire == 0 or vals.nbytes / wire < min_ratio:
            continue
        record_compress(name, vals.nbytes, wire)
        return name, arrays, meta
    return None
