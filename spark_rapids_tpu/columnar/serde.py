"""Batch serializer with optional compression.

TPU analog of the reference's batch serialization layer
(GpuColumnarBatchSerializer.scala + the nvcomp codec integration,
RapidsConf.scala spark.rapids.shuffle.compression.codec): host-side
column component dicts <-> a single framed byte stream, used by the
disk spill tier and any future network shuffle transport.

Format: MAGIC | version | codec | json header (names, dtypes, shapes)
| concatenated (possibly compressed) buffers.  Codecs resolve through
the shared wire-codec registry (columnar/compression/ — byte codecs:
none, zlib; zstd/lz4 are not in this image, zlib is the stdlib
stand-in), so TCP shuffle and the spill tiers report through the same
per-codec stats surface as the H2D wire."""

from __future__ import annotations

import json
import struct

import numpy as np

from spark_rapids_tpu.config import get_conf, register

_MAGIC = b"TPUB"
_VERSION = 1

SHUFFLE_COMPRESSION = register(
    "spark.rapids.tpu.shuffle.compression.codec", "none",
    "Codec for shuffle payloads crossing the TCP block transport: "
    "'none' or 'zlib' (ref: the reference compresses shuffle buffers "
    "on device via nvcomp, NvcompLZ4CompressionCodec.scala:25, conf "
    "spark.rapids.shuffle.compression.codec RapidsConf.scala:905; "
    "this engine's transport is host-side, so the codec runs on the "
    "serialized frame).")

SPILL_COMPRESSION = register(
    "spark.rapids.tpu.memory.spill.compression.codec", "none",
    "Codec for the disk spill tier: 'none' or 'zlib' (ref: "
    "spark.rapids.shuffle.compression.codec, RapidsConf.scala:905).")


def serialize_arrays(arrays: dict, codec: str = "none") -> bytes:
    """Host component dict (str -> np.ndarray) -> framed bytes.  The
    codec resolves through the shared registry (byte form), which also
    accounts raw-vs-wire bytes per codec."""
    from spark_rapids_tpu.columnar import compression as WC
    from spark_rapids_tpu.memory.device_manager import HostBufferPool

    bytes_codec = WC.get_bytes_codec(codec)

    header = []
    items = []
    total = 0
    for name, a in arrays.items():
        a = np.ascontiguousarray(a)
        header.append({"name": name, "dtype": a.dtype.str,
                       "shape": list(a.shape), "nbytes": a.nbytes})
        items.append(a)
        total += a.nbytes
    # one recycled staging buffer instead of a tobytes() copy per
    # array (the pinned-host-pool analog; spill writes are synchronous
    # so the buffer can return to the pool immediately)
    pool = HostBufferPool.get()
    staging = pool.take(max(total, 1))
    off = 0
    for a in items:
        staging[off: off + a.nbytes] = a.view(np.uint8).reshape(-1)
        off += a.nbytes
    body = bytes(staging[:total])
    pool.give(staging)
    body = bytes_codec.compress_bytes(body)
    WC.record_compress(codec, total, len(body))
    hjson = json.dumps({"cols": header, "codec": codec}).encode()
    return b"".join([
        _MAGIC, struct.pack("<HH", _VERSION, 0),  # version, reserved
        struct.pack("<I", len(hjson)), hjson, body,
    ])


def deserialize_arrays(data: bytes) -> dict:
    """Framed bytes -> host component dict."""
    if data[:4] != _MAGIC:
        raise ValueError("not a serialized batch (bad magic)")
    (version, _), = [struct.unpack("<HH", data[4:8])]
    if version != _VERSION:
        raise ValueError(f"unsupported batch version {version}")
    (hlen,) = struct.unpack("<I", data[8:12])
    meta = json.loads(data[12:12 + hlen].decode())
    body = data[12 + hlen:]
    from spark_rapids_tpu.columnar import compression as WC

    body = WC.get_bytes_codec(meta["codec"]).decompress_bytes(body)
    WC.record_decompress(meta["codec"])
    out = {}
    off = 0
    for c in meta["cols"]:
        n = c["nbytes"]
        a = np.frombuffer(body, dtype=np.dtype(c["dtype"]),
                          count=n // np.dtype(c["dtype"]).itemsize,
                          offset=off).reshape(c["shape"])
        out[c["name"]] = a
        off += n
    return out


def spill_codec() -> str:
    """Read ONLY at store construction: spills run on worker threads
    whose thread-local conf is not the user's session conf."""
    return get_conf().get(SPILL_COMPRESSION)


def write_spill_file(path: str, arrays: dict,
                     codec: str = "none") -> None:
    with open(path, "wb") as f:
        f.write(serialize_arrays(arrays, codec))


def read_spill_file(path: str) -> dict:
    with open(path, "rb") as f:
        return deserialize_arrays(f.read())
