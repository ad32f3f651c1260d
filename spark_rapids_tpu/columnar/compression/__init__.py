"""Wire-codec subsystem: host-side compression + device-side
decompression for the H2D wire, unified with the TCP shuffle and
spill tiers through one codec registry and one per-codec stats
surface.  See registry.py for the architecture and
docs/wire_compression.md for the operator view."""

from spark_rapids_tpu.columnar.compression.registry import (  # noqa: F401
    MIN_COMPRESS_BYTES,
    WIRE_BLOCK_ROWS,
    WIRE_CODECS,
    WIRE_ENABLED,
    WIRE_MIN_RATIO,
    Codec,
    choose_and_encode,
    get_bytes_codec,
    get_codec,
    record_compress,
    record_decompress,
    register_codec,
    registry_items,
    reset_stats,
    stats,
    unregister_codec,
    wire_codec_config,
)
from spark_rapids_tpu.columnar.compression import codecs  # noqa: F401,E402
