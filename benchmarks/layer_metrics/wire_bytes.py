"""Bytes per round that crossed from host to device as encoded scan
batches: `columnar/transfer.upload_stats()["wire_bytes"]`."""

NAME, UNIT, BETTER = "wire_bytes", "bytes", "lower"
LAYER, SOURCE, MOVES = "Wire encode and upload", "program_counter", \
    "round_wall_s"


def reduce(run):
    return run.per_round("upload.wire_bytes") or None
