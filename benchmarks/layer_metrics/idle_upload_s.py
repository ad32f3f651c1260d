"""Seconds per round the first chip sat idle while a thread encoded a
batch for the wire or handed one to `jax.device_put` (a `wire.encode`
or `wire.put` span open): the first cause of `_idle.CAUSES`."""

from benchmarks.layer_metrics import _idle

NAME, UNIT, BETTER = "idle_upload_s", "s", "lower"
LAYER, SOURCE, MOVES = "Wire encode and upload", "device_trace", "round_wall_s"


def reduce(run):
    return _idle.idle_s(run, "upload")
