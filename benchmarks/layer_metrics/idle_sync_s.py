"""Seconds per round the first chip sat idle while the host read
something back from it (a `pipe.readback` or `query.fetch.batch` span
open) and no upload was under way: the second cause of
`_idle.CAUSES`."""

from benchmarks.layer_metrics import _idle

NAME, UNIT, BETTER = "idle_sync_s", "s", "lower"
LAYER, SOURCE, MOVES = "Result fetch", "device_trace", "round_wall_s"


def reduce(run):
    return _idle.idle_s(run, "sync")
