"""Seconds per round the first chip sat idle while the operators waited
for the scan to hand them a batch (a `pipe.scan.*.wait_empty` span
open) and neither an upload nor a readback was under way: the third
cause of `_idle.CAUSES`."""

from benchmarks.layer_metrics import _idle

NAME, UNIT, BETTER = "idle_scan_s", "s", "lower"
LAYER, SOURCE, MOVES = "Scan and host decode", "device_trace", "round_wall_s"


def reduce(run):
    return _idle.idle_s(run, "scan")
