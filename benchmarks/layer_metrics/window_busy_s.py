"""Device seconds per round in `jit_tpu__TpuWindowExec__*`: a partition's
sort by (partition keys, order keys) and the segmented scans over
it."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "window_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _operators.busy_s(run, "TpuWindowExec")
