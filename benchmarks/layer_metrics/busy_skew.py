"""The busiest chip's busy seconds over the mean of the cell's chips,
in the traced window: 1.0 is even, and with four chips 4.0 is one chip
doing everything.  Nothing for a cell of one chip."""

from benchmarks.harness import trace_reduce

NAME, UNIT, BETTER = "busy_skew", "ratio", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"


def reduce(run):
    if run.trace is None or len(run.trace.chips) < 2:
        return None
    busy = trace_reduce.chips_busy_s(run.trace)
    return max(busy) * len(busy) / sum(busy) if sum(busy) else None
