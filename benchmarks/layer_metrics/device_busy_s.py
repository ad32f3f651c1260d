"""Seconds per round in which an operation ran on the chip: the union
of the device-op intervals in the profiler's trace, mean over the
cell's chips."""

NAME, UNIT, BETTER = "device_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    busy = run.busy_s()
    return None if busy is None else busy / len(run.rounds)
