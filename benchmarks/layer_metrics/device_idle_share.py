"""Share of the traced window in which no operation ran on the chip:
1 - busy union over window, mean over the cell's chips.  The line's
`device.busy_s` and `device.window_s` are the same two numbers."""

NAME, UNIT, BETTER = "device_idle_share", "%", "lower"
LAYER, SOURCE, MOVES = "Device", "device_trace", "round_wall_s"


def reduce(run):
    busy = run.busy_s()
    return None if busy is None else 100.0 * (1.0 - busy / run.window_s())
