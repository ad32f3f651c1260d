"""Seconds per round the first chip sat idle while an operator was
inside its timed region or deriving the sentinels of one (an
`exec.<op>` or `exec.sentinels` span open: the host is between two
dispatches of a plan) and no earlier cause of `_idle.CAUSES` held:
the fourth and last."""

from benchmarks.layer_metrics import _idle

NAME, UNIT, BETTER = "idle_dispatch_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _idle.idle_s(run, "dispatch")
