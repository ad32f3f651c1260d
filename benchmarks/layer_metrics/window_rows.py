"""Rows per round that entered `TpuWindowExec`: what its child put out
(`rows_in` of its `query.operator` instant).  The window takes a
partition as one batch, so the largest batch is what bounds its memory:
the `window.partition` spans carry each one's capacity, and the run's
record holds them."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "window_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_span", "round_wall_s"


def reduce(run):
    return _operators.counts(run, "TpuWindowExec", "rows_in")
