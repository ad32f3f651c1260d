"""Rows per round that `TpuExpandExec` emitted: its projections times
the rows that entered it (q67's ROLLUP: nine).  Read from the
operator's settled `numOutputRows` (`query.operator`), which the
aggregate ticks for it where it absorbed the expand into its update
program.  Lower is less work for the aggregate above it: a ROLLUP
summed level by level would emit a ninth."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "expand_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    return _operators.counts(run, "TpuExpandExec", "numOutputRows")
