"""Rows per round that the shuffled-hash join put out: its settled
`numOutputRows` (`query.operator` instants).  In q97, a FULL OUTER
join of near-disjoint sides, nearly the rows of both sides together:
the matched pairs, the stream rows without a match and the unmatched
build rows."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "outer_join_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    return _operators.counts(run, "TpuShuffledHashJoinExec", "numOutputRows")
