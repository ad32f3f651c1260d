"""Rows per round that the subquery joins collected as their build
sides: `buildRows` summed over the round's `left_semi` and `left_anti`
join operators (`query.operator` instants).  In q16 that is every
`catalog_sales` row the EXISTS builds from and every `catalog_returns`
row the NOT EXISTS builds from, whatever the strategy.  Nothing where
no such join reported."""

from benchmarks.layer_metrics import _semi

NAME, UNIT, BETTER = "semi_build_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    return _semi.counts(run, "buildRows")
