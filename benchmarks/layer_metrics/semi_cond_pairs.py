"""Pairs per round that a conditional semi or anti join evaluated its
residual condition over: `condPairs` summed over the round's
`left_semi` and `left_anti` join operators (`query.operator`
instants).  In q16 the EXISTS's (order number) pairs, some nine a
probed line.  Nothing where no join counted any (a program without
conditional semi joins)."""

from benchmarks.layer_metrics import _semi

NAME, UNIT, BETTER = "semi_cond_pairs", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    return _semi.counts(run, "condPairs")
