"""Groups per round that the aggregates which end a group-by emitted
(`TpuHashAggregateExec[complete]` or `[final]`): their settled
`numOutputRows`.  In q67 these are the rows the window sorts."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "agg_groups", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    ended = [_operators.counts(run, f"TpuHashAggregateExec[{mode}]",
                               "numOutputRows")
             for mode in ("complete", "final")]
    if all(n is None for n in ended):
        return None
    return sum(n or 0 for n in ended)
