"""Rows per round that the set operations' joins probed: the settled
`streamRows` of the plan's `left_semi` and `left_anti` joins
(`query.operator` instants), which is what INTERSECT and EXCEPT take
in on their left: the first DISTINCT's triples and then what the first
set operation left of them.  Beside `agg_groups`, what the DISTINCTs
put out.  Nothing where no such join reported."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "setop_join_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    probed = [_operators.counts(run, f"{op} {join_type}", "streamRows")
              for op in ("TpuBroadcastHashJoinExec",
                         "TpuShuffledHashJoinExec")
              for join_type in ("left_semi", "left_anti")]
    if all(n is None for n in probed):
        return None
    return sum(n or 0 for n in probed)
