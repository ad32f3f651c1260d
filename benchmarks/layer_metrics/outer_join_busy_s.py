"""Device seconds per round in the shuffled-hash join's programs
(`jit_tpu__TpuShuffledHashJoinExec__*`: probe, expansion and, for a
FULL OUTER join, the `unmatched` program that emits the build rows no
stream batch matched).  In q97 the plan's only shuffled join is the
full outer one, so this is what the outer join costs on the chip;
`join_busy_s` has the two broadcast joins and the runtime filters'
builds beside it."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "outer_join_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _operators.busy_s(run, "TpuShuffledHashJoinExec")
