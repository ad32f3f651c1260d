"""Device seconds per round in `jit_tpu__TpuHashAggregateExec__*`: the
update program (with whatever it absorbed: q67's Expand runs inside
it), the merges of partials and the final pass."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "agg_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _operators.busy_s(run, "TpuHashAggregateExec")
