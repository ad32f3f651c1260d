"""Device seconds per round in the joins' programs
(`jit_tpu__TpuBroadcastHashJoinExec__*`, `...ShuffledHashJoinExec__*`
and the runtime filter's build beside them): build-side sort, probe,
gather of the payload."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "join_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _operators.busy_s(run, "TpuBroadcastHashJoinExec",
                             "TpuShuffledHashJoinExec",
                             "TpuRuntimeFilterBuildExec")
