"""Device seconds per round in the programs that carry the 128-bit
decimal arithmetic: the first chip's `XLA Modules` of
`jit_tpu__TpuHashAggregateExec__*` (the update with its absorbed filter
and products, the merges, the final pass with the averages), in a
round that ran such arithmetic at all."""

from benchmarks.layer_metrics import _decimal

NAME, UNIT, BETTER = "decimal_agg_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _decimal.busy_s(run)
