"""Device seconds per round in the programs of the sort and top-n
operators (`TpuSortExec`, `TpuTopNExec`,
`TpuTakeOrderedAndProjectExec`): q67's ORDER BY of ten columns and
LIMIT 100."""

from benchmarks.layer_metrics import _operators

NAME, UNIT, BETTER = "sort_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    return _operators.busy_s(run, "TpuSortExec", "TpuTopNExec",
                             "TpuTakeOrderedAndProjectExec")
