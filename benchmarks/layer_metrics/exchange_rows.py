"""Rows per round that the plan's collective operators put through
their exchanges: `collectiveRows` summed over the `TpuCollective*`
operators.  The join counts both its sides' rows, the window and the
sort the rows they route; the aggregate counts the groups it emits
(the partial rows its shuffle carried are `collectivePartialRows`, in
the instants and not in the line).  In q67 the ROLLUP's groups are in
it twice: out of the aggregate and through the window's exchange."""

from benchmarks.layer_metrics import _collective

NAME, UNIT, BETTER = "exchange_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Several chips", "program_counter", "round_wall_s"


def reduce(run):
    return _collective.counts(run, "collectiveRows")
