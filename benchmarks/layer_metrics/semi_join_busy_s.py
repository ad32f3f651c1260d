"""Device seconds per round in the subquery joins' own programs: the
union of the first chip's `XLA Modules` intervals of the `left_semi`
and `left_anti` joins' probes, the conditional pass over their pairs
and the compaction they share (`jit_tpu__Tpu<Broadcast|Shuffled>Hash
JoinExec__<tag>` with `<tag>` `left_semi_probe`, `left_anti_probe`,
`semi_cond` or `semi_compact`).  In q16 these are the EXISTS and the
NOT EXISTS; the exchanges that feed them and the collection of their
build sides are outside it (`join_busy_s` has every join's programs).
Nothing where the trace holds no program so named."""

from benchmarks.layer_metrics import _semi

NAME, UNIT, BETTER = "semi_join_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"

TAGS = ("left_semi_probe", "left_anti_probe", "semi_cond", "semi_compact")


def reduce(run):
    return _semi.busy_s(run, *TAGS)
