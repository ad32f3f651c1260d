"""Device seconds per round in the set operations' joins: the union of
the first chip's `XLA Modules` intervals of the `left_semi` and
`left_anti` joins' programs, which `cached_jit` names apart from the
other joins' (`jit_tpu__Tpu<Broadcast|Shuffled>HashJoinExec__<tag>`
with `<tag>` `left_semi_probe`, `left_anti_probe` or the compaction
both share, `semi_compact`).  In q38 and q87 these are INTERSECT's
and EXCEPT's own joins; `join_busy_s` has them and the date and
customer joins under the DISTINCTs together.  Nothing where the trace
holds no program so named (a program from before PR 38 names the
compaction alone, and runs no cell that has one)."""

from benchmarks.harness import trace_reduce

NAME, UNIT, BETTER = "setop_join_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"

JOINS = ("TpuBroadcastHashJoinExec", "TpuShuffledHashJoinExec")
TAGS = ("left_semi_probe", "left_anti_probe", "semi_compact")


def reduce(run):
    if run.trace is None or not run.trace.chips:
        return None
    chip = run.trace.chips[0]
    prefixes = tuple(f"jit_tpu__{op}__{tag}" for op in JOINS for tag in TAGS)
    mine = [at for at, name in enumerate(chip.module_names)
            if name.startswith(prefixes)]
    if not mine:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return trace_reduce.busy_ns(chip.modules[mine], lo, hi) / 1e9 \
        / len(run.rounds)
