"""What the readers of the semi and anti joins share: the settled
counts of the plan's `left_semi` and `left_anti` join operators, from
their `query.operator` instants.  Such a join describes itself as
`Tpu<Broadcast|Shuffled>HashJoinExec left_semi [...]`, or, where the
planner deferred the strategy to the map outputs' sizes,
`TpuAdaptiveJoinExec [left_semi] strategy=...`, which holds the metrics
of the join it decided on.  Their programs are named by the hash join
that runs them (`cached_jit`: `jit_tpu__<op>__<tag>(<fingerprint>)`)."""

from benchmarks.harness import trace_reduce

#: the joins that run programs, and the one that decides between them
HASH_JOINS = ("TpuBroadcastHashJoinExec", "TpuShuffledHashJoinExec")
JOINS = HASH_JOINS + ("TpuAdaptiveJoinExec",)
TYPES = ("left_semi", "left_anti")


def is_semi(desc: str) -> bool:
    op, _, rest = desc.partition(" ")
    return op in JOINS and any(rest.startswith((t + " ", f"[{t}]"))
                               for t in TYPES)


def counts(run, key: str):
    """Per round, `key` summed over the plan's semi and anti joins;
    None where no such join reported it."""
    found = [s.attrs[key] for s in run.spans
             if s.name == "query.operator"
             and is_semi(s.attrs.get("desc", "")) and key in s.attrs]
    if not found:
        return None
    return sum(found) / len(run.rounds)


def busy_s(run, *tags: str):
    """Per round, the seconds the first chip spent in the broadcast and
    shuffled hash joins' programs of the named tags: the union of their
    `XLA Modules` intervals inside the traced window.  None where the
    trace holds no such program."""
    if run.trace is None or not run.trace.chips:
        return None
    chip = run.trace.chips[0]
    prefixes = tuple(f"jit_tpu__{op}__{tag}(" for op in HASH_JOINS
                     for tag in tags)
    mine = [at for at, name in enumerate(chip.module_names)
            if name.startswith(prefixes)]
    if not mine:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return trace_reduce.busy_ns(chip.modules[mine], lo, hi) / 1e9 \
        / len(run.rounds)
