"""Device seconds per round in the programs that move rows between
chips: the union of the first chip's `XLA Modules` intervals named
`jit_tpu__TpuCollective<...>Exec__<tag>` whose tag is an exchange or
route program of a collective stage (`parallel/spmd.py`): `spmdxchg`
(the aggregate's all_to_all with its reduce-side merge, the join's
two sides), `spmdsortroute` and `spmdboundsroute` (the sort's range
routing with its sampling), `spmdwinroute` and `spmdroutecount` (the
window's routing on its partition keys and the count that sizes it).
The send buffer's scatter, the all_to_all and the compaction of what
was received are all inside; `collective_s` is the part of it spent in
the collective operation itself.  Nothing where the trace holds no
such program: a cell of one chip, or a program without these stages."""

from benchmarks.harness import trace_reduce

NAME, UNIT, BETTER = "exchange_busy_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"

TAGS = ("spmdxchg", "spmdsortroute", "spmdboundsroute", "spmdwinroute",
        "spmdroutecount")


def exchanges(name: str) -> bool:
    """Whether a module's name, `jit_tpu__<op>__<tag>(<fingerprint>)`,
    is an exchange program of a collective operator."""
    parts = name.split("(", 1)[0].split("__")
    return len(parts) == 3 and parts[0] == "jit_tpu" \
        and parts[1].startswith("TpuCollective") and parts[2] in TAGS


def reduce(run):
    if run.trace is None or not run.trace.chips:
        return None
    chip = run.trace.chips[0]
    mine = [at for at, name in enumerate(chip.module_names)
            if exchanges(name)]
    if not mine:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return trace_reduce.busy_ns(chip.modules[mine], lo, hi) / 1e9 \
        / len(run.rounds)
