"""What the readers of the "Operators" layer share: the settled
counts of each operator of a plan that ran (`query.operator` instants,
which the engine's history worker stamps at the query's end when the
tracer is on) and the device seconds of the programs an operator's
name starts (`cached_jit` names a program `jit_tpu__<op>__<tag>`).
A program without those instants, or a trace without such programs,
gives None, and the metric is left out of the line."""

from benchmarks.harness import trace_reduce


def counts(run, op: str, key: str):
    """Per round, `key` summed over the operators whose description
    starts with `op`; None where no operator so named reported."""
    found = [s.attrs.get(key, 0) for s in run.spans
             if s.name == "query.operator"
             and s.attrs.get("desc", "").startswith(op)]
    if not found:
        return None
    return sum(found) / len(run.rounds)


def busy_s(run, *ops: str):
    """Per round, the seconds the first chip spent in programs of the
    named operators: the union of their `XLA Modules` intervals inside
    the traced window.  None where the trace holds no such program."""
    if run.trace is None or not run.trace.chips:
        return None
    chip = run.trace.chips[0]
    prefixes = tuple(f"jit_tpu__{op}__" for op in ops)
    mine = [at for at, name in enumerate(chip.module_names)
            if name.startswith(prefixes)]
    if not mine:
        return None
    lo, hi = trace_reduce.window(run.trace)
    return trace_reduce.busy_ns(chip.modules[mine], lo, hi) / 1e9 \
        / len(run.rounds)
