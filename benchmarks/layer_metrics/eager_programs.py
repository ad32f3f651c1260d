"""Programs per round that ran on the first chip without coming
through `cached_jit`: `XLA Modules` events that start in the traced
window and whose name lacks the prefix `jit_tpu__`, which
`execs/jit_cache.named_program` gives every program of an operator.
What is left are single operations dispatched one by one (`jit__take`,
`jit_clip`, `jit_convert_element_type`: ROADMAP S9).  Nothing where no
program in the window has the prefix: that program does not name
them, and every one would count."""

from benchmarks.harness import trace_reduce

NAME, UNIT, BETTER = "eager_programs", "count", "lower"
LAYER, SOURCE, MOVES = "Compiled programs", "device_trace", "round_wall_s"

PREFIX = "jit_tpu__"


def reduce(run):
    if run.trace is None or not run.trace.chips:
        return None
    chip = run.trace.chips[0]
    lo, hi = trace_reduce.window(run.trace)
    named = [name.startswith(PREFIX)
             for name, (start, _) in zip(chip.module_names, chip.modules)
             if lo <= start < hi]
    if not any(named):
        return None
    return named.count(False) / len(run.rounds)
