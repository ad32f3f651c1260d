"""Seconds per round in which a collective operation ran on a chip:
the union of the device-op intervals whose instruction is a collective
(`all-to-all`, `all-gather`, `all-reduce`, `reduce-scatter`,
`collective-permute`, with their `-start` and `-done` forms), on the
`XLA Ops` line and the `Async XLA Ops` line together, mean over the
cell's chips.  It counts the time a chip spends inside the collective,
the wait for the slowest peer with it; how much of it no compute
covers is not told apart (PERF.md section 7).  Nothing where no chip's
trace holds a collective: a cell of one chip, or a program that
exchanged nothing."""

import re
import statistics

import numpy as np

from benchmarks.harness import trace_reduce

NAME, UNIT, BETTER = "collective_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"

#: an event is named by its instruction's whole text, and the opcode
#: tells: `%all_to_all.7 = f32[4,16384,1]{...} all-to-all(f32[...]
#: %bitcast.6), channel_id=1` is one (JAX names the instruction after
#: its own primitive, with underscores); `%fusion = ... fusion(...,
#: f32[...] %all-reduce.1)` only reads one (chip trace, PR 27)
COLLECTIVE = re.compile(
    r"(?:^%?|\s)(all-to-all|all-gather|all-reduce|reduce-scatter|"
    r"collective-permute)(-start|-done)?(\(|(\.\d+)*$)")


def collective_spans(chip) -> np.ndarray:
    """(n, 2) start and end of the chip's collective operations."""
    found = [span for names, spans in ((chip.op_names, chip.ops),
                                       (chip.async_names, chip.async_ops))
             for name, span in zip(names, spans)
             if COLLECTIVE.search(name)]
    return np.array(found, dtype=np.float64).reshape(-1, 2)


def reduce(run):
    if run.trace is None or not run.trace.chips:
        return None
    lo, hi = trace_reduce.window(run.trace)
    spans = [collective_spans(chip) for chip in run.trace.chips]
    if not any(len(s) for s in spans):
        return None
    return statistics.fmean(trace_reduce.busy_ns(s, lo, hi)
                            for s in spans) / 1e9 / len(run.rounds)
