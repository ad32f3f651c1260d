"""The chip's idle seconds, each put down to what the host was doing.

Shared by the four `idle_*` readers; no metric of its own.

Idle intervals are `trace_reduce.gaps` over the first chip's `XLA Ops`
in the traced window.  Each instant of idle goes to the first cause of
`CAUSES` that holds there, over the engine spans of every thread: the
plan runs on `tpu-pipe-result.fetch`, the decode on `tpu-scan-decode*`,
and the thread that called `collect()` only waits for them.  What no
cause covers is left unexplained and has no metric: planning
(`plan_s`), the time between collects, the time after the last
operator's dispatch.

The engine's tracer reads `perf_counter_ns` and the profiler has a
clock of its own.  `engine.Round.t0_ns` is read just before
`bench.round <n>` opens and `t1_ns` just after it closes, so every
traced round gives two offsets between the clocks, one at each end.
The median is used, and where any two of the window differ by more
than `MAX_OFFSET_SPREAD_NS` nothing is attributed at all: the spans
and the device trace then share no clock, and a sum over them would
be a guess.
"""

import re
import statistics
import sys

import numpy as np

from benchmarks.harness import trace_reduce
from benchmarks.harness.trace_reduce import gaps, merged

#: (cause, the span names it holds for), in the order in which an idle
#: instant is offered to them
CAUSES = (
    ("upload", re.compile(r"wire\.(put|encode)")),
    ("sync", re.compile(r"pipe\.readback|query\.fetch\.batch")),
    ("scan", re.compile(r"pipe\.scan\..*\.wait_empty")),
    ("dispatch", re.compile(r"exec\..*")),
)
MAX_OFFSET_SPREAD_NS = 1e6


def clock_offsets_ns(run) -> list:
    """What to add to a `perf_counter_ns` reading to get the trace's
    clock, as each end of each traced round gives it."""
    rounds = {name: (start, end)
              for name, start, end in run.trace.named("bench.round")}
    offsets = []
    for r in run.rounds:
        found = rounds.get(f"bench.round {r.index}")
        if found is not None:
            offsets += [found[0] - r.t0_ns, found[1] - r.t1_ns]
    return offsets


def clock_offset_ns(run):
    """The median offset, or None (and why, on stderr) where the
    window's offsets do not agree within `MAX_OFFSET_SPREAD_NS`."""
    offsets = clock_offsets_ns(run)
    if not offsets:
        print("[idle] no bench.round annotation matches a round: no "
              "clock offset", file=sys.stderr)
        return None
    spread = max(offsets) - min(offsets)
    print(f"[idle] {len(offsets)} clock offsets, spread "
          f"{spread / 1e3:.1f} us", file=sys.stderr)
    if spread > MAX_OFFSET_SPREAD_NS:
        print(f"[idle] the offsets differ by {spread / 1e6:.3f} ms, over "
              f"{MAX_OFFSET_SPREAD_NS / 1e6:g} ms: the engine's spans and "
              "the device trace share no clock, nothing is attributed",
              file=sys.stderr)
        return None
    return statistics.median(offsets)


def attribute(ops: np.ndarray, spans: list, lo: float, hi: float) -> dict:
    """cause -> idle nanoseconds of [lo, hi] that go to it.  `ops` are
    the chip's busy intervals, `spans` (name, start, end) in the same
    clock.  An instant belongs to cause k when a span of k is open and
    none of an earlier cause is: the idle time under the union of the
    first k causes, less that under the first k - 1."""
    busy = merged(ops, lo, hi)
    out, open_so_far, before = {}, np.zeros((0, 2)), 0.0
    for cause, names in CAUSES:
        mine = np.array([(s, e) for n, s, e in spans if names.fullmatch(n)],
                        dtype=np.float64).reshape(-1, 2)
        open_so_far = merged(np.vstack([open_so_far, mine]), lo, hi)
        # idle and under a span = the window less (busy or under none)
        neither = np.vstack([busy, gaps(open_so_far, lo, hi)])
        under = (hi - lo) - trace_reduce.busy_ns(neither, lo, hi)
        out[cause] = under - before
        before = under
    return out


def idle_by_cause(run):
    """cause -> idle seconds per round on the first chip, or None
    where there is no device trace or no common clock.  Worked out
    once per run."""
    if "_idle_by_cause" not in run.__dict__:
        run.__dict__["_idle_by_cause"] = _idle_by_cause(run)
    return run.__dict__["_idle_by_cause"]


def _idle_by_cause(run):
    if run.trace is None or not run.trace.chips:
        return None
    offset = clock_offset_ns(run)
    if offset is None:
        return None
    chip = run.trace.chips[0]
    lo, hi = trace_reduce.window(run.trace)
    spans = [(s.name, s.ts_ns + offset, s.ts_ns + s.dur_ns + offset)
             for s in run.spans if s.dur_ns]
    by_cause = attribute(chip.ops if len(chip.ops) else chip.modules,
                         spans, lo, hi)
    return {c: ns / 1e9 / len(run.rounds) for c, ns in by_cause.items()}


def idle_s(run, cause: str):
    """The reader's answer: idle seconds per round under `cause`; None
    where they cannot be told, or where the program records no span of
    that cause at all (a program from before these spans)."""
    names = dict(CAUSES)[cause]
    if not any(names.fullmatch(s.name) for s in run.spans):
        return None
    found = idle_by_cause(run)
    return None if found is None else found[cause]
