"""From a `jax.profiler` trace (.xplane.pb) to device numbers.

The benchmark's own reduction, so that every PR computes busy time,
idle share and the breakdown in the same way and no
PR that claims a gain can change how.  Read with nothing but
`jax.profiler.ProfileData`.  Checked against the recorded trace in
`testdata/` by `benchmarks/selfcheck/test_trace_reduce.py`.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip
named `/device:TPU:<n>`, with a line `XLA Modules` (one event per
executed program, named `<jit name>(<fingerprint>)`), a line
`XLA Ops` (one event per HLO operation, named by the instruction's
whole text, `%fusion.41 = s32[1048576]... fusion(...)`; a `while`
covers its body's events, which is why busy time is a union and not a
sum) and a line `Async XLA Ops` (`%copy-start`, `%slice-start`, which
run beside the others and are not read); and a plane
`/host:CPU` with one line per host thread, where
`jax.profiler.TraceAnnotation`s appear under their names.  All
planes share one clock, in nanoseconds from the trace's start.
"""

import dataclasses
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: what the benchmark's own annotations start with
ANNOTATION_PREFIX = "bench."
MARKER = "bench.marker"


@dataclasses.dataclass
class Chip:
    index: int
    ops: np.ndarray  # (n, 2) start and end of each XLA op, ns
    op_names: list
    modules: np.ndarray  # (m, 2)
    module_names: list
    #: the `Async XLA Ops` line, which no busy time reads: a collective
    #: may stand there alone
    async_ops: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 2)))
    async_names: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    chips: list  # of Chip, by index
    annotations: list  # (name, start_ns, end_ns) of the benchmark's own

    def named(self, prefix: str) -> list:
        return [a for a in self.annotations if a[0].startswith(prefix)]


def _line_events(line) -> tuple:
    """(names, (n, 2) array of start and end) of a line's events; a
    line the trace does not have is an empty one."""
    names, spans = [], []
    for ev in line.events if line is not None else ():
        names.append(ev.name)
        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return names, np.array(spans, dtype=np.float64).reshape(-1, 2)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    chips, annotations = [], []
    for plane in ProfileData.from_file(path).planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            lines = {ln.name: ln for ln in plane.lines}
            op_names, ops = _line_events(lines.get(OPS_LINE))
            mod_names, mods = _line_events(lines.get(MODULES_LINE))
            async_names, async_ops = _line_events(lines.get(ASYNC_LINE))
            chips.append(Chip(int(found.group(1)), ops, op_names,
                              mods, mod_names, async_ops, async_names))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    chips.sort(key=lambda c: c.index)
    annotations.sort(key=lambda a: a[1])
    return Trace(chips, annotations)


def merged(spans: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The union of the intervals, cut to [lo, hi], as disjoint sorted
    intervals."""
    spans = spans[(spans[:, 1] > lo) & (spans[:, 0] < hi)]
    if not len(spans):
        return np.zeros((0, 2))
    spans = np.clip(spans[np.argsort(spans[:, 0])], lo, hi)
    # an interval starts a new run where it begins after every earlier
    # interval has ended
    ends = np.maximum.accumulate(spans[:, 1])
    first = np.concatenate([[True], spans[1:, 0] > ends[:-1]])
    starts = spans[first, 0]
    last = np.concatenate([first[1:], [True]])
    return np.stack([starts, ends[last]], axis=1)


def busy_ns(spans: np.ndarray, lo: float, hi: float) -> float:
    u = merged(spans, lo, hi)
    return float(np.sum(u[:, 1] - u[:, 0]))


def gaps(spans: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The intervals of [lo, hi] that no interval covers."""
    u = merged(spans, lo, hi)
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def window(trace: Trace) -> tuple:
    """The traced rounds, first start to last end, in the trace's
    clock: the benchmark annotates each round as `bench.round <n>`."""
    rounds = trace.named("bench.round")
    if not rounds:
        raise ValueError("the trace holds no bench.round annotation")
    return min(r[1] for r in rounds), max(r[2] for r in rounds)


def chip_busy_s(trace: Trace, chip: Chip) -> float:
    lo, hi = window(trace)
    spans = chip.ops if len(chip.ops) else chip.modules
    return busy_ns(spans, lo, hi) / 1e9


def chips_busy_s(trace: Trace) -> list:
    """`chip_busy_s` of every chip of the trace, by index."""
    return [chip_busy_s(trace, c) for c in trace.chips]


def top_modules(trace: Trace, chip: Chip, n: int = 10) -> list:
    """[name, seconds] of the programs with most device time."""
    lo, hi = window(trace)
    total: dict = {}
    for name, (start, end) in zip(chip.module_names, chip.modules):
        inside = min(end, hi) - max(start, lo)
        if inside > 0:
            total[name] = total.get(name, 0.0) + inside / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def _covering(spans: list, at: float):
    """The shortest (name, start, end) that covers `at`: the innermost
    of nested spans."""
    over = [s for s in spans if s[1] <= at < s[2]]
    return min(over, key=lambda s: s[2] - s[1])[0] if over else None


def longest_gaps(trace: Trace, chip: Chip, engine_spans: list,
                 n: int = 5) -> list:
    """[label, seconds] of the longest idle gaps on the chip.  A gap is
    labelled with the benchmark annotation (query and round) and the
    engine span open at its middle; `engine_spans` are (name, start,
    end) already in the trace's clock."""
    lo, hi = window(trace)
    spans = chip.ops if len(chip.ops) else chip.modules
    idle = gaps(spans, lo, hi)
    order = np.argsort(idle[:, 0] - idle[:, 1])[:n]
    collects = trace.named("bench.collect")
    out = []
    for start, end in idle[order]:
        mid = (start + end) / 2
        where = _covering(collects, mid) or "between collects"
        doing = _covering(engine_spans, mid) or "no engine span"
        out.append([f"{where} | {doing}", (end - start) / 1e9])
    return out


def clock_offset_ns(trace: Trace, marker_perf_ns: int):
    """What to add to a `perf_counter_ns` reading to get the trace's
    clock: the benchmark reads the counter as it opens `bench.marker`."""
    marks = trace.named(MARKER)
    return marks[0][1] - marker_perf_ns if marks else None
