"""From a run's rounds, spans and trace to the metrics of its line."""

import dataclasses
import statistics

from benchmarks.harness import check, spec, trace_reduce
from benchmarks.harness.peaks import DEVICE_PEAKS


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader is handed.  `rounds` are the
    window's; in a traced run the profiler covered exactly them."""

    cell: spec.Cell
    warmup: list  # of engine.Round
    rounds: list
    setup_s: float
    device_kind: str
    memory_peak_bytes: int
    spans: list = ()  # the engine tracer's events inside the window
    trace: trace_reduce.Trace = None

    @property
    def peaks(self) -> dict:
        return DEVICE_PEAKS[self.device_kind]

    def per_round(self, counter: str):
        """A counter's mean over the window's rounds; None where the
        program has no such counter."""
        if not all(counter in r.counters for r in self.rounds):
            return None
        return statistics.fmean(r.counters[counter] for r in self.rounds)

    def span_seconds(self, *names: str):
        """Per round, the seconds inside the engine spans so named."""
        hit = [s for s in self.spans if s.name in names]
        if not hit:
            return None
        return sum(s.dur_ns for s in hit) / 1e9 / len(self.rounds)

    def busy_s(self):
        """Seconds a chip was busy in the traced window, mean over the
        cell's chips."""
        if self.trace is None or not self.trace.chips:
            return None
        return statistics.fmean(trace_reduce.chips_busy_s(self.trace))

    def window_s(self):
        if self.trace is None:
            return None
        lo, hi = trace_reduce.window(self.trace)
        return (hi - lo) / 1e9


def compared(collects: list) -> dict:
    """What decided `correct`, each number beside its limit, over every
    collect of the run (warm-up included): the widest gap of a double
    from the plain reference's (`check.compare`), the collects whose
    answer differs from it at all, and those whose plan degraded, left
    the device or lacks an operator its step names."""
    gaps = [c.gap for c in collects if c.gap is not None]
    return {
        # 1e300 stands for a NaN or an infinity, which JSON has not
        "double_rel_gap": {"value": min(max(gaps, default=0.0), 1e300),
                           "limit": check.REL_TOL},
        "answers_differing": {
            "value": sum(1 for c in collects if c.failure), "limit": 0},
        "plans_at_fault": {
            "value": sum(1 for c in collects if c.plan_fault), "limit": 0},
    }


def chips_at_work(run: Run, chips: int) -> list:
    """Busy seconds of each chip in the traced window, or ValueError
    where the trace holds fewer chips than the cell's mesh or one of
    them ran nothing: everything on the first chip is no measurement
    of several."""
    busy = trace_reduce.chips_busy_s(run.trace)
    if len(busy) < chips or min(busy[:chips]) <= 0:
        raise ValueError(
            f"the cell's mesh has {chips} chips and the trace shows device "
            f"work on {sum(b > 0 for b in busy)}: busy seconds {busy}")
    return busy


def end_to_end(run: Run) -> dict:
    walls = [r.wall_s for r in run.rounds]
    return {
        "round_wall_s": statistics.median(walls),
        # mean-based on purpose: a stall, a compile or a spill inside
        # the window shows here when the median hides it
        "rows_per_s": run.cell.input_rows() * len(walls) / sum(walls),
        "setup_s": run.setup_s,
    }


def per_layer(run: Run) -> dict:
    """Every per-layer metric the cell reports, by its own reader; a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for metric in run.cell.per_layer:
        value = spec.module("layer_metrics", metric["name"]).reduce(run)
        if value is not None:
            out[metric["name"]] = value
    return out


def breakdown(run: Run, marker_perf_ns: int) -> dict:
    """The ten programs with most device time and the five longest
    idle gaps, on the first chip."""
    chip = run.trace.chips[0]
    offset = trace_reduce.clock_offset_ns(run.trace, marker_perf_ns)
    # spans of the thread that called collect(): where the client waits
    spans = [] if offset is None else [
        (s.name, s.ts_ns + offset, s.ts_ns + s.dur_ns + offset)
        for s in run.spans if s.thread_name == "MainThread" and s.dur_ns]
    return {"device_ops": trace_reduce.top_modules(run.trace, chip),
            "idle_gaps": trace_reduce.longest_gaps(run.trace, chip, spans)}
