"""The part of a run that owns the chip: the only module of the
benchmark that imports JAX and the engine.

From the program it takes the system under test (`TpuSession`, its
DataFrames and `collect()`), its counters (`stage_snapshot`,
`upload_stats`, `spill_stats`, `retry_stats`, `cache_stats`), the
tracer's spans and `compile_cache_dir()`.  `require_devices`,
`CompileCounters` and `off_device` are copied from `chip_smoke.py`
(PR 21), which stays the program's.

A cell of one chip runs a plain session.  A cell of more than one runs
under the collective shuffle over its first `chips` devices
(`TpuSession.enable_collective_shuffle`, the switch a user turns): the
cell's chip count decides it and no key of a file does.
"""

import dataclasses
import os
import sys
import time

from benchmarks.harness import check, spec
from benchmarks.harness.peaks import DEVICE_PEAKS

#: warm up by whole rounds until one brings no new program into the
#: process, and never more than this many
MAX_WARMUP_ROUNDS = 3


class Refused(SystemExit):
    """The run cannot be a measurement: exit non-zero, print no result."""

    def __init__(self, reason: str):
        super().__init__(f"benchmarks.run REFUSED: {reason}")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_devices(chips: int, rehearse: bool) -> list:
    """The devices JAX returned, checked: not JAX_PLATFORMS, which says
    what was asked for."""
    import jax

    devs = jax.devices()
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    if rehearse:
        return devs
    if devs[0].platform != "tpu":
        raise Refused(
            f"JAX found no TPU: jax.devices()[0] is platform "
            f"{devs[0].platform!r}, kind {devs[0].device_kind!r} "
            "(--rehearse runs elsewhere, and is not a measurement)")
    if devs[0].device_kind not in DEVICE_PEAKS:
        raise Refused(f"no peaks for device kind {devs[0].device_kind!r} "
                      "in benchmarks/harness/peaks.py")
    return devs


class CompileCounters:
    """Programs XLA's backend built or loaded, and persistent-cache
    traffic, from JAX's own monitoring events.  The backend-compile
    event wraps the cache lookup too, so it counts every program that
    is new to this process, compiled or read from the cache."""

    def __init__(self):
        import jax.monitoring as mon

        self.backend_compile_s = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs
            self.backend_compiles += 1

    def snapshot(self) -> dict:
        return {"backend_compiles": self.backend_compiles,
                "backend_compile_s": self.backend_compile_s,
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}


def off_device(explain_text: str) -> list:
    """The plan's operators that do not carry the `*` mark
    (planner.py: `!` marks one that runs on the CPU engine), or the
    whole text where no plan is found."""
    plan = []
    for line in explain_text.splitlines():
        if line and not line[0].isspace() and line[0] not in "*!":
            break  # the report's sections (Pipeline:, Fusion:, ...)
        plan.append(line)
    off = [ln for ln in plan
           if ln.strip() and not ln.lstrip().startswith("* ")]
    return off if plan else [explain_text]


def lacking(root, operators) -> list:
    """Those of the named operators that the executed plan does not
    hold.  `root` is the history event's snapshot of the operator tree
    that ran (`desc`, `children`); an operator's description starts
    with its name."""
    held, todo = set(), [root]
    while todo:
        node = todo.pop()
        held.add(node.desc.split(" ", 1)[0])
        todo += node.children
    return [op for op in operators if op not in held]


def stated_conf(config: dict) -> dict:
    """The keys a configuration's file sets over the shipped default
    conf (`conf`), each of which its `assumed.conf` has to account for
    by name: what a cut made necessary, never a tuning knob."""
    said = config.get("assumed", {}).get("conf", "")
    conf = config.get("conf", {})
    for key in conf:
        if key not in said:
            raise Refused(f"the configuration sets {key} and its "
                          "assumed.conf does not say why")
    return conf


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def _flatten(prefix: str, stats: dict, into: dict) -> None:
    """`{"a": {"b": 1}}` under prefix `p` becomes `p.a.b`; what is no
    number is left out."""
    for key, value in stats.items():
        if isinstance(value, dict):
            _flatten(f"{prefix}.{key}", value, into)
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            into[f"{prefix}.{key}"] = value


@dataclasses.dataclass
class Collect:
    """One timed collect and what became of it."""

    query: str
    round: int
    wall_s: float
    result: object  # a pyarrow Table, dropped once it is checked
    failure: str = None  # how the answer differs from the expected one
    plan_fault: str = None  # a degrade, an operator off the device or
    # one that the step names and the plan lacks
    gap: float = None  # check.compare's widest gap of a double


@dataclasses.dataclass
class Round:
    index: int
    wall_s: float
    t0_ns: int  # perf_counter_ns, the engine tracer's clock
    t1_ns: int
    collects: list
    counters: dict  # what the program's counters moved by in this round


class Runner:
    """A session under the cell's configuration, and rounds over it.
    Where the cell has more than one chip the session runs under the
    collective shuffle over a mesh of that many devices, until
    `close()`."""

    def __init__(self, cell: spec.Cell, data, devs: list, trace: bool):
        from spark_rapids_tpu import native
        from spark_rapids_tpu.session import TpuSession

        self.cell, self.data, self.devs = cell, data, devs[:cell.chips]
        self.compiles = CompileCounters()
        if native.load() is None:
            raise Refused("the native host codec is not loaded (no g++?): "
                          "scans would decode through the slow path")
        self.session = TpuSession()
        for key, value in stated_conf(cell.config).items():
            self.session.conf.set(key, value)
        if cell.chips > 1:
            # make_mesh takes the first `chips` of jax.devices()
            self.session.enable_collective_shuffle(cell.chips)
        if trace:
            from spark_rapids_tpu.trace import TRACE_ENABLED

            self.session.conf.set(TRACE_ENABLED.key, True)
        self._cached: dict = {}
        self._seen_queries = -1
        self.rounds_run = 0

    # -- frames ----------------------------------------------------- #

    def _frames(self, step: spec.Step) -> dict:
        """role -> DataFrame: the cached columns, or a fresh scan of
        the columns the query reads.  The files hold every column of
        the table; the scan is handed its read schema, as Spark's
        optimizer hands one to a scan, because the engine's planner
        does not prune file columns itself yet (PERF.md section 7)."""
        if self.cell.resident:
            return {role: self._cached[t.name] for role, t in step.tables}
        wanted = spec.module("queries", step.query).COLUMNS
        return {role: self.session.read_parquet(
            *self.data.paths[t.name], columns=wanted[role])
            for role, t in step.tables}

    def fill_cache(self) -> dict:
        """Where the traffic reads resident tables: cache the columns
        its queries read, filled by one collect of the round's first
        query, and held to the size the rows and widths give."""
        from spark_rapids_tpu.memory import get_store

        columns: dict = {}
        for step in self.cell.round:
            wanted = spec.module("queries", step.query).COLUMNS
            for role, table in step.tables:
                have = columns.setdefault(table.name, [])
                have += [c for c in wanted[role] if c not in have]
        for table in self.cell.tables():
            self._cached[table.name] = self.session.read_parquet(
                *self.data.paths[table.name],
                columns=columns[table.name]).cache()
        t0 = time.perf_counter()
        filled: set = set()
        for step in self.cell.round:
            # a table is filled by the first query that reads it
            reads = {t.name for _, t in step.tables}
            if not reads <= filled:
                self._build(step).collect()
                filled |= reads
        degraded = self._degrades(self._new_events())
        if degraded:
            raise Refused(f"while filling the cache: {degraded}")
        stats = get_store().spill_stats()
        floor = sum(spec.column_bytes(t, columns[t.name])
                    for t in self.cell.tables())
        if stats["device_used"] < floor:
            raise Refused(f"the cache holds {stats['device_used']} device "
                          f"bytes; the rows and widths need {floor}")
        return {"cache_fill_s": time.perf_counter() - t0,
                "cached_device_bytes": stats["device_used"],
                "cached_bytes_floor": floor}

    def _build(self, step: spec.Step):
        return spec.module("queries", step.query).build(
            self.session, self._frames(step))

    # -- counters --------------------------------------------------- #

    def _counters(self) -> dict:
        """Every number the program's counters hold, whole and
        flattened by prefix (`upload.wire_bytes`,
        `stage.scan.upload.consumer_wait_s`), with the process's CPU
        seconds and the compile events: a reader under
        `layer_metrics/` picks its own keys.  A round keeps the
        difference of two readings, so a gauge shows as its change."""
        from spark_rapids_tpu.columnar.transfer import upload_stats
        from spark_rapids_tpu.execs.jit_cache import cache_stats
        from spark_rapids_tpu.execs.retry import retry_stats
        from spark_rapids_tpu.memory import get_store
        from spark_rapids_tpu.parallel.pipeline import stage_snapshot

        cpu = os.times()
        flat = {"cpu_s": cpu.user + cpu.system, **self.compiles.snapshot()}
        for prefix, stats in (("upload", upload_stats()),
                              ("jit_cache", cache_stats()),
                              ("spill", get_store().spill_stats()),
                              ("retry", retry_stats()),
                              ("stage", stage_snapshot())):
            _flatten(prefix, stats, flat)
        return flat

    # -- rounds ----------------------------------------------------- #

    def run_round(self) -> Round:
        """One pass over the cell's queries."""
        import jax

        index = self.rounds_run
        self.rounds_run += 1
        before = self._counters()
        t0_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(f"bench.round {index}"):
            collects = [self._collect(step, index)
                        for step in self.cell.round]
        t1_ns = time.perf_counter_ns()
        done = Round(index, (t1_ns - t0_ns) / 1e9, t0_ns, t1_ns, collects,
                     _delta(self._counters(), before))
        self._note_degrades(done)
        return done

    def _collect(self, step: spec.Step, index: int) -> Collect:
        """As a user pays for it: timed from the frame being built to
        the Arrow table being on the host."""
        import jax

        with jax.profiler.TraceAnnotation(
                f"bench.collect {step.query} round {index}"):
            t0 = time.perf_counter()
            out = self._build(step).collect()
            wall = time.perf_counter() - t0
        return Collect(step.query, index, wall, out)

    def _new_events(self) -> list:
        """The session history's events since the last look, in the
        order of their collects."""
        new = [ev for ev in self.session.history.events
               if ev.query_id > self._seen_queries]
        new.sort(key=lambda ev: ev.query_id)
        if new:
            self._seen_queries = new[-1].query_id
        return new

    def _degrades(self, events: list) -> list:
        """What the history's events say of their collects: one that
        the CPU engine answered, or a plan with an operator off the
        device."""
        why = []
        for ev in events:
            if "[degraded to CPU engine" in ev.explain:
                why.append(f"query {ev.query_id} degraded to the CPU engine")
            why += [f"query {ev.query_id} off the device: {ln}"
                    for ln in off_device(ev.explain)]
        return why

    def _note_degrades(self, done: Round) -> None:
        """Outside the timing.  A degrade fails every collect of the
        round.  A round's events stand in the history in the order of
        its collects, so where a step names operators (`plan_has`) its
        own event's plan is held to them and its own collect fails."""
        events = self._new_events()
        why = self._degrades(events)
        fallbacks = done.counters["retry.cpu_fallbacks"]
        if fallbacks:
            why.append(f"{fallbacks} CPU fallbacks counted by retry_stats")
        if any(step.plan_has for step in self.cell.round):
            if len(events) != len(done.collects):
                why.append(f"{len(events)} events in the history for "
                           f"{len(done.collects)} collects: no plan can "
                           "be held to its plan_has")
            else:
                for c, step, ev in zip(done.collects, self.cell.round,
                                       events):
                    lacks = lacking(ev.root, step.plan_has)
                    if lacks:
                        c.plan_fault = (f"the plan of query {ev.query_id} "
                                        f"lacks {', '.join(lacks)}")
        if why:
            for c in done.collects:
                c.plan_fault = c.plan_fault or "; ".join(why)

    def check(self, done: Round) -> None:
        """Each answer against the plain reference's; the result is
        dropped afterwards."""
        for c, step, want in zip(done.collects, self.cell.round,
                                 self.data.expected):
            ordered = spec.module("queries", step.query).ORDERED
            c.failure, c.gap = check.compare(c.result, want, ordered)
            c.result = None

    def memory_peak_bytes(self) -> int:
        """`peak_bytes_in_use` on the fullest chip; 0 where the backend
        reports none (a rehearsal on the CPU)."""
        return max(self.memory_peaks())

    def memory_peaks(self) -> list:
        """`peak_bytes_in_use` of each of the cell's chips."""
        return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.devs]

    def close(self) -> None:
        for frame in self._cached.values():
            frame.unpersist()
        if self.cell.chips > 1:
            self.session.disable_collective_shuffle()
