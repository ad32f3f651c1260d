"""Profiling tool: per-query operator reports and device traces.

TPU analog of the reference's profiling tool (tools/src/main/scala/...
/tool/profiling/ProfileMain.scala — ApplicationInfo/Analysis over event
logs).  This engine is in-process, so the "event log" is the session's
query history: every TPU collect records its exec tree, whose metrics
(device-synced ns timers, row/batch counts, spill and prune counters)
the report aggregates.

For timeline-level work there is `device_trace(dir)`: a context manager
around jax.profiler.trace producing a Perfetto/XPlane trace (the
nvtx_profiling.md workflow analog, ref: SURVEY §5.1).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Iterator, Optional, Sequence

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu.config import register
from spark_rapids_tpu.execs.base import TpuExec

HISTORY_CAPACITY = register(
    "spark.rapids.tpu.sql.queryHistory.capacity", 100,
    "How many collected queries the session's QueryHistory ring "
    "retains (operator snapshots + explain text per query; the oldest "
    "event is dropped past the cap).",
    check=lambda v: v >= 1)

#: PROCESS-global query-id source: the id doubles as the trace
#: subsystem's correlation key in a process-wide buffer, so two
#: sessions must never both hand out id 0 (their spans would merge in
#: span_stats / EXPLAIN ANALYZE).  itertools.count.__next__ is atomic
#: in CPython.
_QUERY_IDS = itertools.count()


@dataclasses.dataclass
class NodeSnapshot:
    """One operator's description + settled metric values.  History
    stores snapshots, NOT live exec trees — a live tree would pin the
    query's input data (e.g. ArrowSourceExec.table) for the session
    lifetime."""

    desc: str
    metrics: dict
    children: list


@dataclasses.dataclass
class QueryEvent:
    """One collected query (the ApplicationInfo analog).

    Beyond the id-keyed snapshot, events carry WHEN the query ran —
    ``start_ts``/``end_ts`` epoch seconds for human alignment and
    ``start_ns``/``end_ns`` monotonic (perf_counter_ns, same clock as
    the tracer) for in-process interval math — and ``conf_hash``, the
    active conf's fingerprint at collect time.  Event-log records and
    cross-run compares align runs on exactly these fields; ids alone
    are process-local and restart at 0 every run."""

    query_id: int
    explain: str
    root: NodeSnapshot
    wall_s: float
    ts: float
    start_ts: float = 0.0
    end_ts: float = 0.0
    start_ns: int = 0
    end_ns: int = 0
    conf_hash: str = ""


def snapshot_exec(node: TpuExec) -> NodeSnapshot:
    from spark_rapids_tpu.execs.base import TpuMetric, _MetricReaper

    _MetricReaper.get().flush()  # settle device-synced timers
    # settle ALL deferred device counts in one transfer: per-metric
    # flushes would pay one link round trip each
    mets: list = []

    def gather(n: TpuExec) -> None:
        mets.extend(n.metrics.values())
        for c in n.children:
            gather(c)

    gather(node)
    TpuMetric.flush_many(mets)
    return _snap(node)


def _snap(node: TpuExec) -> NodeSnapshot:
    return NodeSnapshot(
        node.node_desc(),
        {name: m.value for name, m in node.metrics.items()},
        [_snap(c) for c in node.children])


def _trace_operators(root: NodeSnapshot, qid: int, at_ns: int) -> None:
    """One `query.operator` instant an operator of the plan that ran,
    stamped at the query's end: its settled counts (rows and batches
    out, `rows_in` the rows its children put out, every other metric
    that moved) on the tracer's timeline, where a reader of spans
    finds them; the counts were deferred, so no operator paid a
    readback for them."""
    todo = [root]
    while todo:
        node = todo.pop()
        todo += node.children
        moved = {k: v for k, v in node.metrics.items()
                 if isinstance(v, (int, float)) and v}
        _trace.TRACER.record(
            "query.operator", at_ns, 0,
            {"query_id": qid, "op": node.desc.split(" ", 1)[0],
             "desc": node.desc[:160],
             "rows_in": sum(c.metrics.get("numOutputRows", 0)
                            for c in node.children), **moved}, ph="i")


def snapshot_delta(after: NodeSnapshot,
                   before: Optional[NodeSnapshot]) -> NodeSnapshot:
    """Positional per-metric subtraction of two snapshots of ONE exec
    tree (same shape by construction): the per-execution attribution
    for re-drained cached plan trees, whose live metrics accumulate
    across executions.  Numeric metrics subtract (clamped at 0 — a
    concurrent settle between the snapshots must never read as
    negative work); anything else reports the after value."""
    if before is None:
        return after
    mets: dict = {}
    for k, v in after.metrics.items():
        b = before.metrics.get(k)
        if isinstance(v, (int, float)) and isinstance(b, (int, float)) \
            and not isinstance(v, bool):
            mets[k] = max(0, v - b)
        else:
            mets[k] = v
    kids = [snapshot_delta(c, before.children[i]
                           if i < len(before.children) else None)
            for i, c in enumerate(after.children)]
    return NodeSnapshot(after.desc, mets, kids)


class QueryHistory:
    """Session-attached ring of recent QueryEvents.

    `record` snapshots on a background worker: settling device-synced
    timers means waiting for completion notifications, which on remote
    PJRT links can lag the actual result by over a second — that wait
    must not sit on collect()'s critical path.  Every reader drains the
    worker first, so observable history is always consistent."""

    #: ONE process-wide snapshot worker (daemon): per-session pools
    #: would leak a thread per TpuSession for the process lifetime
    _pool = None
    _pool_lock = None

    @classmethod
    def _worker(cls):
        import concurrent.futures
        import threading

        if cls._pool_lock is None:
            cls._pool_lock = threading.Lock()
        with cls._pool_lock:
            if cls._pool is None:
                cls._pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="query-history")
            return cls._pool

    def __init__(self, capacity: Optional[int] = None):
        import threading

        if capacity is None:
            from spark_rapids_tpu.config import get_conf

            capacity = int(get_conf().get(HISTORY_CAPACITY))
        self.capacity = capacity
        self._events: list[QueryEvent] = []
        self._pending: list = []
        # guards _pending/_events against caller-thread vs
        # worker/reader races (a reader swapping _pending mid-append
        # would drop a just-recorded snapshot future)
        self._mu = threading.Lock()

    def allocate_id(self) -> int:
        """Claim the next query id BEFORE execution, so trace spans and
        the eventual history event share one correlation key.  Ids are
        process-global: the trace buffer is shared by every session."""
        return next(_QUERY_IDS)

    def record(self, explain: str, exec_tree: Optional[TpuExec],
               wall_s: float, query_id: Optional[int] = None,
               start_ts: float = 0.0, end_ts: float = 0.0,
               start_ns: int = 0, end_ns: int = 0,
               conf_hash: str = "",
               on_event=None, baseline=None) -> None:
        """`on_event(ev)` (optional) runs on the snapshot worker AFTER
        the settled event is appended — the event-log writer's hook:
        it sees device-settled metrics without adding a second settle
        wait to collect()'s critical path.  `baseline` (a settled
        pre-drain NodeSnapshot of the same tree) turns the recorded
        metrics into per-execution deltas — the cached-plan re-drain
        contract; `exec_tree` may be None for queries that executed no
        operators (a result-cache hit), which record a placeholder
        operator node."""
        ts = time.time()
        if query_id is None:
            query_id = next(_QUERY_IDS)

        def snap(qid):
            if exec_tree is None:
                root = NodeSnapshot(
                    "ResultCacheHit [no operators executed]", {}, [])
            else:
                root = snapshot_delta(snapshot_exec(exec_tree),
                                      baseline)
            ev = QueryEvent(qid, explain, root,
                            wall_s, ts, start_ts=start_ts,
                            end_ts=end_ts, start_ns=start_ns,
                            end_ns=end_ns, conf_hash=conf_hash)
            if _trace.TRACER.enabled:
                _trace_operators(root, qid, end_ns)
            with self._mu:
                self._events.append(ev)
                if len(self._events) > self.capacity:
                    self._events.pop(0)
            if on_event is not None:
                try:
                    on_event(ev)
                except Exception as exc:
                    # a failed event-log append (disk full, revoked
                    # dir) must not poison this future: _drain()
                    # re-raises worker exceptions into EVERY later
                    # history read — explain("analyze"), bench's
                    # final drain — after the query itself succeeded
                    import warnings

                    warnings.warn(
                        f"query-history on_event hook failed for "
                        f"query {qid}: {exc!r}", RuntimeWarning)
        with self._mu:
            # drop settled futures so a never-inspected history stays O(1)
            self._pending = [f for f in self._pending if not f.done()]
            self._pending.append(self._worker().submit(snap, query_id))

    def _drain(self) -> None:
        with self._mu:
            pending, self._pending = self._pending, []
        for f in pending:
            f.result()

    @property
    def events(self) -> list[QueryEvent]:
        self._drain()
        with self._mu:
            return list(self._events)


def _walk_snap(s: NodeSnapshot):
    yield s
    for c in s.children:
        yield from _walk_snap(c)


def _op_key(desc: str) -> str:
    """The exec class name a snapshot desc starts with — the join key
    against trace spans' `op` attribute."""
    return desc.split(" ", 1)[0].split("[", 1)[0]


def _jit_cache_line(cache_stats: Optional[dict]) -> Optional[str]:
    """One-line compile-cache summary (callers pass a PER-QUERY delta
    of jit_cache.cache_stats(), next to the per-miss jit.cache_miss
    trace events)."""
    if cache_stats is None:
        return None
    hits = cache_stats.get("hits", 0)
    misses = cache_stats.get("misses", 0)
    total = hits + misses
    rate = f"{hits / total:.2f}" if total else "n/a"
    return (f"jit cache: hits={hits} misses={misses} "
            f"hit_rate={rate}")


def _counter_footer(counters: Optional[dict]) -> list[str]:
    """Recovery + runtime-filter footer lines (callers pass PER-QUERY
    deltas of execs/retry.retry_stats, robustness/faults recovered
    counts and plan/runtime_filter.stats) — the in-process view of
    exactly the counters the event log persists, so explain("analyze")
    and tools/history can never tell a different story."""
    if not counters:
        return []
    lines = []
    r = counters.get("retry")
    if r is not None:
        line = (f"retry: splits={r.get('splits', 0)} "
                f"spill_retries={r.get('spill_retries', 0)} "
                f"task_retries={r.get('task_retries', 0)} "
                f"cpu_fallbacks={r.get('cpu_fallbacks', 0)}")
        if "faults_recovered" in counters:
            line += (f"; recovered_faults="
                     f"{counters['faults_recovered']}")
        lines.append(line)
    rf = counters.get("rf")
    if rf is not None:
        lines.append(
            f"runtime filters: built={rf.get('filters_built', 0)} "
            f"pruned_rows={rf.get('pruned_rows', 0)} "
            f"row_groups_pruned={rf.get('row_groups_pruned', 0)}")
    pc = counters.get("plan_cache")
    if pc is not None:
        lines.append(
            f"plan cache: hits={pc.get('hits', 0)} "
            f"misses={pc.get('misses', 0)} "
            f"evictions={pc.get('evictions', 0)}")
    return lines


def _ledger_footer(ledger: Optional[dict]) -> list[str]:
    """Device-ledger footer: totals + the top programs by device time
    (callers pass a per-query `trace.ledger.summarize(delta)` — the
    same section the event log persists, so explain("analyze") and
    tools/history read one story)."""
    if not ledger:
        return []
    t = ledger.get("totals") or {}
    roof = t.get("roofline")
    lcr = t.get("live_capacity_ratio")
    lines = [
        f"device ledger: programs={t.get('programs', 0)} "
        f"dispatches={t.get('dispatches', 0)} "
        f"device_ms={t.get('device_ms', 0.0):.2f} "
        f"dispatch_ms={t.get('dispatch_ms', 0.0):.2f} "
        + (f"roofline={roof:.6f}" if roof is not None
           else "roofline=n/a")
        + (f" live/cap={lcr:.2f}" if lcr is not None else "")]
    progs = ledger.get("programs") or {}
    for p in t.get("top") or []:
        # per-program efficiency: cost-model bytes x dispatches over
        # settled busy time, against the HBM peak — plus the occupancy
        # ratio saying how much of that traffic was live rows
        e = progs.get(p["key"]) or {}
        eff = e.get("roofline")
        plcr = p.get("live_capacity_ratio")
        lines.append(
            f"  top: {p['key']} op={p['op'] or '-'} "
            f"dispatches={p['dispatches']} "
            f"device_ms={p['device_ms']:.2f} share={p['share']:.0%}"
            + (f" eff={eff:.6f}" if eff is not None else " eff=n/a")
            + (f" live/cap={plcr:.2f}" if plcr is not None else ""))
    return lines


def profile_query(ev: QueryEvent,
                  trace_events: Optional[Sequence] = None,
                  cache_stats: Optional[dict] = None,
                  counters: Optional[dict] = None,
                  ledger: Optional[dict] = None) -> str:
    """Per-operator metrics table for one query (the Analysis /
    ClassWarehouse per-SQL metrics view).  With `trace_events` (a
    spark_rapids_tpu.trace snapshot), a `self_ms` column reports each
    operator's span-derived self-time: the union of its trace spans for
    this query — time the operator was actively running on SOME thread,
    as opposed to summed per-thread busy time.  With `cache_stats` (a
    per-query jit_cache.cache_stats() delta), a compile-cache hit-rate
    footer rides along."""
    stats: dict = {}
    if trace_events is not None:
        from spark_rapids_tpu.trace.export import span_stats

        stats = span_stats(trace_events, query_id=ev.query_id)
    self_col = " self_ms |" if stats else ""
    lines = [
        f"== Query {ev.query_id} ({ev.wall_s:.3f}s wall) ==",
        "",
        f"| operator | rows | batches | time_ms |{self_col}"
        " other metrics |",
        f"|---|---|---|---|{'---|' if stats else ''}---|",
    ]
    for n in _walk_snap(ev.root):
        m = dict(n.metrics)
        rows = m.pop("numOutputRows", "")
        batches = m.pop("numOutputBatches", "")
        t = m.pop("totalTime", None)
        others = [f"{k}={v}" for k, v in sorted(m.items()) if v]
        t_ms = f"{t / 1e6:.2f}" if t is not None else ""
        extra = ""
        if stats:
            st = stats.get(_op_key(n.desc))
            extra = (f" {st['wall_ns'] / 1e6:.2f} |" if st
                     else "  |")
        lines.append(
            f"| {n.desc[:60]} | {rows} | {batches} | {t_ms} |{extra}"
            f" {' '.join(others)} |")
    footer = ([] if cache_stats is None
              else [_jit_cache_line(cache_stats)])
    footer += _counter_footer(counters)
    footer += _ledger_footer(ledger)
    if footer:
        lines += [""] + footer
    return "\n".join(lines) + "\n"


def render_analyze(ev: QueryEvent,
                   trace_events: Optional[Sequence] = None,
                   cache_stats: Optional[dict] = None,
                   counters: Optional[dict] = None,
                   ledger: Optional[dict] = None) -> str:
    """EXPLAIN ANALYZE: the post-run plan tree, each operator annotated
    with its SETTLED metrics (wall time per device-synced totalTime,
    rows, batches) and — when a trace is available — span-derived
    busy/self/overlap: busy sums this operator's span time across all
    threads, self is the union of those intervals, and overlap =
    busy - self (concurrent execution the aggregate timers hide).
    Span figures aggregate per operator CLASS (spans carry the exec
    name), so two instances of one class — a partial and a final
    aggregate — show the class total on each.  The aggregate's
    `specHits`/`specOverflows` and the join's `expandRows`/
    `expandCapacityRows` (pairs counted over the capacities expanded
    at: the fill) come through the regular metric annotations.
    `cache_stats` (a per-query
    jit_cache.cache_stats() delta) appends the compile-cache hit
    rate.  `ledger` (a per-query `trace.ledger.summarize(delta)`,
    present when the device ledger is on) adds a per-operator
    ``roofline=`` column — that operator's ATTRIBUTED roofline
    fraction: cost-model bytes x dispatches of the programs it
    compiled, over their settled device time, against the HBM peak —
    plus a top-programs footer (docs/device_ledger.md)."""
    stats: dict = {}
    if trace_events is not None:
        from spark_rapids_tpu.trace.export import span_stats

        stats = span_stats(trace_events, query_id=ev.query_id)
    op_roof: dict = {}
    if ledger:
        from spark_rapids_tpu.trace.ledger import per_op

        op_roof = per_op(ledger.get("programs") or {})
    lines = [f"== Physical Plan (ANALYZE, query {ev.query_id}, "
             f"{ev.wall_s:.3f}s wall) =="]

    def walk(n: NodeSnapshot, indent: int) -> None:
        m = n.metrics
        ann = []
        t = m.get("totalTime")
        if t is not None:
            ann.append(f"time={t / 1e6:.2f}ms")
        ann.append(f"rows={m.get('numOutputRows', 0)}")
        ann.append(f"batches={m.get('numOutputBatches', 0)}")
        st = stats.get(_op_key(n.desc))
        if st:
            ann.append(
                f"span(busy={st['busy_ns'] / 1e6:.2f}ms "
                f"self={st['wall_ns'] / 1e6:.2f}ms "
                f"overlap={st['overlap_ns'] / 1e6:.2f}ms)")
        lr = op_roof.get(_op_key(n.desc))
        if lr:
            # the ledger's attributed per-operator roofline (the
            # column ROADMAP #2's fusion work is judged against)
            ann.append(
                "roofline=" + (f"{lr['roofline']:.6f}"
                               if lr["roofline"] is not None
                               else "n/a")
                + f" device={lr['device_ms']:.2f}ms"
                  f" dispatches={lr['dispatches']}"
                + (f" live/cap={lr['live_capacity_ratio']:.2f}"
                   if lr.get("live_capacity_ratio") is not None
                   else ""))
        extras = {k: v for k, v in m.items()
                  if k not in ("totalTime", "numOutputRows",
                               "numOutputBatches") and v}
        if extras:
            ann.append(" ".join(f"{k}={v}"
                                for k, v in sorted(extras.items())))
        lines.append("  " * indent + "+- " + n.desc
                     + "  [" + " ".join(ann) + "]")
        for c in n.children:
            walk(c, indent + 1)

    walk(ev.root, 0)
    jc = _jit_cache_line(cache_stats)
    if jc is not None:
        lines.append(jc)
    lines.extend(_counter_footer(counters))
    lines.extend(_ledger_footer(ledger))
    return "\n".join(lines) + "\n"


def profile_report(history: QueryHistory) -> str:
    """Whole-session report: store/spill health plus per-query operator
    tables (ProfileMain's aggregate + per-app sections)."""
    from spark_rapids_tpu.memory import get_store

    store = get_store()
    lines = [
        "# Profile report",
        "",
        f"queries: {len(history.events)}",
        "",
        "## Memory / spill health (HealthCheck analog)",
        "",
        f"- device bytes in store: {store.device_used}",
        f"- host bytes in store: {store.host_used}",
        f"- spilled device->host: {store.spilled_device_to_host}",
        f"- spilled host->disk: {store.spilled_host_to_disk}",
        "",
        "## Queries",
        "",
    ]
    for ev in history.events:
        lines.append(profile_query(ev))
    return "\n".join(lines)


@contextlib.contextmanager
def device_trace(trace_dir: str) -> Iterator[None]:
    """Capture an XLA device trace viewable in Perfetto/TensorBoard
    (jax.profiler.trace), the nsys/NVTX workflow analog."""
    import jax

    with jax.profiler.trace(trace_dir):
        yield


def generate_dot(ev: QueryEvent) -> str:
    """SQL-plan DOT graph (GenerateDot.scala analog)."""
    lines = ["digraph plan {", "  node [shape=box fontname=monospace];"]
    ids: dict[int, int] = {}

    def nid(n) -> int:
        if id(n) not in ids:
            ids[id(n)] = len(ids)
        return ids[id(n)]

    for n in _walk_snap(ev.root):
        rows = n.metrics.get("numOutputRows")
        label = n.desc.replace("\\", "\\\\").replace('"', "'")[:80]
        if rows:
            label += f"\\nrows={rows}"
        lines.append(f'  n{nid(n)} [label="{label}"];')
        for c in n.children:
            lines.append(f"  n{nid(c)} -> n{nid(n)};")
    lines.append("}")
    return "\n".join(lines)
