"""User-facing session + DataFrame API.

The reference plugs into Spark's existing frontend; this framework ships
its own minimal DataFrame surface (SURVEY.md §7: "a small DataFrame/plan
frontend plus a CPU engine that plays the role of CPU Spark").  The API
deliberately mirrors PySpark's shape (select/where/groupBy/agg/join/
orderBy/limit/collect/explain) so reference test cases translate
directly."""

from __future__ import annotations

from typing import Optional, Sequence, Union

import dataclasses

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import SQL_ENABLED, TpuConf, get_conf
from spark_rapids_tpu.execs.sort import SortKey
from spark_rapids_tpu.exprs.aggregates import (
    Average,
    Count,
    CountStar,
    First,
    Last,
    Max,
    Min,
    NamedAgg,
    Sum,
)
from spark_rapids_tpu.exprs.base import ColumnReference, Expression, lit
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.planner import collect_exec, plan_query

ExprLike = Union[str, Expression]
AggLike = Union[NamedAgg, tuple]


class AnalysisException(TypeError):
    """Engine-layer analysis failure (ref: Spark's AnalysisException):
    the plan is rejected before execution — e.g. UNION members with no
    common column type.  Subclasses TypeError so generic type-error
    handling keeps working, but frontends should catch THIS (a blanket
    `except TypeError` would rebrand incidental engine bugs as user
    errors)."""


def col(name: str) -> ColumnReference:
    return ColumnReference(name)


def _expr(e: ExprLike) -> Expression:
    return ColumnReference(e) if isinstance(e, str) else e


def _coerce_union_member(plan: "L.LogicalPlan",
                         widened: Sequence[Optional[T.DataType]]):
    """Project a UNION member onto the widened column types (positional
    bound references: name-based ones would resolve duplicate output
    names to the first occurrence); no-op when nothing changes."""
    from spark_rapids_tpu.exprs.base import Alias, BoundReference
    from spark_rapids_tpu.exprs.cast import Cast

    exprs: list[Expression] = []
    changed = False
    for i, (f, ct) in enumerate(zip(plan.schema.fields, widened)):
        ref = BoundReference(i, f.dtype, f.nullable, f.name)
        if ct is not None and f.dtype != ct:
            exprs.append(Alias(Cast(ref, ct), f.name))
            changed = True
        else:
            exprs.append(ref)
    return L.Project(exprs, plan) if changed else plan


# function-style aggregate constructors (pyspark.sql.functions shape)
def sum_(e: ExprLike) -> Sum:
    return Sum(_expr(e))


def count(e: ExprLike) -> Count:
    return Count(_expr(e))


def count_distinct(e: ExprLike):
    from spark_rapids_tpu.exprs.aggregates import CountDistinct

    return CountDistinct(_expr(e))


def count_star() -> CountStar:
    return CountStar()


def min_(e: ExprLike) -> Min:
    return Min(_expr(e))


def max_(e: ExprLike) -> Max:
    return Max(_expr(e))


def avg(e: ExprLike) -> Average:
    return Average(_expr(e))


def collect_list(e: ExprLike):
    from spark_rapids_tpu.exprs.aggregates import CollectList

    return CollectList(_expr(e))


def collect_set(e: ExprLike):
    from spark_rapids_tpu.exprs.aggregates import CollectSet

    return CollectSet(_expr(e))


def first(e: ExprLike, ignore_nulls: bool = False) -> First:
    return First(_expr(e), ignore_nulls)


def last(e: ExprLike, ignore_nulls: bool = False) -> Last:
    return Last(_expr(e), ignore_nulls)


def array(*exprs: ExprLike):
    from spark_rapids_tpu.exprs.collections import CreateArray

    return CreateArray(*[_expr(e) for e in exprs])


def from_unixtime(e: ExprLike, fmt: str = "yyyy-MM-dd HH:mm:ss"):
    from spark_rapids_tpu.exprs.datetime import FromUnixTime

    return FromUnixTime(_expr(e), fmt)


def date_format(e: ExprLike, fmt: str = "yyyy-MM-dd"):
    from spark_rapids_tpu.exprs.datetime import DateFormatClass

    return DateFormatClass(_expr(e), fmt)


def scalar_subquery(df) -> Expression:
    """A 1x1 DataFrame as a scalar expression (ref: GpuScalarSubquery);
    evaluated once at planning and spliced in as a literal."""
    from spark_rapids_tpu.exprs.subquery import ScalarSubquery

    return ScalarSubquery(df._plan)


def rand(seed: int = 0):
    from spark_rapids_tpu.exprs.nondeterministic import Rand

    return Rand(seed)


def monotonically_increasing_id():
    from spark_rapids_tpu.exprs.nondeterministic import (
        MonotonicallyIncreasingID,
    )

    return MonotonicallyIncreasingID()


def spark_partition_id():
    from spark_rapids_tpu.exprs.nondeterministic import SparkPartitionID

    return SparkPartitionID()


def nanvl(a: ExprLike, b: ExprLike):
    from spark_rapids_tpu.exprs.math import NaNvl

    return NaNvl(_expr(a), _expr(b))


def replace_(e: ExprLike, search: str, replacement: str):
    from spark_rapids_tpu.exprs.strings import StringReplace

    return StringReplace(_expr(e), lit(search), lit(replacement))


def regexp_replace(e: ExprLike, pattern: str, replacement: str):
    from spark_rapids_tpu.exprs.strings import RegExpReplace

    return RegExpReplace(_expr(e), lit(pattern), lit(replacement))


def lpad(e: ExprLike, length: int, pad: str = " "):
    from spark_rapids_tpu.exprs.strings import StringLPad

    return StringLPad(_expr(e), lit(length), lit(pad))


def rpad(e: ExprLike, length: int, pad: str = " "):
    from spark_rapids_tpu.exprs.strings import StringRPad

    return StringRPad(_expr(e), lit(length), lit(pad))


def locate(substr: str, e: ExprLike, start: int = 1):
    from spark_rapids_tpu.exprs.strings import StringLocate

    return StringLocate(lit(substr), _expr(e), lit(start))


def substring_index(e: ExprLike, delim: str, count: int):
    from spark_rapids_tpu.exprs.strings import SubstringIndex

    return SubstringIndex(_expr(e), lit(delim), lit(count))


def initcap(e: ExprLike):
    from spark_rapids_tpu.exprs.strings import InitCap

    return InitCap(_expr(e))


def concat_ws(sep: str, *exprs: ExprLike):
    from spark_rapids_tpu.exprs.strings import ConcatWs

    return ConcatWs(lit(sep), *[_expr(e) for e in exprs])


def _forbid_nested_explode(e: Expression) -> None:
    """Explode is only valid at the top level of a select list (Spark
    raises the same analysis error for nested generators)."""
    from spark_rapids_tpu.exprs.collections import Explode

    for c in e.children:
        if isinstance(c, Explode):
            raise ValueError(
                "explode/posexplode must be at the top level of a "
                "select list")
        _forbid_nested_explode(c)


def explode(e: ExprLike):
    from spark_rapids_tpu.exprs.collections import Explode

    return Explode(_expr(e))


def explode_outer(e: ExprLike):
    from spark_rapids_tpu.exprs.collections import Explode

    return Explode(_expr(e), outer=True)


def posexplode(e: ExprLike):
    from spark_rapids_tpu.exprs.collections import Explode

    return Explode(_expr(e), pos=True)


def posexplode_outer(e: ExprLike):
    from spark_rapids_tpu.exprs.collections import Explode

    return Explode(_expr(e), pos=True, outer=True)


def array_size(e: ExprLike):
    from spark_rapids_tpu.exprs.collections import Size

    return Size(_expr(e))


def get_item(e: ExprLike, index: int):
    from spark_rapids_tpu.exprs.collections import GetArrayItem

    return GetArrayItem(_expr(e), lit(index))


def array_contains(e: ExprLike, value):
    from spark_rapids_tpu.exprs.collections import ArrayContains

    return ArrayContains(_expr(e), lit(value))


def _extract_windows(e: Expression, acc: list) -> Expression:
    """Replace every WindowExpression subtree with a reference to a
    generated column the Window node will produce."""
    from spark_rapids_tpu.exprs.window import WindowExpression

    if isinstance(e, WindowExpression):
        name = f"__w{len(acc)}"
        acc.append((e, name))
        return ColumnReference(name)
    kids = e.children
    if not kids:
        return e
    new = [_extract_windows(c, acc) for c in kids]
    if all(n is o for n, o in zip(new, kids)):
        return e
    return e.with_children(new)


class TpuSession:
    """Counterpart of the SparkSession with the plugin installed
    (ref: SQLPlugin.scala — here session == plugin)."""

    def __init__(self, conf: Optional[TpuConf] = None,
                 tenant: str = "default",
                 priority: Optional[int] = None):
        from spark_rapids_tpu.eventlog import maybe_writer
        from spark_rapids_tpu.tools.profiling import (
            HISTORY_CAPACITY,
            QueryHistory,
        )

        self.conf = conf or get_conf()
        #: serving-tier identity: which admission queue this session's
        #: queries join, and with what weighted-fair share (None =
        #: spark.rapids.tpu.serving.defaultPriority).  Inert unless
        #: serving.maxConcurrent > 0 (docs/serving.md).
        self.tenant = tenant
        self.priority = priority
        #: recent TPU-collected queries, input to the profiling tool
        self.history = QueryHistory(
            int(self.conf.get(HISTORY_CAPACITY)))
        #: persistent event-log writer, or None when
        #: spark.rapids.tpu.eventLog.enabled=false — the disabled
        #: path's entire per-query cost is one `is not None` check in
        #: _collect_tpu (docs/eventlog.md)
        self._eventlog = maybe_writer(self.conf)
        self._plan_cache = None  # lazy; most sessions never prepare
        #: in-flight CancelTokens of this session's queries (the
        #: session.cancel() surface; serving/cancel.py) — empty and
        #: untouched while serving.cancellation.enabled is false
        from spark_rapids_tpu.serving.cancel import TokenSet

        self._tokens = TokenSet()

    @property
    def plan_cache(self):
        """This session's prepared-plan cache (LRU of lowered exec
        trees, spark.rapids.tpu.serving.planCache.capacity); created on
        first use so non-serving sessions pay nothing."""
        if self._plan_cache is None:
            from spark_rapids_tpu.serving import PLAN_CACHE_CAPACITY
            from spark_rapids_tpu.serving.plan_cache import PlanCache

            self._plan_cache = PlanCache(
                int(self.conf.get(PLAN_CACHE_CAPACITY)))
        return self._plan_cache

    def prepare(self, df: "DataFrame") -> "PreparedQuery":
        """Prepare a DataFrame template: lower it ONCE into the plan
        cache and return a PreparedQuery whose execute()/
        execute_stream() re-drain the cached lowered plan — repeated
        templates skip parse/plan/tag/lower entirely (docs/serving.md).
        SQL-text templates with :name parameters prepare through
        ``frontends.sql.SqlSession.prepare``."""
        from spark_rapids_tpu.serving.prepared import PreparedQuery

        if not isinstance(df, DataFrame):
            raise TypeError(
                "TpuSession.prepare takes a DataFrame; for SQL text "
                "use frontends.sql.SqlSession.prepare(sql)")
        pq = PreparedQuery(self, df=df)
        pq._resolve(None)  # warm: pay the lowering at prepare time
        return pq

    def cancel(self, query_id: Optional[int] = None,
               reason: str = "cancelled") -> int:
        """Cooperatively cancel this session's in-flight queries (all
        of them, or just ``query_id`` — the id ``_collect_tpu``
        returns and the history/event log record).  The cancelled
        collect/stream raises
        :class:`~spark_rapids_tpu.serving.cancel.QueryCancelled` at
        its next checkpoint and unwinds cleanly (admission slot
        released, pipeline stages joined, exec tree closed); its
        event-log record carries ``engine="cancelled"``.  Returns how
        many queries this call newly cancelled (0 when none matched —
        a query that already finished cannot be cancelled).  Requires
        spark.rapids.tpu.serving.cancellation.enabled (the default);
        queries still waiting in the admission queue have no id yet
        and are only reached by the cancel-all form
        (docs/robustness.md)."""
        return self._tokens.cancel(query_id, reason)

    @property
    def event_log_path(self) -> Optional[str]:
        """Path of this session's event-log file (None when the event
        log is disabled).  Records are appended by the history snapshot
        worker; reading ``session.history.events`` drains it, so the
        file is complete afterwards."""
        return self._eventlog.path if self._eventlog is not None \
            else None

    def export_trace(self, path: str) -> str:
        """Write the process's collected engine trace as Chrome Trace
        Format JSON (viewable in Perfetto / chrome://tracing).  Run
        queries with spark.rapids.tpu.trace.enabled=true first; see
        docs/observability.md for overlaying the device_trace()
        XPlane capture."""
        from spark_rapids_tpu.trace.export import export_chrome_trace

        return export_chrome_trace(path)

    # -- sources -------------------------------------------------------- #

    def create_dataframe(self, data: Union[pa.Table, dict]) -> "DataFrame":
        table = data if isinstance(data, pa.Table) else pa.table(data)
        return DataFrame(L.InMemoryRelation(table), self)

    def read_parquet(self, *paths: str,
                     columns: Optional[Sequence[str]] = None) -> "DataFrame":
        return DataFrame(L.ParquetRelation(list(paths), columns), self)

    def read_orc(self, *paths: str,
                 columns: Optional[Sequence[str]] = None) -> "DataFrame":
        return DataFrame(L.OrcRelation(list(paths), columns), self)

    def read_csv(self, *paths: str,
                 schema: Optional[T.Schema] = None) -> "DataFrame":
        return DataFrame(L.CsvRelation(list(paths), schema), self)

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(L.RangeRel(start, end, step), self)

    def enable_collective_shuffle(self, n_devices: Optional[int] = None,
                                  mesh=None):
        """Activate the tier-2 collective shuffle transport over a device
        mesh: grouped aggregates lower to fused all_to_all SPMD programs
        (ref: the spark.rapids.shuffle.transport.enabled switch +
        UCXShuffleTransport bring-up, re-designed for ICI collectives)."""
        from spark_rapids_tpu.parallel.mesh import make_mesh, set_active_mesh
        from spark_rapids_tpu.shuffle.transport import SHUFFLE_TRANSPORT

        mesh = mesh or make_mesh(n_devices)
        set_active_mesh(mesh)
        self.conf.set(SHUFFLE_TRANSPORT.key, "collective")
        return mesh

    def disable_collective_shuffle(self) -> None:
        from spark_rapids_tpu.parallel.mesh import set_active_mesh
        from spark_rapids_tpu.shuffle.transport import SHUFFLE_TRANSPORT

        set_active_mesh(None)
        self.conf.set(SHUFFLE_TRANSPORT.key, "local")


def _begin_query(session: "TpuSession", conf) -> tuple:
    """Per-query prologue, ONE definition shared by the materialized
    (`_collect_tpu_admitted`) and streaming (`_stream_tpu`) collect
    paths so they can never drift: align the process-global subsystems
    with this session's conf — the tracer (spans carry this query),
    the fault registry (conf-armed chaos schedules take effect per
    query), the device semaphore (per-session concurrentTpuTasks
    changes resize the live permit pool, which also re-sizes serving
    admission), the device-utilization ledger and the telemetry
    sampler (which also attaches this session's event-log writer for
    periodic `telemetry` records) and the live ops plane (one conf
    read when disabled; enabled, the query registers in-flight under
    /queries with its tenant and cancel token) — then allocate the query id, snapshot the event-log
    counters (the per-query event-log check: `elog` is None when
    disabled — no writer thread, nothing on the batch loop) and stamp
    the clocks.

    Returns (qid, elog, pre, conf_hash, start_ts, t0, t0_ns)."""
    import time as _time

    from spark_rapids_tpu import obs as _obs
    from spark_rapids_tpu import trace as _trace
    from spark_rapids_tpu.eventlog import conf_fingerprint
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.robustness import faults as _faults
    from spark_rapids_tpu.robustness import lock_tracker as _locks
    from spark_rapids_tpu.trace import ledger as _ledger
    from spark_rapids_tpu.trace import telemetry as _telemetry

    _trace.sync_conf(conf)
    _faults.sync_conf(conf)
    _locks.sync_conf(conf)
    TpuSemaphore.sync_conf(conf)
    _ledger.sync_conf(conf)
    _telemetry.sync_conf(conf, writer=session._eventlog)
    _obs.sync_conf(conf, writer=session._eventlog)
    qid = session.history.allocate_id()
    conf_hash = conf_fingerprint(conf)
    if _obs.REGISTRY.enabled:
        # register in the live ops plane (/queries) with whatever is
        # known at the prologue; plan/plan_hash arrive via annotate()
        # once planning renders them
        from spark_rapids_tpu.serving import cancel as _cancel

        _obs.REGISTRY.begin(qid, tenant=session.tenant,
                            token=_cancel.current_token(),
                            conf_hash=conf_hash)
    elog = session._eventlog
    pre = elog.query_begin() if elog is not None else None
    return (qid, elog, pre, conf_hash, _time.time(),
            _time.perf_counter(), _time.perf_counter_ns())


def _record_query(session: "TpuSession", explain_text: str, exec_tree,
                  qid: int, conf_hash: str, start_ts: float, t0: float,
                  t0_ns: int, on_event, baseline=None,
                  engine: str = "tpu") -> None:
    """Per-query epilogue shared by the collect paths: the history
    record with the full clock set (the event-log hook rides
    `on_event` onto the snapshot worker).  `baseline` — a settled
    pre-drain metric snapshot — makes the record report THIS
    execution's deltas on a re-drained cached exec tree (the metrics
    on the long-lived tree itself accumulate); `exec_tree` may be
    None for executions that ran no operators at all (a result-cache
    hit).  With the ops plane on, the query deregisters from the live
    registry here and its (tenant, wall, admission wait) observation
    feeds the SLO watchdog's rolling windows — `engine` labels the
    outcome ("tpu", "cancelled", "deadline_exceeded", ...)."""
    import time as _time

    from spark_rapids_tpu import obs as _obs

    # deregister BEFORE the history record: the serving context (the
    # admission wait the watchdog windows) is still live here, and the
    # registry must never show a query whose record already landed
    _obs.REGISTRY.finish(qid, engine=engine)
    session.history.record(
        explain_text, exec_tree, _time.perf_counter() - t0,
        query_id=qid, start_ts=start_ts, end_ts=_time.time(),
        start_ns=t0_ns, end_ns=_time.perf_counter_ns(),
        conf_hash=conf_hash, on_event=on_event, baseline=baseline)


def _prune_scan_columns(plan, exprs):
    """Column pruning into file scans (Spark's ColumnPruning rule, at
    the logical-build seam where references are still by NAME): a
    select directly above an unpruned file relation rebuilds the
    relation to read only the referenced columns — fewer bytes
    decoded, and rebase/fastpar checks see the true read schema."""
    import copy as _copy

    from spark_rapids_tpu.plan.logical import OrcRelation, ParquetRelation

    if not isinstance(plan, (ParquetRelation, OrcRelation)) \
            or plan.columns is not None:
        return plan
    refs: set = set()

    def walk(e) -> bool:
        """Collect referenced names; False = unprunable reference."""
        from spark_rapids_tpu.exprs.base import BoundReference
        from spark_rapids_tpu.exprs.nondeterministic import InputFileName
        from spark_rapids_tpu.exprs.window import WindowExpression

        if isinstance(e, BoundReference):
            return False  # pre-bound ordinals would shift
        if isinstance(e, InputFileName):
            return True  # rewritten later; reads no file column
        if isinstance(e, ColumnReference):
            refs.add(e.col_name)
            return True
        return all(walk(c) for c in e.children)

    if not all(walk(e) for e in exprs):
        return plan
    names = [f.name for f in plan.schema.fields if f.name in refs]
    if not names or len(names) == len(plan.schema.fields):
        # nothing referenced (pure generated columns) or nothing to
        # prune: keep the full scan — the zero-column count-only path
        # belongs to aggregates, not projections
        return plan
    # COPY the relation instead of re-running __init__: the ctor would
    # re-expand paths (losing Hive partition discovery on bare file
    # lists) and re-read a footer
    part_names = {f.name for f in plan.partition_fields}
    by_name = {f.name: f for f in plan.schema.fields}
    rel2 = _copy.copy(plan)
    rel2.columns = [n for n in names if n not in part_names]
    rel2.partition_fields = [f for f in plan.partition_fields
                             if f.name in refs]
    rel2._schema = T.Schema(
        [by_name[n] for n in names if n not in part_names]
        + rel2.partition_fields)
    return rel2


class _CoGrouped:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        if isinstance(schema, pa.Schema):
            schema = schema_from_arrow(schema)
        return DataFrame(
            L.CoGroupedPandas(
                self._left._key_names(), self._right._key_names(),
                fn, schema, self._left._df._plan,
                self._right._df._plan),
            self._left._df._session)


class GroupedData:
    """Grouped frame; `grouping_sets` (a list of included-key-name sets)
    switches to the Expand-based grouping-set rewrite that Spark's
    analyzer performs for rollup/cube (ref: GpuExpandExec.scala:67)."""

    def __init__(self, df: "DataFrame", keys: list[Expression],
                 grouping_sets: Optional[list[frozenset]] = None):
        self._df = df
        self._keys = keys
        self._sets = grouping_sets
        self._pivot: Optional[tuple] = None

    def pivot(self, pivot_col: ExprLike,
              values: Sequence) -> "GroupedData":
        """pyspark-shaped pivot with an EXPLICIT value list (ref:
        GpuPivotFirst; Spark's implicit-distinct-values mode needs a
        pre-query and is not supported): each aggregate expands into
        one masked aggregate per pivot value, named `{value}` for a
        single aggregate or `{value}_{name}` otherwise."""
        if self._sets is not None:
            raise ValueError("pivot over rollup/cube is not supported")
        self._pivot = (_expr(pivot_col), list(values))
        return self

    def _named(self, aggs) -> list[NamedAgg]:
        named = []
        for i, a in enumerate(aggs):
            if isinstance(a, NamedAgg):
                named.append(a)
            elif isinstance(a, tuple):
                fn, name = a
                named.append(NamedAgg(fn, name))
            else:
                named.append(NamedAgg(a, f"{a.name}_{i}"))
        return named

    def agg(self, *aggs: AggLike) -> "DataFrame":
        from spark_rapids_tpu.exprs.aggregates import CountDistinct

        named = self._named(aggs)
        if self._pivot is not None:
            named = self._expand_pivot(named)
        named = [na2 for na in named
                 for na2 in (na.fn.expand(na.out_name)
                             if hasattr(na.fn, "expand") else (na,))]
        if any(isinstance(na.fn, CountDistinct) for na in named):
            return self._agg_distinct(named)
        if self._sets is not None:
            return self._agg_grouping_sets(named)
        return DataFrame(
            L.Aggregate(self._keys, named, self._df._plan),
            self._df._session)

    def _key_names(self) -> list[str]:
        names = []
        for k in self._keys:
            if isinstance(k, ColumnReference):
                names.append(k.col_name)
            elif hasattr(k, "out_name"):
                names.append(k.out_name)
            else:
                raise ValueError(
                    "grouped pandas UDFs need plain column keys")
        return names

    def cogroup(self, other: "GroupedData") -> "_CoGrouped":
        """pyspark cogroup: pair with another grouped frame for
        applyInPandas over co-grouped frames."""
        return _CoGrouped(self, other)

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """pyspark applyInPandas (ref: GpuFlatMapGroupsInPandasExec):
        fn(pd.DataFrame per group) -> pd.DataFrame with `schema`."""
        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        if isinstance(schema, pa.Schema):
            schema = schema_from_arrow(schema)
        return DataFrame(
            L.GroupedPandas(self._key_names(), fn, schema, "flatmap",
                            self._df._plan),
            self._df._session)

    def agg_in_pandas(self, *aggs) -> "DataFrame":
        """Pandas UDAFs (ref: GpuAggregateInPandasExec): each agg is
        (out_name, fn(pd.Series) -> scalar, input_col); output =
        group keys + one DOUBLE column per agg."""
        from spark_rapids_tpu import types as T

        child_schema = self._df._plan.schema
        key_names = self._key_names()
        fields = [child_schema.field(k) for k in key_names]
        fields += [T.Field(name, T.DOUBLE, True)
                   for name, _fn, _c in aggs]
        return DataFrame(
            L.GroupedPandas(key_names, list(aggs), T.Schema(fields),
                            "agg", self._df._plan),
            self._df._session)

    def transform_in_pandas(self, *fns) -> "DataFrame":
        """Pandas window UDFs over unbounded frames (ref:
        GpuWindowInPandasExecBase): each entry is (out_name,
        fn(pd.Series) -> scalar, input_col); the scalar broadcasts to
        every row of its group, appended after the child's columns."""
        from spark_rapids_tpu import types as T

        child_schema = self._df._plan.schema
        fields = list(child_schema.fields) + [
            T.Field(name, T.DOUBLE, True) for name, _fn, _c in fns]
        return DataFrame(
            L.GroupedPandas(self._key_names(), list(fns),
                            T.Schema(fields), "window",
                            self._df._plan),
            self._df._session)

    def _expand_pivot(self, named: list[NamedAgg]) -> list[NamedAgg]:
        from spark_rapids_tpu.exprs.aggregates import expand_pivot_aggs

        pcol, values = self._pivot
        return expand_pivot_aggs(pcol, values, named,
                                 single=len(named) == 1)

    def _agg_distinct(self, named: list[NamedAgg]) -> "DataFrame":
        """count(DISTINCT x) as a two-level aggregate: group by
        (keys, x) to dedupe, then count x per key group (the
        single-distinct specialization of Spark's
        RewriteDistinctAggregates)."""
        from spark_rapids_tpu.exprs.aggregates import Count, CountDistinct
        from spark_rapids_tpu.execs.jit_cache import expr_key

        if self._sets is not None:
            raise ValueError(
                "count_distinct over rollup/cube is not supported yet")
        dist = [na for na in named if isinstance(na.fn, CountDistinct)]
        others = [na for na in named if not isinstance(na.fn, CountDistinct)]
        if others:
            raise ValueError(
                "mixing count_distinct with other aggregates is not "
                "supported yet")
        key0 = expr_key(dist[0].fn.child)
        if any(expr_key(na.fn.child) != key0 for na in dist[1:]):
            raise ValueError(
                "multiple count_distinct over different expressions are "
                "not supported yet")
        inner_x = dist[0].fn.child.alias("__dist")
        inner = L.Aggregate(self._keys + [inner_x], [], self._df._plan)
        key_names = [f.name for f in inner.schema.fields[:len(self._keys)]]
        outer = L.Aggregate(
            [ColumnReference(n) for n in key_names],
            [NamedAgg(Count(ColumnReference("__dist")), na.out_name)
             for na in dist],
            inner)
        return DataFrame(outer, self._df._session)

    def _agg_grouping_sets(self, named: list[NamedAgg]) -> "DataFrame":
        from spark_rapids_tpu.exprs import base as B

        child = self._df._plan
        key_names = []
        for k in self._keys:
            if not isinstance(k, ColumnReference):
                raise ValueError(
                    "rollup/cube keys must be plain columns")
            key_names.append(k.col_name)
        names = [f.name for f in child.schema.fields] + ["__gid"]
        projections = []
        for gid, included in enumerate(self._sets):
            proj: list[Expression] = []
            for f in child.schema.fields:
                if f.name in key_names and f.name not in included:
                    proj.append(B.Literal(None, f.dtype))
                else:
                    proj.append(ColumnReference(f.name))
            proj.append(B.Literal.of(gid))
            projections.append(proj)
        expand = L.Expand(projections, names, child)
        agg = L.Aggregate(
            list(self._keys) + [ColumnReference("__gid")], named, expand)
        out_names = key_names + [na.out_name for na in named]
        return DataFrame(
            L.Project([ColumnReference(n) for n in out_names], agg),
            self._df._session)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session: TpuSession):
        self._plan = plan
        self._session = session

    @property
    def schema(self) -> T.Schema:
        return self._plan.schema

    # -- transformations ------------------------------------------------ #

    def select(self, *exprs: ExprLike) -> "DataFrame":
        """Projection; window expressions anywhere in the select list are
        extracted into Window nodes under the projection (one node per
        (partition_by, order_by) group), mirroring Spark's
        ExtractWindowExpressions analysis rule."""
        from spark_rapids_tpu.exprs.window import WindowExpression

        from spark_rapids_tpu.exprs.base import Alias
        from spark_rapids_tpu.exprs.collections import Explode

        exprs_ = [_expr(e) for e in exprs]
        acc: list[tuple[WindowExpression, str]] = []
        rewritten = [_extract_windows(e, acc) for e in exprs_]
        # prune on the ORIGINAL exprs: window/generator extraction
        # introduces synthetic refs that hide the real columns
        plan = _prune_scan_columns(self._plan, exprs_)

        # generator extraction (ref: Spark's ExtractGenerator rule):
        # a top-level explode/posexplode becomes a Generate node under
        # the projection
        gens = [(i, e) for i, e in enumerate(rewritten)
                if isinstance(e, Explode)
                or (isinstance(e, Alias) and isinstance(e.child, Explode))]
        if gens:
            if len(gens) > 1:
                raise ValueError("only one explode per select")
            i, e = gens[0]
            alias_name = e.out_name if isinstance(e, Alias) else None
            gen = e.child if isinstance(e, Alias) else e
            if gen.pos and alias_name is not None:
                raise ValueError(
                    "posexplode yields two columns (pos, col); alias "
                    "them with a following select")
            out_name = alias_name or "col"
            plan = L.Generate(gen, plan, out_name=out_name)
            repl: list[Expression] = []
            if gen.pos:
                repl.append(ColumnReference("pos"))
            repl.append(ColumnReference(out_name))
            rewritten[i:i + 1] = repl
        for e in rewritten:
            _forbid_nested_explode(e)

        if acc:
            from spark_rapids_tpu.execs.jit_cache import exprs_key

            from spark_rapids_tpu.execs.jit_cache import expr_key

            groups: dict[tuple, list] = {}
            for we, name in acc:
                # structural keys for BOTH components: display repr is
                # name-only and would merge distinct order-by exprs that
                # share a name (or split structurally identical ones)
                gk = (exprs_key(we.spec.partition_by),
                      tuple((expr_key(k.expr), k.descending, k.nulls_last)
                            for k in we.spec.order_by))
                groups.setdefault(gk, []).append((we, name))
            for group in groups.values():
                plan = L.Window(group, plan)
        return DataFrame(L.Project(rewritten, plan), self._session)

    def where(self, cond: Expression) -> "DataFrame":
        return DataFrame(L.Filter(cond, self._plan), self._session)

    filter = where

    def with_column(self, name: str, e: Expression) -> "DataFrame":
        exprs: list[Expression] = [
            ColumnReference(f.name) for f in self.schema.fields
            if f.name != name]
        exprs.append(e.alias(name))
        return self.select(*exprs)

    def group_by(self, *keys: ExprLike) -> GroupedData:
        return GroupedData(self, [_expr(k) for k in keys])

    def rollup(self, *keys: str) -> GroupedData:
        """GROUP BY ROLLUP: hierarchical grouping sets
        (a,b,c) -> {(a,b,c), (a,b), (a), ()}."""
        sets = [frozenset(keys[:i]) for i in range(len(keys), -1, -1)]
        return GroupedData(self, [_expr(k) for k in keys],
                           grouping_sets=sets)

    def cube(self, *keys: str) -> GroupedData:
        """GROUP BY CUBE: all subsets of the grouping keys."""
        import itertools

        sets = [frozenset(c)
                for r in range(len(keys), -1, -1)
                for c in itertools.combinations(keys, r)]
        return GroupedData(self, [_expr(k) for k in keys],
                           grouping_sets=sets)

    def grouping_sets(self, sets: Sequence[Sequence[str]],
                      keys: Sequence[str]) -> GroupedData:
        return GroupedData(self, [_expr(k) for k in keys],
                           grouping_sets=[frozenset(s) for s in sets])

    def agg(self, *aggs: AggLike) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on: Union[str, Sequence[str], None]
             = None, how: str = "inner",
             left_on: Optional[Sequence[ExprLike]] = None,
             right_on: Optional[Sequence[ExprLike]] = None,
             condition: Optional[Expression] = None,
             null_safe: Union[bool, Sequence[bool]] = False
             ) -> "DataFrame":
        """`null_safe` says which key pairs compare with `<=>` (NULL
        equals NULL and nothing else) where the others compare with
        `=` (a NULL key matches nothing): one bool for all of them, or
        one a pair."""
        if on is not None:
            names = [on] if isinstance(on, str) else list(on)
            lk = [ColumnReference(n) for n in names]
            rk = [ColumnReference(n) for n in names]
        else:
            lk = [_expr(e) for e in (left_on or [])]
            rk = [_expr(e) for e in (right_on or [])]
        return DataFrame(
            L.Join(self._plan, other._plan, lk, rk, how, condition,
                   null_safe=null_safe),
            self._session)

    def _widened_members(self, other: "DataFrame", what: str) -> list:
        """Both members of a set operation, columns matched by position
        and coerced to a common type (Spark's WidenSetOperationTypes),
        or analysis fails."""
        lf, rf = self.schema.fields, other.schema.fields
        if len(lf) != len(rf):
            raise AnalysisException(
                f"{what} members must have the same column count "
                f"({len(lf)} vs {len(rf)})")
        widened: list[Optional[T.DataType]] = []
        for i, (a, b) in enumerate(zip(lf, rf)):
            if a.dtype == b.dtype:
                widened.append(None)
                continue
            ct = T.common_type(a.dtype, b.dtype)
            if ct is None:
                raise AnalysisException(
                    f"{what} member column {i + 1} ({a.name!r}) has "
                    f"incompatible types {a.dtype.name} and "
                    f"{b.dtype.name}")
            widened.append(ct)
        return [_coerce_union_member(self._plan, widened),
                _coerce_union_member(other._plan, widened)]

    def union(self, other: "DataFrame") -> "DataFrame":
        """Spark's WidenSetOperationTypes, enforced at the engine layer
        (every frontend funnels through here): members are coerced
        per-column to a common type, or analysis fails.  Without this,
        TpuUnionExec re-tags every member batch with the first member's
        schema, silently truncating e.g. DOUBLE data shipped under an
        INT tag.  The lint dtype-flow checker (DT001) remains the
        backstop for hand-built L.Union plans that bypass this method."""
        return DataFrame(L.Union(self._widened_members(other, "UNION")),
                         self._session)

    def distinct(self) -> "DataFrame":
        """SELECT DISTINCT *: the rows without their duplicates (two
        rows with a NULL in the same column and equal elsewhere are
        duplicates)."""
        return DataFrame(L.distinct(self._plan), self._session)

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """INTERSECT DISTINCT: the distinct rows of this frame that
        `other` holds too, columns matched by position and widened as
        `union` widens them, NULL equal to NULL.  Lowered as Spark
        lowers it: a distinct over a `left_semi` join whose every key
        is `<=>`."""
        return DataFrame(
            L.set_operation(*self._widened_members(other, "INTERSECT"),
                            "left_semi"), self._session)

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """EXCEPT DISTINCT (pyspark's `subtract`): the distinct rows of
        this frame that `other` does not hold, NULL equal to NULL: a
        distinct over a `left_anti` join whose every key is `<=>`."""
        return DataFrame(
            L.set_operation(*self._widened_members(other, "EXCEPT"),
                            "left_anti"), self._session)

    def order_by(self, *keys, desc: bool = False) -> "DataFrame":
        sks = []
        for k in keys:
            if isinstance(k, SortKey):
                sks.append(k)
            else:
                sks.append(SortKey(_expr(k), descending=desc,
                                   nulls_last=desc))
        return DataFrame(L.Sort(sks, self._plan), self._session)

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(n, self._plan), self._session)

    def cache(self) -> "DataFrame":
        """Mark this frame for materialize-once re-serving (Spark
        df.cache; ref: InMemoryTableScanExec, SURVEY Appendix A).  The
        first TPU collect that fully drains the subtree stores its
        batches in the spillable BufferStore; later collects (of this
        frame or frames derived AFTER cache()) skip the subtree."""
        if not isinstance(self._plan, L.Cached):
            self._plan = L.Cached(self._plan)
        return self

    persist = cache

    def unpersist(self) -> "DataFrame":
        """Drop the cached batches (store entries close; accounting
        returns to zero)."""
        if isinstance(self._plan, L.Cached):
            self._plan.slot.clear()
        return self

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """pyspark mapInPandas (ref: GpuMapInPandasExec): fn over
        pd.DataFrame batches in the isolated python worker pool."""
        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        if isinstance(schema, pa.Schema):
            eng_schema = schema_from_arrow(schema)
        else:
            eng_schema = schema
        node = L.MapInArrow(fn, eng_schema, self._plan)
        node.pandas = True
        return DataFrame(node, self._session)

    def map_in_arrow(self, fn, schema) -> "DataFrame":
        """Apply `fn(pa.Table) -> pa.Table` batch-wise in a
        process-isolated python worker pool (the mapInArrow analog;
        ref: GpuArrowEvalPythonExec + python/rapids/worker.py).
        `schema` (pyarrow or engine Schema) is the declared output
        contract; `fn` must be picklable (module-level)."""
        import pyarrow as _pa

        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        if isinstance(schema, _pa.Schema):
            schema = schema_from_arrow(schema)
        return DataFrame(L.MapInArrow(fn, schema, self._plan),
                         self._session)

    # -- writes ---------------------------------------------------------- #

    @property
    def write(self) -> "DataFrameWriter":
        """Spark-shaped writer: df.write.mode('overwrite')
        .partition_by('k').parquet(path)."""
        return DataFrameWriter(self)

    def write_parquet(self, path: str, mode: str = "error",
                      partition_by: Sequence[str] = ()):
        return self.write.mode(mode).partition_by(
            *partition_by).parquet(path)

    def write_csv(self, path: str, mode: str = "error",
                  partition_by: Sequence[str] = ()):
        return self.write.mode(mode).partition_by(*partition_by).csv(path)

    def write_orc(self, path: str, mode: str = "error",
                  partition_by: Sequence[str] = ()):
        return self.write.mode(mode).partition_by(*partition_by).orc(path)

    # -- actions --------------------------------------------------------- #

    def to_device_arrays(self) -> list[dict]:
        """Execute on TPU and hand back the DEVICE-RESIDENT results as
        jax arrays — no D2H round trip (the ColumnarRdd analog, ref:
        sql/rapids/execution/InternalColumnarRddConverter.scala /
        ColumnarRdd.scala exposing GPU Tables to ML libraries
        zero-copy).  Returns one dict per batch:
        {column_name: jax.Array (physical values),
         column_name + "__valid": jax.Array bool} plus "__num_rows";
        a jax model consumes the SQL output straight from HBM.

        Nested (struct/map/list) output columns are not exposed this
        way — project to flat columns first."""
        from spark_rapids_tpu.columnar.column import Column

        conf = self._session.conf
        exec_, _meta = plan_query(self._plan, conf)
        out = []
        for b in exec_.execute():
            d: dict = {}
            for f, c in zip(b.schema.fields, b.columns):
                if not isinstance(c, Column):
                    raise TypeError(
                        f"column {f.name!r} ({f.dtype.name}) has no "
                        "flat device array form — project it first")
                d[f.name] = c.data
                d[f.name + "__valid"] = c.validity
            d["__num_rows"] = b.num_rows
            out.append(d)
        return out

    def collect(self, engine: Optional[str] = None) -> pa.Table:
        """engine: 'tpu' (plan rewrite + fallback), 'cpu' (reference
        engine), default from spark.rapids.tpu.sql.enabled."""
        conf = self._session.conf
        if engine is None:
            engine = "tpu" if conf.get(SQL_ENABLED) else "cpu"
        if engine == "cpu":
            from spark_rapids_tpu.cpu.engine import execute_cpu

            return execute_cpu(self._plan)
        return self._collect_tpu()[0]

    def _collect_tpu(self, exec_=None, meta=None, drain_lock=None,
                     serving_facts=None,
                     token_sink=None) -> tuple[pa.Table, int]:
        """TPU-engine collect; returns (result, query_id) so callers
        that need the history/trace correlation key (EXPLAIN ANALYZE)
        can find THEIR event instead of trusting events[-1] under
        concurrent collects.

        With a prebuilt (exec_, meta) — the prepared-plan-cache hit
        path (serving/prepared.py) — planning is skipped entirely: no
        query.plan/tag/lower spans, the cached lowered tree is drained
        directly.  Either way the query passes through the serving
        tier's admission control first (a single conf read when
        serving.maxConcurrent is 0, the default).

        `drain_lock` (the cache entry's re-drain lock) is acquired
        INSIDE admission: taking it before would deadlock when an
        admitted query nested-executes the template a waiting thread
        already locked.  `serving_facts` (the plan-cache verdict,
        plus the binding-independent `admission_group` template key
        that admission-aware batching coalesces on) is deposited into
        the serving context inside the query's admission scope, so a
        nested query's facts land in ITS record and never pollute the
        outer query's.

        With cross-tenant sharing on (serving.sharing.enabled), the
        process-wide result cache is consulted INSIDE admission and
        before the drain lock: a hit returns the cached result with
        zero plan/lower/compile/scan work, and a completed miss
        offers its result back (docs/work_sharing.md).  Disabled =
        one conf read.

        Cancellation (serving/cancel.py): the query carries a
        CancelToken (one conf read + None when
        serving.cancellation.enabled is false) honoring
        session.cancel(), the serving deadline and the tenant
        breaker; a cancelled query unwinds through the normal
        teardown paths, is recorded with engine="cancelled"/
        "deadline_exceeded", and raises QueryCancelled.
        ``token_sink`` (a cancel.TokenSet) additionally tracks the
        token for a narrower cancel scope (PreparedQuery.cancel)."""
        import contextlib

        conf = self._session.conf
        from spark_rapids_tpu.serving import update_serving_context
        from spark_rapids_tpu.serving import cancel as _cancel
        from spark_rapids_tpu.serving.scheduler import admission

        facts = dict(serving_facts) if serving_facts else None
        group = facts.pop("admission_group", None) if facts else None
        tok = _cancel.begin(conf, tenant=self._session.tenant)
        self._session._tokens.add(tok)
        if token_sink is not None:
            token_sink.add(tok)
        try:
            with (_cancel.attach_token(tok) if tok is not None
                  else contextlib.nullcontext()), \
                    admission(conf, tenant=self._session.tenant,
                              priority=self._session.priority,
                              group=group, token=tok):
                if facts:
                    update_serving_context(**facts)
                from spark_rapids_tpu.serving import work_share as _ws

                sharing = _ws.enabled(conf)
                if sharing:
                    cached, verdict = _ws.lookup_result(self._plan,
                                                        conf)
                    if verdict is not None:
                        update_serving_context(result_cache=verdict)
                    if cached is not None:
                        return self._result_cache_hit(cached, meta)
                with drain_lock if drain_lock is not None \
                        else contextlib.nullcontext():
                    out, qid = self._collect_tpu_admitted(exec_, meta)
                if sharing:
                    _ws.offer_result(self._plan, conf, out)
                return out, qid
        except _cancel.QueryCancelled as e:
            self._record_cancelled(e, facts)
            raise
        finally:
            self._session._tokens.discard(tok)
            if token_sink is not None:
                token_sink.discard(tok)
            _cancel.end(tok)

    def _record_cancelled(self, e, facts=None) -> None:
        """Cancellation epilogue: count the outcome once, and when the
        query unwound BEFORE its execution prologue ran (deadline
        expired in the admission queue), emit the per-query record
        HERE with ``engine=e.reason`` and a zero counter delta — a
        cancelled query is an observable outcome, not a gap.
        Mid-flight cancels were already recorded (with their partial
        metrics) by the admitted/stream paths.

        ``facts`` are the caller's undeposited serving facts: the
        connect front door's wire section (peer, wire_bytes,
        translate_ms) normally lands in the serving context INSIDE
        admission, AFTER admit() succeeds — a query shed in the queue
        unwinds before that deposit, so without re-depositing here its
        deadline_exceeded record would silently drop the ``connect``
        section (the fleet's shed-by-peer attribution)."""
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.serving import (
            clear_serving_context,
            current_serving_context,
            update_serving_context,
        )
        from spark_rapids_tpu.serving import cancel as _cancel

        _cancel.tick_outcome(e.reason)
        if e.recorded:
            return
        conf = self._session.conf
        qid, elog, pre, conf_hash, start_ts, t0, t0_ns = \
            _begin_query(self._session, conf)
        if e.query_id is None:
            e.query_id = qid
        expl = (f"CancelledBeforeExecution [{e.reason}: shed in the "
                f"admission queue; no operator ran]\n")
        deposited = False
        prev_ctx = None
        if facts and facts.get("connect"):
            # admission never deposited the wire facts (shed in the
            # queue): deposit them NOW so query_end's serving-context
            # capture — which runs inside _on_event() below, on this
            # thread — folds the connect section into the record.
            # Save/restore around it (the nested-admission idiom): an
            # outer query's restored context must survive this record.
            prev_ctx = current_serving_context()
            update_serving_context(connect=facts["connect"])
            deposited = True

        def _on_event():
            if elog is None:
                return None
            post = elog.query_end(pre)
            return lambda ev: elog.log_query(ev, post, expl, e.reason)

        try:
            with _trace.trace_context(query_id=qid):
                if _trace.TRACER.enabled:
                    _trace.event("cancel.shed", query_id=qid,
                                 reason=e.reason)
            _record_query(self._session, expl, None, qid, conf_hash,
                          start_ts, t0, t0_ns, _on_event(),
                          engine=e.reason)
        finally:
            if deposited:
                clear_serving_context()
                if prev_ctx:
                    update_serving_context(**prev_ctx)
        e.recorded = True

    def _result_cache_hit(self, out: pa.Table,
                          meta) -> tuple[pa.Table, int]:
        """Serve a collect from the cross-tenant result cache: no exec
        tree ever exists, but the query still runs the full history/
        event-log lifecycle (the record carries the real digest and
        rows, the serving context's result_cache verdict, and a
        near-zero counter delta) so fleet tooling sees served traffic,
        not a gap."""
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.eventlog import table_digest

        conf = self._session.conf
        qid, elog, pre, conf_hash, start_ts, t0, t0_ns = \
            _begin_query(self._session, conf)
        expl = meta.explain() if meta is not None else \
            "ResultCacheHit [plan not lowered — served from the " \
            "cross-tenant result cache]\n"

        def _on_event():
            if elog is None:
                return None
            post = elog.query_end(pre)
            return lambda ev: elog.log_query(
                ev, post, expl, "tpu",
                result_digest=table_digest(out), rows=out.num_rows)

        with _trace.trace_context(query_id=qid):
            if _trace.TRACER.enabled:
                _trace.event("serve.result_cache_hit", query_id=qid,
                             rows=out.num_rows)
        _record_query(self._session, expl, None, qid, conf_hash,
                      start_ts, t0, t0_ns, _on_event())
        return out, qid

    def _collect_tpu_admitted(self, exec_=None,
                              meta=None) -> tuple[pa.Table, int]:
        conf = self._session.conf

        from spark_rapids_tpu import obs as _obs
        from spark_rapids_tpu.eventlog import table_digest

        qid, elog, pre, conf_hash, start_ts, t0, t0_ns = \
            _begin_query(self._session, conf)
        from spark_rapids_tpu.serving import cancel as _cancel

        tok = _cancel.current_token()
        if tok is not None:
            # the id session.cancel(query_id) targets from now on
            tok.query_id = qid
        baseline = None
        if exec_ is not None:
            # re-draining a CACHED exec tree (the prepared-plan hit
            # path): its metrics accumulate across executions, so
            # snapshot the settled pre-drain totals — the history/
            # event-log record then reports THIS execution's deltas,
            # not the running total (docs/serving.md)
            from spark_rapids_tpu.tools.profiling import snapshot_exec

            baseline = snapshot_exec(exec_)

        def _on_event(render_plan, engine: str, result):
            """History-worker hook appending the event-log record once
            metrics have settled (None when the log is disabled).
            Counter/pipeline/fault capture happens HERE, at query end
            on the calling thread — a later reset/disarm (bench
            between queries, tests tearing down chaos) must not erase
            this query's attribution.  The result digest and the
            annotated-plan render are deferred to the worker: both
            read immutable state, and neither belongs on collect()'s
            critical path.  `result` is None for unwound (cancelled)
            queries: no digest, no rows — the record still lands."""
            if elog is None:
                return None
            post = elog.query_end(pre)
            return lambda ev: elog.log_query(
                ev, post, render_plan(), engine,
                result_digest=table_digest(result)
                if result is not None else None,
                rows=result.num_rows if result is not None else None)

        try:
            return self._collect_tpu_admitted_registered(
                exec_, meta, conf, qid, elog, pre, conf_hash,
                start_ts, t0, t0_ns, baseline, _on_event)
        finally:
            # safety net for paths that never reach an epilogue (a
            # crash that is not CPU-degradable): the live registry
            # must not keep a dead query in flight
            _obs.REGISTRY.drop(qid)

    def _collect_tpu_admitted_registered(
            self, exec_, meta, conf, qid, elog, pre, conf_hash,
            start_ts, t0, t0_ns, baseline, _on_event):
        from spark_rapids_tpu import obs as _obs
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.eventlog import render_plan_report
        from spark_rapids_tpu.serving import cancel as _cancel

        with _trace.trace_context(query_id=qid):
            if exec_ is None:
                with _trace.span("query.plan"):
                    exec_, meta = plan_query(self._plan, conf)
            if _obs.REGISTRY.enabled:
                from spark_rapids_tpu.eventlog import plan_fingerprint

                ptext = meta.explain()
                _obs.REGISTRY.annotate(
                    qid, plan=ptext,
                    plan_hash=plan_fingerprint(ptext))
            try:
                with _trace.span("query.execute"):
                    out = collect_exec(exec_)
            except _cancel.QueryCancelled as e:
                # cooperative unwind mid-flight: the drain loop's
                # close-on-raise already tore the tree down (pipeline
                # stages joined, shuffle blocks dropped); record the
                # query as an observable cancelled outcome with its
                # partial metric deltas, then let it propagate
                if e.query_id is None:
                    e.query_id = qid
                expl = (meta.explain()
                        + f"\n[query unwound: {e.reason}]")
                _record_query(
                    self._session, expl, exec_, qid, conf_hash,
                    start_ts, t0, t0_ns,
                    _on_event(lambda: expl, e.reason, None),
                    baseline=baseline, engine=e.reason)
                e.recorded = True
                raise
            except BaseException as e:
                from spark_rapids_tpu.execs.retry import (
                    should_cpu_fallback,
                )

                if not should_cpu_fallback(e):
                    raise
                # device lost / exhausted after task retries: degrade
                # the query to the CPU engine (executor-blacklisting
                # analog) — the LAST rung of the escalation ladder
                import warnings

                from spark_rapids_tpu.cpu.engine import execute_cpu
                from spark_rapids_tpu.execs import retry as _retry

                warnings.warn(
                    f"TPU execution failed with a device error ({e}); "
                    "re-running this query on the CPU engine",
                    RuntimeWarning, stacklevel=2)
                out = execute_cpu(self._plan)
                _retry.note_cpu_fallback(e)
                # degraded queries are the ones operators most need to
                # see in the history (and the event log: the health
                # checker's CPU-fallback rule keys off this record)
                expl = (meta.explain() + "\n[degraded to CPU engine: "
                        f"{type(e).__name__}]")
                _record_query(
                    self._session, expl, exec_, qid, conf_hash,
                    start_ts, t0, t0_ns,
                    _on_event(lambda: expl, "cpu_fallback", out),
                    baseline=baseline, engine="cpu_fallback")
                return out, qid
            _record_query(
                self._session, meta.explain(), exec_, qid, conf_hash,
                start_ts, t0, t0_ns,
                _on_event(lambda: render_plan_report(exec_, meta),
                          "tpu", out),
                baseline=baseline)
        return out, qid

    def _stream_tpu(self, exec_=None, meta=None,
                    batch_rows: Optional[int] = None,
                    drain_lock=None, serving_facts=None,
                    token_sink=None):
        """Streaming TPU collect (serving tier): yield the result as
        Arrow record batches INCREMENTALLY off the pipelined fetch path
        (planner.stream_exec) instead of one materialized table, with
        backpressure from the prefetch stage's bounded queue.  Admitted,
        traced and history/event-log-recorded like _collect_tpu (the
        record carries rows but no result digest — the batches were
        never held together); no CPU-degrade ladder mid-stream: a
        device failure raises to the consumer, who may re-run via
        collect().  The admission slot is held until the stream drains
        or the generator is closed."""
        import contextlib
        import time as _time

        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.plan.planner import stream_exec
        from spark_rapids_tpu.serving import update_serving_context
        from spark_rapids_tpu.serving import cancel as _cancel
        from spark_rapids_tpu.serving.scheduler import admission

        conf = self._session.conf
        facts = dict(serving_facts) if serving_facts else None
        group = facts.pop("admission_group", None) if facts else None
        tok = _cancel.begin(conf, tenant=self._session.tenant)
        self._session._tokens.add(tok)
        if token_sink is not None:
            token_sink.add(tok)
        qid_box: list = []
        try:
            yield from self._stream_tpu_cancellable(
                exec_, meta, batch_rows, drain_lock, facts, group,
                tok, qid_box)
        except _cancel.QueryCancelled as e:
            self._record_cancelled(e, facts)
            raise
        finally:
            self._session._tokens.discard(tok)
            if token_sink is not None:
                token_sink.discard(tok)
            _cancel.end(tok)
            if qid_box:
                # safety net: an ABANDONED stream (generator closed
                # early) records nothing — but it must not keep a dead
                # query in the live registry either (no-op after a
                # drained stream's normal finish)
                from spark_rapids_tpu import obs as _obs

                _obs.REGISTRY.drop(qid_box[0])

    def _stream_tpu_cancellable(self, exec_, meta, batch_rows,
                                drain_lock, facts, group, tok,
                                qid_box=None):
        import contextlib
        import time as _time

        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.plan.planner import stream_exec
        from spark_rapids_tpu.serving import update_serving_context
        from spark_rapids_tpu.serving import cancel as _cancel
        from spark_rapids_tpu.serving.scheduler import admission

        conf = self._session.conf
        with admission(conf, tenant=self._session.tenant,
                       priority=self._session.priority, group=group,
                       token=tok), \
                (drain_lock if drain_lock is not None
                 else contextlib.nullcontext()):
            if facts:
                update_serving_context(**facts)
            from spark_rapids_tpu.serving import work_share as _ws

            sharing = _ws.enabled(conf)
            #: sharing-on miss path: accumulate the streamed batches
            #: (bounded to the result cache's own single-result cap,
            #: budget/4) so a fully-drained stream populates the
            #: cross-tenant result cache exactly like a collect — the
            #: wire front door streams every query, and a front door
            #: that never fills the cache would defeat the sharing
            #: economics (docs/connect.md).  None = not accumulating.
            share_acc: Optional[list] = None
            share_cap = 0
            if sharing:
                cached, verdict = _ws.lookup_result(self._plan, conf)
                if verdict is not None:
                    update_serving_context(result_cache=verdict)
                if cached is not None:
                    # serve the stream from the cached result: the
                    # same record-batch surface, the same per-query
                    # record, zero execution
                    out, _qid = self._result_cache_hit(cached, meta)
                    for rb in out.to_batches(max_chunksize=batch_rows):
                        yield rb
                    return
                share_acc = []
                share_cap = conf.get(_ws.RESULT_CACHE_BUDGET) // 4
            qid, elog, pre, conf_hash, start_ts, t0, t0_ns = \
                _begin_query(self._session, conf)
            if qid_box is not None:
                qid_box.append(qid)
            if tok is not None:
                tok.query_id = qid
            baseline = None
            if exec_ is not None:
                # cached-tree re-drain: record per-execution metric
                # deltas, not the tree's running totals
                from spark_rapids_tpu.tools.profiling import (
                    snapshot_exec,
                )

                baseline = snapshot_exec(exec_)
            with _trace.trace_context(query_id=qid), \
                    _cancel.attach_token(tok):
                if exec_ is None:
                    with _trace.span("query.plan"):
                        exec_, meta = plan_query(self._plan, conf)
                tctx = _trace.current_context()
            from spark_rapids_tpu import obs as _obs

            if _obs.REGISTRY.enabled:
                from spark_rapids_tpu.eventlog import plan_fingerprint

                ptext = meta.explain()
                _obs.REGISTRY.annotate(
                    qid, plan=ptext,
                    plan_hash=plan_fingerprint(ptext), token=tok)
            rows = 0
            gen = stream_exec(exec_, stage="serve.stream.fetch")
            try:
                #: wire frames re-chunked from the current engine
                #: table — drained with a cancellation checkpoint per
                #: frame, so a cancel lands between frames even when
                #: the whole result arrived as ONE table (otherwise a
                #: stalled consumer's cancel could not interrupt the
                #: re-chunk loop; the connect server's disconnect
                #: cancellation rests on this)
                pending: list = []
                while True:
                    # re-attach the query's trace context AND cancel
                    # token around each pull (NOT across yields: the
                    # consumer's own work between pulls must not
                    # inherit this query's id or its cancel scope)
                    with _trace.attach_context(tctx), \
                            _cancel.attach_token(tok):
                        try:
                            if pending:
                                _cancel.check_point()
                                rb = pending.pop(0)
                            else:
                                tbl = next(gen)
                                rows += tbl.num_rows
                                _obs.REGISTRY.note_batch(
                                    qid, tbl.num_rows)
                                if share_acc is not None:
                                    share_acc.append(tbl)
                                    if sum(t.nbytes
                                           for t in share_acc) \
                                            > share_cap:
                                        # past the cache's single-
                                        # result cap: stop
                                        # accumulating, free the held
                                        share_acc = None
                                pending = list(tbl.to_batches(
                                    max_chunksize=batch_rows))
                                continue
                        except StopIteration:
                            break
                        except _cancel.QueryCancelled as e:
                            # record the unwound stream (partial rows,
                            # no digest) before propagating — an
                            # ABANDONED stream records nothing, a
                            # CANCELLED one is an observable outcome
                            if e.query_id is None:
                                e.query_id = qid
                            # bind NOW: the except-variable `e` is
                            # unbound when the block exits, but the
                            # closure runs later on the history worker
                            reason = e.reason
                            expl = (meta.explain()
                                    + f"\n[stream unwound: {reason}]")

                            def _on_cancel_event():
                                if elog is None:
                                    return None
                                post = elog.query_end(pre)
                                return lambda ev: elog.log_query(
                                    ev, post, expl, reason,
                                    result_digest=None, rows=rows)

                            _record_query(
                                self._session, expl, exec_, qid,
                                conf_hash, start_ts, t0, t0_ns,
                                _on_cancel_event(), baseline=baseline,
                                engine=reason)
                            e.recorded = True
                            raise
                    yield rb
            finally:
                gen.close()
            if share_acc:
                # fully drained with sharing on: offer the result so
                # the next tenant's identical query is a cache hit
                # (offer_result re-checks shareability and size;
                # empty results are simply not offered)
                _ws.offer_result(self._plan, conf,
                                 pa.concat_tables(share_acc))
            # fully drained: record the query (an ABANDONED stream —
            # generator closed early — records nothing; its partial
            # metrics would read as a complete run).  The execute span
            # is recorded whole-drain so span-derived busy/self
            # analytics see streamed queries like collected ones.
            if _trace.TRACER.enabled:
                _trace.record_complete(
                    "query.execute", t0_ns,
                    _time.perf_counter_ns() - t0_ns, query_id=qid,
                    streamed=True)
            streamed = rows

            def _on_event(render_plan):
                if elog is None:
                    return None
                post = elog.query_end(pre)
                return lambda ev: elog.log_query(
                    ev, post, render_plan(), "tpu",
                    result_digest=None, rows=streamed)

            from spark_rapids_tpu.eventlog import render_plan_report

            _record_query(
                self._session, meta.explain(), exec_, qid, conf_hash,
                start_ts, t0, t0_ns,
                _on_event(lambda: render_plan_report(exec_, meta)),
                baseline=baseline)

    def to_batches(self, batch_rows: Optional[int] = None):
        """Stream the result as Arrow record batches (the ColumnarRdd
        export analog — hand accelerated data to external libraries
        without one giant materialization)."""
        from spark_rapids_tpu.columnar.rows import columnar_export

        return columnar_export(self, batch_rows)

    def rows(self):
        """Iterate result rows as tuples (the columnar->row boundary,
        ref: GpuColumnarToRowExec)."""
        for rb in self.to_batches():
            cols = [c.to_pylist() for c in rb.columns]
            for i in range(rb.num_rows):
                yield tuple(c[i] for c in cols)

    def explain(self, mode: str = "simple") -> str:
        """Plan explanation.  mode="simple" (default): the static
        replacement/lint/pipeline report.  mode="analyze": EXPLAIN
        ANALYZE — run the query on the TPU engine, then render the
        plan annotated per-operator with SETTLED metrics (device-synced
        wall time, rows, batches) and, when tracing is on, span-derived
        busy/self/overlap times (docs/observability.md)."""
        if mode.lower() == "analyze":
            from spark_rapids_tpu import trace as _trace
            from spark_rapids_tpu.execs.jit_cache import cache_stats
            from spark_rapids_tpu.execs.retry import retry_stats
            from spark_rapids_tpu.plan import runtime_filter as _rf
            from spark_rapids_tpu.robustness import faults as _faults
            from spark_rapids_tpu.tools.profiling import render_analyze

            from spark_rapids_tpu.serving import plan_cache as _pc
            from spark_rapids_tpu.trace import ledger as _ledger

            before = cache_stats()
            retry0 = retry_stats()
            faults0 = _faults.recovered_total()
            rf0 = _rf.stats()
            pc0 = _pc.stats()
            # sync NOW (normally a _begin_query job) so the pre-collect
            # snapshot sees a conf-enabled ledger on the first analyze
            _ledger.sync_conf(self._session.conf)
            led0 = _ledger.snapshot() if _ledger.LEDGER.enabled \
                else None
            _out, qid = self._collect_tpu()
            after = cache_stats()
            # per-QUERY deltas (counters are process-wide cumulative;
            # concurrent collects can bleed into the diff, which is
            # fine for a diagnostics footer) — the same counter
            # surface the event log persists per query
            cs = {"hits": after["hits"] - before["hits"],
                  "misses": after["misses"] - before["misses"]}
            retry1 = retry_stats()
            rf1 = _rf.stats()
            pc1 = _pc.stats()
            counters = {
                "retry": {k: max(0, retry1[k] - retry0[k])
                          for k in retry1},
                "faults_recovered": max(
                    0, _faults.recovered_total() - faults0),
                "rf": {k: max(0, rf1[k] - rf0[k]) for k in rf1},
                # prepared-plan cache activity in this window (nonzero
                # when the analyzed collect rode a PreparedQuery or a
                # concurrent session resolved one — docs/serving.md)
                "plan_cache": {
                    k: max(0, pc1[k] - pc0[k])
                    for k in ("hits", "misses", "evictions")},
            }
            # find OUR event by id — events[-1] may be a concurrent
            # collect's record (fall back to it only if concurrent
            # collects evicted ours from a tiny history ring)
            # per-query device-ledger attribution (the roofline column
            # + top-programs footer; docs/device_ledger.md) — settled
            # off the critical path, bounded-waited here
            led = None
            if led0 is not None and _ledger.LEDGER.enabled:
                _ledger.LEDGER.flush(timeout=2.0)
                led = _ledger.summarize(
                    _ledger.delta(led0, _ledger.snapshot()))
            events_ = self._session.history.events
            ev = next((e for e in reversed(events_)
                       if e.query_id == qid), events_[-1])
            events = _trace.snapshot() if _trace.is_enabled() else None
            return render_analyze(ev, events, cache_stats=cs,
                                  counters=counters, ledger=led)
        exec_, meta = plan_query(self._plan, self._session.conf)
        # the lowered plan + its static annotation sections (lint
        # findings, pipeline stages, runtime-filter sites) — shared
        # with the event-log writer so the persisted plan matches this
        # in-process view exactly (docs/eventlog.md)
        from spark_rapids_tpu.eventlog import render_plan_report

        return render_plan_report(exec_, meta)

    def __repr__(self) -> str:
        return f"DataFrame[{self.schema}]"


class DataFrameWriter:
    """Builder for durable output (ref: the GpuDataSource /
    GpuFileFormatWriter entry surface, sql/rapids/GpuDataSource.scala).
    The child query runs through the normal planner (plan rewrite + CPU
    fallback); encoding happens in per-partition write tasks."""

    def __init__(self, df: DataFrame):
        self._df = df
        self._mode = "error"
        self._partition_by: list[str] = []
        self._compression = "snappy"

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m
        return self

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        self._partition_by.extend(cols)
        return self

    def compression(self, c: str) -> "DataFrameWriter":
        self._compression = c
        return self

    def parquet(self, path: str):
        from spark_rapids_tpu.io.write import ParquetWriteExec

        return self._run(ParquetWriteExec, path)

    def csv(self, path: str):
        from spark_rapids_tpu.io.write import CsvWriteExec

        return self._run(CsvWriteExec, path)

    def orc(self, path: str):
        from spark_rapids_tpu.io.write import OrcWriteExec

        return self._run(OrcWriteExec, path)

    def _run(self, exec_cls, path: str):
        from spark_rapids_tpu.io.write import prepare_target

        if not prepare_target(path, self._mode):
            return None  # mode=ignore on existing target
        df = self._df
        child, _meta = plan_query(df._plan, df._session.conf)
        kwargs = {}
        if exec_cls.FORMAT in ("parquet", "orc"):
            kwargs["compression"] = self._compression
        w = exec_cls(path, child, partition_by=self._partition_by,
                     **kwargs)
        return w.run()
