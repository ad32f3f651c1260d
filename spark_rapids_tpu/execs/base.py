"""Physical operator (exec) base classes and metrics.

TPU re-design of the reference's GpuExec
(ref: sql-plugin/.../GpuExec.scala:40-217 — doExecuteColumnar contract +
tiered GpuMetric hierarchy).

The TPU twist: execs that are pure per-batch transforms (project, filter,
...) expose `make_batch_fn()`, and `execute()` *fuses* every consecutive
fusable ancestor into ONE `jax.jit` program per pipeline — the columnar
equivalent of Spark's whole-stage codegen, and the idiomatic XLA answer to
the reference's per-operator cudf kernel launches: one compiled program per
(pipeline, capacity-bucket) with all elementwise work fused by the
compiler.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import METRICS_LEVEL, get_conf


class TpuMetric:
    """A named counter, levelled like the reference's ESSENTIAL/MODERATE/
    DEBUG GpuMetrics (ref: GpuExec.scala:32-160).

    Counts may be *deferred device scalars* (`add_lazy`): a filtered
    batch's row count lives on device, and forcing it per batch would put
    a host<->device round trip in every operator's hot loop.  Deferred
    counts are summed with one transfer when the metric is read, and
    flushed in bulk past a bound so a long query does not pin one tiny
    device buffer per batch."""

    __slots__ = ("name", "level", "_value", "_pending", "_lock")

    _FLUSH_AT = 1024

    def __init__(self, name: str, level: str = "MODERATE"):
        self.name = name
        self.level = level
        self._value = 0
        self._pending: list = []  # device int scalars, flushed on read
        self._lock = threading.Lock()

    def add(self, v: int) -> None:
        with self._lock:
            self._value += v

    def add_lazy(self, v, times: int = 1) -> None:
        """Add a host int now or a device scalar at read time, `times`
        times over (an expand's fan-out of its input's count)."""
        if isinstance(v, int):
            self.add(v * times)
            return
        with self._lock:
            self._pending.extend([v] * times)
            if len(self._pending) < self._FLUSH_AT:
                return
            pending, self._pending = self._pending, []
        # blocking transfer outside the lock
        import numpy as _np

        s = sum(int(_np.asarray(x).sum()) for x in jax.device_get(pending))
        with self._lock:
            self._value += s

    @property
    def value(self) -> int:
        with self._lock:
            pending, self._pending = self._pending, []
        if pending:
            import numpy as _np

            s = sum(int(_np.asarray(x).sum())
                    for x in jax.device_get(pending))
            with self._lock:
                self._value += s
        with self._lock:
            return self._value

    def __repr__(self) -> str:
        return f"{self.name}={self.value}"

    @staticmethod
    def flush_many(metrics: "Sequence[TpuMetric]") -> None:
        """Settle deferred device counts for MANY metrics with ONE
        device transfer.  Per-metric flushing costs a blocking
        device-to-host round trip each; a whole-tree metrics snapshot
        must pay one."""
        import numpy as _np

        grabbed: list[tuple["TpuMetric", list]] = []
        for m in metrics:
            with m._lock:
                if m._pending:
                    grabbed.append((m, m._pending))
                    m._pending = []
        if not grabbed:
            return
        fetched = jax.device_get([p for _m, p in grabbed])
        for (m, _p), vals in zip(grabbed, fetched):
            s = sum(int(_np.asarray(x).sum()) for x in vals)
            with m._lock:
                m._value += s


METRICS_DEVICE_SYNC = None  # registered lazily to avoid an import cycle


def _device_sync_enabled() -> bool:
    global METRICS_DEVICE_SYNC
    if METRICS_DEVICE_SYNC is None:
        from spark_rapids_tpu.config import register

        METRICS_DEVICE_SYNC = register(
            "spark.rapids.tpu.sql.metrics.deviceSync", True,
            "Block on the produced batch inside metric timers so "
            "totalTime measures device execution, not async dispatch. "
            "Disable to trade metric accuracy for pipeline overlap "
            "within a task.")
    return get_conf().get(METRICS_DEVICE_SYNC)


class _MetricReaper:
    """Background completion-waiter making operator timers measure device
    execution without blocking the producing pipeline: timed regions hand
    their output arrays here, and a daemon thread records
    dispatch-to-completion elapsed time into the metric.  The producing
    thread keeps dispatching (overlap preserved); the clock still stops
    only when the device work is done — the truth the reference gets from
    synchronous NVTX ranges around blocking cudf calls."""

    _instance: Optional["_MetricReaper"] = None
    _lock = threading.Lock()

    def __init__(self) -> None:
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name="tpu-metric-reaper", daemon=True)
        self._thread.start()

    @classmethod
    def get(cls) -> "_MetricReaper":
        with cls._lock:
            if cls._instance is None:
                cls._instance = _MetricReaper()
            return cls._instance

    def submit(self, metric: TpuMetric, t0: int, observed) -> None:
        # settle on the producing thread what can be settled here: at
        # most one zero-row SENTINEL per device set that holds an
        # unfinished leaf (its completion bounds everything the region
        # queued there), and none where every leaf is complete — a
        # cache hit of resident arrays — whose time is known now
        # (trace.ledger.sentinels_of).  The reaper exclusively owns the
        # sentinels: polling the observed arrays themselves would race
        # the spill store's .delete() (is_ready on a deleted PJRT
        # buffer segfaults)
        from spark_rapids_tpu.trace.ledger import sentinels_of

        with _trace.span("exec.sentinels", metric=metric.name) as s:
            sentinels, leaves = sentinels_of(observed)
            s.note(leaves=leaves, sentinels=len(sentinels),
                   ready=not sentinels)
        if not sentinels:
            # complete (or host-only, or every leaf consumed): the
            # timer ticks without a wait, like the non-observing
            # MetricTimer branch
            metric.add(time.perf_counter_ns() - t0)
            return
        # correlation context crosses to the reaper thread by capture
        ctx = _trace.current_context() if _trace.TRACER.enabled else None
        self._q.put((metric, t0, sentinels, ctx))

    def flush(self) -> None:
        """Wait until every submitted region has been timed."""
        self._q.join()

    def _run(self) -> None:
        while True:
            metric, t0, sentinels, ctx = self._q.get()
            try:
                # POLL readiness instead of block_until_ready: on remote
                # PJRT backends a blocking wait from this thread
                # serializes the whole client — concurrent device_put
                # calls from task threads stall for seconds behind it
                # (measured: 4ms -> 2.5s per 24MB upload).  is_ready()
                # is a local, lock-free check; 1ms polling granularity
                # is far below any per-op time worth recording.
                w0 = time.perf_counter_ns()
                for x in sentinels:
                    while not x.is_ready():
                        time.sleep(0.001)
                now = time.perf_counter_ns()
                metric.add(now - t0)
                if _trace.TRACER.enabled:
                    with _trace.attach_context(ctx):
                        _trace.record_complete(
                            f"metric.settle.{metric.name}", w0, now - w0,
                            metric=metric.name)
            except Exception:
                pass
            finally:
                self._q.task_done()


class MetricTimer:
    """Context manager adding elapsed ns to a metric — the NVTX-with-metric
    pattern (ref: NvtxWithMetrics.scala:25-42).

    JAX dispatch is asynchronous; to make `totalTime` mean device time the
    timed region registers its output via `observe(batch)` and the elapsed
    time is recorded when the output's device work completes (measured on
    a background thread so the pipeline keeps overlapping), or at once
    when the output is already complete.  Disable via
    spark.rapids.tpu.sql.metrics.deviceSync to time dispatch only.

    With `op` set (the owning exec's name) and tracing enabled, the
    timed region is also recorded as an ``exec.<op>`` span — the
    NvtxWithMetrics pairing: operators get timeline spans for free
    wherever they already time themselves; `attrs` are said on that
    span beside `op`."""

    def __init__(self, metric: Optional[TpuMetric],
                 op: Optional[str] = None, **attrs):
        self.metric = metric
        self.op = op
        self.attrs = attrs
        self._observed = None

    def observe(self, out):
        """Register the region's device output to be waited on."""
        self._observed = out
        return out

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self.op is not None and _trace.TRACER.enabled:
            # the dispatch-side interval (device settlement is the
            # reaper's metric.settle span)
            _trace.record_complete(
                f"exec.{self.op}", self.t0,
                time.perf_counter_ns() - self.t0, op=self.op, **self.attrs)
        if self.metric is None:
            return False
        if self._observed is not None and exc[0] is None \
                and _device_sync_enabled():
            _MetricReaper.get().submit(self.metric, self.t0, self._observed)
        else:
            self.metric.add(time.perf_counter_ns() - self.t0)
        return False


# standard metric names (ref: GpuExec.scala companion constants)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
TOTAL_TIME = "totalTime"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"


class TpuExec:
    """Base physical operator producing an iterator of device batches."""

    def __init__(self, *children: "TpuExec"):
        self.children: list[TpuExec] = list(children)
        self.metrics: dict[str, TpuMetric] = {}
        for name in (NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES, TOTAL_TIME):
            self.metrics[name] = TpuMetric(name, "ESSENTIAL")
        for name, lvl in self.additional_metrics():
            self.metrics[name] = TpuMetric(name, lvl)

    # -- overridables ---------------------------------------------------- #

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    def additional_metrics(self) -> list[tuple[str, str]]:
        return []

    # -- partitioned execution (the Spark task-per-partition model, ref:
    # SURVEY.md §2.9).  Narrow execs propagate the child's partitioning;
    # wide execs (global sort/limit, broadcast-style join, complete
    # aggregation) consume every child partition and emit ONE.  Execs
    # must override execute() (wide) or execute_partition() (narrow). -- #

    @property
    def num_partitions(self) -> int:
        return 1

    @property
    def output_partitioning(self):
        """The data distribution this exec's output satisfies (a
        Partitioning, or None = unknown) — the planner's
        EnsureRequirements analog uses it to skip redundant exchanges."""
        return None

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        """Produce one output partition's batches."""
        assert self.num_partitions == 1, type(self).__name__
        if p == 0:
            yield from self.execute()

    def execute(self) -> Iterator[ColumnarBatch]:
        """All partitions, chained (ref: GpuExec.doExecuteColumnar)."""
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)

    def close(self) -> None:
        """Release query-lifetime resources (shuffle blocks, broadcast
        batches).  Called by the query root when the plan is drained or
        abandoned; propagates down the tree."""
        for c in self.children:
            c.close()

    # -- plumbing -------------------------------------------------------- #

    @property
    def name(self) -> str:
        return type(self).__name__

    def node_desc(self) -> str:
        return self.name

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + "+- " + self.node_desc() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def _count_output(self, batch: ColumnarBatch) -> ColumnarBatch:
        # THE per-operator cooperative cancellation checkpoint: every
        # exec counts each output batch here, so one check covers the
        # whole tree's stream loops (serving/cancel.py; one
        # thread-local read when no token is attached)
        from spark_rapids_tpu.serving.cancel import check_point

        check_point()
        self.metrics[NUM_OUTPUT_BATCHES].add(1)
        # device-scalar row counts are deferred (summed when the metric is
        # read) — forcing them here would put a host round trip in every
        # operator's per-batch loop
        self.metrics[NUM_OUTPUT_ROWS].add_lazy(batch.num_rows)
        return batch

    def collect_metrics(self) -> dict[str, dict[str, int]]:
        _MetricReaper.get().flush()  # settle in-flight device timings
        level = get_conf().get(METRICS_LEVEL)
        rank = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}[level]
        out = {}
        for node in self._walk():
            m = {k: v.value for k, v in node.metrics.items()
                 if rank >= {"ESSENTIAL": 0, "MODERATE": 1,
                             "DEBUG": 2}[v.level]}
            out.setdefault(node.name, {}).update(m)
        return out

    def _walk(self):
        yield self
        for c in self.children:
            yield from c._walk()


BatchFn = Callable[[ColumnarBatch], ColumnarBatch]


FUSION_ENABLED = None  # registered lazily to avoid import-order churn


def _fusion_conf():
    global FUSION_ENABLED
    if FUSION_ENABLED is None:
        from spark_rapids_tpu.config import register

        FUSION_ENABLED = register(
            "spark.rapids.tpu.sql.fusion.enabled", True,
            "Whole-stage program fusion: compile consecutive fusable "
            "execs (filter/project/...), the wire decode of an "
            "encoded scan batch, and the hash aggregate's update "
            "phase into ONE XLA program per (pipeline key, capacity "
            "bucket) — the XLA analog of Spark's WholeStageCodegen "
            "(docs/fusion.md).  Off: every exec compiles and "
            "dispatches its own per-batch program and scans upload "
            "eagerly-decoded batches — the dispatch-soup baseline "
            "the fusion smoke measures against.  Results are "
            "bit-identical either way.")
    return FUSION_ENABLED


def fusion_enabled() -> bool:
    return get_conf().get(_fusion_conf())


WARM_DISPATCH_BUDGET = None  # registered lazily, like FUSION_ENABLED


def _budget_conf():
    global WARM_DISPATCH_BUDGET
    if WARM_DISPATCH_BUDGET is None:
        from spark_rapids_tpu.config import register

        WARM_DISPATCH_BUDGET = register(
            "spark.rapids.tpu.sql.fusion.warmDispatchBudget", 256,
            "Per-query WARM dispatch budget: the maximum ledger "
            "program-launch count a warm (compile-cache-hot) "
            "milestone query may pay per collect before the bench "
            "dispatch-budget gate and run_fusion_smoke fail the "
            "round.  Turns ROADMAP #2's dispatch-soup diagnosis "
            "(HC010) into a regression GATE instead of a diagnostic: "
            "un-fusing a chain or destabilizing a jit key shows up as "
            "a hard assertion, not a slow drift.  0 disables the "
            "gate.", check=lambda v: v >= 0)
    return WARM_DISPATCH_BUDGET


def warm_dispatch_budget() -> int:
    return int(get_conf().get(_budget_conf()))


#: process-global fusion activity counters (reset per bench query like
#: the pipeline/speculation/ledger stats): `chains` = fused chain
#: programs BUILT (>= 2 execs, or 1 exec + in-program wire decode);
#: `fused_dispatches` = executions of such programs;
#: `saved_dispatches` = program launches those executions did NOT pay
#: vs the unfused engine (chain length - 1, +1 when the wire decode
#: rode inside) — bench.py's q*_fusion_chains /
#: q*_fused_dispatch_savings fields.
_FUSION_LOCK = threading.Lock()
_FUSION_STATS = {"chains": 0, "fused_dispatches": 0,
                 "saved_dispatches": 0}


def record_fused_chain() -> None:
    """One fused chain planned for the current query (called by the
    planner's _plan_fusion, once per 'one program' line it reports —
    so the counter agrees with explain()'s Fusion section by
    construction)."""
    with _FUSION_LOCK:
        _FUSION_STATS["chains"] += 1


def record_fused_dispatch(n_execs: int, decode_fused: bool) -> None:
    saved = (n_execs - 1) + (1 if decode_fused else 0)
    if saved <= 0:
        return
    with _FUSION_LOCK:
        _FUSION_STATS["fused_dispatches"] += 1
        _FUSION_STATS["saved_dispatches"] += saved


def fusion_stats() -> dict:
    with _FUSION_LOCK:
        return dict(_FUSION_STATS)


def reset_fusion_stats() -> None:
    with _FUSION_LOCK:
        for k in _FUSION_STATS:
            _FUSION_STATS[k] = 0


class FusableExec(TpuExec):
    """An exec that is a pure per-batch device transform (narrow: output
    partitioning == child's).  Consecutive fusable execs compile into a
    single XLA program per batch pipeline, shared across partitions."""

    def make_batch_fn(self) -> BatchFn:
        """Return a traceable ColumnarBatch -> ColumnarBatch function."""
        raise NotImplementedError

    def fuse_key(self):
        """Structural key identifying this exec's batch fn for the global
        compile cache (None = not cacheable; the pipeline then compiles
        per exec instance)."""
        return None

    def fusion_exprs(self):
        """The expression trees this exec evaluates per batch; used to
        detect PartitionAware expressions needing partition context."""
        return ()

    #: True for execs whose output row count differs from their input's
    #: (Expand/Generate): a PartitionAware exec above one must not fuse
    #: across it — the shared row_offset would advance by INPUT rows
    #: while ids were assigned per OUTPUT row
    MULTIPLIES_ROWS = False

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    def fusion_chain(self):
        """(fns, source_node, aware, keys): the composed per-batch
        transform chain rooted here, UN-jitted — minor-first (fns[0]
        runs first).  `keys` are the per-exec fuse keys (None entries =
        uncacheable).  Lets a non-fusable CONSUMER (e.g. the hash
        aggregate's update phase) absorb this chain into its own traced
        program, so the whole scan->filter->update path is one program
        execution per batch — every execution has a fixed dispatch
        cost, so program count, not FLOPs, bounds small-query
        latency."""
        from spark_rapids_tpu.exprs.nondeterministic import (
            tree_is_partition_aware,
        )

        def is_aware(x: "FusableExec") -> bool:
            return any(tree_is_partition_aware(e)
                       for e in x.fusion_exprs())

        # walk down through fusable children, composing their batch fns;
        # stop before a row-multiplying exec if anything above it needs
        # partition context (its row_offset counts THIS chain's input).
        # With fusion disabled the chain is just this exec — every
        # operator dispatches its own program (the unfused baseline
        # the fusion smoke and the on/off digest gates compare).
        execs: list[FusableExec] = [self]
        node: TpuExec = self.children[0]
        aware = is_aware(self)
        if fusion_enabled():
            while isinstance(node, FusableExec):
                if aware and node.MULTIPLIES_ROWS:
                    break
                execs.append(node)  # type: ignore[arg-type]
                aware = aware or is_aware(node)
                node = node.children[0]
        return (list(reversed(execs)), node, aware,
                [e.fuse_key() for e in execs])

    def _fused_pipeline(self):
        cached = getattr(self, "_fused", None)
        if cached is not None:
            return cached
        chain, node, aware, keys = self.fusion_chain()
        fns: list[BatchFn] = [e.make_batch_fn() for e in chain]
        from spark_rapids_tpu.exprs.base import (
            ansi_capture,
            ansi_enabled,
            fold_ansi_flags,
        )

        ansi = ansi_enabled()
        if aware:
            from spark_rapids_tpu.exprs.base import partition_info

            def pipeline(batch: ColumnarBatch, pidx, off):
                with partition_info(pidx, off):
                    if ansi:
                        with ansi_capture() as flags:
                            for f in fns:
                                batch = f(batch)
                        return batch, fold_ansi_flags(flags)
                    for f in fns:
                        batch = f(batch)
                return batch
        else:
            def pipeline(batch: ColumnarBatch):  # type: ignore[misc]
                if ansi:
                    with ansi_capture() as flags:
                        for f in fns:
                            batch = f(batch)
                    return batch, fold_ansi_flags(flags)
                for f in fns:
                    batch = f(batch)
                return batch

        from spark_rapids_tpu.execs.jit_cache import (
            cached_jit,
            named_program,
        )

        if all(k is not None for k in keys):
            jitted = cached_jit(("fused", tuple(keys), ansi),
                                lambda: pipeline, op=self.name)
        else:
            jitted = jax.jit(named_program(pipeline, self.name,
                                           "unkeyed"))
        self._fused = (jitted, node, aware, ansi, len(chain))
        return self._fused

    def _fused_pipeline_encoded(self):
        """Jitted pipeline variant whose input is a wire-form
        EncodedBatch: the decode runs inside the same program as the
        transform chain (one execution per batch).  Returns
        (jitted, donated, n_execs); with donation enabled the wire
        components are donate_argnums'd into the program — they are
        fresh per-batch uploads consumed exactly once, so XLA may
        write the decoded columns into their HBM (the driver marks
        the batch consumed via transfer.run_consuming)."""
        cached = getattr(self, "_fused_enc", None)
        if cached is not None:
            return cached
        chain, node, aware, keys = self.fusion_chain()
        fns = [e.make_batch_fn() for e in chain]
        from spark_rapids_tpu.exprs.base import (
            ansi_capture,
            ansi_enabled,
            fold_ansi_flags,
        )

        ansi = ansi_enabled()

        def pipeline(eb):
            batch = eb.decode()
            if ansi:
                with ansi_capture() as flags:
                    for f in fns:
                        batch = f(batch)
                return batch, fold_ansi_flags(flags)
            for f in fns:
                batch = f(batch)
            return batch

        from spark_rapids_tpu.execs.jit_cache import (
            cached_jit,
            donation_enabled,
            named_program,
        )

        donated = False
        if all(k is not None for k in keys):
            donated = donation_enabled()
            jitted = cached_jit(("fusedenc", tuple(keys), ansi),
                                lambda: pipeline, op=self.name,
                                donate=(0,))
        else:
            jitted = jax.jit(named_program(pipeline, self.name,
                                           "unkeyed"))
        self._fused_enc = (jitted, donated, len(chain))
        return self._fused_enc

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.columnar.transfer import (
            EncodedBatch,
            run_consuming,
        )
        from spark_rapids_tpu.exprs.base import raise_if_ansi_error
        from spark_rapids_tpu.trace import ledger as _ledger

        fused, node, aware, ansi, n_execs = self._fused_pipeline()
        if aware:
            pidx = jnp.asarray(p, jnp.int32)
            off = jnp.asarray(0, jnp.int64)
        for batch in node.execute_partition(p):
            if isinstance(batch, EncodedBatch):
                if aware:
                    # partition-aware chains thread (pidx, off) through
                    # a different signature; decode eagerly instead
                    batch = batch.decode_now()
                else:
                    fn_enc, donated, n_enc = \
                        self._fused_pipeline_encoded()
                    # consumed = a re-run resuming from the memoized
                    # output; no program launches, stats must not tick
                    resumed = donated and batch.consumed
                    with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                        out = run_consuming(fn_enc, batch) if donated \
                            else fn_enc(batch)
                        if ansi:
                            out, err = out
                            raise_if_ansi_error(jax.device_get(err))
                        out = t.observe(out)
                    if not resumed:
                        record_fused_dispatch(n_enc, decode_fused=True)
                    yield self._count_output(out)
                    continue
            # the promotion below hides num_rows from the ledger's
            # argument scan (device scalar); state it while host-known
            if _ledger.LEDGER.enabled and type(batch.num_rows) is int:
                _ledger.note_occupancy(batch.num_rows, batch.capacity)
            b = batch.with_device_num_rows()
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                if aware:
                    out = fused(b, pidx, off)
                    # row_offset advances by the INPUT batch's live rows
                    # (lazy device add; no sync)
                    off = off + jnp.asarray(b.num_rows, jnp.int64)
                else:
                    out = fused(b)
                if ansi:
                    out, err = out
                    # the one host sync ANSI mode costs: the program
                    # can't raise, so the error code is polled here
                    # (the reference pays the same via cudf's throw)
                    raise_if_ansi_error(jax.device_get(err))
                out = t.observe(out)
            record_fused_dispatch(n_execs, decode_fused=False)
            yield self._count_output(out)

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)
