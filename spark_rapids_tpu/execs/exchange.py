"""Shuffle exchange exec.

Counterpart of GpuShuffleExchangeExecBase (ref: sql-plugin/.../sql/
rapids/execution/GpuShuffleExchangeExec.scala:80,167-270): the map stage
partitions every child batch (murmur3-pmod on device), writes the slices
to the in-process shuffle manager (device-resident, spillable at
shuffle-output priority), and reduce partitions read their blocks back.
Map tasks (one per child partition) run on a thread pool gated by the
task semaphore — the execution model of Spark executor task slots +
GpuSemaphore.  On a multi-chip mesh the planner can instead lower an
exchange+aggregation pair to the fused collective all_to_all program in
parallel.exchange (SURVEY.md §5.8 tier-2 path)."""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import register, get_conf
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.memory import TpuSemaphore
from spark_rapids_tpu.ops.partition import (
    Partitioning,
    RoundRobinPartitioning,
    split_batch,
)
from spark_rapids_tpu.shuffle import get_shuffle_manager

SHUFFLE_PARTITIONS = register(
    "spark.rapids.tpu.sql.shuffle.partitions", 8,
    "Number of reduce partitions for shuffle exchanges (the "
    "spark.sql.shuffle.partitions analog).")
TASK_THREADS = register(
    "spark.rapids.tpu.sql.taskThreads", 4,
    "Host threads running map tasks concurrently (device work "
    "serializes on the chip; threads overlap host IO/decode).")


class TpuShuffleExchangeExec(TpuExec):
    def __init__(self, partitioning: Partitioning, child: TpuExec):
        super().__init__(child)
        self.partitioning = partitioning.bind(child.schema)
        self._map_done = False
        self._map_lock = threading.Lock()
        self._shuffle_id = None
        self._pid_fns: dict = {}
        self._pid_lock = threading.Lock()

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    @property
    def output_partitioning(self):
        return self.partitioning

    def node_desc(self) -> str:
        return f"TpuShuffleExchangeExec {self.partitioning.describe()}"

    def additional_metrics(self):
        return [("shuffleWriteRows", "ESSENTIAL"),
                ("mapTasks", "MODERATE")]

    # -- map stage -------------------------------------------------------- #

    def _run_map_task(self, child_part: int) -> None:
        from spark_rapids_tpu.execs.retry import with_task_retries

        with_task_retries(lambda: self._map_task_attempt(child_part),
                          desc=f"map task {child_part}")
        self.metrics["mapTasks"].add(1)

    def _map_task_attempt(self, child_part: int) -> None:
        """One attempt of a deterministic map task.  Output batches
        register with the spill store immediately (spillable under
        pressure) but publish to the shuffle manager only when the
        whole attempt COMMITS — a failed attempt closes its handles
        and leaves no partial blocks (MapStatus commit protocol; the
        retry wrapper then re-runs from lineage)."""
        sem = TpuSemaphore.get()
        task_id = threading.get_ident() ^ (child_part << 20)
        manager = get_shuffle_manager()
        n = self.num_partitions
        part = self.partitioning
        pid_fn = None
        if n > 1:  # single destination never reads partition ids
            key = 0
            if isinstance(part, RoundRobinPartitioning):
                # offset per map task so output stays balanced (the
                # reference randomizes the start position per task)
                key = child_part % n
                part = RoundRobinPartitioning(n, start=key)
            with self._pid_lock:
                pid_fn = self._pid_fns.get(key)
                if pid_fn is None:
                    from spark_rapids_tpu.execs.jit_cache import (
                        cached_jit,
                        exprs_key,
                    )

                    ck = ("part", type(part).__name__, part.num_partitions,
                          getattr(part, "start", 0),
                          exprs_key(getattr(part, "exprs", ())))
                    pid_fn = self._pid_fns[key] = cached_jit(
                        ck, lambda: part.partition_ids,
                        op=self.name)
        from collections import deque

        from spark_rapids_tpu.columnar.column import pad_capacity
        from spark_rapids_tpu.memory import SpillPriorities, get_store
        from spark_rapids_tpu.ops.partition import (
            split_batch_dispatch,
            split_batch_finish,
        )
        from spark_rapids_tpu.parallel import pipeline as P
        from spark_rapids_tpu.parallel import speculation as SP

        store = get_store()
        pending: list[tuple[int, object, int, int]] = []
        spec_on = SP.speculation_enabled()
        #: (grouped, counts-or-None, ReadbackFuture) whose split counts
        #: ride the async harvester; finished opportunistically in
        #: stream order, drained at task end (map output order does not
        #: matter, only the commit does).  BOUNDED: queued grouped
        #: batches are full-capacity device buffers the spill store
        #: cannot see yet (they register only once their counts
        #: arrive), so past the bound the head is finished BLOCKING —
        #: the same natural backpressure the synchronous readback gave,
        #: just `max_inflight` batches later
        inflight: deque = deque()
        max_inflight = P.stage_depth() + 1

        def dispatch(batch):
            """Async half: partition-id program + grouping sort for
            batch k+1 dispatch before batch k's count readback."""
            sem.acquire_if_necessary(task_id)
            batch = batch.with_device_num_rows()
            if pid_fn is None:
                return batch, None
            return split_batch_dispatch(batch, pid_fn(batch), n)

        def register_slices(subs) -> None:
            """Host half: register the non-empty reduce slices, given
            as (partition, batch, rows) once the counts are host-side."""
            for rid, sub, rows in subs:
                if rows:
                    sub = sub.shrink_to_capacity(pad_capacity(rows))
                    h = store.register(
                        sub, SpillPriorities.OUTPUT_FOR_SHUFFLE)
                    h.unpin()
                    pending.append((rid, h, h.nbytes, rows))

        from spark_rapids_tpu.execs import retry as R

        def finish_inflight(item) -> None:
            """Register the slices of one harvested batch — its own
            spill-retry transaction (slice registrations roll back, the
            cached ReadbackFuture re-resolves for free); an exhausted
            retry escalates to the whole-task rung, where the atomic
            commit protocol keeps correctness."""
            grouped, has_counts, fut = item

            def att():
                n0 = len(pending)
                try:
                    v = fut.result()
                    if has_counts:
                        register_slices(
                            (rid, sub, sub.num_rows) for rid, sub in
                            split_batch_finish(grouped, v, self.name))
                    else:
                        register_slices([(0, grouped, int(v))])
                except BaseException:
                    for _rid, h, _b, _r in pending[n0:]:
                        h.close()
                    del pending[n0:]
                    raise

            R.run_with_oom_retry(att, desc="exchange.finish")

        def finish_entry(entry):
            """Sizing half for one dispatched batch — the split-retry
            unit's tail.  With speculation on, the count readback is
            HARVESTED asynchronously: the map loop keeps dispatching
            while the harvester pulls counts, and slices register as
            their counts arrive (zero blocking syncs in steady state).
            Off, it is the one blocking batched readback per input
            batch, as before.  Rolls back its own slice registrations
            (and its own in-flight entry) on failure so the ladder can
            re-run the batch — at the split size after a bisect —
            without duplicating reduce blocks."""
            grouped, counts = entry
            n0 = len(pending)
            own = None
            try:
                if spec_on:
                    fut = P.device_read_async(
                        counts if counts is not None
                        else grouped.num_rows,
                        tag="exchange.split")
                    own = (grouped, counts is not None, fut)
                    inflight.append(own)
                elif counts is None:
                    rows = P.device_read_int(grouped.num_rows,
                                             tag="exchange.split")
                    register_slices([(0, grouped, rows)])
                else:
                    counts_np = P.device_read(counts,
                                              tag="exchange.split")
                    register_slices(
                        (rid, sub, sub.num_rows) for rid, sub in
                        split_batch_finish(grouped, counts_np, self.name))
            except BaseException:
                if own is not None:
                    try:
                        inflight.remove(own)
                    except ValueError:
                        pass  # already drained (its slices roll back)
                for _rid, h, _b, _r in pending[n0:]:
                    h.close()
                del pending[n0:]
                raise
            return ()

        def drain_opportunistic():
            # opportunistic in-flight drain OUTSIDE the ladder: each
            # harvested item is its own retry transaction above
            while inflight and (inflight[0][2].done()
                                or len(inflight) > max_inflight):
                finish_inflight(inflight.popleft())

        dispatch_guarded, retire_guarded = R.guarded_pipeline(
            dispatch, finish_entry, desc="exchange.map",
            after=drain_opportunistic)

        try:
            for _ in P.pipelined(
                    self.children[0].execute_partition(child_part),
                    dispatch_guarded, retire_guarded,
                    tag="exchange.map"):
                pass
            while inflight:
                finish_inflight(inflight.popleft())
        except BaseException:
            for _rid, h, _b, _r in pending:
                h.close()
            raise
        finally:
            sem.release_if_necessary(task_id)
        try:
            manager.commit_task(self._shuffle_id, pending)
        except BaseException:
            for _rid, h, _b, _r in pending:
                h.close()
            raise
        for _rid, _h, _b, rows in pending:
            self.metrics["shuffleWriteRows"].add(rows)

    def _ensure_map_stage(self) -> None:
        from spark_rapids_tpu.ops.partition import RangePartitioning

        with self._map_lock:
            if self._map_done:
                return
            self._shuffle_id = get_shuffle_manager().new_shuffle_id()
            n_tasks = self.children[0].num_partitions
            threads = min(get_conf().get(TASK_THREADS), max(n_tasks, 1))
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                if isinstance(self.partitioning, RangePartitioning):
                    self._run_range_map_stage(threads)
                else:
                    self._run_tasks(self._run_map_task, n_tasks, threads)
            self._map_done = True

    # -- range partitioning: two-pass map stage -------------------------- #
    # Bounds must exist before any batch can be split, and bounds come
    # from a global sample — so pass 1 streams the child into spillable
    # storage while sampling keys (ref: GpuRangePartitioner.sketch), and
    # pass 2 splits the parked batches against the chosen bounds
    # (ref: determineBounds + the device upper-bound search :167).

    def _run_range_map_stage(self, threads: int) -> None:
        import dataclasses as _dc

        import numpy as np

        from spark_rapids_tpu.execs.jit_cache import cached_jit, exprs_key
        from spark_rapids_tpu.execs.sort import SORT_SAMPLE_PER_BATCH
        from spark_rapids_tpu.memory import SpillPriorities, get_store
        from spark_rapids_tpu.ops.range_partition import choose_bounds

        part = self.partitioning
        n = self.num_partitions
        n_sample = get_conf().get(SORT_SAMPLE_PER_BATCH)
        pkey = (exprs_key([k.expr for k in part.keys]),
                tuple((k.descending, k.nulls_last) for k in part.keys))
        store = get_store()
        manager = get_shuffle_manager()
        sem = TpuSemaphore.get()
        rng = np.random.default_rng(0x52414E47)
        rng_lock = threading.Lock()
        handles: list = []
        samples: list = []
        state_lock = threading.Lock()

        def pass1(child_part: int) -> None:
            from spark_rapids_tpu.execs.retry import with_task_retries

            def attempt():
                """Accumulates locally; merges into the shared state
                only on success so a retried attempt never double-adds
                samples or leaks handles."""
                task_id = threading.get_ident() ^ (child_part << 20)
                local_s: list = []
                local_h: list = []
                try:
                    for batch in self.children[0].execute_partition(
                            child_part):
                        sem.acquire_if_necessary(task_id)
                        rows = batch.concrete_num_rows()
                        if rows == 0:
                            continue
                        batch = _dc.replace(batch, num_rows=rows)
                        jit_sample = cached_jit(
                            ("rangesample", pkey, batch.capacity,
                             n_sample, repr(batch.schema)),
                            op=self.name,
                            make_fn=lambda: lambda b, p: part.key_batch(
                                b).gather(p, n_sample))
                        with rng_lock:
                            pos = rng.integers(0, rows, n_sample).astype(
                                np.int32)
                        local_s.append(
                            jit_sample(batch, jnp.asarray(pos,
                                                          jnp.int32)))
                        local_h.append(store.register(
                            batch, SpillPriorities.COALESCE_PENDING))
                except BaseException:
                    for h in local_h:
                        h.close()
                    raise
                finally:
                    sem.release_if_necessary(task_id)
                with state_lock:
                    samples.extend(local_s)
                    handles.extend(local_h)

            with_task_retries(attempt, desc=f"range pass1 {child_part}")

        n_tasks = self.children[0].num_partitions
        self._run_tasks(pass1, n_tasks, threads)
        if not handles:
            return

        k = len(samples)
        pool_live = k * n_sample
        orders = part.key_orders()

        def pool_and_bound(sample_list):
            from spark_rapids_tpu.columnar.batch import concat_batches

            pooled = concat_batches(sample_list)
            return choose_bounds(pooled, orders, n, pool_live)

        bounds = cached_jit(
            ("rangebounds", pkey, k, n_sample, n,
             tuple(s.capacity for s in samples)),
            lambda: pool_and_bound, op=self.name)(samples)

        from spark_rapids_tpu.columnar.column import pad_capacity

        def pass2(idx: int) -> None:
            from spark_rapids_tpu.execs.retry import with_task_retries

            def attempt():
                """Buffers output handles and commits atomically (same
                MapStatus protocol as the hash map task)."""
                task_id = threading.get_ident() ^ (idx << 20) ^ 0x2
                pending: list = []
                h = handles[idx]
                try:
                    batch = h.get()
                    sem.acquire_if_necessary(task_id)
                    pid_fn = cached_jit(
                        ("rangepid", pkey, n, batch.capacity,
                         repr(batch.schema)),
                        lambda: lambda b, bd:
                            part.partition_ids_with_bounds(b, bd),
                        op=self.name)
                    subs = split_batch(batch, pid_fn(batch, bounds), n,
                                       self.name)
                    for rid, sub in subs:
                        rows = sub.concrete_num_rows()
                        if rows:
                            sub = sub.shrink_to_capacity(
                                pad_capacity(rows))
                            bh = store.register(
                                sub, SpillPriorities.OUTPUT_FOR_SHUFFLE)
                            bh.unpin()
                            pending.append((rid, bh, bh.nbytes, rows))
                except BaseException:
                    for _rid, bh, _b, _r in pending:
                        bh.close()
                    h.unpin()  # input stays retryable
                    raise
                finally:
                    sem.release_if_necessary(task_id)
                try:
                    manager.commit_task(self._shuffle_id, pending)
                except BaseException:
                    for _rid, bh, _b, _r in pending:
                        bh.close()
                    h.unpin()
                    raise
                for _rid, _bh, _b, rows in pending:
                    self.metrics["shuffleWriteRows"].add(rows)

            with_task_retries(attempt, desc=f"range pass2 {idx}")
            # Close the input AFTER the retry wrapper: anything that runs
            # post-commit inside the retried closure would, on failure,
            # re-run the attempt and publish the same reduce blocks twice
            # (the commit must be the attempt's final observable effect).
            handles[idx].close()

        try:
            self._run_tasks(pass2, len(handles), threads)
        finally:
            for h in handles:
                h.close()

    def _run_tasks(self, fn, n_tasks: int, threads: int) -> None:
        if threads <= 1 or n_tasks <= 1:
            for p in range(n_tasks):
                fn(p)
            return
        # conf is THREAD-LOCAL: install the calling (session) thread's
        # snapshot on every pool thread, or each task silently reads
        # defaults (batch sizing, pipeline depth/kill-switch, chunk
        # rows) for everything executing below the exchange.  The trace
        # correlation context makes the same hop, so map-task spans
        # stay attributable to the query that dispatched them — and so
        # does the query's cancel token, so a cancelled query's map
        # tasks unwind at their own checkpoints instead of running the
        # whole map stage for nobody.
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.config import get_conf, set_conf
        from spark_rapids_tpu.serving import cancel as _cancel

        conf = get_conf()
        tctx = _trace.current_context()
        ctok = _cancel.current_token()

        def run(p: int) -> None:
            set_conf(conf)
            # no op= attr here: the exec's MetricTimer span already
            # covers the map stage, and a second op-keyed span per task
            # would double-count the exchange in span_stats
            with _trace.attach_context(tctx), \
                    _cancel.attach_token(ctok), \
                    _trace.span("exchange.task", task=p):
                fn(p)

        with ThreadPoolExecutor(
                max_workers=threads,
                thread_name_prefix="tpu-exchange-map") as pool:
            futures = [pool.submit(run, p) for p in range(n_tasks)]
            for f in futures:
                f.result()

    # -- reduce side ------------------------------------------------------ #

    def materialize_stats(self) -> list[tuple[int, int]]:
        """Run the map stage (once) and return per-reduce-partition
        (bytes, rows) — the query-stage materialization adaptive
        execution builds on (ref: ShuffleQueryStageExec.mapStats)."""
        self._ensure_map_stage()
        return get_shuffle_manager().partition_stats(
            self._shuffle_id, self.num_partitions)

    def block_counts(self) -> list[int]:
        """Committed blocks per reduce partition (map stage must have
        materialized; callers go through materialize_stats first)."""
        self._ensure_map_stage()
        return get_shuffle_manager().block_counts(
            self._shuffle_id, self.num_partitions)

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        self._ensure_map_stage()
        for b in get_shuffle_manager().read(self._shuffle_id, p):
            yield self._count_output(b)

    def execute_partition_keep(self, p: int) -> Iterator[ColumnarBatch]:
        """Non-consuming variant for readers that visit a reduce
        partition more than once (skew-split slices); blocks stay
        registered until close()/unregister."""
        self._ensure_map_stage()
        for b in get_shuffle_manager().read_keep(self._shuffle_id, p):
            yield self._count_output(b)

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)

    def close(self) -> None:
        """Drop any unread shuffle blocks (a downstream limit may abandon
        reduce partitions; without this their SpillableBatch handles stay
        registered in the process-global store forever)."""
        super().close()
        if self._shuffle_id is not None:
            get_shuffle_manager().unregister(self._shuffle_id)
            self._shuffle_id = None
            self._map_done = False
