"""Coalesce execs: batch coalescing within a partition, and N child
partitions -> 1 pulled concurrently.

Three reference mechanisms meet here:
- GpuCoalesceBatches (ref: GpuCoalesceBatches.scala:340 with the
  targetSizeBytes goal): concatenate consecutive small columnar batches
  up to a target size before expensive operators, so fused chains,
  joins and aggregates run dense programs over few large blocks instead
  of many starved ones — TpuCoalesceBatchesExec below, inserted by the
  planner under spark.rapids.tpu.sql.coalesce.enabled
  (docs/occupancy.md), and on the stream side of every semi or anti
  join, whatever the conf (execs/join.py);
- the plan shape of CoalesceExec / a SinglePartitioning exchange feeding
  a grand aggregate (ref: GpuShuffleExchangeExec.scala:80 with
  GpuSinglePartitioning) — but without the shuffle-manager detour: a
  single consumer needs no partitioned blocks, so routing one-destination
  exchanges through spill-registered shuffle storage is pure overhead;
- the multi-file cloud reader's background thread pool
  (ref: GpuParquetScan.scala:882-895 MultiFileCloudParquetPartitionReader):
  worker threads run whole child partitions (host decode, H2D upload, the
  per-batch jitted programs) ahead of the consumer, so upload and device
  compute overlap across partitions.  A bounded queue provides
  backpressure; the task semaphore caps device residency per worker.

Output order is partition-completion order (like Spark's reduce-side
pulls, batch order *within* a partition is preserved; order across
partitions is not guaranteed — callers needing total order must sort).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.config import MAX_CAPACITY, get_conf, register
from spark_rapids_tpu.execs.base import (
    NUM_INPUT_BATCHES,
    NUM_INPUT_ROWS,
    MetricTimer,
    TpuExec,
)
from spark_rapids_tpu.memory import TpuSemaphore

_DONE = object()

COALESCE_ENABLED = register(
    "spark.rapids.tpu.sql.coalesce.enabled", False,
    "Insert TpuCoalesceBatchesExec below fused chains, joins, "
    "aggregates and sorts: consecutive small device batches are "
    "concatenated up to coalesce.targetRows / coalesce.targetBytes "
    "before the expensive operator, so its programs run dense over few "
    "large blocks instead of starved over many small ones (ref: "
    "GpuCoalesceBatches + targetSizeBytes).  Off (the default) the "
    "plan is bit-for-bit unchanged; on, results are bit-identical — "
    "coalescing only re-buckets rows (docs/occupancy.md).")
COALESCE_TARGET_ROWS = register(
    "spark.rapids.tpu.sql.coalesce.targetRows", 1 << 20,
    "Row-count goal per coalesced batch: buffered batches flush once "
    "their combined live rows reach this (the TPU analog of the "
    "reference's targetSizeBytes goal — rows, because XLA programs are "
    "specialized per capacity bucket).",
    check=lambda v: v > 0)
COALESCE_TARGET_BYTES = register(
    "spark.rapids.tpu.sql.coalesce.targetBytes", 128 << 20,
    "Device-byte goal per coalesced batch: buffered batches flush once "
    "their combined device footprint reaches this, whichever of the "
    "row/byte goals hits first (ref: "
    "spark.rapids.sql.batchSizeBytes).",
    check=lambda v: v > 0)


def coalesce_enabled(conf=None) -> bool:
    return bool((conf or get_conf()).get(COALESCE_ENABLED))


class TpuCoalesceBatchesExec(TpuExec):
    """Concatenate consecutive small device batches up to a target size.

    The TPU redesign of GpuCoalesceBatches: instead of cudf's
    Table.concatenate per flush, one CACHED concat program per observed
    (capacities, row-counts) shape packs every part into a fresh
    pad_capacity(total) bucket with dynamic_update_slice — row counts
    are host-known here, so the offsets are static and the program is
    pure data movement (no compaction scan).  Composition contracts:

    - only batches with HOST-known row counts buffer (scan/cache/CPU
      outputs); traced-count batches (filters mid-stream) pass through
      unchanged — coalescing them would force a device sync per batch;
    - EncodedBatch inputs decode eagerly first (the cached decode
      program), so wire components compose;
    - each coalesced output remembers its input row counts in
      `coalesce_seams` (host-side attribute, not part of the pytree):
      the retry ladder's bisect splits along the seam nearest the
      midpoint, so an OOM inside a downstream program retries on the
      original input granularity instead of arbitrary halves;
    - the output is a regular prefix-compact batch: donation,
      speculation and the spill store see nothing new;
    - `counts_vary=True` is for inputs whose row counts change with the
      data, an exchange's blocks: the concat then takes no shape from a
      count (`concat_batches`' cached placement programs, one a piece
      and leaf), so a new count compiles nothing.
    """

    def __init__(self, child: TpuExec,
                 target_rows: Optional[int] = None,
                 target_bytes: Optional[int] = None,
                 goal_rows: Optional[int] = None,
                 counts_vary: bool = False):
        super().__init__(child)
        # goal_rows: the pre-occupancy exec's parameter name, kept for
        # callers that built plans against it
        self._target_rows = target_rows if target_rows is not None \
            else goal_rows
        self._target_bytes = target_bytes
        self._counts_vary = counts_vary

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    @property
    def output_partitioning(self):
        return self.children[0].output_partitioning

    def node_desc(self) -> str:
        return "TpuCoalesceBatchesExec"

    def additional_metrics(self):
        return [(NUM_INPUT_ROWS, "MODERATE"),
                (NUM_INPUT_BATCHES, "MODERATE"),
                ("numConcats", "MODERATE"),
                ("concatTime", "MODERATE")]

    def _goals(self) -> tuple[int, int, int]:
        conf = get_conf()
        rows = self._target_rows if self._target_rows is not None \
            else int(conf.get(COALESCE_TARGET_ROWS))
        nbytes = self._target_bytes if self._target_bytes is not None \
            else int(conf.get(COALESCE_TARGET_BYTES))
        return rows, nbytes, int(conf.get(MAX_CAPACITY))

    def _concat(self, buf: list[ColumnarBatch]) -> ColumnarBatch:
        """One cached concat program per (schema widths, capacities,
        row counts) shape.  ns are static (host-known) so they sit in
        the structural key — bounded in practice because scans emit
        fixed-size batches with at most one ragged tail per file, and
        the program-census test keeps this honest."""
        from spark_rapids_tpu.columnar.column import pad_capacity
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        ns = tuple(b.num_rows for b in buf)
        caps = tuple(b.capacity for b in buf)
        if self._counts_vary:
            def fn(parts):
                return concat_batches(parts, op=self.name)
        else:
            # the output bucket depends on the thread's capacity POLICY
            # (pow2 vs pow2x3), which the traced pad_capacity call reads
            # at trace time — fold the resolved capacity into the key so
            # sessions under different policies never share this program
            key = ("coalesce", caps, ns, pad_capacity(sum(ns)))
            fn = cached_jit(key, lambda: concat_batches, op=self.name)
        with MetricTimer(self.metrics["concatTime"], op=self.name) as t:
            out = t.observe(fn(buf))
        self.metrics["numConcats"].add(1)
        # host-side seam record for the retry ladder's bisect — NOT in
        # the pytree, so it lives exactly as long as this host object
        out.coalesce_seams = ns
        return out

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.columnar.transfer import EncodedBatch
        from spark_rapids_tpu.memory.store import batch_device_bytes

        target_rows, target_bytes, max_cap = self._goals()
        buf: list[ColumnarBatch] = []
        buf_rows = 0
        buf_bytes = 0

        def flush():
            nonlocal buf, buf_rows, buf_bytes
            if not buf:
                return None
            out = buf[0] if len(buf) == 1 else self._concat(buf)
            buf, buf_rows, buf_bytes = [], 0, 0
            return out

        for batch in self.children[0].execute_partition(p):
            if isinstance(batch, EncodedBatch):
                if batch.num_rows is None:
                    out = flush()
                    if out is not None:
                        yield self._count_output(out)
                    yield self._count_output(batch)
                    continue
                batch = batch.decode_now()
            if type(batch.num_rows) is not int:
                # traced row count: sizing it would sync — pass through
                out = flush()
                if out is not None:
                    yield self._count_output(out)
                yield self._count_output(batch)
                continue
            n = batch.num_rows
            nbytes = batch_device_bytes(batch)
            self.metrics[NUM_INPUT_BATCHES].add(1)
            self.metrics[NUM_INPUT_ROWS].add(n)
            if buf and buf_rows + n > max_cap:
                out = flush()
                if out is not None:
                    yield self._count_output(out)
            buf.append(batch)
            buf_rows += n
            buf_bytes += nbytes
            if buf_rows >= target_rows or buf_bytes >= target_bytes:
                out = flush()
                if out is not None:
                    yield self._count_output(out)
        out = flush()
        if out is not None:
            yield self._count_output(out)


class TpuCoalescePartitionsExec(TpuExec):
    def __init__(self, child: TpuExec):
        super().__init__(child)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        return "TpuCoalescePartitionsExec"

    def additional_metrics(self):
        return [("fetchWaitTime", "MODERATE")]

    def execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.config import get_conf
        from spark_rapids_tpu.execs.exchange import TASK_THREADS

        child = self.children[0]
        n_parts = child.num_partitions
        threads = min(get_conf().get(TASK_THREADS), max(n_parts, 1))
        if n_parts <= 1 or threads <= 1:
            for b in child.execute():
                yield self._count_output(b)
            return

        out_q: queue.Queue = queue.Queue(maxsize=threads * 2)
        stop = threading.Event()
        next_part = iter(range(n_parts))
        part_lock = threading.Lock()

        # thread-locals do not follow the work onto the task threads:
        # the query's trace context is handed over as prefetch hands it
        tctx = _trace.current_context()

        def worker() -> None:
            with _trace.attach_context(tctx):
                run_tasks()

        def run_tasks() -> None:
            sem = TpuSemaphore.get()
            task_id = threading.get_ident()
            try:
                while not stop.is_set():
                    with part_lock:
                        p = next(next_part, None)
                    if p is None:
                        break
                    for batch in child.execute_partition(p):
                        sem.acquire_if_necessary(task_id)
                        while not stop.is_set():
                            try:
                                out_q.put(batch, timeout=0.1)
                                break
                            except queue.Full:
                                continue
                        if stop.is_set():
                            return
            except BaseException as e:  # surface to the consumer
                out_q.put(e)
            finally:
                sem.release_if_necessary(task_id)
                out_q.put(_DONE)

        workers = [threading.Thread(target=worker, daemon=True,
                                    name=f"tpu-coalesce-task-{i}")
                   for i in range(threads)]
        for w in workers:
            w.start()
        done = 0
        import time

        try:
            while done < threads:
                t0 = time.perf_counter_ns()
                item = out_q.get()
                self.metrics["fetchWaitTime"].add(
                    time.perf_counter_ns() - t0)
                if item is _DONE:
                    done += 1
                elif isinstance(item, BaseException):
                    raise item
                else:
                    yield self._count_output(item)
        finally:
            # consumer abandoned (limit) or raised: unblock workers
            stop.set()
            while done < threads:
                item = out_q.get()
                if item is _DONE:
                    done += 1
            for w in workers:
                w.join()
