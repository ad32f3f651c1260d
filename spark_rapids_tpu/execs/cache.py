"""Cache exec: materialize-once, re-serve-forever (InMemoryTableScan).

TPU re-design of the reference's cached-batch path (ref: SURVEY
Appendix A — the spark311 shim replaces InMemoryTableScanExec;
docs/additional-functionality/cache-serializer.md describes the
columnar cache serializer).  On this engine a cached subtree's batches
register with the process BufferStore: DEVICE-resident while HBM
allows, spilling to HOST/DISK under pressure like every other
long-lived buffer, and re-materializing on `get()` — so `df.cache()`
costs no dedicated memory pool and participates in the global spill
policy.

First drain: batches stream THROUGH to the consumer while handles
accumulate; the slot publishes only when every partition fully drained
(a LIMIT that stops early must not publish a truncated cache).
Subsequent plans referencing the slot serve straight from the store and
never execute the child (scans are skipped entirely — metric-visible).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec


class TpuCacheExec(TpuExec):
    def __init__(self, slot, child: TpuExec):
        import threading

        super().__init__(child)
        self.slot = slot
        self._staged: dict[int, list] = {}
        self._complete: set[int] = set()
        # partitions may drain concurrently (exchange task threads);
        # the completion check + publish must be one atomic step or two
        # finishers can double-publish (the loser's cleanup would close
        # the winner's handles)
        self._stage_lock = threading.Lock()

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        if self.slot.filled:
            return max(1, len(self.slot.parts))
        return self.children[0].num_partitions

    def node_desc(self) -> str:
        state = "cached" if self.slot.filled else "materializing"
        return f"TpuCacheExec [{state}]"

    def additional_metrics(self):
        return [("cacheHits", "ESSENTIAL"), ("cacheWrites", "ESSENTIAL")]

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        parts = self.slot.parts
        if parts is not None:
            if p >= len(parts):
                return
            for h in parts[p]:
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    out = t.observe(h.get())
                # keep the entry spillable between queries: the
                # consumer's pipeline holds the device arrays it needs;
                # the store may re-spill afterwards.  Unpinned after the
                # timed region has read the arrays' readiness, so no
                # spill deletes them under that read
                h.unpin()
                self.metrics["cacheHits"].add(1)
                yield self._count_output(out)
            return

        from spark_rapids_tpu.memory import SpillPriorities, get_store

        store = get_store()
        staged: list = []
        with self._stage_lock:
            self._staged[p] = staged
        for batch in self.children[0].execute_partition(p):
            n = batch.concrete_num_rows()
            pinned = dataclasses.replace(batch, num_rows=n)
            h = store.register(pinned, SpillPriorities.CACHED)
            h.unpin()
            staged.append(h)
            self.metrics["cacheWrites"].add(1)
            yield self._count_output(batch)
        n_parts = self.children[0].num_partitions
        with self._stage_lock:
            self._complete.add(p)
            if len(self._complete) < n_parts:
                return
            parts = [self._staged.get(i, []) for i in range(n_parts)]
            self._staged = {}
            self._complete = set()
        self.slot.publish(parts)

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)

    def close(self) -> None:
        # a partial drain (LIMIT, error) must not leak store entries
        with self._stage_lock:
            staged, self._staged = self._staged, {}
            self._complete = set()
        for handles in staged.values():
            for h in handles:
                h.close()
        super().close()
