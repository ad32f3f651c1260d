"""Output partitioners.

TPU counterparts of the reference's four partitioning strategies
(ref: GpuHashPartitioning.scala, GpuRoundRobinPartitioning.scala,
GpuSinglePartitioning.scala, GpuRangePartitioning.scala; base mechanics
in GpuPartitioning.scala:45-73 — cudf Table.partition + contiguousSplit).

Here a partitioner produces per-row partition ids on device; the split
into per-partition sub-batches reuses the stable-argsort compaction: one
sort by pid groups rows, a sizing sync reads the per-partition counts,
and each sub-batch is a sliced gather of the grouped batch.  Hash
partitioning is murmur3-pmod, bit-for-bit Spark-compatible (the parity
requirement the reference calls out), so a row lands on the same
partition index as it would under Spark CPU."""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exprs.base import EvalContext, Expression, bind_references
from spark_rapids_tpu.exprs.hashing import partition_ids
from spark_rapids_tpu.ops.sort import stable_argsort


class Partitioning:
    """Computes per-row partition ids for a batch (traceable)."""

    num_partitions: int

    def bind(self, schema) -> "Partitioning":
        return self

    def partition_ids(self, batch: ColumnarBatch) -> jax.Array:
        raise NotImplementedError

    def describe(self) -> str:
        return type(self).__name__


@dataclasses.dataclass
class HashPartitioning(Partitioning):
    exprs: Sequence[Expression]
    num_partitions: int

    def bind(self, schema) -> "HashPartitioning":
        return HashPartitioning(
            [bind_references(e, schema) for e in self.exprs],
            self.num_partitions)

    def partition_ids(self, batch: ColumnarBatch) -> jax.Array:
        ctx = EvalContext.for_batch(batch)
        cols = [e.eval(ctx) for e in self.exprs]
        return partition_ids(cols, batch.capacity, self.num_partitions)

    def describe(self) -> str:
        return (f"hashpartitioning({', '.join(e.name for e in self.exprs)},"
                f" {self.num_partitions})")


@dataclasses.dataclass
class RangePartitioning(Partitioning):
    """Range partitioning for distributed ORDER BY (ref:
    GpuRangePartitioning.scala + GpuRangePartitioner.scala:30,167).
    Bounds are sampled at exchange map time (two-pass map stage); rows
    compare to bounds via the total-order lexicographic keys of
    ops.range_partition, so partition index order IS the sort order."""

    keys: Sequence  # of execs.sort.SortKey
    num_partitions: int

    def bind(self, schema) -> "RangePartitioning":
        from spark_rapids_tpu.execs.sort import SortKey

        return RangePartitioning(
            [SortKey(bind_references(k.expr, schema), k.descending,
                     k.nulls_last) for k in self.keys],
            self.num_partitions)

    def key_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Evaluate the sort-key expressions into a key-column batch
        (traceable); both samples and bounds live in this layout."""
        from spark_rapids_tpu import types as T

        ctx = EvalContext.for_batch(batch)
        cols = [k.expr.eval(ctx) for k in self.keys]
        schema = T.Schema([T.Field(f"__rk{i}", k.expr.dtype)
                           for i, k in enumerate(self.keys)])
        return ColumnarBatch(cols, batch.num_rows, schema)

    def key_orders(self):
        from spark_rapids_tpu.ops.sort import SortOrder

        return [SortOrder(i, k.descending, k.nulls_last)
                for i, k in enumerate(self.keys)]

    def partition_ids_with_bounds(self, batch: ColumnarBatch,
                                  bounds: ColumnarBatch) -> jax.Array:
        """Traceable; `bounds` is a key-layout batch of
        num_partitions-1 rows."""
        from spark_rapids_tpu.ops.range_partition import bucket_ids

        return bucket_ids(self.key_batch(batch), bounds,
                          self.key_orders(), self.num_partitions - 1)

    def partition_ids(self, batch: ColumnarBatch) -> jax.Array:
        raise TypeError("RangePartitioning needs sampled bounds; the "
                        "exchange runs its two-pass map stage")

    def describe(self) -> str:
        ks = ", ".join(
            f"{k.expr.name}{' DESC' if k.descending else ''}"
            for k in self.keys)
        return f"rangepartitioning({ks}, {self.num_partitions})"


@dataclasses.dataclass
class RoundRobinPartitioning(Partitioning):
    num_partitions: int
    start: int = 0

    def partition_ids(self, batch: ColumnarBatch) -> jax.Array:
        idx = jnp.arange(batch.capacity, dtype=jnp.int32)
        return (idx + jnp.int32(self.start)) % jnp.int32(self.num_partitions)

    def describe(self) -> str:
        return f"roundrobin({self.num_partitions})"


@dataclasses.dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids(self, batch: ColumnarBatch) -> jax.Array:
        return jnp.zeros((batch.capacity,), jnp.int32)

    def describe(self) -> str:
        return "single"


def split_batch_dispatch(batch: ColumnarBatch, pids: jax.Array,
                         n_parts: int):
    """Device half of split_batch, NO sync: group rows by partition id
    and count them.  Returns (grouped_batch, device_counts) — the
    sizing readback is the caller's, so a pipelined map loop can
    dispatch batch k+1's sort while batch k's counts are in flight."""
    live = batch.row_mask()
    key = jnp.where(live, pids, jnp.int32(n_parts))
    order = stable_argsort(key)
    grouped = batch.gather(order, batch.num_rows)
    counts = jax.ops.segment_sum(live.astype(jnp.int32), key,
                                 num_segments=n_parts)
    return grouped, counts


def split_batch_finish(grouped: ColumnarBatch, counts_np,
                       n_parts: int) -> list[ColumnarBatch]:
    """Slice the per-partition batches once the counts are host-side.
    `counts_np` is any host array-like — typically the harvested value
    of a `device_read`/`device_read_async` on split_batch_dispatch's
    counts (already host memory; the asarray below is a view, not a
    device sync)."""
    counts_np = np.asarray(counts_np)
    offsets = np.concatenate([[0], np.cumsum(counts_np)])
    out = []
    cap = grouped.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    for p in range(n_parts):
        off, cnt = int(offsets[p]), int(counts_np[p])
        take = jnp.clip(idx + off, 0, cap - 1)
        sub = grouped.gather(take, cnt)
        live_p = idx < cnt
        cols = [c.with_validity(c.validity & live_p) for c in sub.columns]
        out.append(ColumnarBatch(cols, cnt, grouped.schema))
    return out


def split_batch(batch: ColumnarBatch, pids: jax.Array, n_parts: int
                ) -> list[ColumnarBatch]:
    """Group rows by partition id and slice out per-partition batches.
    One device sort + one sizing sync per input batch (the analog of
    cudf's Table.partition returning parts + offsets)."""
    if n_parts == 1:
        # single destination: the batch IS the slice (grand-aggregate
        # exchanges hit this constantly)
        return [batch]
    from spark_rapids_tpu.parallel.pipeline import device_read

    grouped, counts = split_batch_dispatch(batch, pids, n_parts)
    counts_np = device_read(counts, tag="exchange.split")
    return split_batch_finish(grouped, counts_np, n_parts)
