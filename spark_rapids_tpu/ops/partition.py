"""Output partitioners.

TPU counterparts of the reference's four partitioning strategies
(ref: GpuHashPartitioning.scala, GpuRoundRobinPartitioning.scala,
GpuSinglePartitioning.scala, GpuRangePartitioning.scala; base mechanics
in GpuPartitioning.scala:45-73 — cudf Table.partition + contiguousSplit).

Here a partitioner produces per-row partition ids on device; the split
into per-partition sub-batches reuses the stable-argsort compaction: one
sort by pid groups rows, a sizing sync reads the per-partition counts,
and one cached program cuts each non-empty partition out of the grouped
batch at the capacity bucket of its count.  Hash
partitioning is murmur3-pmod, bit-for-bit Spark-compatible (the parity
requirement the reference calls out), so a row lands on the same
partition index as it would under Spark CPU."""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch, map_rows
from spark_rapids_tpu.columnar.column import pad_capacity
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


def _cut(cap: int):
    """The program that cuts one partition out of a grouped batch of
    capacity C at capacity `cap`: every leaf's rows `clip(off +
    arange(cap), 0, C - 1)`, padding rows included, the validity masked
    from `cnt` on.  `off` and `cnt` are arguments, so one program
    serves every partition whose count pads to `cap`."""
    def cut(columns, off, cnt):
        c_rows = columns[0].capacity
        # a dynamic slice's start is clamped to C - cap; what the clamp
        # took off shifts the rows, and those past the last row repeat it
        shift = jnp.maximum(off - (c_rows - cap), 0)

        def rows(a):
            head = jax.lax.dynamic_slice_in_dim(a, off, cap)
            tail = jnp.broadcast_to(head[-1:], head.shape)
            return jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([head, tail]), shift, cap)

        live = jnp.arange(cap, dtype=jnp.int32) < cnt
        out = [map_rows(c, rows) for c in columns]
        return [c.with_validity(c.validity & live) for c in out]
    return cut


def split_batch_finish(grouped: ColumnarBatch, counts_np,
                       op: str | None = None
                       ) -> list[tuple[int, ColumnarBatch]]:
    """Cut the non-empty partitions out of a grouped batch once the
    counts are host-side: `(partition, batch)` pairs, each batch at
    `pad_capacity` of its count (never above the grouped capacity),
    by one cached program a partition; an empty partition launches
    nothing.  `counts_np` is any host array-like, typically the
    harvested value of a `device_read`/`device_read_async` on
    split_batch_dispatch's counts; `op` names the programs."""
    from spark_rapids_tpu import trace as _trace
    from spark_rapids_tpu.execs.jit_cache import cached_jit

    counts = [int(c) for c in counts_np]
    cap = grouped.capacity
    leaves, tree = jax.tree_util.tree_flatten(grouped.columns)
    key = ("cut", str(tree),
           tuple((a.shape, a.dtype.name) for a in leaves), cap)
    out = []
    with _trace.span("exchange.cut", op=op, capacity=cap) as sp:
        off = 0
        for p, cnt in enumerate(counts):
            if cnt:
                cut_cap = min(pad_capacity(cnt), cap)
                cut = cached_jit(key + (cut_cap,), lambda: _cut(cut_cap),
                                 op=op)
                cols = cut(grouped.columns, np.int32(off), np.int32(cnt))
                out.append((p, ColumnarBatch(cols, cnt, grouped.schema)))
            off += cnt
        sp.note(parts=len(out), rows=off,
                cut_capacity=sum(b.capacity for _, b in out))
    return out


def split_batch(batch: ColumnarBatch, pids: jax.Array, n_parts: int,
                op: str | None = None) -> list[tuple[int, ColumnarBatch]]:
    """Group rows by partition id and cut out the non-empty partitions
    as `(partition, batch)` pairs.  One device sort + one sizing sync
    per input batch (the analog of cudf's Table.partition returning
    parts + offsets)."""
    if n_parts == 1:
        # single destination: the batch IS the slice (grand-aggregate
        # exchanges hit this constantly)
        return [(0, batch)]
    from spark_rapids_tpu.parallel.pipeline import device_read

    grouped, counts = split_batch_dispatch(batch, pids, n_parts)
    counts_np = device_read(counts, tag="exchange.split")
    return split_batch_finish(grouped, counts_np, op)
