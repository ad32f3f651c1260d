"""Window exec: all window columns of one (partition_by, order_by) group
in a single segmented-scan XLA program.

Counterpart of GpuWindowExec (ref: GpuWindowExec.scala:27,92) — but where
the reference launches one cudf rolling/group-window kernel per window
aggregation, here the batch is sorted once by (partition keys, order
keys) and every window column (ranking, lead/lag, framed aggregates)
derives from shared segmented-scan primitives (ops.window) inside one
fused program.  Output rows are in sorted order (row order of a window
exec's output is unspecified in SQL, as in Spark).

Out-of-core scaling (ref: GpuWindowExec streaming): with a
partition_by, the planner inserts a hash exchange over the partition
keys and sets `partitioned` — window groups are then co-located per
reduce partition and each partition windows independently, bounding
memory to the largest reduce partition instead of the whole input.
Without partition keys (or on single-partition children) the exec
consumes its input as one batch (spill-registered while collecting,
like the sort exec)."""

from __future__ import annotations

from typing import Iterator, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.column import AnyColumn, Column
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.execs.sort import SortKey
from spark_rapids_tpu.exprs.aggregates import Average, Count, CountStar, \
    Max, Min, Sum
from spark_rapids_tpu.exprs.base import EvalContext
from spark_rapids_tpu.exprs.window import (
    DenseRank,
    Lead,
    Rank,
    RowNumber,
    WindowAgg,
    WindowExpression,
)
from spark_rapids_tpu.ops.groupby import _keys_equal_adjacent, _sum_dtype
from spark_rapids_tpu.ops.sort import SortOrder, sort_permutation
from spark_rapids_tpu.ops import window as W


class TpuWindowExec(TpuExec):
    def __init__(self, window_exprs: Sequence[tuple[WindowExpression, str]],
                 child: TpuExec):
        super().__init__(child)
        assert window_exprs
        self.named = [(we.bind(child.schema), name)
                      for we, name in window_exprs]
        spec0 = self.named[0][0].spec
        for we, _ in self.named[1:]:
            assert (we.spec.partition_by, we.spec.order_by) == \
                (spec0.partition_by, spec0.order_by), \
                "one TpuWindowExec handles one (partition, order) group"
        self.spec = spec0
        self._schema = T.Schema(
            list(child.schema.fields)
            + [T.Field(name, we.dtype, we.nullable)
               for we, name in self.named])

    #: True when the child is hash-partitioned on partition_by: window
    #: groups are partition-local, so each partition windows alone
    partitioned = False

    @property
    def schema(self) -> T.Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions if self.partitioned \
            else 1

    def node_desc(self) -> str:
        fns = ", ".join(f"{we.fn.describe()}->{n}" for we, n in self.named)
        tag = " [per-partition]" if self.partitioned else ""
        return f"TpuWindowExec [{fns}] over ({self.spec.describe()})" + tag

    # -- traceable window program --------------------------------------- #

    def _window_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        spec = self.spec
        n_data = batch.num_cols
        cap = batch.capacity
        ctx = EvalContext.for_batch(batch)
        pkey_cols = [e.eval(ctx) for e in spec.partition_by]
        okey_cols = [k.expr.eval(ctx) for k in spec.order_by]

        # sort by (pkeys, okeys); padding rows land at the back
        aug_schema = T.Schema(
            list(batch.schema.fields)
            + [T.Field(f"__pk{i}", e.dtype)
               for i, e in enumerate(spec.partition_by)]
            + [T.Field(f"__ok{i}", k.expr.dtype)
               for i, k in enumerate(spec.order_by)])
        aug = ColumnarBatch(
            list(batch.columns) + pkey_cols + okey_cols,
            batch.num_rows, aug_schema)
        orders = [SortOrder(n_data + i)
                  for i in range(len(pkey_cols))] + \
                 [SortOrder(n_data + len(pkey_cols) + i, k.descending,
                            k.nulls_last)
                  for i, k in enumerate(spec.order_by)]
        perm = sort_permutation(aug, orders)
        saug = aug.gather(perm, aug.num_rows)
        live = saug.row_mask()

        spkeys = saug.columns[n_data:n_data + len(pkey_cols)]
        sokeys = saug.columns[n_data + len(pkey_cols):]
        idx = jnp.arange(cap, dtype=jnp.int32)

        same_part = jnp.ones((cap,), bool)
        for kc in spkeys:
            same_part = same_part & _keys_equal_adjacent(kc)
        is_start = live & ((idx == 0) | ~same_part)

        same_peer = same_part
        for kc in sokeys:
            same_peer = same_peer & _keys_equal_adjacent(kc)
        peer_start = live & ((idx == 0) | ~same_peer)

        start_idx, end_idx = W.segment_positions(is_start, live)
        _, peer_end = W.segment_positions(peer_start, live)

        sctx = EvalContext.for_batch(saug)
        out_cols: list[AnyColumn] = list(saug.columns[:n_data])
        for we, _name in self.named:
            out_cols.append(self._eval_window_fn(
                we, sctx, live, idx, is_start, peer_start,
                start_idx, end_idx, peer_end, cap, sokeys))
        return ColumnarBatch(out_cols, saug.num_rows, self._schema)

    def _eval_window_fn(self, we: WindowExpression, sctx: EvalContext,
                        live, idx, is_start, peer_start,
                        start_idx, end_idx, peer_end, cap: int,
                        sokeys=()) -> AnyColumn:
        fn = we.fn
        if isinstance(fn, RowNumber):
            rn = (idx - start_idx + 1).astype(jnp.int64)
            return Column(rn, live, T.LONG)
        if isinstance(fn, DenseRank):
            d = jnp.cumsum(peer_start.astype(jnp.int64))
            base = jnp.take(d, jnp.clip(start_idx, 0, cap - 1))
            return Column(d - base + 1, live, T.LONG)
        if isinstance(fn, Rank):
            first_peer = jax.lax.cummax(jnp.where(peer_start, idx, 0))
            r = (first_peer - start_idx + 1).astype(jnp.int64)
            return Column(r, live, T.LONG)
        if isinstance(fn, Lead):  # Lag subclasses Lead
            col = fn.child.eval(sctx)
            g, ok = W.gather_in_segment(col, fn.shift, start_idx, end_idx,
                                        live, cap)
            if fn.default is not None:
                dflt = fn.default.eval(sctx)
                data = jnp.where(ok, g.data, dflt.data)
                valid = jnp.where(ok, g.validity, dflt.validity) & live
                return Column(data, valid, col.dtype)
            return g.with_validity(g.validity & ok)
        assert isinstance(fn, WindowAgg), fn
        return self._eval_window_agg(fn, we, sctx, live, is_start,
                                     start_idx, end_idx, peer_end, cap,
                                     peer_start, sokeys)

    def _eval_window_agg(self, fn: WindowAgg, we: WindowExpression, sctx,
                         live, is_start, start_idx, end_idx,
                         peer_end, cap: int, peer_start=None,
                         sokeys=()) -> Column:
        frame = we.spec.resolved_frame()
        if frame.mode == "rows":
            lo, hi = W.frame_bounds(start_idx, end_idx, frame.start,
                                    frame.end, cap)
        elif frame.start is None and frame.end in (None, 0):
            # range: unbounded preceding .. current peer group / end
            lo = start_idx
            hi = end_idx if frame.end is None else peer_end
        else:  # bounded value-based range frame over the one order key
            k = we.spec.order_by[0]
            lo, hi = W.range_frame_bounds(
                sokeys[0], k.descending, not k.nulls_last,
                frame.start, frame.end, start_idx, end_idx,
                peer_start, peer_end, live, cap)
        agg = fn.agg

        if isinstance(agg, CountStar):
            n = (hi - lo + 1).astype(jnp.int64)
            return Column(jnp.maximum(n, 0), live, T.LONG)

        vcol = agg.inputs()[0].eval(sctx)
        assert isinstance(vcol, Column), "window agg over strings"
        if isinstance(agg, Count):
            _, n = W.windowed_sum_count(vcol, lo, hi, live, T.LONG)
            return Column(n, live, T.LONG)
        if isinstance(agg, Sum):
            out_dtype = _sum_dtype(vcol.dtype)
            s, n = W.windowed_sum_count(vcol, lo, hi, live, out_dtype)
            return Column(s, live & (n > 0), out_dtype)
        if isinstance(agg, Average):
            s, n = W.windowed_sum_count(vcol, lo, hi, live, T.DOUBLE)
            denom = jnp.where(n > 0, n, 1).astype(jnp.float64)
            return Column(s / denom, live & (n > 0), T.DOUBLE)
        assert isinstance(agg, (Min, Max)), agg
        op = "min" if isinstance(agg, Min) else "max"
        out, nonempty = W.windowed_minmax(
            vcol, op, is_start, live, lo, hi,
            anchored_start=frame.start is None, cap=cap)
        return Column(out, live & nonempty, vcol.dtype)

    # -- driver ---------------------------------------------------------- #

    def _cache_key(self) -> tuple:
        from spark_rapids_tpu.execs.jit_cache import expr_key, exprs_key

        spec = self.spec
        return ("window",
                exprs_key(spec.partition_by),
                tuple((expr_key(k.expr), k.descending, k.nulls_last)
                      for k in spec.order_by),
                tuple((expr_key_fn(we), n) for we, n in self.named),
                repr(self._schema))

    def _window_source(self, source) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        store = get_store()
        handles = []
        # the child drained to one batch: this operator's wait for
        # those below it, then the concat
        with _trace.span("window.collect") as collected:
            try:
                for b in source:
                    handles.append(store.register(
                        b, SpillPriorities.COALESCE_PENDING))
                if not handles:
                    return
                batches = [h.get() for h in handles]
                big = batches[0] if len(batches) == 1 else \
                    concat_batches(batches, op=self.name)
            finally:
                for h in handles:
                    h.close()
            collected.note(batches=len(batches), capacity=big.capacity,
                           rows=_host_rows(big))
        # partitioned check first: the unpartitioned path must not pay
        # a sizing round trip just to test emptiness (the window program
        # handles zero live rows; empty SOURCES returned above)
        if self.partitioned and big.concrete_num_rows() == 0:
            return  # empty reduce partition
        fn = cached_jit(self._cache_key(), lambda: self._window_batch,
                        op=self.name)
        with _trace.span("window.partition", capacity=big.capacity,
                         rows=_host_rows(big)):
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                out = fn(big.with_device_num_rows())
        # this frame stays suspended while the operators above work on
        # `out`: it must not hold the partition's input beside it
        del batches, big
        yield self._count_output(out)

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if not self.partitioned:
            assert p == 0
            yield from self.execute()
            return
        # hash exchange upstream co-located each window group in one
        # reduce partition: window it independently (bounded memory)
        yield from self._window_source(
            self.children[0].execute_partition(p))

    def execute(self) -> Iterator[ColumnarBatch]:
        if not self.partitioned:
            yield from self._window_source(self.children[0].execute())
            return
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)


def _host_rows(batch: ColumnarBatch):
    """The batch's row count where the host holds it, else None: a
    span never pays a readback for its attributes."""
    return batch.num_rows if isinstance(batch.num_rows, int) else None


def expr_key_fn(we: WindowExpression) -> tuple:
    """Structural key for one window expression (WindowSpec/WindowFrame
    are not Expressions, so expr_key alone would fall back to object
    repr)."""
    from spark_rapids_tpu.execs.jit_cache import expr_key, exprs_key

    fn = we.fn
    frame = we.spec.resolved_frame()
    fk: tuple
    if isinstance(fn, Lead):
        fk = (type(fn).__name__, expr_key(fn.child), fn.offset,
              expr_key(fn.default) if fn.default is not None else None)
    elif isinstance(fn, WindowAgg):
        fk = ("agg", type(fn.agg).__name__, exprs_key(fn.agg.inputs()))
    else:
        fk = (type(fn).__name__,)
    return fk + (frame.mode, frame.start, frame.end)
