"""Sort and top-N execs.

TPU counterparts of GpuSortExec (ref: sql-plugin/.../GpuSortExec.scala:
FullSortSingleBatch / SortEachBatch / OutOfCoreSort modes) and
GpuTopN/GpuTakeOrderedAndProjectExec (ref: limit.scala:148,260).

Sort keys are arbitrary expressions: they are projected as appended key
columns, the batch is sorted on them via the total-order-key lexsort in
ops.sort, and the appended columns are dropped — the same bind/project
approach the reference takes with SortOrder child expressions.

Inputs up to `spark.rapids.tpu.sql.sort.singleBatchRows` sort as one
device batch (the reference's FullSortSingleBatch).  Larger inputs take
the out-of-core **sample-split sort**: stream the input into spillable
storage while sampling keys, choose range bounds, split every batch into
key-range buckets (vectorized lexicographic bound search on device, ops.
range_partition), park the grouped rows host-side, then sort each
bounded bucket independently and emit buckets in bound order.  This is
the TPU-idiomatic redesign of GpuOutOfCoreSortIterator
(ref: GpuSortExec.scala:213): the reference's cursor-based k-way merge
is row-at-a-time host logic with per-round device round trips; the
sample-split design is two streaming passes of fixed-shape device
programs."""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.column import (
    Column,
    StringColumn,
    pad_capacity,
    pad_width,
)
from spark_rapids_tpu.config import register
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.exprs.base import EvalContext, Expression, bind_references
from spark_rapids_tpu.ops.sort import SortOrder, sort_batch, stable_argsort

SORT_SINGLE_BATCH_ROWS = register(
    "spark.rapids.tpu.sql.sort.singleBatchRows", 1 << 21,
    "Row threshold above which a global sort switches from one-device-"
    "batch sorting to the out-of-core sample-split sort (the "
    "OutOfCoreSort mode analog, ref: GpuSortExec.scala:38-40).")
#: a global sort counts an input batch of more than this capacity
#: before it augments it, and sizes it to its rows: one readback in an
#: operator that waits for all of its input anyway, against a sort
#: paid by capacity.  The aggregate's `_DEFER_SYNC_CAP`, for the same
#: reason.
_COUNT_ABOVE_CAPACITY = 1 << 18

SORT_SAMPLE_PER_BATCH = register(
    "spark.rapids.tpu.sql.sort.samplesPerBatch", 128,
    "Rows sampled from each input batch to estimate range-bucket bounds "
    "for the out-of-core sort (ref: GpuRangePartitioner.sketch).")
SORT_MAX_BUCKETS = register(
    "spark.rapids.tpu.sql.sort.maxBuckets", 64,
    "Upper bound on out-of-core sort range buckets (bound-search program "
    "size grows with bucket count).")


@dataclasses.dataclass
class SortKey:
    """Frontend sort key: expression + direction/null placement."""

    expr: Expression
    descending: bool = False
    nulls_last: bool = False


class _SortMixin(TpuExec):
    def _bind(self, keys: Sequence[SortKey], child: TpuExec):
        self.keys = [SortKey(bind_references(k.expr, child.schema),
                             k.descending, k.nulls_last) for k in keys]

    def _keys_cache_key(self) -> tuple:
        from spark_rapids_tpu.execs.jit_cache import expr_key

        return tuple((expr_key(k.expr), k.descending, k.nulls_last)
                     for k in self.keys)

    def _sorted(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Append evaluated key columns, sort, drop them (traceable)."""
        ctx = EvalContext.for_batch(batch)
        n_data = batch.num_cols
        key_cols = [k.expr.eval(ctx) for k in self.keys]
        aug_schema = T.Schema(
            list(batch.schema.fields)
            + [T.Field(f"__sortkey{i}", k.expr.dtype)
               for i, k in enumerate(self.keys)])
        aug = ColumnarBatch(list(batch.columns) + key_cols, batch.num_rows,
                            aug_schema)
        orders = [SortOrder(n_data + i, k.descending, k.nulls_last)
                  for i, k in enumerate(self.keys)]
        out = sort_batch(aug, orders)
        return ColumnarBatch(out.columns[:n_data], out.num_rows, batch.schema)


class TpuSortExec(_SortMixin):
    """scope='global': total order over all input (one output
    partition); scope='partition': sort each child partition (the
    reduce-side sorter below a range exchange — partition index order
    then equals total order); scope='batch': sort each batch
    independently (the SortEachBatch mode used below partial
    aggregations).  `global_sort=False` is the legacy spelling of
    scope='batch'."""

    #: a global sort counts an input batch of more than
    #: `_COUNT_ABOVE_CAPACITY` and sizes it to its rows BEFORE it
    #: augments and sorts it (see `ingest`).  Without that a sort above
    #: a selective filter runs at the filter's input capacity: q67's
    #: last sort was a 5.55 GB program for 1,100 rows, more than the
    #: chip had left.  A caller that cannot run without it may ask.
    sizes_counted_input = True

    def __init__(self, keys: Sequence[SortKey], child: TpuExec,
                 global_sort: bool = True, scope: Optional[str] = None):
        super().__init__(child)
        self._bind(keys, child)
        if scope is None:
            scope = "global" if global_sort else "batch"
        assert scope in ("global", "partition", "batch"), scope
        self.scope = scope
        self.global_sort = scope == "global"
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        self._jit_sorted = cached_jit(("sort", self._keys_cache_key()),
                                      lambda: self._sorted,
                                      op=self.name)
        # augmented layout: data columns ++ evaluated key columns
        child_schema = child.schema
        self._n_data = len(child_schema.fields)
        self.aug_schema = T.Schema(
            list(child_schema.fields)
            + [T.Field(f"__sortkey{i}", k.expr.dtype)
               for i, k in enumerate(self.keys)])
        self.aug_orders = [SortOrder(self._n_data + i, k.descending,
                                     k.nulls_last)
                           for i, k in enumerate(self.keys)]

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        ks = ", ".join(
            f"{k.expr.name}{' DESC' if k.descending else ''}" for k in self.keys)
        return f"TpuSortExec [{ks}] scope={self.scope}"

    def additional_metrics(self):
        return [("sortBuckets", "MODERATE"), ("oocRows", "MODERATE")]

    @property
    def num_partitions(self) -> int:
        if self.scope == "global":
            return 1
        return self.children[0].num_partitions

    @property
    def output_partitioning(self):
        # a partition-scoped sort preserves the child's distribution
        if self.scope == "partition":
            return self.children[0].output_partitioning
        return None

    # -- traceable pieces ------------------------------------------------ #

    def _augment(self, batch: ColumnarBatch) -> ColumnarBatch:
        ctx = EvalContext.for_batch(batch)
        key_cols = [k.expr.eval(ctx) for k in self.keys]
        return ColumnarBatch(list(batch.columns) + key_cols,
                             batch.num_rows, self.aug_schema)

    def _sort_drop(self, aug: ColumnarBatch) -> ColumnarBatch:
        out = sort_batch(aug, self.aug_orders)
        return ColumnarBatch(out.columns[: self._n_data], out.num_rows,
                             self.schema)

    def _group_by_bounds(self, aug: ColumnarBatch, bounds: ColumnarBatch,
                         n_parts: int):
        """pid per row, rows grouped by bucket, per-bucket counts."""
        from spark_rapids_tpu.ops.range_partition import bucket_ids

        pid = bucket_ids(aug, bounds, self.aug_orders, n_parts - 1)
        live = aug.row_mask()
        key = jnp.where(live, pid, jnp.int32(n_parts))
        order = stable_argsort(key)
        grouped = aug.gather(order, aug.num_rows)
        counts = jax.ops.segment_sum(live.astype(jnp.int32), key,
                                     num_segments=n_parts + 1)[:n_parts]
        return grouped, counts

    # -- driver ---------------------------------------------------------- #

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if self.scope == "global":
            assert self.num_partitions == 1
            if p == 0:
                yield from self.execute()
            return
        if self.scope == "batch":
            for b in self.children[0].execute_partition(p):
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    out = t.observe(self._jit_sorted(
                        b.with_device_num_rows()))
                yield self._count_output(out)
            return
        yield from self._sort_stream(
            self.children[0].execute_partition(p))

    def execute(self) -> Iterator[ColumnarBatch]:
        if self.scope == "global":
            yield from self._sort_stream(self.children[0].execute())
        else:
            for p in range(self.num_partitions):
                yield from self.execute_partition(p)

    def _sort_stream(self, source, depth: int = 0
                     ) -> Iterator[ColumnarBatch]:
        import dataclasses as _dc

        from spark_rapids_tpu.config import get_conf
        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        conf = get_conf()
        single_rows = conf.get(SORT_SINGLE_BATCH_ROWS)
        n_sample = conf.get(SORT_SAMPLE_PER_BATCH)
        store = get_store()
        kkey = self._keys_cache_key()
        jit_aug = cached_jit(("sortaug", kkey, repr(self.aug_schema)),
                             lambda: self._augment, op=self.name)

        # collect phase: augment + register (spillable).  Sampling starts
        # only once the running total crosses the single-batch threshold
        # (small sorts — the common case — pay zero sampling cost);
        # already-registered batches are back-sampled at that point.
        handles: list = []
        rows: list[int] = []
        samples: list[ColumnarBatch] = []
        rng = np.random.default_rng(0x5047 + depth)

        def take_sample(aug, n):
            pos = rng.integers(0, n, n_sample).astype(np.int32)
            jit_sample = cached_jit(
                ("sortsample", kkey, aug.capacity, n_sample,
                 repr(self.aug_schema)),
                lambda: lambda a, p: a.gather(p, n_sample),
                op=self.name)
            samples.append(jit_sample(aug, jnp.asarray(pos, jnp.int32)))

        def pin_deferred() -> None:
            """Fix up capacity-bound row counts with ONE batched fetch
            (deferred batches must not feed sampling or bucket math
            with padding rows counted as live)."""
            nonlocal total
            idxs = list(deferred)
            if not idxs:
                return
            acquired: list = []
            try:
                batches = []
                for i in idxs:
                    batches.append(handles[i].get())
                    acquired.append(handles[i])
                from spark_rapids_tpu.parallel.pipeline import (
                    device_read_many,
                )

                ns = device_read_many([b.num_rows for b in batches],
                                      tag="sort.size")
            except BaseException:
                # a failed acquire/readback must leave the runs
                # evictable: the ladder re-runs this path, and pins
                # left behind would accumulate per attempt, making the
                # out-of-core sort's main memory unspillable
                for h in acquired:
                    h.unpin()
                raise
            for i, b, nn in zip(idxs, batches, ns):
                nn = int(nn)
                total += nn - rows[i]
                rows[i] = nn
                handles[i].unpin()
            deferred.clear()

        from spark_rapids_tpu.execs import retry as R

        try:
            total = 0
            deferred: list[int] = []  # handle indices with capacity-
            # bound row counts (sizing sync skipped)

            def ingest(b) -> None:
                """Augment + register ONE input batch — the
                split-and-retry unit of the OOC sort's collect phase.
                Rolls back its partial bookkeeping (handles/rows/
                samples/deferred/total) on failure so the ladder can
                spill-and-re-run it, or bisect it into two smaller
                runs (more runs is always valid input to the bucket
                merge)."""
                nonlocal total
                h0, r0, s0 = len(handles), len(rows), len(samples)
                d0, t0, rows0 = list(deferred), total, list(rows)
                try:
                    if depth == 0:
                        if not isinstance(b.num_rows, int) \
                                and b.capacity > _COUNT_ABOVE_CAPACITY:
                            # count it first and size it to its rows:
                            # augment, sort and gather are paid by
                            # capacity, and a filter's output keeps its
                            # input's (q67: 1,100 live rows in a bucket
                            # of 2^21)
                            n = b.concrete_num_rows()
                            b = _dc.replace(b, num_rows=n) \
                                .shrink_to_capacity(pad_capacity(n))
                        aug = jit_aug(b.with_device_num_rows())
                    else:
                        aug = b  # recursive input: already augmented
                    if not isinstance(aug.num_rows, int) \
                            and total + aug.capacity <= single_rows:
                        # defer the sizing sync: capacity bounds the
                        # rows, and while the running total stays below
                        # the single-batch threshold the exact count
                        # changes no decision (the sort handles dead
                        # rows).  Each skipped sync saves a device
                        # round trip.  Batches kept capacity-bound
                        # never feed the sample pool.
                        n = aug.capacity
                    else:
                        if deferred:
                            pin_deferred()
                        n = aug.concrete_num_rows()
                        if n == 0:
                            return
                        aug = _dc.replace(aug, num_rows=n)
                    crossing = total <= single_rows < total + n
                    total += n
                    handles.append(store.register(
                        aug, SpillPriorities.COALESCE_PENDING))
                    rows.append(n)
                    if not isinstance(aug.num_rows, int):
                        deferred.append(len(handles) - 1)
                    if crossing and len(handles) > 1:
                        # threshold crossed: back-sample earlier batches
                        for h, hn in zip(handles[:-1], rows[:-1]):
                            prev = h.get()
                            try:
                                take_sample(prev, hn)
                            finally:
                                # a mid-sample failure must not leave
                                # the batch pinned: the ladder's spill
                                # rung needs it evictable on the re-run
                                h.unpin()
                    if total > single_rows:
                        take_sample(aug, n)
                except BaseException:
                    for h in handles[h0:]:
                        h.close()
                    del handles[h0:]
                    rows[:] = rows0[:r0]
                    del samples[s0:]
                    deferred[:] = d0
                    total = t0
                    raise

            for b in source:
                for _ in R.with_split_retry(
                        lambda bb: ingest(bb) or (), b,
                        desc="sort.collect"):
                    pass
            if total == 0:
                return
            if total <= single_rows or len(handles) == 1:
                batches = [h.get() for h in handles]
                if len(batches) > 1:
                    # pin every deferred count in one batched fetch so
                    # the host concat sizes on true rows
                    traced = [i for i, bb in enumerate(batches)
                              if not isinstance(bb.num_rows, int)]
                    if traced:
                        from spark_rapids_tpu.parallel.pipeline import (
                            device_read_many,
                        )

                        ns = device_read_many(
                            [batches[i].num_rows for i in traced],
                            tag="sort.size")
                        for i, nn in zip(traced, ns):
                            batches[i] = _dc.replace(batches[i],
                                                     num_rows=int(nn))
                big = batches[0] if len(batches) == 1 \
                    else concat_batches(batches)
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    out = t.observe(self._jit_sort_drop()(
                        big.with_device_num_rows()))
                for h in handles:
                    h.close()
                handles.clear()
                yield self._count_output(out)
                return
            yield from self._merge_buckets(store, handles, rows, samples,
                                           total, single_rows, depth)
        finally:
            for h in handles:
                h.close()

    def _jit_sort_drop(self):
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        return cached_jit(
            ("sortdrop", self._keys_cache_key(), repr(self.aug_schema)),
            lambda: self._sort_drop, op=self.name)

    def _merge_buckets(self, store, handles, rows, samples, total,
                       single_rows, depth: int = 0
                       ) -> Iterator[ColumnarBatch]:
        """Out-of-core phase: bounds -> per-batch range split (device) ->
        host-parked grouped runs -> per-bucket assemble/sort/emit.

        A bucket that still exceeds the single-batch threshold (skewed
        bounds) is recursively re-sampled and re-split once; past the
        recursion limit it sorts as one oversized batch — a single key
        group larger than device memory is the one shape ranges cannot
        subdivide (the cursor-merge alternative pays steady per-round
        host round trips to handle it; documented tradeoff)."""
        from spark_rapids_tpu.config import get_conf
        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.memory import SpillPriorities
        from spark_rapids_tpu.memory.store import _batch_to_host
        from spark_rapids_tpu.ops.range_partition import choose_bounds

        conf = get_conf()
        kkey = self._keys_cache_key()
        n_parts = min(max(2, -(-total // single_rows)),
                      conf.get(SORT_MAX_BUCKETS))
        self.metrics["sortBuckets"].add(n_parts)
        self.metrics["oocRows"].add(total)

        # bounds from the pooled fixed-size samples (one compiled program)
        k = len(samples)
        n_sample = samples[0].concrete_num_rows()
        pool_live = k * n_sample

        def pool_and_bound(sample_list):
            pooled = concat_batches(sample_list)
            return choose_bounds(pooled, self.aug_orders, n_parts,
                                 pool_live)

        bounds = cached_jit(
            ("sortbounds", kkey, k, n_sample, n_parts,
             tuple(s.capacity for s in samples)),
            lambda: pool_and_bound, op=self.name)(samples)

        # split phase: group each collected batch by bucket, park on host
        runs: list[tuple[object, np.ndarray, np.ndarray]] = []
        run_handles: list = []
        try:
            for h, n in zip(handles, rows):
                aug = h.get()
                jit_group = cached_jit(
                    ("sortgroup", kkey, n_parts, aug.capacity,
                     repr(self.aug_schema),
                     tuple(getattr(c, "width", 0) for c in aug.columns)),
                    lambda: lambda a, bd: self._group_by_bounds(
                        a, bd, n_parts))
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    grouped, counts = jit_group(
                        aug.with_device_num_rows(), bounds)
                    t.observe(grouped)
                from spark_rapids_tpu.parallel.pipeline import device_read

                counts_np = np.asarray(device_read(counts,
                                                   tag="sort.split"))
                import dataclasses as _dc

                grouped = _dc.replace(grouped, num_rows=n)
                arrays = _batch_to_host(grouped)  # D2H + free device copy
                h.close()
                rh = store.register_host(
                    arrays, self.aug_schema,
                    SpillPriorities.COALESCE_PENDING)
                run_handles.append(rh)
                offsets = np.concatenate(
                    [[0], np.cumsum(counts_np)]).astype(np.int64)
                runs.append((rh, counts_np, offsets))
            handles.clear()

            # emit phase: assemble each bucket host-side, sort on device
            fn = self._jit_sort_drop()
            for b in range(n_parts):
                total_b = sum(int(c[b]) for _, c, _ in runs)
                if total_b == 0:
                    continue
                if depth < 1 and total_b > 2 * single_rows:
                    # skewed bucket: recursively sample-split it
                    yield from self._sort_stream(
                        self._bucket_chunks(runs, b, single_rows),
                        depth + 1)
                    continue
                bucket = self._assemble_bucket(runs, b)
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    out = t.observe(fn(bucket.with_device_num_rows()))
                yield self._count_output(out)
        finally:
            for rh in run_handles:
                rh.close()

    def _bucket_chunks(self, runs, b: int, chunk_rows: int
                       ) -> Iterator[ColumnarBatch]:
        """Bucket b's rows as a stream of augmented chunk batches (the
        recursive sample-split input); per-run slicing, no global
        assembly."""
        for rh, counts, offsets in runs:
            cnt = int(counts[b])
            if not cnt:
                continue
            start = int(offsets[b])
            for off in range(0, cnt, chunk_rows):
                m = min(chunk_rows, cnt - off)
                yield self._assemble_range(rh, start + off, m)
            rh.unpin()

    def _assemble_range(self, rh, start: int, m: int) -> ColumnarBatch:
        """One run's rows [start, start+m) as a device aug batch."""
        arrays = rh.get_host()
        cap = pad_capacity(m)
        comps: list[np.ndarray] = []
        recipe: list[tuple] = []
        for ci, f in enumerate(self.aug_schema.fields):
            if isinstance(f.dtype, T.StringType):
                chars = arrays[f"c{ci}_chars"][start:start + m]
                w = chars.shape[1]
                cpad = np.zeros((cap, w), np.uint8)
                cpad[:m] = chars
                lpad = np.zeros(cap, np.int32)
                lpad[:m] = arrays[f"c{ci}_lengths"][start:start + m]
                vpad = np.zeros(cap, np.bool_)
                vpad[:m] = arrays[f"c{ci}_valid"][start:start + m]
                recipe.append(("str", len(comps), f.dtype))
                comps.extend([cpad, lpad, vpad])
            else:
                phys = T.to_numpy_dtype(f.dtype)
                dpad = np.zeros(cap, phys)
                dpad[:m] = arrays[f"c{ci}_data"][start:start + m]
                vpad = np.zeros(cap, np.bool_)
                vpad[:m] = arrays[f"c{ci}_valid"][start:start + m]
                recipe.append(("fixed", len(comps), f.dtype))
                comps.extend([dpad, vpad])
        return self._upload_components(comps, recipe, m)

    def _upload_components(self, comps, recipe, num_rows
                           ) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.arrow import (
            _make_unpack,
            _pack_components,
        )
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        buf, layout = _pack_components(comps)
        unpack = cached_jit(("unpack", layout),
                            lambda: _make_unpack(layout), op=self.name)
        dev = unpack(jnp.asarray(buf))
        cols: list = []
        for kind, i, dtype in recipe:
            if kind == "str":
                cols.append(StringColumn(dev[i], dev[i + 1], dev[i + 2]))
            else:
                cols.append(Column(dev[i], dev[i + 1], dtype))
        return ColumnarBatch(cols, num_rows, self.aug_schema)

    def _assemble_bucket(self, runs, b: int) -> Optional[ColumnarBatch]:
        """Concatenate bucket b's row ranges from every host-parked run
        and upload as one packed transfer."""
        total_b = sum(int(counts[b]) for _, counts, _ in runs)
        if total_b == 0:
            return None
        cap = pad_capacity(total_b)
        fields = self.aug_schema.fields
        # fetch each contributing run's host arrays ONCE (a disk-tier
        # entry reloads its file per get_host call), unpin when done
        contributing = [(rh, rh.get_host(), offsets)
                        for rh, counts, offsets in runs if counts[b]]
        comps: list[np.ndarray] = []
        recipe: list[tuple] = []
        for ci, f in enumerate(fields):
            if isinstance(f.dtype, T.StringType):
                pieces = [(arrays[f"c{ci}_chars"][int(offs[b]):
                                                  int(offs[b + 1])],
                           arrays[f"c{ci}_lengths"][int(offs[b]):
                                                    int(offs[b + 1])],
                           arrays[f"c{ci}_valid"][int(offs[b]):
                                                  int(offs[b + 1])])
                          for _, arrays, offs in contributing]
                w = pad_width(max(p[0].shape[1] for p in pieces))
                chars = np.zeros((cap, w), np.uint8)
                lengths = np.zeros(cap, np.int32)
                valid = np.zeros(cap, np.bool_)
                off = 0
                for pc, pl, pv in pieces:
                    m = len(pl)
                    chars[off:off + m, : pc.shape[1]] = pc
                    lengths[off:off + m] = pl
                    valid[off:off + m] = pv
                    off += m
                recipe.append(("str", len(comps), f.dtype))
                comps.extend([chars, lengths, valid])
            else:
                phys = T.to_numpy_dtype(f.dtype)
                data = np.zeros(cap, phys)
                valid = np.zeros(cap, np.bool_)
                off = 0
                for _, arrays, offs in contributing:
                    s, e = int(offs[b]), int(offs[b + 1])
                    m = e - s
                    data[off:off + m] = arrays[f"c{ci}_data"][s:e]
                    valid[off:off + m] = arrays[f"c{ci}_valid"][s:e]
                    off += m
                recipe.append(("fixed", len(comps), f.dtype))
                comps.extend([data, valid])
        for rh, _, _ in contributing:
            rh.unpin()  # stay spillable between buckets
        return self._upload_components(comps, recipe, total_b)


class TpuTakeOrderedAndProjectExec(_SortMixin):
    """ORDER BY ... LIMIT n: keeps a running top-n batch; each incoming
    batch is concatenated, sorted, and truncated to n (the reference's
    per-batch sort+slice then final sort, limit.scala:148)."""

    def __init__(self, n: int, keys: Sequence[SortKey], child: TpuExec,
                 project: Optional[Sequence[Expression]] = None):
        super().__init__(child)
        assert n >= 0
        self.n = n
        self._bind(keys, child)
        self.project = None
        if project is not None:
            self.project = [bind_references(e, child.schema) for e in project]
            from spark_rapids_tpu.execs.basic import output_field

            self._schema = T.Schema(
                [output_field(e, i) for i, e in enumerate(self.project)])
        else:
            self._schema = child.schema

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"TpuTakeOrderedAndProjectExec n={self.n}"

    def _topn(self, batch: ColumnarBatch) -> ColumnarBatch:
        s = self._sorted(batch)
        return s.slice_prefix(self.n)

    def execute(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.execs.jit_cache import cached_jit, exprs_key

        jit_topn = cached_jit(
            ("topn", self.n, self._keys_cache_key()), lambda: self._topn,
            op=self.name)
        top: Optional[ColumnarBatch] = None
        for b in self.children[0].execute():
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                merged = b if top is None else concat_batches([top, b])
                top = jit_topn(merged.with_device_num_rows())
                # compact so concat_batches sees the concrete top-n rows
                top = ColumnarBatch(top.columns, top.concrete_num_rows(),
                                    top.schema)
        if top is None:
            return
        out = top
        if self.project is not None:
            def proj(batch):
                ctx = EvalContext.for_batch(batch)
                return ColumnarBatch([e.eval(ctx) for e in self.project],
                                     batch.num_rows, self._schema)

            out = cached_jit(
                ("topn_proj", exprs_key(self.project), repr(self._schema)),
                lambda: proj, op=self.name)(out)
        yield self._count_output(out)


class TpuTopNExec(_SortMixin):
    """ORDER BY + LIMIT n as a streaming top-n (ref: GpuTopN /
    Spark's TakeOrderedAndProject) — the full global sort a LIMIT
    would otherwise pay is replaced by a per-batch candidate filter
    plus one tiny final sort.

    Exactness argument: per batch, rows are pruned against the batch's
    n-th best PRIMARY key value under a monotone scalar image of the
    primary order (its float32 rounding, NaN canonicalized to +inf —
    order-preserving, tie-collapsing).  Any row strictly worse
    than n rows on the primary alone cannot be in the global top n
    regardless of tiebreak keys, so keeping every row at-or-beyond the
    threshold (ties included, NULLs per null-placement) is a provable
    superset of the answer.  The final multi-key lexsort then runs over
    only the accumulated candidates (typically O(n) per batch)."""

    def __init__(self, n: int, keys: Sequence[SortKey], child: TpuExec):
        super().__init__(child)
        self.n = n
        self._bind(keys, child)
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        self._jit_cand = cached_jit(
            ("topn_cand", self.n, self._keys_cache_key()),
            lambda: self._candidates, op=self.name)
        self._jit_final = cached_jit(
            ("topnfinal", self.n, self._keys_cache_key()),
            lambda: self._final, op=self.name)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return 1

    def node_desc(self) -> str:
        ks = ", ".join(
            f"{k.expr.name}{' DESC' if k.descending else ''}"
            for k in self.keys)
        return f"TpuTopNExec n={self.n} [{ks}]"

    def additional_metrics(self):
        return [("candidateRows", "MODERATE")]

    # -- traceable ------------------------------------------------------- #

    def _primary_scalar(self, kc):
        """Monotone 'larger = selected by top_k' float32 image of the
        primary sort order.  Rounding to float32 never reorders, it
        only collapses near-ties, which the superset argument allows;
        and top_k over a 32-bit operand is the one the TPU has a
        kernel for (64-bit operands go through a two-operand sort that
        compiles for a minute and a half)."""
        k0 = self.keys[0]
        d = kc.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(jnp.isnan(d), jnp.inf, d)
        v = d.astype(jnp.float32)
        return v if k0.descending else -v

    def _candidates(self, batch: ColumnarBatch) -> ColumnarBatch:
        ctx = EvalContext.for_batch(batch)
        kc = self.keys[0].expr.eval(ctx)
        live = batch.row_mask()
        valid = kc.validity & live
        sm = jnp.where(valid, self._primary_scalar(kc),
                       jnp.float32(-jnp.inf))
        k = min(self.n, batch.capacity)
        thr = jax.lax.top_k(sm, k)[0][k - 1]
        mask = valid & (sm >= thr)
        nulls = live & ~kc.validity
        if self.keys[0].nulls_last:
            # NULLs only matter when non-null rows cannot fill the top n
            short = jnp.sum(valid.astype(jnp.int32)) < self.n
            mask = mask | (nulls & short)
        else:
            # NULLs sort first: every one is a candidate (their mutual
            # order is decided by the tiebreak keys)
            mask = mask | nulls
        return batch.compact(mask)

    def _final(self, batch: ColumnarBatch) -> ColumnarBatch:
        return self._sorted(batch).slice_prefix(self.n)

    # -- driver ---------------------------------------------------------- #

    def execute_partition(self, p: int):
        if p == 0:
            yield from self.execute()

    def execute(self):
        import dataclasses

        from spark_rapids_tpu.columnar.batch import concat_batches
        from spark_rapids_tpu.columnar.column import pad_capacity
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        store = get_store()
        pending: list = []
        try:
            for batch in self.children[0].execute():
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    cand = t.observe(self._jit_cand(
                        batch.with_device_num_rows()))
                pending.append(store.register(
                    cand, SpillPriorities.COALESCE_PENDING))
            if not pending:
                return
            batches = [h.get() for h in pending]
            # ONE batched sizing fetch, then shrink candidates to their
            # (typically O(n)) real size before the final sort
            from spark_rapids_tpu.parallel.pipeline import device_read_many

            ns = [int(v) for v in device_read_many(
                [b.num_rows for b in batches], tag="sort.size")]
            self.metrics["candidateRows"].add(sum(ns))
            shrunk = []
            for b, nn in zip(batches, ns):
                if nn == 0:
                    continue
                b = dataclasses.replace(b, num_rows=nn)
                shrunk.append(b.shrink_to_capacity(pad_capacity(nn)))
            if not shrunk:
                return
            # candidate volume is unbounded in degenerate shapes (a
            # mostly-NULL nulls-first key keeps every null row): reduce
            # HIERARCHICALLY so no single device batch exceeds the cap
            # — each chunk's top n provably contains every global
            # top-n row the chunk holds, so chunk winners compose
            cap_rows = getattr(self, "reduce_cap_rows",
                               max(4 * self.n, 1 << 16))
            while True:
                total = sum(b.concrete_num_rows() for b in shrunk)
                if len(shrunk) == 1 or total <= cap_rows:
                    break
                chunks: list = []
                cur: list = []
                cur_rows = 0
                for b in shrunk:
                    nb = b.concrete_num_rows()
                    if cur and cur_rows + nb > cap_rows:
                        chunks.append(cur)
                        cur, cur_rows = [], 0
                    cur.append(b)
                    cur_rows += nb
                if cur:
                    chunks.append(cur)
                nxt = []
                for ch in chunks:
                    big = ch[0] if len(ch) == 1 else concat_batches(ch)
                    with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                        win = t.observe(self._jit_final(
                            big.with_device_num_rows()))
                    wn = win.concrete_num_rows()
                    win = dataclasses.replace(win, num_rows=wn)
                    nxt.append(win.shrink_to_capacity(pad_capacity(wn)))
                nxt_total = sum(b.concrete_num_rows() for b in nxt)
                shrunk = nxt  # winners are <= n rows each: keep them
                if nxt_total >= total:
                    break  # no further reduction possible
            big = shrunk[0] if len(shrunk) == 1 else \
                concat_batches(shrunk)
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                out = t.observe(self._jit_final(
                    big.with_device_num_rows()))
            yield self._count_output(out)
        finally:
            for h in pending:
                h.close()
