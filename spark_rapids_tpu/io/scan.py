"""Scan execs: host-decoded columnar reads uploaded to device.

TPU analog of the reference's scan layer (ref: GpuParquetScan.scala:84 —
CPU footer parse + device decode; GpuCSVScan at GpuBatchScanExec.scala:90).
Stage-5 design from SURVEY.md §7: pyarrow does file decode on host
(multi-threaded C++), and batches are uploaded H2D through the single
arrow seam; device-side Parquet decode (Pallas) is a later optimization.
"""

from __future__ import annotations

import time
from typing import Iterator, Optional, Sequence

import pyarrow as pa

from spark_rapids_tpu import config as _config
from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.arrow import from_arrow, schema_to_arrow
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.base import MetricTimer, TpuExec


def _conf_batch_rows() -> int:
    from spark_rapids_tpu.config import BATCH_SIZE_ROWS, get_conf

    return get_conf().get(BATCH_SIZE_ROWS)


class ArrowSourceExec(TpuExec):
    """Leaf over a host Arrow table: slices it into device batches (the
    receiving end of every CPU->TPU transition, ref: HostColumnarToGpu)."""

    def __init__(self, table: pa.Table, schema: Optional[T.Schema] = None,
                 batch_rows: Optional[int] = None):
        super().__init__()
        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        self.table = table
        self._schema = schema or schema_from_arrow(table.schema)
        self.batch_rows = batch_rows or _conf_batch_rows()

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"ArrowSourceExec [{self.table.num_rows} rows]"

    @property
    def num_partitions(self) -> int:
        return max(1, -(-self.table.num_rows // self.batch_rows))

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        t = self.table
        if t.num_rows == 0:
            yield self._count_output(
                from_arrow(t.cast(schema_to_arrow(self._schema))))
            return
        chunk = t.slice(p * self.batch_rows, self.batch_rows)
        yield self._count_output(from_arrow(chunk))


def constant_column(value, dtype: T.DataType, n: int, cap: int):
    """A device column holding one repeated value for n live rows (the
    partition-value appender, ref:
    ColumnarPartitionReaderWithPartitionValues.scala)."""
    import numpy as np

    from spark_rapids_tpu.columnar.column import Column, StringColumn, pad_width

    if isinstance(dtype, T.StringType):
        b = (value or "").encode("utf-8")
        w = pad_width(max(len(b), 1))
        chars = np.zeros((cap, w), np.uint8)
        lengths = np.zeros(cap, np.int32)
        valid = np.zeros(cap, np.bool_)
        if value is not None:
            chars[:n, : len(b)] = np.frombuffer(b, np.uint8)
            lengths[:n] = len(b)
            valid[:n] = True
        import jax.numpy as jnp

        return StringColumn(jnp.asarray(chars), jnp.asarray(lengths),
                            jnp.asarray(valid))
    vals = np.zeros(n, T.to_numpy_dtype(dtype))
    validity = np.zeros(n, np.bool_)
    if value is not None:
        vals[:] = value
        validity[:] = True
    return Column.from_numpy(vals, dtype, validity, capacity=cap)


FILES_PER_TASK_BYTES = _config.register(
    "spark.rapids.tpu.sql.scan.taskTargetBytes", 512 << 20,
    "Target total file size per scan task: small files coalesce into one "
    "task up to this size (the multi-file reader analog, ref: "
    "GpuParquetScan.scala:882 MultiFileParquetPartitionReader).")

MAX_READ_BATCH_BYTES = _config.register(
    "spark.rapids.tpu.sql.scan.maxReadBatchSizeBytes", 128 << 20,
    "Target device bytes per scanned batch (ref: "
    "spark.rapids.sql.reader.batchSizeBytes, RapidsConf.scala:446). "
    "Scan batches are sized rows = bytes/estimated-row-width: batches "
    "this size amortize per-dispatch/per-transfer latency while still "
    "pipelining decode -> upload -> compute across batches.")

HOST_PREFILTER = _config.register(
    "spark.rapids.tpu.sql.scan.hostPrefilter", True,
    "Evaluate a scan-adjacent Filter's deterministic condition on the "
    "host right after decode and ship only surviving rows across the "
    "host->device link (the filter-pushdown-into-scan contract of "
    "DataSourceV2; ref: the reference's row-group/page pruning, "
    "GpuParquetScan.scala:263-306, taken to row granularity).  The "
    "exact Filter still runs on device — the prefilter only shrinks "
    "the wire, it never decides semantics.")

SCAN_DECODE_THREADS = _config.register(
    "spark.rapids.tpu.sql.scan.decodeThreads", 4,
    "Host threads decoding a task's files concurrently (the multi-file "
    "cloud reader's pool, ref: GpuParquetScan.scala:882-895 "
    "MultiFileCloudParquetPartitionReader).")

FAST_DECODE = _config.register(
    "spark.rapids.tpu.sql.scan.fastDecode", True,
    "Decode supported Parquet column chunks with the native host codec "
    "and evaluate pushed single-column predicates on dictionary values "
    "(io/fastpar.py) instead of the general pyarrow read path — the "
    "host-side mirror of the reference's device page decode (ref: "
    "GpuParquetScan.scala:495-560).  Files with unsupported encodings, "
    "nulls, or nested types silently use the standard path.")


def _task_target_bytes() -> int:
    return _config.get_conf().get(FILES_PER_TASK_BYTES)


def _scan_batch_rows(schema: T.Schema) -> int:
    """Rows per scanned batch from the byte target; an explicitly set
    global batchSizeRows still caps it exactly (tests and memory-tight
    deployments rely on that), as does maxBatchCapacity."""
    import numpy as np

    from spark_rapids_tpu.config import BATCH_SIZE_ROWS, MAX_CAPACITY
    from spark_rapids_tpu.memory.device_manager import (
        effective_batch_size_rows,
    )

    conf = _config.get_conf()
    rows_cap = effective_batch_size_rows(conf)
    if rows_cap == BATCH_SIZE_ROWS.default:
        rows_cap = 64 << 20  # defer to the byte target
    def _w(dt: T.DataType) -> int:
        if isinstance(dt, T.StringType):
            return 40
        if isinstance(dt, T.ListType):
            return 128
        if isinstance(dt, T.StructType):
            return 1 + sum(_w(f2.dtype) for f2 in dt.fields)
        if isinstance(dt, T.MapType):
            return 192
        return np.dtype(T.to_numpy_dtype(dt)).itemsize

    est = 2  # validity byte + slack
    for f in schema.fields:
        est += _w(f.dtype)
    by_bytes = max(1024, conf.get(MAX_READ_BATCH_BYTES) // est)
    # round down to a power of two: full batches then sit exactly on
    # their capacity bucket — no device padding, no wire padding, and
    # one compiled program shape for every full batch
    by_bytes = 1 << (by_bytes.bit_length() - 1)
    return int(max(1, min(rows_cap, by_bytes, conf.get(MAX_CAPACITY))))


def _record_decode(t0_ns: int, fi: int, path: str, tables) -> None:
    """Close a `scan.decode.file` span opened at `t0_ns` on the thread
    that decoded: `tables` is what the decoder handed back (Tables or
    RecordBatches), before partition columns and the host prefilter."""
    _trace.record_complete(
        "scan.decode.file", t0_ns, time.perf_counter_ns() - t0_ns,
        file=fi, path=path, rows=sum(t.num_rows for t in tables),
        bytes=sum(t.nbytes for t in tables))


def _prefetched(gen, stage: str = "scan.decode",
                depth: Optional[int] = None):
    """Run a generator on a background pipeline stage so host-side work
    (footer pruning, Parquet decode) overlaps the consumer's upload +
    device compute (the cloud-reader thread-pool idea, ref:
    GpuParquetScan.scala:882-895 MultiFileCloudParquetPartitionReader).
    Items must stay host-side; device residency belongs to the
    consuming task thread.  Thin shim over the shared
    parallel.pipeline stage (clean join-on-abort shutdown, error
    propagation, occupancy metrics)."""
    from spark_rapids_tpu.parallel.pipeline import prefetch

    return prefetch(gen, depth=depth, stage=stage)


class ParquetScanExec(TpuExec):
    """Multi-file coalesced Parquet scan with footer predicate pushdown.

    - files group into tasks up to a byte target (ref:
      MultiFileParquetPartitionReader, GpuParquetScan.scala:882);
    - a scan-adjacent Filter's condition prunes whole files on Hive
      partition values and row groups on footer min/max statistics
      before any byte is read (ref: filterBlocks :263-306) — the exact
      Filter still runs afterwards;
    - each task's decode+upload runs prefetched on a background thread;
    - per-file Hive partition values append as trailing constants."""

    def __init__(self, paths: Sequence[str], schema: T.Schema,
                 columns: Optional[Sequence[str]] = None,
                 batch_rows: Optional[int] = None,
                 partition_values: Optional[Sequence[dict]] = None,
                 partition_fields: Sequence[T.Field] = ()):
        super().__init__()
        self.paths = list(paths)
        self._schema = schema
        self.columns = list(columns) if columns is not None else None
        self.batch_rows = batch_rows or _scan_batch_rows(schema)
        self.partition_values = list(partition_values or [])
        self.partition_fields = list(partition_fields)
        self.pushed_filter = None  # set by the planner (Filter above)
        #: [(column_name, RuntimeFilter)] registered by the
        #: runtime-filter planner pass (plan/runtime_filter.py): build-
        #: side join-key filters applied host-side before encode+upload
        self.runtime_filters: list = []
        self._groups = self._group_files()

    def _group_files(self) -> list[list[int]]:
        import os

        target = _task_target_bytes()
        groups: list[list[int]] = []
        cur: list[int] = []
        cur_bytes = 0
        for i, p in enumerate(self.paths):
            try:
                sz = os.path.getsize(p)
            except OSError:
                sz = target
            if cur and cur_bytes + sz > target:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += sz
        if cur:
            groups.append(cur)
        return groups or [[]]

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        pf = ""
        if self.pushed_filter is not None:
            pf = f" pushed=[{self.pushed_filter.name}]"
        return (f"ParquetScanExec [{len(self.paths)} files, "
                f"{len(self._groups)} tasks]{pf}")

    def additional_metrics(self):
        return [("scanTime", "MODERATE"),
                ("filesPruned", "ESSENTIAL"),
                ("rowGroupsPruned", "ESSENTIAL"),
                ("hostFilteredRows", "ESSENTIAL"),
                ("rfPrunedRows", "ESSENTIAL"),
                ("rfRowGroupsPruned", "ESSENTIAL")]

    def _ready_runtime_filters(self) -> list:
        """Published filters only — an unpublished filter applies
        nothing (never block the scan on the build side)."""
        return [(n, rf) for n, rf in self.runtime_filters if rf.ready]

    @property
    def num_partitions(self) -> int:
        return len(self._groups)

    def _partition_value(self, p: int, f: T.Field):
        v = self.partition_values[p].get(f.name) \
            if p < len(self.partition_values) else None
        if v is not None and isinstance(f.dtype, T.LongType):
            v = int(v)
        return v

    def _conjuncts(self):
        if self.pushed_filter is None:
            return None
        from spark_rapids_tpu.io.pushdown import split_conjuncts

        return split_conjuncts(self.pushed_filter)

    def _host_partition_array(self, fi: int, f: T.Field,
                              n: int) -> pa.Array:
        """A host Arrow array repeating file fi's partition value."""
        import numpy as np

        atype = schema_to_arrow(T.Schema([f])).field(0).type
        v = self._partition_value(fi, f)
        if v is None:
            return pa.nulls(n, atype)
        if isinstance(f.dtype, T.StringType):
            one = pa.array([str(v)], atype)
        else:
            one = pa.array([v]).cast(atype)
        return one.take(pa.array(np.zeros(n, np.int32)))

    def _partition_only_tables(self, fi: int, n_total: int):
        """Chunks for a projection with no file columns: bare row counts
        (zero-column schema) or repeated partition values."""
        for off in range(0, n_total, self.batch_rows):
            n = min(self.batch_rows, n_total - off)
            if not self.partition_fields:
                yield n
            else:
                yield pa.Table.from_arrays(
                    [self._host_partition_array(fi, f, n)
                     for f in self.partition_fields],
                    [f.name for f in self.partition_fields])

    def _file_tables(self, fi: int, conjuncts):
        """One file's surviving data as HOST Arrow tables (full output
        schema: file columns + repeated partition values), or bare ints
        (row counts) when the projection has zero columns.

        Pruning and Parquet decode run while this generator is iterated
        (on the prefetch thread); uploads happen later on the consuming
        task thread, which holds the TPU semaphore — prefetched data
        waits on HOST, as in the reference's cloud reader."""
        import pyarrow.parquet as pq

        from spark_rapids_tpu.io.pushdown import (
            partition_may_match,
            row_group_may_match,
        )

        if conjuncts is not None and self.partition_fields:
            pv = self.partition_values[fi] \
                if fi < len(self.partition_values) else {}
            if not partition_may_match(conjuncts, self._schema, pv,
                                       self.partition_fields):
                self.metrics["filesPruned"].add(1)
                return

        if self.columns is not None and not self.columns:
            # no file columns to read: only row counts matter
            yield from self._partition_only_tables(
                fi, pq.read_metadata(self.paths[fi]).num_rows)
            return

        f = pq.ParquetFile(self.paths[fi])
        from spark_rapids_tpu.io.rebase import REBASE_MODE_READ, check_rebase

        read_fields = [fl for fl in self._schema.fields
                       if self.columns is None or fl.name in self.columns]
        check_rebase(self.paths[fi], f.metadata, T.Schema(read_fields),
                     getattr(self, "_rebase_mode", None)
                     or _config.get_conf().get(REBASE_MODE_READ))
        n_rgs = f.metadata.num_row_groups
        if conjuncts is not None:
            keep_rgs = [g for g in range(n_rgs)
                        if row_group_may_match(
                            conjuncts, self._schema,
                            f.metadata.row_group(g))]
            self.metrics["rowGroupsPruned"].add(n_rgs - len(keep_rgs))
            if not keep_rgs:
                return
        else:
            keep_rgs = list(range(n_rgs))

        rfs = self._ready_runtime_filters()
        if rfs:
            # runtime-filter min/max as an extra footer conjunct: the
            # build side's key range decides row-group reachability
            # before any byte is decoded
            from spark_rapids_tpu.io.pushdown import (
                runtime_range_may_match,
            )

            before = len(keep_rgs)
            keep_rgs = [g for g in keep_rgs
                        if all(runtime_range_may_match(
                            n, rf, f.metadata.row_group(g))
                            for n, rf in rfs)]
            if before != len(keep_rgs):
                from spark_rapids_tpu.plan import runtime_filter as _RF

                self.metrics["rfRowGroupsPruned"].add(
                    before - len(keep_rgs))
                _RF.record_row_groups_pruned(before - len(keep_rgs))
            if not keep_rgs:
                return

        # `scan.decode.file` spans close before every yield: one left
        # open across it would charge the consumer's time to the decoder
        tracing = _trace.TRACER.enabled
        t0 = time.perf_counter_ns() if tracing else 0
        fast = self._try_fast_tables(f, fi, keep_rgs, conjuncts)
        if fast is not None:
            tables, fast_rf_complete = fast
            if tracing:
                _record_decode(t0, fi, "fast", tables)
            for tbl in tables:
                for f2 in self.partition_fields:
                    tbl = tbl.append_column(
                        f2.name,
                        self._host_partition_array(fi, f2, tbl.num_rows))
                # multi-column conjuncts (not applied by the fast
                # decoder) still prefilter here; survivors are few.
                # Runtime filters the decoder fully applied are NOT
                # re-probed (skip_rf) — the mask is deterministic
                yield self._host_prefilter(tbl,
                                           skip_rf=fast_rf_complete)
            return

        if f.metadata.num_rows <= self.batch_rows:
            # whole file fits one scan batch: single threaded columnar
            # read (iter_batches re-slices row groups and serializes
            # column decode; read_row_groups decodes all columns with
            # the Arrow C++ pool)
            tbl = f.read_row_groups(keep_rgs, columns=self.columns,
                                    use_threads=True)
            if tracing:
                # from t0: a fast attempt that gave up is decode time too
                _record_decode(t0, fi, "pyarrow", (tbl,))
            for f2 in self.partition_fields:
                tbl = tbl.append_column(
                    f2.name,
                    self._host_partition_array(fi, f2, tbl.num_rows))
            yield self._host_prefilter(tbl)
            return
        for rb in f.iter_batches(batch_size=self.batch_rows,
                                 columns=self.columns,
                                 row_groups=keep_rgs,
                                 use_threads=True):
            if tracing:
                # one span for each `next()` of the reader
                _record_decode(t0, fi, "pyarrow", (rb,))
            tbl = pa.Table.from_batches([rb])
            for f2 in self.partition_fields:
                tbl = tbl.append_column(
                    f2.name,
                    self._host_partition_array(fi, f2, rb.num_rows))
            yield self._host_prefilter(tbl)
            t0 = time.perf_counter_ns() if tracing else 0

    def _try_fast_tables(self, f, fi: int, keep_rgs,
                         conjuncts) -> Optional[tuple]:
        """Native fast-decode path (io/fastpar.py): returns (the
        file's surviving rows as host tables, whether runtime filters
        were FULLY applied inside the decoder — so the prefilter can
        skip its redundant re-probe), or None to use pyarrow."""
        if not getattr(self, "_fast_decode", True):
            return None
        from spark_rapids_tpu.io import fastpar

        file_cols = self.columns
        if file_cols is None:
            pnames = {pf.name for pf in self.partition_fields}
            file_cols = [fl.name for fl in self._schema.fields
                         if fl.name not in pnames]
        if not file_cols:
            return None
        use_conjs = conjuncts if getattr(self, "_prefilter_on", False) \
            else None
        rfs = self._ready_runtime_filters()
        counters: dict = {}
        tables = fastpar.read_file(
            self.paths[fi], keep_rgs, file_cols, use_conjs,
            self._schema, pqfile=f,
            max_decoded_bytes=getattr(self, "_max_batch_bytes",
                                      64 << 20),
            runtime_filters=rfs or None, counters=counters)
        if tables is None:
            return None
        rf_pruned = counters.get("rf_pruned", 0)
        if rf_pruned:
            from spark_rapids_tpu.plan import runtime_filter as _RF

            self.metrics["rfPrunedRows"].add(rf_pruned)
            _RF.record_pruned_rows(rf_pruned)
        if use_conjs:
            kept_rg_rows = sum(f.metadata.row_group(g).num_rows
                               for g in keep_rgs)
            after = sum(t.num_rows for t in tables)
            self.metrics["hostFilteredRows"].add(
                kept_rg_rows - after - rf_pruned)
        return tables, bool(rfs) and counters.get("rf_complete", False)

    @staticmethod
    def _harmonize_dicts(tables: list) -> list:
        """Decode dictionary columns to plain wherever the accumulated
        tables disagree (one file kept its Parquet dict, another came
        back plain) — pa.concat_tables requires identical schemas."""
        if len(tables) <= 1 or len({t.schema for t in tables}) <= 1:
            return tables
        out = []
        for t in tables:
            cols, changed = {}, False
            for name in t.schema.names:
                c = t[name]
                if pa.types.is_dictionary(c.type):
                    c = c.cast(c.type.value_type)
                    changed = True
                cols[name] = c
            out.append(pa.table(cols) if changed else t)
        return out

    def _upload(self, tables: list) -> ColumnarBatch:
        tables = self._harmonize_dicts(tables)
        tbl = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
        if getattr(self, "emit_encoded", False) and tbl.num_rows > 0:
            # planner marked the consumer as decode-fusing: ship the
            # batch in wire form; the consumer's program decodes it
            # (one program execution per batch instead of two)
            from spark_rapids_tpu.columnar.transfer import encode_batch

            tbl = tbl.combine_chunks()
            arrays = []
            for c in tbl.columns:
                a = c.combine_chunks() if isinstance(c, pa.ChunkedArray) \
                    else c
                arrays.append(a.chunk(0) if isinstance(a, pa.ChunkedArray)
                              else a)
            eb = encode_batch(arrays, self._schema, tbl.num_rows)
            if eb is not None:
                return eb
        b = from_arrow(tbl)
        return ColumnarBatch(b.columns, b.num_rows, self._schema)

    def _prefilter_active(self) -> bool:
        if self.pushed_filter is None \
                or not _config.get_conf().get(HOST_PREFILTER):
            return False
        from spark_rapids_tpu.exprs.nondeterministic import (
            tree_is_partition_aware,
        )

        # a nondeterministic predicate must evaluate exactly once, on
        # device, with its partition context — never pre-applied
        return not tree_is_partition_aware(self.pushed_filter)

    def _host_prefilter(self, tbl: pa.Table,
                        skip_rf: bool = False) -> pa.Table:
        """Drop rows the pushed Filter must reject, BEFORE they cross
        the wire.  Prefers the compiled pyarrow.compute form (C++
        multi-threaded, GIL-free — decode-speed); falls back to the CPU
        engine's interpreter for predicates outside that subset.
        Conservative only in failure: any evaluation problem disables
        prefiltering and ships everything; the device Filter is always
        the source of truth."""
        if not skip_rf:
            tbl = self._apply_runtime_filters(tbl)
        if not getattr(self, "_prefilter_on", False) or tbl.num_rows == 0:
            # suppression must still run (accumulated tables are
            # concatenated and need one consistent schema)
            return self._suppress_upload_cols(tbl)
        try:
            import pyarrow.compute as pc

            mask = None
            if self._pa_filter is not None:
                try:
                    mask = self._pa_filter(tbl)
                except Exception:
                    # compiled form hit a kernel gap (e.g. date32 vs
                    # int literal): the CPU engine's interpreter below
                    # is the complete fallback
                    self._pa_filter = None
            if mask is None:
                from spark_rapids_tpu.cpu.engine import cpu_eval

                mask = cpu_eval(self.pushed_filter, tbl)
            kept = tbl.filter(pc.fill_null(mask, False))
        except Exception:
            if getattr(self, "exact_prefilter", False):
                # the planner ELIDED the device Filter on the promise
                # that this prefilter is exact — failing silently here
                # would return unfiltered rows as final results
                raise
            self._prefilter_on = False  # unsupported expr: stop trying
            return tbl
        self.metrics["hostFilteredRows"].add(tbl.num_rows - kept.num_rows)
        return self._suppress_upload_cols(kept)

    def _apply_runtime_filters(self, tbl: pa.Table) -> pa.Table:
        """Application point 3 (plan/runtime_filter.py): drop decoded
        rows whose join key provably/probabilistically matches no build
        key, BEFORE they are encoded and cross the wire.  Dictionary
        columns probe their dictionary once (LUT + gather); anything
        the probe cannot model is skipped — pruning is an IO
        optimization, the join stays the source of truth."""
        rfs = self._ready_runtime_filters()
        if not rfs or tbl.num_rows == 0:
            return tbl
        names = set(tbl.schema.names)
        rfs = [(n, rf) for n, rf in rfs if n in names]
        if not rfs:
            return tbl
        from spark_rapids_tpu.io.pa_filter import (
            runtime_filter_column_mask,
        )

        with _trace.span("rf.apply", scan=self.name,
                         rows=tbl.num_rows):
            keep = None
            for name, rf in rfs:
                m = runtime_filter_column_mask(tbl.column(name), rf)
                if m is None:
                    continue
                keep = m if keep is None else (keep & m)
            if keep is None:
                return tbl
            n_keep = int(keep.sum())
            if n_keep == tbl.num_rows:
                return tbl
            kept = tbl.filter(pa.array(keep))
        pruned = tbl.num_rows - kept.num_rows
        from spark_rapids_tpu.plan import runtime_filter as _RF

        self.metrics["rfPrunedRows"].add(pruned)
        _RF.record_pruned_rows(pruned)
        return kept

    def _suppress_upload_cols(self, tbl: pa.Table) -> pa.Table:
        """Replace filter-only columns with all-NULL arrays AFTER the
        host prefilter consumed their values: the planner proved no
        operator above the elided Filter reads them, and the wire
        encoder ships an all-null column as zero bytes (kind 'null').
        Schema and ordinals stay intact, so bound references above are
        unaffected."""
        cols = getattr(self, "null_upload_cols", None)
        if not cols:
            return tbl
        for i, name in enumerate(tbl.schema.names):
            if name in cols:
                ft = tbl.schema.field(i).type
                if pa.types.is_dictionary(ft):
                    ft = ft.value_type
                tbl = tbl.set_column(i, pa.field(name, ft),
                                     pa.nulls(tbl.num_rows, ft))
        return tbl

    def _upload_units(self, items):
        """Accumulate decoded host tables ACROSS row groups and files up
        to batch_rows; yield upload-ready units — int row counts
        (zero-column projections) or lists of host tables summing to at
        most batch_rows.  Pure host work: runs on the decode->upload
        pipeline stage when the planner inserted one."""
        acc: list[pa.Table] = []
        acc_rows = 0
        pending_count = 0  # zero-column case: rows are pure counts
        for item in items:
            if isinstance(item, int):
                pending_count += item
                if pending_count >= self.batch_rows:
                    yield pending_count
                    pending_count = 0
                continue
            acc.append(item)
            acc_rows += item.num_rows
            while acc_rows >= self.batch_rows:
                acc = self._harmonize_dicts(acc)
                tbl = pa.concat_tables(acc) if len(acc) > 1 else acc[0]
                head = tbl.slice(0, self.batch_rows)
                tail = tbl.slice(self.batch_rows)
                yield [head]
                acc = [tail] if tail.num_rows else []
                acc_rows = tail.num_rows
        if pending_count:
            yield pending_count
        if acc_rows:
            yield acc

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        """Accumulates decoded host tables ACROSS row groups and files
        up to batch_rows, then uploads each accumulated chunk in one
        transfer round: few big batches, not many small ones — on TPU
        the per-dispatch/per-transfer latency dominates small batches.

        With cross-tenant sharing on (serving/work_share.py), an
        identical scan task already decoding for another query is
        joined instead of repeated: the first arrival LEADS (decoding
        and publishing its upload units), later arrivals SUBSCRIBE
        and ride the same decode — and, while consumers overlap, the
        same uploaded device batch.  Scans with runtime filters
        registered never share (their pruning is query-dependent)."""
        conjuncts = self._conjuncts()
        self._prefilter_on = self._prefilter_active() \
            or getattr(self, "exact_prefilter", False)
        self._pa_filter = None
        if self._prefilter_on:
            from spark_rapids_tpu.io.pa_filter import compile_filter

            self._pa_filter = compile_filter(self.pushed_filter)
        # conf is THREAD-LOCAL: snapshot on the calling (session) thread
        # — task() runs on the prefetch producer thread, where get_conf()
        # would return a fresh default and silently ignore session
        # settings (decode threads, batch bytes, fastDecode)
        conf = _config.get_conf()
        self._fast_decode = conf.get(FAST_DECODE)
        self._max_batch_bytes = conf.get(MAX_READ_BATCH_BYTES)
        from spark_rapids_tpu.io.rebase import REBASE_MODE_READ

        self._rebase_mode = conf.get(REBASE_MODE_READ)

        from spark_rapids_tpu.parallel import pipeline as P

        depth = getattr(self, "_pipeline_depth", None)
        if depth is None:
            depth = P.stage_depth(conf)

        share = None
        if not self.runtime_filters:
            from spark_rapids_tpu.serving import work_share as _ws

            if _ws.scan_sharing_enabled(conf):
                from spark_rapids_tpu.plan.share_key import (
                    scan_share_key,
                )

                skey = scan_share_key(self, p, conf)
                if skey is not None:
                    share, leader = _ws.SCAN_REGISTRY.begin(skey)
                    if share is not None and not leader:
                        yield from self._subscribe_shared(
                            share, p, conf, conjuncts, depth)
                        return
        yield from self._drain_units(
            self._local_units(conf, conjuncts, p, depth), p,
            share=share)

    def _local_units(self, conf, conjuncts, p: int, depth):
        """The scan's own decode pipeline: prefetched file decode ->
        upload-unit accumulation (optionally on its own pipeline
        stage).  Every decoded item ticks the tapped decode counter —
        THE evidence shared/cached executions decode nothing."""

        def _counted(gen):
            from spark_rapids_tpu.serving.work_share import (
                record_scan_decode,
            )

            for item in gen:
                record_scan_decode(
                    item if isinstance(item, int) else item.num_rows)
                yield item

        def task():
            import os

            files = self._groups[p]
            # the pool materializes each file's decoded tables before
            # yielding, so it is bounded to files that fit one scan
            # batch (threads x batch bytes of host memory); bigger
            # files keep the one-table-at-a-time streaming path.  The
            # gate compares COMPRESSED on-disk size, so it budgets a
            # conservative 4x decode expansion (dict/RLE+snappy)
            def _size_or_big(path: str) -> int:
                # un-stat-able paths (object-store/remote URIs) must count
                # as big: excluding them would let the pool materialize
                # unbounded decoded tables, defeating the memory gate
                try:
                    return os.path.getsize(path)
                except OSError:
                    return 1 << 62

            big = any(
                _size_or_big(self.paths[fi]) >
                self._max_batch_bytes // 4
                for fi in files)
            threads = min(conf.get(SCAN_DECODE_THREADS), len(files))
            if threads <= 1 or big:
                for fi in files:
                    yield from _counted(self._file_tables(fi,
                                                          conjuncts))
                return
            # per-file decode pool with a bounded in-flight window (the
            # MultiFileCloud reader shape): file k+threads starts while
            # file k's tables are being consumed, order preserved
            from concurrent.futures import ThreadPoolExecutor

            # thread-locals do not follow the work onto the pool: hand
            # it the query's trace context as prefetch hands it to us
            tctx = _trace.current_context()

            def decode(fi):
                with _trace.attach_context(tctx):
                    return list(_counted(self._file_tables(fi,
                                                           conjuncts)))

            with ThreadPoolExecutor(
                    max_workers=threads,
                    thread_name_prefix="tpu-scan-decode") as pool:
                pending = []
                it = iter(files)
                for fi in it:
                    pending.append(pool.submit(decode, fi))
                    if len(pending) >= threads:
                        break
                while pending:
                    done = pending.pop(0)
                    nxt = next(it, None)
                    if nxt is not None:
                        pending.append(pool.submit(decode, nxt))
                    yield from done.result()

        from spark_rapids_tpu.parallel import pipeline as P

        units = self._upload_units(
            _prefetched(task(), stage="scan.decode", depth=depth))
        if depth:
            # decode->upload boundary: accumulation/slicing (host CPU
            # work) runs one stage ahead of the consumer's upload +
            # device compute; units are host tables (no device
            # residency crosses the stage queue)
            units = P.prefetch(units, depth=depth, stage="scan.upload")
        return units

    def _empty_scan_batch(self) -> ColumnarBatch:
        aschema = schema_to_arrow(self._schema)
        return from_arrow(pa.Table.from_arrays(
            [pa.array([], fl.type) for fl in aschema],
            schema=aschema))

    def _drain_units(self, units, p: int, share=None,
                     skip: int = 0) -> Iterator[ColumnarBatch]:
        """Upload-and-yield loop over upload units.  As the LEADER of
        a shared scan (`share` set), every unit is published for
        subscribers — plain decoded device batches ride along so
        overlapping consumers skip their own upload; wire-form
        EncodedBatches never do (donation bookkeeping makes them
        mutable).  `skip` replays a deterministic prefix without
        re-uploading it (the subscriber-fallback path: those batches
        were already served from the aborted share entry)."""
        empty = True
        completed = False
        try:
            for i, unit in enumerate(units):
                empty = False
                if i < skip:
                    continue
                # scanTime: host-unit -> device-batch (encode + upload
                # dispatch, settled when the device work completes) —
                # the reference's GpuScan scan-time metric; the decode
                # wait ahead of it lives on the scan.decode stage
                with MetricTimer(self.metrics["scanTime"],
                                 op=self.name) as t:
                    if isinstance(unit, int):
                        b = ColumnarBatch([], unit, self._schema)
                    else:
                        b = t.observe(self._upload(unit))
                if share is not None:
                    share.publish(
                        unit, b if type(b) is ColumnarBatch else None)
                yield self._count_output(b)
            completed = True
        finally:
            if share is not None:
                from spark_rapids_tpu.serving import work_share as _ws

                if completed:
                    share.complete()
                else:
                    # died or was abandoned mid-stream: wake the
                    # subscribers so they fall back to their own
                    # decode instead of waiting forever
                    share.abort()
                _ws.SCAN_REGISTRY.release(share)
        if empty and skip == 0 and p == 0:
            yield self._count_output(self._empty_scan_batch())

    def _subscribe_shared(self, share, p: int, conf, conjuncts,
                          depth) -> Iterator[ColumnarBatch]:
        """Ride another query's identical scan: replay its buffered
        upload units (and, while in flight, its uploaded device
        batches), then follow live.  If the leader aborts mid-stream,
        fall back to a local decode, skipping the deterministic
        prefix already served."""
        from spark_rapids_tpu.serving import work_share as _ws

        _ws.tick("scan_subscribes")
        consumed = 0
        aborted = False
        try:
            for unit, dev in share.subscribe_units():
                with MetricTimer(self.metrics["scanTime"],
                                 op=self.name) as t:
                    if dev is not None:
                        _ws.tick("scan_upload_shared")
                        b = dev
                    elif isinstance(unit, int):
                        b = ColumnarBatch([], unit, self._schema)
                    else:
                        b = t.observe(self._upload(unit))
                _ws.tick("scan_units_shared")
                consumed += 1
                yield self._count_output(b)
        except _ws.ScanShareAborted:
            aborted = True
        finally:
            _ws.SCAN_REGISTRY.release(share)
        if aborted:
            yield from self._drain_units(
                self._local_units(conf, conjuncts, p, depth), p,
                skip=consumed)
            return
        if consumed == 0 and p == 0:
            yield self._count_output(self._empty_scan_batch())


class OrcScanExec(ParquetScanExec):
    """ORC scan: stripes play the role of row groups (ref:
    GpuOrcScan.scala — stripe-granular reads).  Reuses the Parquet
    exec's task coalescing, host accumulation, partition pruning and
    prefetching; footer min/max stripe pruning is skipped (pyarrow does
    not expose ORC stripe statistics)."""

    def node_desc(self) -> str:
        pf = ""
        if self.pushed_filter is not None:
            pf = f" pushed=[{self.pushed_filter.name}]"
        return (f"OrcScanExec [{len(self.paths)} files, "
                f"{len(self._groups)} tasks]{pf}")

    def _file_tables(self, fi: int, conjuncts):
        import pyarrow.orc as paorc

        from spark_rapids_tpu.io.pushdown import partition_may_match

        if conjuncts is not None and self.partition_fields:
            pv = self.partition_values[fi] \
                if fi < len(self.partition_values) else {}
            if not partition_may_match(conjuncts, self._schema, pv,
                                       self.partition_fields):
                self.metrics["filesPruned"].add(1)
                return

        f = paorc.ORCFile(self.paths[fi])
        if self.columns is not None and not self.columns:
            yield from self._partition_only_tables(fi, f.nrows)
            return

        for si in range(f.nstripes):
            rb = f.read_stripe(si, columns=self.columns)
            tbl = pa.Table.from_batches([rb])
            for f2 in self.partition_fields:
                tbl = tbl.append_column(
                    f2.name,
                    self._host_partition_array(fi, f2, tbl.num_rows))
            yield self._host_prefilter(tbl)


class CsvScanExec(TpuExec):
    def __init__(self, paths: Sequence[str], schema: T.Schema,
                 batch_rows: Optional[int] = None,
                 partition_values: Optional[Sequence[dict]] = None,
                 partition_fields: Sequence[T.Field] = ()):
        super().__init__()
        self.paths = list(paths)
        self._schema = schema
        self.batch_rows = batch_rows or _conf_batch_rows()
        self.partition_values = list(partition_values or [])
        self.partition_fields = list(partition_fields)
        n_file = len(schema.fields) - len(self.partition_fields)
        self.file_aschema = schema_to_arrow(
            T.Schema(schema.fields[:n_file]))

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"CsvScanExec {self.paths}"

    @property
    def num_partitions(self) -> int:
        return len(self.paths)

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        import pyarrow.csv as pacsv

        t = pacsv.read_csv(self.paths[p]).cast(self.file_aschema)
        for off in range(0, max(t.num_rows, 1), self.batch_rows):
            chunk = t.slice(off, self.batch_rows)
            batch = from_arrow(chunk)
            if self.partition_fields:
                n = batch.concrete_num_rows()
                cap = max(batch.capacity, 1)
                cols = list(batch.columns)
                for f in self.partition_fields:
                    v = self.partition_values[p].get(f.name) \
                        if p < len(self.partition_values) else None
                    if v is not None and isinstance(f.dtype, T.LongType):
                        v = int(v)
                    cols.append(constant_column(v, f.dtype, n, cap))
                batch = ColumnarBatch(cols, batch.num_rows, self._schema)
            yield self._count_output(batch)
