"""Logical plan nodes.

The role Catalyst's logical/physical plans play for the reference: the
engine-neutral description of a query that both the TPU planner
(plan.planner) and the CPU engine (cpu.engine) consume.  Expressions are
the shared Expression trees (unbound ColumnReferences resolved against
child schemas at construction, so every node knows its output schema)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.execs.sort import SortKey
from spark_rapids_tpu.exprs.aggregates import NamedAgg
from spark_rapids_tpu.exprs.base import Expression, bind_references


class LogicalPlan:
    children: list["LogicalPlan"]

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    def estimated_rows(self) -> Optional[int]:
        """Upper-bound row estimate for physical strategy choices (e.g.
        broadcast-vs-shuffle join, ref: CostBasedOptimizer.scala's row
        counts).  None = unknown.  Narrow nodes propagate their child's
        estimate (a filter can only shrink)."""
        if len(self.children) == 1:
            return self.children[0].estimated_rows()
        return None

    def estimated_bytes(self) -> Optional[int]:
        n = self.estimated_rows()
        if n is None:
            return None
        return n * row_width_bytes(self.schema)

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


def row_width_bytes(schema: T.Schema) -> int:
    """Fixed-width physical bytes per row (+1 validity byte per column);
    strings estimated at 32 chars."""
    total = 0
    for f in schema.fields:
        if isinstance(f.dtype, T.StringType):
            total += 32 + 4
        else:
            try:
                total += T.to_numpy_dtype(f.dtype).itemsize
            except TypeError:
                total += 8
        total += 1
    return max(total, 1)


def _output_fields(exprs: Sequence[Expression]) -> T.Schema:
    from spark_rapids_tpu.execs.basic import output_field

    return T.Schema([output_field(e, i) for i, e in enumerate(exprs)])


#: content_digest() computations since process start — the serving
#: test's proof that repeated prepare()s of one in-memory table hash
#: its content once, not once per structural-key build
_DIGESTS_COMPUTED = 0


def digests_computed() -> int:
    return _DIGESTS_COMPUTED


class InMemoryRelation(LogicalPlan):
    """Leaf over a host Arrow table (test sources, fallback boundaries)."""

    def __init__(self, table: pa.Table):
        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        self.children = []
        self.table = table
        self._schema = schema_from_arrow(table.schema)
        self._content_digest: Optional[str] = None

    def content_digest(self) -> str:
        """Memoized content digest of the wrapped table, for structural
        plan keys (serving/plan_cache).  Arrow tables are immutable, so
        hashing once per RELATION is sound — without the memo every
        prepare() of a large in-memory table re-hashed its buffers on
        the serving hot path.  The underscore slot keeps the memo out
        of the structural key itself."""
        global _DIGESTS_COMPUTED
        if self._content_digest is None:
            from spark_rapids_tpu.eventlog import table_digest

            _DIGESTS_COMPUTED += 1
            self._content_digest = table_digest(self.table)
        return self._content_digest

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def estimated_rows(self) -> Optional[int]:
        return self.table.num_rows

    def node_desc(self) -> str:
        return f"InMemoryRelation [{self.table.num_rows} rows]"


def expand_scan_paths(paths: Sequence[str], ext: str
                      ) -> tuple[list[str], list[dict], list[str]]:
    """Expand directory paths into data files, discovering Hive-style
    key=value partition directories written by the file writers
    (ref: the partition-discovery side of Spark's file index; per-file
    partition values feed ColumnarPartitionReaderWithPartitionValues).

    Returns (files, per-file partition-value dicts, partition col names).
    """
    import os

    files: list[str] = []
    values: list[dict] = []
    part_cols: list[str] = []
    for p in paths:
        if not os.path.isdir(p):
            files.append(p)
            values.append({})
            continue
        for root, dirs, names in sorted(os.walk(p)):
            dirs.sort()
            rel = os.path.relpath(root, p)
            pv: dict = {}
            if rel != ".":
                for seg in rel.split(os.sep):
                    if "=" not in seg:
                        pv = None
                        break
                    k, _, v = seg.partition("=")
                    pv[k] = None if v == "__HIVE_DEFAULT_PARTITION__" \
                        else _unescape_part(v)
                if pv is None:
                    continue
            for name in sorted(names):
                if name.startswith(("_", ".")) or not name.endswith(ext):
                    continue
                files.append(os.path.join(root, name))
                values.append(dict(pv))
                for k in pv:
                    if k not in part_cols:
                        part_cols.append(k)
    return files, values, part_cols


def _unescape_part(v: str) -> str:
    import re

    return re.sub("%([0-9A-Fa-f]{2})",
                  lambda m: chr(int(m.group(1), 16)), v)


def infer_partition_fields(part_cols: Sequence[str],
                           values: Sequence[dict]) -> list:
    """Type each partition column: int64 when every value parses, else
    string (the common subset of Spark's partition-type inference)."""
    from spark_rapids_tpu import types as T

    fields = []
    for c in part_cols:
        vs = [pv.get(c) for pv in values]
        dtype: T.DataType = T.LONG
        for v in vs:
            if v is None:
                continue
            try:
                int(v)
            except (TypeError, ValueError):
                dtype = T.STRING
                break
        fields.append(T.Field(c, dtype, True))
    return fields


class _FileRelation(LogicalPlan):
    """Shared Hive-discovered file-scan leaf: path expansion, column/
    partition projection resolution, lazy footer row estimates.
    Partition columns trail the file columns (Spark's layout)."""

    EXT = ""

    def __init__(self, paths: Sequence[str],
                 columns: Optional[Sequence[str]] = None):
        self.children = []
        self.paths, self.partition_values, part_cols = expand_scan_paths(
            list(paths), self.EXT)
        if not self.paths:
            raise FileNotFoundError(f"no {self.EXT} files under {paths}")
        self.partition_fields = infer_partition_fields(
            part_cols, self.partition_values)
        file_schema = self._file_schema(self.paths[0])
        if columns is not None:
            part_names = {f.name for f in self.partition_fields}
            file_cols = [c for c in columns if c not in part_names]
            by_name = {f.name: f for f in file_schema.fields}
            file_fields = [by_name[c] for c in file_cols]
            self.columns: Optional[list[str]] = file_cols
            self.partition_fields = [f for f in self.partition_fields
                                     if f.name in set(columns)]
        else:
            self.columns = None
            file_fields = list(file_schema.fields)
        self._schema = T.Schema(file_fields + self.partition_fields)
        self._est_rows: Optional[int] = None
        self._est_done = False

    def _file_schema(self, path: str) -> T.Schema:
        raise NotImplementedError

    def _file_rows(self, path: str) -> int:
        raise NotImplementedError

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def estimated_rows(self) -> Optional[int]:
        """Lazy (footer reads cost IO; only joins ever ask), memoized."""
        if not self._est_done:
            self._est_done = True
            try:
                self._est_rows = sum(self._file_rows(p)
                                     for p in self.paths)
            except Exception:
                pass
        return self._est_rows

    def node_desc(self) -> str:
        return f"{type(self).__name__} {self.paths}"


class ParquetRelation(_FileRelation):
    """Parquet scan leaf (ref: GpuParquetScan.scala — here the footer/
    row-group handling is pyarrow's; device decode is a later stage).
    Directory paths are expanded with Hive partition discovery; partition
    values surface as trailing columns."""

    EXT = ".parquet"

    def _file_schema(self, path: str) -> T.Schema:
        import pyarrow.parquet as pq

        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        return schema_from_arrow(pq.read_schema(path))

    def _file_rows(self, path: str) -> int:
        import pyarrow.parquet as pq

        return pq.read_metadata(path).num_rows


class CsvRelation(LogicalPlan):
    """CSV scan leaf (ref: GpuCSVScan in GpuBatchScanExec.scala:90)."""

    def __init__(self, paths: Sequence[str],
                 schema: Optional[T.Schema] = None):
        import pyarrow.csv as pacsv

        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        self.children = []
        self.paths, self.partition_values, part_cols = expand_scan_paths(
            list(paths), ".csv")
        if not self.paths:
            raise FileNotFoundError(f"no csv files under {paths}")
        self.partition_fields = infer_partition_fields(
            part_cols, self.partition_values)
        if schema is None:
            head = pacsv.read_csv(self.paths[0])
            schema = schema_from_arrow(head.schema)
        self.file_schema = schema
        self._schema = T.Schema(
            list(schema.fields) + self.partition_fields)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"CsvRelation {self.paths}"


class OrcRelation(_FileRelation):
    """ORC scan leaf (ref: GpuOrcScan.scala — CPU footer parse + device
    decode; here pyarrow's ORC reader decodes stripes on host and the
    scan exec uploads them like Parquet row groups)."""

    EXT = ".orc"

    def _file_schema(self, path: str) -> T.Schema:
        import pyarrow.orc as paorc

        from spark_rapids_tpu.columnar.arrow import schema_from_arrow

        return schema_from_arrow(paorc.ORCFile(path).schema)

    def _file_rows(self, path: str) -> int:
        import pyarrow.orc as paorc

        return paorc.ORCFile(path).nrows


class RangeRel(LogicalPlan):
    def __init__(self, start: int, end: int, step: int = 1):
        self.children = []
        self.start, self.end, self.step = start, end, step
        self._schema = T.Schema([T.Field("id", T.LONG, False)])

    def estimated_rows(self) -> Optional[int]:
        return max(0, -(-(self.end - self.start) // self.step))

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"Range ({self.start}, {self.end}, step={self.step})"


class CacheSlot:
    """Shared materialization slot behind `df.cache()`: filled once by
    the first TPU collect that drains the cached subtree, then every
    plan referencing the slot re-serves the stored batches instead of
    re-running the subtree (the InMemoryTableScanExec replacement the
    reference installs per shim, Spark311Shims.scala + the cache
    serializer doc).  Device batches live in the BufferStore — spillable
    and pin-counted like every other long-lived buffer."""

    def __init__(self):
        import threading

        self.lock = threading.Lock()
        #: list per partition of SpillableBatch handles (None = empty)
        self.parts = None
        self.cpu_table = None  # CPU-engine materialization

    @property
    def filled(self) -> bool:
        return self.parts is not None

    def publish(self, parts) -> None:
        with self.lock:
            if self.parts is None:
                self.parts = parts
            else:  # lost the race: keep first, drop ours
                for handles in parts:
                    for h in handles:
                        h.close()

    def clear(self) -> None:
        with self.lock:
            parts, self.parts = self.parts, None
            self.cpu_table = None
        if parts:
            for handles in parts:
                for h in handles:
                    h.close()


class Cached(LogicalPlan):
    """df.cache()/persist() marker (ref: SURVEY Appendix A
    InMemoryTableScanExec + docs/additional-functionality/
    cache-serializer.md)."""

    def __init__(self, child: LogicalPlan, slot: Optional[CacheSlot]
                 = None):
        self.children = [child]
        self.slot = slot or CacheSlot()

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        state = "materialized" if self.slot.filled else "pending"
        return f"Cached [{state}]"


class Project(LogicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: LogicalPlan):
        self.children = [child]
        self.exprs = [bind_references(e, child.schema) for e in exprs]
        self._schema = _output_fields(self.exprs)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"Project [{', '.join(e.name for e in self.exprs)}]"


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.children = [child]
        self.condition = bind_references(condition, child.schema)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        return f"Filter [{self.condition!r}]"


class Aggregate(LogicalPlan):
    def __init__(self, groups: Sequence[Expression],
                 aggs: Sequence[NamedAgg], child: LogicalPlan):
        self.children = [child]
        self.groups = [bind_references(g, child.schema) for g in groups]
        self.aggs = [NamedAgg(na.fn.bind(child.schema), na.out_name)
                     for na in aggs]
        key_fields = list(_output_fields(self.groups).fields)
        self._schema = T.Schema(
            key_fields + [na.output_field() for na in self.aggs])

    def estimated_rows(self) -> Optional[int]:
        if not self.groups:
            return 1  # grand aggregate: exactly one output row
        return self.children[0].estimated_rows()  # upper bound

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        ks = ", ".join(g.name for g in self.groups)
        asr = ", ".join(f"{na.fn.name}->{na.out_name}" for na in self.aggs)
        return f"Aggregate keys=[{ks}] [{asr}]"


class Expand(LogicalPlan):
    """Multiple projection lists over each input row (ref:
    GpuExpandExec.scala:67): one output row per (input row, projection).
    Grouping-set rewrites (rollup/cube) and distinct-aggregate rewrites
    build on this node the way Spark's analyzer does."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: LogicalPlan):
        assert projections and all(
            len(p) == len(names) for p in projections)
        self.children = [child]
        self.projections = [
            [bind_references(e, child.schema) for e in proj]
            for proj in projections]
        fields = []
        for i, name in enumerate(names):
            dt = None
            for proj in self.projections:
                pdt = proj[i].dtype
                if not isinstance(pdt, T.NullType):
                    dt = pdt
                    break
            fields.append(T.Field(name, dt or T.NULL, True))
        self.names = list(names)
        self._schema = T.Schema(fields)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return (f"Expand [{len(self.projections)} projections, "
                f"{len(self.names)} cols]")


class Generate(LogicalPlan):
    """Generator over each input row (ref: GpuGenerateExec.scala:378):
    child columns repeated per generated row, generator output columns
    appended ('pos' for posexplode, 'col' for the element)."""

    def __init__(self, generator, child: LogicalPlan,
                 out_name: str = "col"):
        from spark_rapids_tpu.exprs.collections import Explode

        assert isinstance(generator, Explode)
        self.children = [child]
        self.generator = generator.with_children(
            [bind_references(generator.child, child.schema)])
        # analysis error, not a fallback: no engine can explode a
        # non-array (Spark raises AnalysisException the same way)
        self.generator.check_supported()
        self.out_name = out_name
        fields = list(child.schema.fields)
        if self.generator.pos:
            fields.append(T.Field("pos", T.INT, self.generator.outer))
        fields.append(T.Field(out_name, self.generator.dtype, True))
        self._schema = T.Schema(fields)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"Generate [{self.generator.name}]"


class Sort(LogicalPlan):
    def __init__(self, keys: Sequence[SortKey], child: LogicalPlan):
        self.children = [child]
        self.keys = [SortKey(bind_references(k.expr, child.schema),
                             k.descending, k.nulls_last) for k in keys]

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        ks = ", ".join(
            f"{k.expr.name}{' DESC' if k.descending else ''}"
            for k in self.keys)
        return f"Sort [{ks}]"


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.children = [child]
        self.n = n

    def estimated_rows(self) -> Optional[int]:
        c = self.children[0].estimated_rows()
        return self.n if c is None else min(self.n, c)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        return f"Limit {self.n}"


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 condition: Optional[Expression] = None,
                 null_safe: Sequence[bool] = ()):
        from spark_rapids_tpu.execs.join import (
            JOIN_TYPES,
            _nullable_fields,
            normalize_null_safe,
        )

        assert join_type in JOIN_TYPES, join_type
        self.children = [left, right]
        self.join_type = join_type
        #: which key pairs compare with `<=>` (NULL equals NULL), one
        #: bool a pair; `()` where none does
        self.null_safe = normalize_null_safe(null_safe, len(left_keys))
        self.left_keys = [bind_references(k, left.schema) for k in left_keys]
        self.right_keys = [bind_references(k, right.schema)
                           for k in right_keys]
        joined = T.Schema(list(left.schema.fields)
                          + list(right.schema.fields))
        self.condition = (bind_references(condition, joined)
                          if condition is not None else None)
        lf, rf = list(left.schema.fields), list(right.schema.fields)
        if join_type in ("left_outer", "full_outer"):
            rf = _nullable_fields(right.schema)
        if join_type in ("right_outer", "full_outer"):
            lf = _nullable_fields(left.schema)
        if join_type in ("left_semi", "left_anti"):
            self._schema = left.schema
        else:
            self._schema = T.Schema(lf + rf)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        from spark_rapids_tpu.execs.join import describe_keys

        ks = describe_keys(self.left_keys, self.right_keys, self.null_safe)
        c = f" cond={self.condition!r}" if self.condition is not None else ""
        return f"Join {self.join_type} [{ks}]{c}"


def _all_columns(plan: LogicalPlan) -> list[Expression]:
    """The plan's output columns by position (a name may stand twice)."""
    from spark_rapids_tpu.exprs.base import BoundReference

    return [BoundReference(i, f.dtype, f.nullable, f.name)
            for i, f in enumerate(plan.schema.fields)]


def is_distinct(plan: LogicalPlan) -> bool:
    """No two rows of the plan's output are equal, by its shape: an
    aggregate grouped by exactly its output columns, or a semi or anti
    join that keeps rows of such a side."""
    if isinstance(plan, Aggregate):
        return bool(plan.groups) and not plan.aggs
    if isinstance(plan, Join) and plan.join_type in ("left_semi",
                                                     "left_anti"):
        return is_distinct(plan.children[0])
    return False


def distinct(plan: LogicalPlan) -> LogicalPlan:
    """SELECT DISTINCT *: an aggregate of every column and no aggregate
    function (NULL is a value of a group key), or the plan itself where
    its shape already says so."""
    if is_distinct(plan):
        return plan
    return Aggregate(_all_columns(plan), [], plan)


def set_operation(left: LogicalPlan, right: LogicalPlan,
                  join_type: str) -> LogicalPlan:
    """INTERSECT (`left_semi`) or EXCEPT (`left_anti`), the DISTINCT
    forms, as Spark's optimizer lowers them
    (ReplaceIntersectWithSemiJoin, ReplaceExceptWithAntiJoin): a
    distinct over a join of the sides' columns by position, every key
    `<=>`.  The sides have as many columns, of the same types."""
    assert join_type in ("left_semi", "left_anti"), join_type
    keys = _all_columns(left)
    return distinct(Join(left, right, keys, _all_columns(right), join_type,
                         null_safe=(True,) * len(keys)))


class Window(LogicalPlan):
    """One (partition_by, order_by) group of window expressions appended
    to the child's output (ref: Spark's WindowExec contract; the session
    frontend splits mixed specs into a chain of Window nodes)."""

    def __init__(self, window_exprs, child: LogicalPlan):
        self.children = [child]
        self.window_exprs = [(we.bind(child.schema), name)
                             for we, name in window_exprs]
        spec0 = self.window_exprs[0][0].spec
        for we, _ in self.window_exprs[1:]:
            assert (we.spec.partition_by, we.spec.order_by) == \
                (spec0.partition_by, spec0.order_by)
        self._schema = T.Schema(
            list(child.schema.fields)
            + [T.Field(name, we.dtype, we.nullable)
               for we, name in self.window_exprs])

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        fns = ", ".join(f"{we.fn.describe()}->{n}"
                        for we, n in self.window_exprs)
        return f"Window [{fns}] ({self.window_exprs[0][0].spec.describe()})"


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        assert children
        self.children = list(children)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def estimated_rows(self) -> Optional[int]:
        total = 0
        for c in self.children:
            n = c.estimated_rows()
            if n is None:
                return None
            total += n
        return total


class GroupedPandas(LogicalPlan):
    """Grouped pandas-UDF nodes (ref: the reference's python exec
    family): kind in {"flatmap", "agg", "window"}; `payload` is the
    user fn (flatmap) or [(out_name, fn, in_col)] (agg/window).
    Requires ClusteredDistribution on `key_names` — the planner
    inserts the hash exchange."""

    def __init__(self, key_names, payload, schema, kind: str,
                 child: LogicalPlan):
        assert kind in ("flatmap", "agg", "window"), kind
        self.children = [child]
        self.key_names = list(key_names)
        self.payload = payload
        self.kind = kind
        self._schema = schema
        for k in self.key_names:
            child.schema.index_of(k)  # raises on unknown key

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"GroupedPandas[{self.kind}] keys={self.key_names}"


class CoGroupedPandas(LogicalPlan):
    """cogroup(...).applyInPandas (ref: GpuFlatMapCoGroupsInPandasExec):
    fn(left group frame, right group frame) -> frame."""

    def __init__(self, left_keys, right_keys, fn, schema,
                 left: LogicalPlan, right: LogicalPlan):
        self.children = [left, right]
        self.left_key_names = list(left_keys)
        self.right_key_names = list(right_keys)
        self.fn = fn
        self._schema = schema
        for k in self.left_key_names:
            left.schema.index_of(k)
        for k in self.right_key_names:
            right.schema.index_of(k)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        return f"CoGroupedPandas keys={self.left_key_names}"


class MapInArrow(LogicalPlan):
    #: True when `fn` is a pandas-frame function (mapInPandas); the
    #: planner then lowers to the pandas exec variant
    pandas = False

    """Arrow-batch python transform over the child (the
    mapInArrow/mapInPandas family the reference schedules onto GPU
    python workers, ref: GpuArrowEvalPythonExec + python/rapids/
    worker.py).  `fn` runs in a process-isolated worker pool; the
    declared schema is the contract both engines cast results to."""

    def __init__(self, fn, schema: T.Schema, child: LogicalPlan):
        self.children = [child]
        self.fn = fn
        self._schema = schema

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def estimated_rows(self):
        return None  # an arbitrary python transform may grow rows

    def node_desc(self) -> str:
        name = getattr(self.fn, "__name__", "fn")
        return f"MapInArrow [{name}]"
