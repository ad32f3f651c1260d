"""Device-resident columnar batches.

TPU-native counterpart of the reference's Spark `ColumnarBatch` of
GpuColumnVectors (ref: GpuColumnVector.java:571,603) plus the coalescing
machinery of GpuCoalesceBatches (ref: GpuCoalesceBatches.scala:133-455).

Invariants:
- all columns share one static `capacity` (power-of-two bucket);
- valid rows are a *prefix*: rows [0, num_rows) are live, the rest padding;
- `num_rows` may be a Python int (statically known, e.g. straight from a
  scan) or a traced/device int32 scalar (e.g. after a filter).  Operators
  must work with both; host materialization forces a sync.

The prefix-compact invariant is what lets aggregations/sorts/joins run as
fixed-shape XLA programs with a row-activity mask derived from
`arange(capacity) < num_rows`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (
    AnyColumn,
    Column,
    ListColumn,
    MapColumn,
    StringColumn,
    StructColumn,
    pad_capacity,
    pad_width,
)

RowCount = Union[int, jax.Array]

#: device scalar cache: row counts repeat heavily (full batches, tiny
#: partials) and an eager scalar upload is a full dispatch round trip on
#: high-latency device links, so promote each distinct value once
_DEVICE_INT_CACHE: dict[int, jax.Array] = {}
_DEVICE_INT_LOCK = __import__("threading").Lock()


def _device_int32(v: int) -> jax.Array:
    with _DEVICE_INT_LOCK:
        a = _DEVICE_INT_CACHE.get(v)
        if a is None or a.is_deleted():
            if len(_DEVICE_INT_CACHE) > 4096:
                _DEVICE_INT_CACHE.clear()
            a = _DEVICE_INT_CACHE[v] = jnp.asarray(v, jnp.int32)
        return a


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarBatch:
    columns: list[AnyColumn]
    num_rows: RowCount
    schema: T.Schema

    def tree_flatten(self):
        static_rows = self.num_rows if isinstance(self.num_rows, int) else None
        if static_rows is None:
            return (tuple(self.columns), self.num_rows), (None, self.schema)
        return (tuple(self.columns),), (static_rows, self.schema)

    @classmethod
    def tree_unflatten(cls, aux, children):
        static_rows, schema = aux
        if static_rows is None:
            cols, num_rows = children
        else:
            (cols,) = children
            num_rows = static_rows
        return cls(list(cols), num_rows, schema)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def row_mask(self) -> jax.Array:
        """Boolean mask of live rows, shape (capacity,)."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < jnp.asarray(
            self.num_rows, dtype=jnp.int32
        )

    def column(self, i: int) -> AnyColumn:
        return self.columns[i]

    def with_columns(self, columns: Sequence[AnyColumn],
                     schema: T.Schema) -> "ColumnarBatch":
        return ColumnarBatch(list(columns), self.num_rows, schema)

    def concrete_num_rows(self) -> int:
        """Force num_rows to a host int (syncs if it is a device scalar)."""
        n = self.num_rows
        return n if isinstance(n, int) else int(jax.device_get(n))

    def with_device_num_rows(self) -> "ColumnarBatch":
        """Promote a Python-int num_rows to a device scalar so jitted
        pipelines key their compile cache on capacity only (a static int
        lives in pytree aux data and would recompile per distinct ragged
        tail count)."""
        if not isinstance(self.num_rows, int):
            return self
        return ColumnarBatch(self.columns,
                             _device_int32(self.num_rows),
                             self.schema)

    # ------------------------------------------------------------------ #
    # Construction / host interop
    # ------------------------------------------------------------------ #

    @staticmethod
    def empty(schema: T.Schema) -> "ColumnarBatch":
        """Zero-row batch of a schema (minimum capacity bucket)."""
        data = {
            f.name: np.array(
                [], dtype=object if isinstance(f.dtype, T.StringType)
                else T.to_numpy_dtype(f.dtype))
            for f in schema.fields}
        return ColumnarBatch.from_numpy(data, schema)

    @staticmethod
    def from_numpy(data: dict[str, np.ndarray],
                   schema: T.Schema,
                   validity: Optional[dict[str, np.ndarray]] = None,
                   capacity: Optional[int] = None) -> "ColumnarBatch":
        validity = validity or {}
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity if capacity is not None else pad_capacity(n)
        cols: list[AnyColumn] = []
        for f in schema.fields:
            vals = data[f.name]
            if isinstance(f.dtype, T.StringType):
                cols.append(StringColumn.from_list(list(vals), capacity=cap))
                if f.name in validity:
                    sc = cols[-1]
                    v = np.zeros(cap, np.bool_)
                    v[:n] = validity[f.name]
                    cols[-1] = sc.with_validity(jnp.asarray(v))
            else:
                cols.append(
                    Column.from_numpy(vals, f.dtype,
                                      validity.get(f.name), capacity=cap)
                )
        return ColumnarBatch(cols, n, schema)

    def to_pydict(self) -> dict[str, list]:
        """Host materialization (syncs). NULLs become None."""
        n = self.concrete_num_rows()
        out: dict[str, list] = {}
        for f, col in zip(self.schema.fields, self.columns):
            out[f.name] = _col_to_pylist(col, f.dtype, n)
        return out

    # ------------------------------------------------------------------ #
    # Batch surgery
    # ------------------------------------------------------------------ #

    def gather(self, indices: jax.Array, num_rows: RowCount,
               index_valid: Optional[jax.Array] = None) -> "ColumnarBatch":
        cols = [c.gather(indices, index_valid) for c in self.columns]
        return ColumnarBatch(cols, num_rows, self.schema)

    def compact(self, keep: jax.Array) -> "ColumnarBatch":
        """Keep rows where `keep` is True, preserving order; result is
        prefix-compact with a traced num_rows.  This is the XLA equivalent
        of cudf's filter/gather (ref: basicPhysicalOperators.scala:230):
        a cumsum ranks the kept rows and a searchsorted inverts that rank
        into gather indices — O(n) scan + O(n log n) vectorized binary
        search, much cheaper than the full stable argsort it replaces
        (filters are the hottest op in the engine)."""
        keep = keep & self.row_mask()
        csum = jnp.cumsum(keep.astype(jnp.int32))
        n = csum[-1]
        # output slot j takes the row where csum first reaches j+1
        src = jnp.searchsorted(
            csum, jnp.arange(self.capacity, dtype=jnp.int32) + 1,
            side="left").astype(jnp.int32)
        src = jnp.minimum(src, self.capacity - 1)
        cols = [c.gather(src) for c in self.columns]
        # rows past n are garbage; invalidate them so padding stays NULL
        live = jnp.arange(self.capacity, dtype=jnp.int32) < n
        cols = [c.with_validity(c.validity & live) for c in cols]
        return ColumnarBatch(cols, n, self.schema)

    def shrink_to_capacity(self, new_cap: int) -> "ColumnarBatch":
        """Re-bucket to a smaller capacity (cheap device slice).  Callers
        must know num_rows <= new_cap (i.e. after a concrete_num_rows
        sync).  Keeps downstream programs (exchange splits, concats,
        merges) sized to the data instead of the producer's input bucket —
        e.g. a grand-aggregate partial is 1 live row in a million-row
        bucket without this."""
        if not self.columns or new_cap >= self.capacity:
            return self
        cols = [map_rows(c, lambda a: a[:new_cap]) for c in self.columns]
        return ColumnarBatch(cols, self.num_rows, self.schema)

    def slice_prefix(self, n: RowCount) -> "ColumnarBatch":
        """Logically truncate to the first n rows (no data movement)."""
        if isinstance(n, int) and isinstance(self.num_rows, int):
            new_n: RowCount = min(n, self.num_rows)
        else:
            new_n = jnp.minimum(jnp.asarray(n, jnp.int32),
                                jnp.asarray(self.num_rows, jnp.int32))
        live = jnp.arange(self.capacity, dtype=jnp.int32) < jnp.asarray(
            new_n, jnp.int32)
        cols = [c.with_validity(c.validity & live) for c in self.columns]
        return ColumnarBatch(cols, new_n, self.schema)


def _col_to_pylist(col, dtype: T.DataType, n: int) -> list:
    """One column -> python values (recursive; host sync per leaf)."""
    if isinstance(col, StringColumn):
        return col.to_list(n)
    if isinstance(col, StructColumn):
        valid = np.asarray(col.validity)[:n]
        kids = [_col_to_pylist(c, f.dtype, n)
                for c, f in zip(col.children, dtype.fields)]
        names = [f.name for f in dtype.fields]
        return [dict(zip(names, vals)) if valid[i] else None
                for i, vals in enumerate(zip(*kids))] if kids else \
            [{} if v else None for v in valid]
    if isinstance(col, MapColumn):
        keys = np.asarray(col.keys)[:n]
        vals = np.asarray(col.values)[:n]
        ev = np.asarray(col.entry_validity)[:n]
        lens = np.asarray(col.lengths)[:n]
        valid = np.asarray(col.validity)[:n]
        out = []
        for i in range(n):
            if not valid[i]:
                out.append(None)
            else:
                m = int(lens[i])
                out.append({keys[i, j].item():
                            (vals[i, j].item() if ev[i, j] else None)
                            for j in range(m)})
        return out
    if isinstance(col, ListColumn):
        vals = np.asarray(col.values)[:n]
        ev = np.asarray(col.elem_validity)[:n]
        lens = np.asarray(col.lengths)[:n]
        valid = np.asarray(col.validity)[:n]
        return [[vals[i, j].item() if ev[i, j] else None
                 for j in range(int(lens[i]))] if valid[i] else None
                for i in range(n)]
    vals = np.asarray(col.data)[:n]
    valid = np.asarray(col.validity)[:n]
    if vals.ndim == 2:  # a wide decimal's words: its unscaled integer
        return [(int(vals[i, 1]) << 64) + (int(vals[i, 0]) & (2**64 - 1))
                if valid[i] else None for i in range(n)]
    return [(vals[i].item() if valid[i] else None) for i in range(n)]


def map_rows(c: AnyColumn, fn) -> AnyColumn:
    """`c` with `fn` applied to every array that holds a row per row
    (data, validity, lengths, codes, nested children; recursive).  A
    dictionary holds no rows and passes as it is."""
    if isinstance(c, StringColumn):
        return StringColumn(
            fn(c.chars), fn(c.lengths), fn(c.validity), c.dtype,
            fn(c.codes) if c.codes is not None else None,
            c.dict_chars, c.dict_lens, c.dict_len)
    if isinstance(c, ListColumn):
        return ListColumn(fn(c.values), fn(c.lengths),
                          fn(c.elem_validity), fn(c.validity), c.dtype)
    if isinstance(c, StructColumn):
        return StructColumn(tuple(map_rows(k, fn) for k in c.children),
                            fn(c.validity), c.dtype)
    if isinstance(c, MapColumn):
        return MapColumn(fn(c.keys), fn(c.values), fn(c.entry_validity),
                         fn(c.lengths), fn(c.validity), c.dtype)
    return Column(fn(c.data), fn(c.validity), c.dtype,
                  fn(c.codes) if c.codes is not None else None,
                  c.dict_values, c.dict_len)


def _device_of(batch: ColumnarBatch):
    """The one device a batch was committed to, read off its first
    column; None for a traced, host-born or partitioned batch."""
    if not batch.columns:
        return None
    leaf = batch.columns[0].validity
    if isinstance(leaf, jax.core.Tracer) or not isinstance(leaf, jax.Array) \
            or not leaf.committed:
        return None
    devices = leaf.devices()
    return next(iter(devices)) if len(devices) == 1 else None


def concat_batches(batches: Sequence[ColumnarBatch],
                   op: Optional[str] = None) -> ColumnarBatch:
    """Concatenate batches of one schema into a single larger batch.

    TPU analog of GpuCoalesceBatches' cudf Table.concatenate
    (ref: GpuCoalesceBatches.scala:340).  Row counts must be concrete
    (host-side sizing decision, like the reference's coalesce goal
    logic), but the data never leaves the device: each part is packed
    into the output with dynamic_update_slice — no host round trip.

    The parts live on one device.  Batches parked on different chips
    of a mesh (a collective stage's shards handed to an operator that
    has no collective lowering) are refused here, with `op`, the
    operator that asked, and the two devices."""
    assert batches, "concat of zero batches"
    ns = [b.concrete_num_rows() for b in batches]
    # a part without rows is not read, wherever it lives
    homes = [_device_of(b) for b, n in zip(batches, ns) if n]
    first = next((d for d in homes if d is not None), None)
    apart = next((d for d in homes if d is not None and d != first), None)
    if apart is not None:
        raise ValueError(
            f"{op or 'concat_batches'}: cannot concatenate batches that "
            f"live on different devices, {first} and {apart}: the shards "
            "of a collective stage reach an operator that runs on one "
            "chip and has no collective lowering")
    schema = batches[0].schema
    total = sum(ns)
    cap = pad_capacity(total)
    out_cols: list[AnyColumn] = []
    for ci, f in enumerate(schema.fields):
        parts = [b.columns[ci] for b in batches]
        out_cols.append(_concat_cols(parts, ns, cap, f.dtype))
    return ColumnarBatch(out_cols, total, schema)


def concat_batches_traced(batches: Sequence[ColumnarBatch]
                          ) -> Optional[ColumnarBatch]:
    """Concatenate small batches WITHOUT host row counts: stack every
    part at full capacity, then compact the dead interior rows inside
    the program, yielding a prefix-compact batch with a traced total.

    This is the sizing-sync-free sibling of concat_batches: on
    high-latency device links each host sizing fetch costs a full D2H
    round trip, which dominates small-partial pipelines (aggregate
    partials are a few hundred rows in <=4K-capacity buckets).  The
    compact pays O(total_cap log total_cap) device work — trivial at
    these sizes, never worth it for scan-sized batches.

    Returns None when a column kind has no stacked form yet (nested
    types) — callers fall back to the host-pinned path."""
    schema = batches[0].schema
    caps = [b.capacity for b in batches]
    out_cols: list[AnyColumn] = []
    for ci, f in enumerate(schema.fields):
        parts = [b.columns[ci] for b in batches]
        if isinstance(f.dtype, T.StringType):
            w = pad_width(max(p.width for p in parts))
            chars = jnp.concatenate(
                [jnp.pad(p.chars, ((0, 0), (0, w - p.width)))
                 if p.width < w else p.chars for p in parts])
            lengths = jnp.concatenate(
                [p.lengths.astype(jnp.int32) for p in parts])
            valid = jnp.concatenate([p.validity for p in parts])
            out_cols.append(StringColumn(chars, lengths, valid))
        elif isinstance(f.dtype, (T.ListType, T.StructType, T.MapType)):
            return None
        else:
            phys = T.to_numpy_dtype(f.dtype)
            data = jnp.concatenate(
                [p.data.astype(phys) for p in parts])
            valid = jnp.concatenate([p.validity for p in parts])
            out_cols.append(Column(data, valid, f.dtype))
    keep = jnp.concatenate([b.row_mask() for b in batches])
    stacked = ColumnarBatch(out_cols, sum(caps), schema)
    return stacked.compact(keep)


def _place(out: jax.Array, piece: jax.Array, n: jax.Array,
           off: jax.Array) -> jax.Array:
    """`piece` written whole into `out` from row `off`, its rows from
    `n` on zeroed."""
    live = jnp.arange(piece.shape[0], dtype=jnp.int32) < n
    live = live.reshape((-1,) + (1,) * (piece.ndim - 1))
    piece = jnp.where(live, piece, jnp.zeros((), piece.dtype))
    return jax.lax.dynamic_update_slice(
        out, piece, (off,) + (jnp.zeros((), off.dtype),) * (piece.ndim - 1))


def _pack(pieces: list, ns: list[int], cap: int) -> jax.Array:
    """The first `ns[i]` rows of each piece, one after another, in an
    array of `cap` rows that is zero after them.

    No shape here follows a row count, so the same programs serve
    whatever the counts are (a slice of exactly `n` rows is a program
    of its own for every `n`: the pieces an exchange hands its reduce
    side have another count in every partition and under every seed).
    Each piece is written whole, in order, so a later piece overwrites
    the zeroed rows an earlier one left over its place; the buffer has
    room past `cap` for the longest piece written at the last row."""
    from spark_rapids_tpu.execs.jit_cache import cached_jit

    place = cached_jit(("concat_place",), lambda: _place, op="Concat")
    room = max((p.shape[0] for p, n in zip(pieces, ns) if n), default=0)
    out = jnp.zeros((cap + room,) + pieces[0].shape[1:], pieces[0].dtype)
    off = 0
    for p, n in zip(pieces, ns):
        if n:
            out = place(out, p, np.int32(n), np.int32(off))
            off += n
    return out[:cap]


def _concat_cols(parts: list, ns: list[int], cap: int,
                 dtype: T.DataType) -> AnyColumn:
    """Concatenate column parts into one capacity-`cap` column
    (recursive for nested types)."""
    f = T.Field("_", dtype)
    valid = _pack([p.validity for p in parts], ns, cap)
    if isinstance(f.dtype, T.StructType):
        kids = tuple(
            _concat_cols([p.children[i] for p in parts], ns, cap,
                         cf.dtype)
            for i, cf in enumerate(f.dtype.fields))
        return StructColumn(kids, valid, f.dtype)

    def widened(arrays: list, width: int) -> list:
        """Second axis padded to `width` (a map's or list's longest
        entry count, a string's byte width)."""
        return [jnp.pad(a, ((0, 0), (0, width - a.shape[1])))
                if a.shape[1] < width else a for a in arrays]

    def lengths() -> jax.Array:
        return _pack([p.lengths.astype(jnp.int32) for p in parts], ns, cap)

    if isinstance(f.dtype, T.MapType):
        kphys = T.to_numpy_dtype(f.dtype.key)
        vphys = T.to_numpy_dtype(f.dtype.value)
        L = max(p.max_len for p in parts)
        return MapColumn(
            _pack(widened([p.keys.astype(kphys) for p in parts], L),
                  ns, cap),
            _pack(widened([p.values.astype(vphys) for p in parts], L),
                  ns, cap),
            _pack(widened([p.entry_validity for p in parts], L), ns, cap),
            lengths(), valid, f.dtype)
    if isinstance(f.dtype, T.ListType):
        phys = T.to_numpy_dtype(f.dtype.element)
        L = max(p.max_len for p in parts)  # type: ignore[union-attr]
        return ListColumn(
            _pack(widened([p.values.astype(phys) for p in parts], L),
                  ns, cap),
            lengths(),
            _pack(widened([p.elem_validity for p in parts], L), ns, cap),
            valid, f.dtype)
    if isinstance(f.dtype, T.StringType):
        w = pad_width(max(p.width for p in parts))  # type: ignore[union-attr]
        return StringColumn(
            _pack(widened([p.chars for p in parts], w), ns, cap),
            lengths(), valid)
    phys = T.to_numpy_dtype(f.dtype)
    return Column(_pack([p.data.astype(phys) for p in parts], ns, cap),
                  valid, f.dtype)
