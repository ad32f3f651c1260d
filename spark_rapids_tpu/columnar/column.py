"""Device-resident columns.

TPU-native counterpart of the reference's GpuColumnVector
(ref: sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java).
cudf stores variable-length row counts and offset-encoded strings; XLA wants
static shapes, so the design here is different by construction:

- every column in a batch is padded to the batch *capacity* (a power-of-two
  bucket) so the per-operator XLA programs are compiled once per bucket and
  reused (the reference instead re-launches dynamically-shaped kernels);
- SQL NULLs are a boolean `validity` array (True = valid), matching the
  semantics (not the bit-packing) of Arrow/cudf validity buffers;
- strings are a fixed-width `(capacity, width)` uint8 byte matrix plus an
  int32 `lengths` array.  `width` is the max byte length in the batch,
  rounded up to a small bucket for compile-cache stability.

Columns are registered as JAX pytrees so whole batches can flow through
`jax.jit` / `shard_map` directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import get_conf, register

ArrayLike = Union[jax.Array, np.ndarray]

#: minimum capacity bucket; keeps tiny test batches from fragmenting the
#: compile cache.
MIN_CAPACITY = 8

#: string width buckets (bytes)
_WIDTH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

CAPACITY_POLICY = register(
    "spark.rapids.tpu.sql.capacity.policy", "pow2",
    "Capacity bucket policy.  'pow2' (default) rounds row counts up to "
    "the next power of two; 'pow2x3' additionally admits 3*pow2/2 "
    "intermediate buckets (12, 24, 48, ...) when the pow2 bucket's "
    "live-row ratio would fall below "
    "spark.rapids.tpu.sql.capacity.liveRatioFloor, halving worst-case "
    "pad waste from ~2x to ~4/3x.  At most one extra bucket per octave, "
    "so the compile-cache key space stays bounded.  Results are "
    "bit-identical under either policy: capacity only controls how many "
    "pad rows a program carries.",
    check=lambda v: v in ("pow2", "pow2x3"))
CAPACITY_LIVE_RATIO_FLOOR = register(
    "spark.rapids.tpu.sql.capacity.liveRatioFloor", 0.75,
    "Under capacity.policy=pow2x3: a batch whose live/capacity ratio in "
    "its pow2 bucket would be below this floor drops to the 3*pow2/2 "
    "bucket instead (when it fits).  0.75 re-buckets every batch that "
    "fits the intermediate bucket; lower values re-bucket only sparser "
    "batches; values below 0.5 disable re-bucketing (pow2 buckets "
    "already guarantee ratio > 1/2).",
    check=lambda v: 0.0 <= v <= 1.0)


def pad_capacity(n: int) -> int:
    """Round a row count up to its capacity bucket.

    Default policy is next-power-of-two.  Under capacity.policy=pow2x3
    an intermediate 3*pow2/2 bucket (12, 24, 48, ...) is chosen when the
    pow2 bucket would leave the live ratio below the configured floor —
    e.g. 5 of 8 rows live (0.625) re-buckets to 6 (0.83 live).  The
    policy is read per call (host-side, thread-local dict get) so tests
    can flip it; programs are keyed by the resulting capacity either
    way, so mixing policies in one process is safe, just cache-wasteful.
    """
    c = MIN_CAPACITY
    while c < n:
        c <<= 1
    if c > MIN_CAPACITY and n > 0:
        conf = get_conf()
        if conf.get(CAPACITY_POLICY) == "pow2x3":
            mid = 3 * (c >> 2)  # the 3*pow2/2 bucket between c/2 and c
            if n <= mid and n / c <= conf.get(CAPACITY_LIVE_RATIO_FLOOR):
                return mid
    return c


def pad_width(w: int) -> int:
    for b in _WIDTH_BUCKETS:
        if w <= b:
            return b
    return ((w + 4095) // 4096) * 4096


#: process-shared device all-True masks, one per capacity bucket.  Fully
#: valid columns reference these instead of uploading per-batch bool
#: arrays; the spill store must never .delete() them (is_shared_array).
_SHARED_MASKS: dict[int, jax.Array] = {}
_SHARED_LOCK = __import__("threading").Lock()


def all_valid_mask(cap: int) -> jax.Array:
    with _SHARED_LOCK:
        m = _SHARED_MASKS.get(cap)
        if m is None or m.is_deleted():
            m = _SHARED_MASKS[cap] = jnp.ones(cap, jnp.bool_)
        return m


#: device arrays CURRENTLY shared across consumers (the work-sharing
#: tier's shared scan batches, serving/work_share.py): id -> weakref.
#: Identity-keyed with a GC callback, so a recycled id can never alias
#: a dead shared array onto a fresh private one.  Spilling a shared
#: array copies it to host but must never .delete() the device copy —
#: another query may be mid-compute over the same buffers; release
#: defers to the last Python reference instead.
_SHARED_ARRAYS: dict[int, object] = {}


def mark_shared_array(a) -> None:
    """Register one device array as cross-consumer shared (see
    _SHARED_ARRAYS).  Idempotent; non-arrays are ignored."""
    import weakref as _weakref

    if not isinstance(a, jax.Array):
        return
    key = id(a)
    try:
        ref = _weakref.ref(
            a, lambda _r, _k=key: _SHARED_ARRAYS.pop(_k, None))
    except TypeError:
        return
    with _SHARED_LOCK:
        _SHARED_ARRAYS[key] = ref


def is_shared_array(a) -> bool:
    """True for process-shared immortal arrays (spill must not delete)."""
    with _SHARED_LOCK:
        if any(m is a for m in _SHARED_MASKS.values()):
            return True
        ref = _SHARED_ARRAYS.get(id(a))
        return ref is not None and ref() is a


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """A fixed-width device column: `data[capacity]` + `validity[capacity]`
(`data[capacity, 2]` for a decimal past precision 18, `types.py`).

    Rows past the owning batch's `num_rows` are padding with arbitrary data
    and validity False.
    """

    data: ArrayLike
    validity: ArrayLike
    dtype: T.DataType

    #: Optional dictionary sidecar, populated when the wire encoder
    #: shipped this fixed-width column dict-encoded
    #: (columnar/transfer.py "dict"): `codes[capacity]` (0 on
    #: null/padding rows) indexing the device-resident
    #: `dict_values[k]`.  Mirrors StringColumn's sidecar: the coded
    #: group-by (ops/groupby.py) uses codes as dense group ids for
    #: low-cardinality INTEGER/FLOAT keys, replacing the device
    #: lexsort.  Ops that cannot cheaply preserve it drop it; it is a
    #: hint, never a requirement.
    codes: Optional[ArrayLike] = None
    dict_values: Optional[ArrayLike] = None
    #: tight upper bound on the TRUE dictionary entry count
    #: (`dict_values` is padded to its pow2 capacity bucket by the
    #: wire; this is bucketed to a multiple of 16 so jit treedefs do
    #: not fragment per exact cardinality).  Consumers sizing code
    #: domains must use this, not the padded shape.  Static aux data:
    #: it survives jit boundaries alongside dtype.
    dict_len: Optional[int] = None

    def tree_flatten(self):
        return (self.data, self.validity, self.codes,
                self.dict_values), (self.dtype, self.dict_len)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity, codes, dvals = children
        return cls(data, validity, aux[0], codes, dvals, aux[1])

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def with_validity(self, validity: ArrayLike) -> "Column":
        # codes describe data, not validity: the sidecar survives
        return dataclasses.replace(self, validity=validity)

    def gather(self, indices: ArrayLike, index_valid: Optional[ArrayLike] = None
               ) -> "Column":
        """Take rows by index; out-of-range/invalid indices produce NULLs.
        A dictionary sidecar rides along (codes gather like data)."""
        idx = jnp.clip(indices, 0, self.capacity - 1)
        data = jnp.take(self.data, idx, axis=0)
        validity = jnp.take(self.validity, idx, axis=0)
        if index_valid is not None:
            validity = validity & index_valid
        codes = None if self.codes is None \
            else jnp.take(self.codes, idx, axis=0)
        return Column(data, validity, self.dtype, codes,
                      self.dict_values, self.dict_len)

    @staticmethod
    def from_numpy(values: np.ndarray, dtype: T.DataType,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "Column":
        n = len(values)
        cap = capacity if capacity is not None else pad_capacity(n)
        phys = T.to_numpy_dtype(dtype)
        if T.is_wide_decimal(dtype):
            # two words a row (types.py); one value a row widens
            data = np.zeros((cap, 2), dtype=phys)
            values = np.asarray(values).astype(phys, copy=False)
            if values.ndim == 1:
                values = np.stack([values, values >> 63], axis=1)
            data[:n] = values
        else:
            data = np.zeros(cap, dtype=phys)
            data[:n] = values.astype(phys, copy=False)
        valid = np.zeros(cap, dtype=np.bool_)
        if validity is None:
            valid[:n] = True
        else:
            valid[:n] = validity
        return Column(jnp.asarray(data), jnp.asarray(valid), dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StringColumn:
    """Fixed-width string column: `chars[capacity, width]` uint8 +
    `lengths[capacity]` int32 + `validity[capacity]`.

    Bytes past a row's length are zero.  This is the TPU answer to cudf's
    offset+chars layout: every string op becomes a dense 2-D vectorized op
    on the MXU/VPU instead of a ragged traversal.
    """

    chars: ArrayLike
    lengths: ArrayLike
    validity: ArrayLike

    dtype: T.DataType = dataclasses.field(default_factory=lambda: T.STRING)

    #: Optional dictionary sidecar, populated when the wire encoder
    #: shipped this column dict-encoded (columnar/transfer.py "sdict"):
    #: `codes[capacity]` int32 (0 on null/padding rows), plus the
    #: device-resident dictionary `dict_chars[k, w]` / `dict_lens[k]`.
    #: The group-by coded fast path (ops/groupby.py) uses the codes as
    #: dense group ids, skipping the O(n log n) lexsort entirely.  Ops
    #: that cannot cheaply preserve the sidecar (concat, expression
    #: results) drop it; consumers must treat it as a hint, never a
    #: requirement.
    codes: Optional[ArrayLike] = None
    dict_chars: Optional[ArrayLike] = None
    dict_lens: Optional[ArrayLike] = None
    #: tight (16-bucketed) upper bound on the TRUE dictionary entry
    #: count (dict_chars/dict_lens are padded to their pow2 capacity
    #: bucket by the wire); domain sizing must use this.
    dict_len: Optional[int] = None

    def tree_flatten(self):
        return (self.chars, self.lengths, self.validity, self.codes,
                self.dict_chars, self.dict_lens), (self.dtype,
                                                   self.dict_len)

    @classmethod
    def tree_unflatten(cls, aux, children):
        chars, lengths, validity, codes, dchars, dlens = children
        return cls(chars, lengths, validity, aux[0], codes, dchars,
                   dlens, aux[1])

    @property
    def capacity(self) -> int:
        return int(self.chars.shape[0])

    @property
    def width(self) -> int:
        return int(self.chars.shape[1])

    def with_validity(self, validity: ArrayLike) -> "StringColumn":
        return dataclasses.replace(self, validity=validity)

    def gather(self, indices: ArrayLike, index_valid: Optional[ArrayLike] = None
               ) -> "StringColumn":
        idx = jnp.clip(indices, 0, self.capacity - 1)
        chars = jnp.take(self.chars, idx, axis=0)
        lengths = jnp.take(self.lengths, idx, axis=0)
        validity = jnp.take(self.validity, idx, axis=0)
        if index_valid is not None:
            validity = validity & index_valid
        # per-row codes follow the gather; the dictionary is row-invariant
        codes = (jnp.take(self.codes, idx, axis=0)
                 if self.codes is not None else None)
        return StringColumn(chars, lengths, validity, self.dtype,
                            codes, self.dict_chars, self.dict_lens,
                            self.dict_len)

    @staticmethod
    def from_list(values: list[Optional[str]],
                  capacity: Optional[int] = None,
                  width: Optional[int] = None) -> "StringColumn":
        n = len(values)
        cap = capacity if capacity is not None else pad_capacity(n)
        encoded = [v.encode("utf-8") if v is not None else b"" for v in values]
        maxw = max((len(b) for b in encoded), default=0)
        w = width if width is not None else pad_width(max(maxw, 1))
        chars = np.zeros((cap, w), dtype=np.uint8)
        lengths = np.zeros(cap, dtype=np.int32)
        valid = np.zeros(cap, dtype=np.bool_)
        for i, (b, v) in enumerate(zip(encoded, values)):
            chars[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
            lengths[i] = len(b)
            valid[i] = v is not None
        return StringColumn(jnp.asarray(chars), jnp.asarray(lengths),
                            jnp.asarray(valid))

    def to_list(self, num_rows: int) -> list[Optional[str]]:
        chars = np.asarray(self.chars)[:num_rows]
        lengths = np.asarray(self.lengths)[:num_rows]
        valid = np.asarray(self.validity)[:num_rows]
        out: list[Optional[str]] = []
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
            else:
                out.append(bytes(chars[i, : lengths[i]]).decode("utf-8"))
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ListColumn:
    """Fixed-width list column: `values[capacity, max_len]` +
    `lengths[capacity]` int32 + per-element `elem_validity` + per-row
    `validity`.

    The dense-matrix answer to ragged arrays (the StringColumn pattern
    applied to list<primitive>): cudf's offsets+child layout is a ragged
    traversal, XLA wants one static 2-D shape — explode becomes a
    flatten+compact, element access a column gather."""

    values: ArrayLike          # (capacity, max_len) element physical type
    lengths: ArrayLike         # (capacity,) int32
    elem_validity: ArrayLike   # (capacity, max_len) bool
    validity: ArrayLike        # (capacity,) bool — row-level NULL
    dtype: T.DataType = dataclasses.field(
        default_factory=lambda: T.ListType(T.LONG))

    def tree_flatten(self):
        return ((self.values, self.lengths, self.elem_validity,
                 self.validity), (self.dtype,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, lengths, elem_validity, validity = children
        return cls(values, lengths, elem_validity, validity, aux[0])

    @property
    def capacity(self) -> int:
        return int(self.values.shape[0])

    @property
    def max_len(self) -> int:
        return int(self.values.shape[1])

    def with_validity(self, validity: ArrayLike) -> "ListColumn":
        return ListColumn(self.values, self.lengths, self.elem_validity,
                          validity, self.dtype)

    def gather(self, indices: ArrayLike,
               index_valid: Optional[ArrayLike] = None) -> "ListColumn":
        idx = jnp.clip(indices, 0, self.capacity - 1)
        validity = jnp.take(self.validity, idx, axis=0)
        if index_valid is not None:
            validity = validity & index_valid
        return ListColumn(jnp.take(self.values, idx, axis=0),
                          jnp.take(self.lengths, idx, axis=0),
                          jnp.take(self.elem_validity, idx, axis=0),
                          validity, self.dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StructColumn:
    """Struct-of-columns: one child AnyColumn per field + row validity.

    The TPU answer to cudf's nested column hierarchy (ref:
    GpuColumnVector's nested support + TypeChecks.scala:129): children
    recurse through the same column protocol, so gather/concat/spill
    machinery needs no special cases beyond dispatch."""

    children: tuple   # of AnyColumn, one per struct field
    validity: ArrayLike
    dtype: T.DataType = dataclasses.field(
        default_factory=lambda: T.StructType([]))

    def tree_flatten(self):
        return (tuple(self.children), self.validity), (self.dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kids, validity = children
        return cls(tuple(kids), validity, aux[0])

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    def with_validity(self, validity: ArrayLike) -> "StructColumn":
        return StructColumn(self.children, validity, self.dtype)

    def gather(self, indices: ArrayLike,
               index_valid: Optional[ArrayLike] = None) -> "StructColumn":
        validity = jnp.take(self.validity,
                            jnp.clip(indices, 0, self.capacity - 1),
                            axis=0)
        if index_valid is not None:
            validity = validity & index_valid
        return StructColumn(
            tuple(c.gather(indices, index_valid) for c in self.children),
            validity, self.dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MapColumn:
    """map<k,v> as two aligned dense list matrices sharing lengths:
    `keys[capacity, max_len]`, `values[capacity, max_len]`, per-slot
    `entry_validity` for values (map keys are non-null by SQL rules),
    `lengths[capacity]`, row `validity` (ref: GpuGetMapValue,
    complexTypeExtractors.scala — cudf walks list<struct<k,v>>; the
    dense twin-matrix form makes lookup one vectorized compare)."""

    keys: ArrayLike            # (capacity, max_len) key physical type
    values: ArrayLike          # (capacity, max_len) value physical type
    entry_validity: ArrayLike  # (capacity, max_len) value-slot validity
    lengths: ArrayLike         # (capacity,) int32
    validity: ArrayLike        # (capacity,) bool
    dtype: T.DataType = dataclasses.field(
        default_factory=lambda: T.MapType(T.LONG, T.LONG))

    def tree_flatten(self):
        return ((self.keys, self.values, self.entry_validity,
                 self.lengths, self.validity), (self.dtype,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, values, ev, lengths, validity = children
        return cls(keys, values, ev, lengths, validity, aux[0])

    @property
    def capacity(self) -> int:
        return int(self.lengths.shape[0])

    @property
    def max_len(self) -> int:
        return int(self.keys.shape[1])

    def with_validity(self, validity: ArrayLike) -> "MapColumn":
        return MapColumn(self.keys, self.values, self.entry_validity,
                         self.lengths, validity, self.dtype)

    def gather(self, indices: ArrayLike,
               index_valid: Optional[ArrayLike] = None) -> "MapColumn":
        idx = jnp.clip(indices, 0, self.capacity - 1)
        validity = jnp.take(self.validity, idx, axis=0)
        if index_valid is not None:
            validity = validity & index_valid
        return MapColumn(jnp.take(self.keys, idx, axis=0),
                         jnp.take(self.values, idx, axis=0),
                         jnp.take(self.entry_validity, idx, axis=0),
                         jnp.take(self.lengths, idx, axis=0),
                         validity, self.dtype)


AnyColumn = Union[Column, StringColumn, ListColumn, StructColumn,
                  MapColumn]


def column_to_numpy(col: AnyColumn, num_rows: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Return (values, validity) trimmed to num_rows (host copies)."""
    if isinstance(col, StringColumn):
        vals = np.array(col.to_list(num_rows), dtype=object)
        return vals, np.asarray(col.validity)[:num_rows].copy()
    return (np.asarray(col.data)[:num_rows].copy(),
            np.asarray(col.validity)[:num_rows].copy())
