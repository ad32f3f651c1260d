"""Arrow RecordBatch <-> device ColumnarBatch conversion.

TPU analog of the reference's row/columnar transitions and host interop:
HostColumnarToGpu (ref: sql-plugin/.../HostColumnarToGpu.scala) for
host Arrow -> device, and GpuColumnarToRowExec's device -> host path
(ref: GpuColumnarToRowExec.scala:287).  Arrow is the host substrate the
CPU engine and all file formats speak, so this module is the single H2D /
D2H seam of the framework.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    AnyColumn,
    Column,
    ListColumn,
    MapColumn,
    StringColumn,
    StructColumn,
    all_valid_mask,
    pad_capacity,
    pad_width,
)


def schema_from_arrow(aschema: pa.Schema) -> T.Schema:
    return T.Schema(
        [T.Field(f.name, T.from_arrow_type(f.type), f.nullable)
         for f in aschema]
    )


def schema_to_arrow(schema: T.Schema) -> pa.Schema:
    return pa.schema(
        [pa.field(f.name, T.to_arrow_type(f.dtype), f.nullable)
         for f in schema.fields]
    )


def _fixed_host(arr: pa.Array, dtype: T.DataType, cap: int
                ) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Decode one fixed-width column to padded host buffers:
    (data[cap], validity[cap] or None when fully valid)."""
    n = len(arr)
    phys = T.to_numpy_dtype(dtype)
    if isinstance(dtype, T.DecimalType):
        validity = np.asarray(arr.is_valid()) if arr.null_count else None
        np_vals = decimal_words(arr)
        if not dtype.wide:
            np_vals = np_vals[:, 0]  # |v| < 10^18: the low word is v
        if validity is not None:
            np_vals = np.where(
                validity.reshape((-1,) + (1,) * (np_vals.ndim - 1)),
                np_vals, 0)
    else:
        # zero-copy-ish: fill nulls then view as numpy
        if arr.null_count:
            validity = np.asarray(arr.is_valid())
            arr = arr.fill_null(_zero_value(dtype))
        else:
            validity = None
        if isinstance(dtype, T.DateType):
            np_vals = arr.cast(pa.int32()).to_numpy(zero_copy_only=False)
        elif isinstance(dtype, T.TimestampType):
            np_vals = arr.cast(pa.int64()).to_numpy(zero_copy_only=False)
        else:
            np_vals = arr.to_numpy(zero_copy_only=False)
    if n == cap:
        # exact-fit fast path: use the decoded buffer directly — no host
        # pad-copy (scans with power-of-two batch sizes hit this on every
        # full batch)
        data = np.ascontiguousarray(np_vals.astype(phys, copy=False))
    else:
        data = np.zeros((cap,) + np_vals.shape[1:], phys)
        data[:n] = np_vals.astype(phys, copy=False)
    if validity is None and n == cap:
        vhost = None  # fully valid: the device-shared mask stands in
    else:
        vhost = np.zeros(cap, np.bool_)
        vhost[:n] = True if validity is None else validity
    return data, vhost


def decimal_words(arr: pa.Array) -> np.ndarray:
    """A decimal128 array's values as `(n, 2)` int64 words (low, high):
    its value buffer as it lies, no per-value conversion.  Null slots
    hold whatever the buffer holds."""
    n = len(arr)
    buf = arr.buffers()[1]
    if buf is None:
        return np.zeros((n, 2), np.int64)
    words = np.frombuffer(buf, np.int64)
    return words[2 * arr.offset: 2 * (arr.offset + n)].reshape(n, 2)


def _decimal_to_arrow(vals: np.ndarray, valid: np.ndarray,
                      dtype: T.DecimalType, atype) -> pa.Array:
    """Host words (an int64 per row, or `(n, 2)` words) -> decimal128,
    built on the buffers."""
    n = len(valid)
    vals = np.asarray(vals, np.int64)
    if vals.ndim == 1:
        vals = np.stack([vals, vals >> 63], axis=1)
    vals = np.where(valid[:, None], vals, 0)
    bitmap = None
    if n and not valid.all():
        bitmap = pa.array(valid, pa.bool_()).buffers()[1]
    return pa.Array.from_buffers(
        atype, n, [bitmap, pa.py_buffer(np.ascontiguousarray(vals))])


def _zero_value(dtype: T.DataType):
    if isinstance(dtype, T.BooleanType):
        return False
    if isinstance(dtype, (T.DateType,)):
        import datetime

        return datetime.date(1970, 1, 1)
    if isinstance(dtype, T.TimestampType):
        import datetime

        return datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)
    if isinstance(dtype, (T.FloatType, T.DoubleType)):
        return 0.0
    return 0


def _string_host(arr: pa.Array, cap: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Decode one string column to (chars[cap,w], lengths[cap],
    validity[cap]) host buffers."""
    n = len(arr)
    sarr = arr.cast(pa.large_string())
    buf_offsets = np.frombuffer(sarr.buffers()[1], dtype=np.int64,
                                count=n + 1, offset=sarr.offset * 8)
    data_buf = sarr.buffers()[2]
    raw = np.frombuffer(data_buf, dtype=np.uint8) if data_buf is not None \
        else np.zeros(0, np.uint8)
    lengths_np = (buf_offsets[1:] - buf_offsets[:-1]).astype(np.int32)
    validity = np.asarray(arr.is_valid()) if arr.null_count else np.ones(
        n, np.bool_)
    lengths_np = np.where(validity, lengths_np, 0).astype(np.int32)
    maxw = int(lengths_np.max()) if n else 0
    w = pad_width(max(maxw, 1))
    chars = np.zeros((cap, w), np.uint8)
    for i in range(n):
        ln = lengths_np[i]
        if ln:
            s = buf_offsets[i]
            chars[i, :ln] = raw[s:s + ln]
    lengths = np.zeros(cap, np.int32)
    lengths[:n] = lengths_np
    valid = np.zeros(cap, np.bool_)
    valid[:n] = validity
    return chars, lengths, valid


def _list_host(arr: pa.Array, dtype: T.ListType, cap: int
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Decode one list<primitive> column to dense host buffers:
    (values[cap, L], lengths[cap], elem_validity[cap, L], validity[cap])."""
    n = len(arr)
    phys = T.to_numpy_dtype(dtype.element)
    larr = arr.cast(pa.large_list(T.to_arrow_type(dtype.element)))
    offsets = np.frombuffer(larr.buffers()[1], dtype=np.int64,
                            count=n + 1, offset=larr.offset * 8)
    flat = larr.values
    if len(flat):
        fv = np.asarray(flat.is_valid()) if flat.null_count \
            else np.ones(len(flat), np.bool_)
        if flat.null_count:
            flat = flat.fill_null(_zero_value(dtype.element))
        if isinstance(dtype.element, T.DateType):
            flat_np = flat.cast(pa.int32()).to_numpy(zero_copy_only=False)
        elif isinstance(dtype.element, T.TimestampType):
            flat_np = flat.cast(pa.int64()).to_numpy(zero_copy_only=False)
        else:
            flat_np = flat.to_numpy(zero_copy_only=False).astype(
                phys, copy=False)
    else:
        flat_np = np.zeros(0, phys)
        fv = np.zeros(0, np.bool_)
    validity = np.asarray(arr.is_valid()) if arr.null_count \
        else np.ones(n, np.bool_)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    lens = np.where(validity, lens, 0).astype(np.int32)
    maxlen = int(lens.max()) if n else 0
    L = pad_width(max(maxlen, 1))
    values = np.zeros((cap, L), phys)
    evalid = np.zeros((cap, L), np.bool_)
    if n:
        idx = offsets[:-1, None] + np.arange(L)[None, :]
        mask = np.arange(L)[None, :] < lens[:, None]
        safe = np.clip(idx, 0, max(len(flat_np) - 1, 0))
        if len(flat_np):
            values[:n] = np.where(mask, flat_np[safe], 0)
            evalid[:n] = mask & fv[safe]
    lengths = np.zeros(cap, np.int32)
    lengths[:n] = lens
    valid = np.zeros(cap, np.bool_)
    valid[:n] = validity
    return values, lengths, evalid, valid


def _host_any_column(arr: pa.Array, dtype: T.DataType, cap: int):
    """Recursive host-side (numpy-backed) column builder for ANY dtype
    — the nested-type entry point (struct-of-columns / twin-matrix
    maps); flat types reuse the component decoders."""
    if isinstance(dtype, T.StructType):
        n = len(arr)
        validity = np.zeros(cap, np.bool_)
        validity[:n] = np.asarray(arr.is_valid()) if arr.null_count \
            else True
        kids = []
        for i, f in enumerate(dtype.fields):
            child = arr.field(i)
            # a null struct row must null its children too (arrow may
            # leave garbage under null parents)
            kids.append(_host_any_column(child, f.dtype, cap))
            kv = kids[-1].validity.copy()
            kv[:n] &= validity[:n]
            kids[-1] = kids[-1].with_validity(kv)
        return StructColumn(tuple(kids), validity, dtype)
    if isinstance(dtype, T.MapType):
        return _map_host_column(arr, dtype, cap)
    if isinstance(dtype, T.StringType):
        chars, lengths, valid = _string_host(arr, cap)
        return StringColumn(chars, lengths, valid)
    if isinstance(dtype, T.ListType):
        values, lengths, ev, valid = _list_host(arr, dtype, cap)
        return ListColumn(values, lengths, ev, valid, dtype)
    data, vhost = _fixed_host(arr, dtype, cap)
    if vhost is None:
        vhost = np.zeros(cap, np.bool_)
        vhost[:len(arr)] = True
    return Column(data, vhost, dtype)


def _map_host_column(arr: pa.Array, dtype: T.MapType,
                     cap: int) -> MapColumn:
    """pa.MapArray -> dense twin matrices (keys/values share lengths)."""
    n = len(arr)
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    offsets = np.asarray(arr.offsets)[: n + 1].astype(np.int64)
    keys_flat = arr.keys
    items_flat = arr.items
    kphys = T.to_numpy_dtype(dtype.key)
    vphys = T.to_numpy_dtype(dtype.value)

    def _flat_np(a, dt, phys):
        if len(a) == 0:
            return np.zeros(0, phys), np.zeros(0, np.bool_)
        fv = np.asarray(a.is_valid()) if a.null_count \
            else np.ones(len(a), np.bool_)
        if a.null_count:
            a = a.fill_null(_zero_value(dt))
        if isinstance(dt, T.DateType):
            vals = a.cast(pa.int32()).to_numpy(zero_copy_only=False)
        elif isinstance(dt, T.TimestampType):
            vals = a.cast(pa.int64()).to_numpy(zero_copy_only=False)
        else:
            vals = a.to_numpy(zero_copy_only=False).astype(
                phys, copy=False)
        return vals, fv

    kf, _ = _flat_np(keys_flat, dtype.key, kphys)
    vf, vvalid = _flat_np(items_flat, dtype.value, vphys)
    validity_np = np.asarray(arr.is_valid()) if arr.null_count \
        else np.ones(n, np.bool_)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    lens = np.where(validity_np, lens, 0).astype(np.int32)
    L = pad_width(max(int(lens.max()) if n else 0, 1))
    keys = np.zeros((cap, L), kphys)
    values = np.zeros((cap, L), vphys)
    evalid = np.zeros((cap, L), np.bool_)
    if n and len(kf):
        # offsets are ABSOLUTE into the full (unsliced) child arrays
        # that .keys/.items return — no base subtraction (a sliced
        # MapArray would otherwise decode shifted entries)
        idx = offsets[:-1, None] + np.arange(L)[None, :]
        mask = np.arange(L)[None, :] < lens[:, None]
        safe = np.clip(idx, 0, max(len(kf) - 1, 0))
        keys[:n] = np.where(mask, kf[safe], 0)
        values[:n] = np.where(mask, vf[safe], 0)
        evalid[:n] = mask & vvalid[safe]
    lengths = np.zeros(cap, np.int32)
    lengths[:n] = lens
    valid = np.zeros(cap, np.bool_)
    valid[:n] = validity_np
    return MapColumn(keys, values, evalid, lengths, valid, dtype)


# --------------------------------------------------------------------- #
# Packed upload: one H2D transfer per batch
# --------------------------------------------------------------------- #
# Device links have a per-transfer cost (dispatch + latency), so
# shipping a scan batch as one packed
# byte buffer + one jitted unpack program beats per-column uploads — the
# single staging-buffer design the reference gets from assembling one
# host buffer per Parquet read (ref: GpuParquetScan.scala:495-560).

_PACKED_UPLOAD = None  # config entry, registered lazily


def _packed_enabled() -> bool:
    global _PACKED_UPLOAD
    if _PACKED_UPLOAD is None:
        from spark_rapids_tpu.config import get_conf, register

        _PACKED_UPLOAD = register(
            "spark.rapids.tpu.sql.scan.packedUpload", True,
            "Ship each scanned batch's column components in one batched "
            "device_put (a single transfer round) instead of one "
            "transfer per component.")
    from spark_rapids_tpu.config import get_conf

    return get_conf().get(_PACKED_UPLOAD)


def _pack_components(comps: list[np.ndarray]) -> tuple[np.ndarray, tuple]:
    layout = []
    total = 0
    for a in comps:
        total = (total + 7) & ~7
        layout.append((total, a.shape, str(a.dtype)))
        total += a.nbytes
    buf = np.zeros(total, np.uint8)
    for a, (off, _, _) in zip(comps, layout):
        buf[off:off + a.nbytes] = np.ascontiguousarray(a).view(
            np.uint8).reshape(-1)
    return buf, tuple(layout)


def _make_unpack(layout: tuple):
    def unpack(buf: jax.Array) -> list[jax.Array]:
        out = []
        for off, shape, dt in layout:
            npdt = np.dtype(dt)
            count = int(np.prod(shape))
            raw = jax.lax.dynamic_slice(buf, (off,),
                                        (count * npdt.itemsize,))
            if npdt == np.uint8:
                col = raw.reshape(shape)
            elif npdt == np.bool_:
                col = (raw.reshape(shape) != 0)
            else:
                col = jax.lax.bitcast_convert_type(
                    raw.reshape(count, npdt.itemsize), npdt).reshape(shape)
            out.append(col)
        return out

    return unpack


def from_arrow(rb: pa.RecordBatch | pa.Table,
               capacity: Optional[int] = None) -> ColumnarBatch:
    """Host Arrow batch -> device ColumnarBatch (the H2D upload)."""
    if isinstance(rb, pa.Table):
        rb = rb.combine_chunks()
        arrays = [
            c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
            for c in rb.columns
        ]
        arrays = [a.chunk(0) if isinstance(a, pa.ChunkedArray) else a
                  for a in arrays]
        aschema = rb.schema
        n = rb.num_rows
    else:
        arrays = rb.columns
        aschema = rb.schema
        n = rb.num_rows
    schema = schema_from_arrow(aschema)

    if capacity is None and n > 0 and _packed_enabled():
        # encoded single-buffer upload: one device_put + cached unpack
        # program (bias/dict wire encodings, device-side validity synth)
        from spark_rapids_tpu.columnar import transfer

        enc = transfer.encode_for_device(arrays, schema, n)
        if enc is not None:
            comps_list, plan = enc
            cols = transfer.decode_on_device(comps_list, plan, schema)
            return ColumnarBatch(cols, n, schema)

    cap = capacity if capacity is not None else pad_capacity(n)

    # host-decode every column into padded component buffers
    comps: list[np.ndarray] = []
    recipe: list[tuple] = []  # (kind, first-component index, dtype)
    for arr, f in zip(arrays, schema.fields):
        if isinstance(arr, pa.DictionaryArray):
            # only the wire encoder ships dicts as-is; this fallback
            # materializes (cast through the value type)
            arr = arr.cast(arr.type.value_type)
        if isinstance(f.dtype, T.StringType):
            chars, lengths, valid = _string_host(arr, cap)
            recipe.append(("str", len(comps), f.dtype))
            comps.extend([chars, lengths, valid])
        elif isinstance(f.dtype, T.ListType):
            values, lengths, evalid, valid = _list_host(arr, f.dtype, cap)
            recipe.append(("list", len(comps), f.dtype))
            comps.extend([values, lengths, evalid, valid])
        elif isinstance(f.dtype, (T.StructType, T.MapType)):
            # nested: the column is itself a pytree of host buffers;
            # device_put moves every leaf in the same batched transfer
            recipe.append(("nested", len(comps), f.dtype))
            comps.append(_host_any_column(arr, f.dtype, cap))
        else:
            data, vhost = _fixed_host(arr, f.dtype, cap)
            if vhost is None:
                recipe.append(("fixed_shared", len(comps), f.dtype))
                comps.append(data)
            else:
                recipe.append(("fixed", len(comps), f.dtype))
                comps.extend([data, vhost])

    if (len(comps) > 1 and _packed_enabled()) or any(
            not isinstance(a, np.ndarray) for a in comps):
        # one batched transfer round for every component (beats a packed
        # staging buffer: no unpack program, and jax batches the
        # copies); nested columns are pytrees — device_put moves every
        # leaf, jnp.asarray would choke on the dataclass.  Routed
        # through the transfer.upload fault seam + in-place retry.
        from spark_rapids_tpu.columnar.transfer import upload_components

        dev = upload_components(comps)
    else:
        dev = [jnp.asarray(a) for a in comps]

    cols: list[AnyColumn] = []
    for kind, i, dtype in recipe:
        if kind == "str":
            cols.append(StringColumn(dev[i], dev[i + 1], dev[i + 2]))
        elif kind == "list":
            cols.append(ListColumn(dev[i], dev[i + 1], dev[i + 2],
                                   dev[i + 3], dtype))
        elif kind == "nested":
            cols.append(dev[i])
        elif kind == "fixed_shared":
            cols.append(Column(dev[i], all_valid_mask(cap), dtype))
        else:
            cols.append(Column(dev[i], dev[i + 1], dtype))
    return ColumnarBatch(cols, n, schema)


#: one-round fetch threshold: below this FULL-CAPACITY size, fetching
#: count+data together beats a count sync followed by a shrunk fetch
#: (breakeven = link_rtt * bandwidth; retuning it for the chip's own
#: link is ROADMAP S3)
_FUSED_FETCH_BYTES = 2 << 20


def _full_fetch_bytes(batch: ColumnarBatch) -> int:
    """Static D2H size estimate if the batch shipped at full capacity."""
    total = 0
    for c in batch.columns:
        if isinstance(c, StringColumn):
            total += c.chars.shape[0] * (c.chars.shape[1] + 5)
        elif hasattr(c, "data"):
            total += c.data.shape[0] * (c.data.dtype.itemsize + 1)
        else:
            # nested (list/struct/map): no cheap estimate — report
            # over-threshold so the classic count-then-shrink path runs
            return _FUSED_FETCH_BYTES + 1
    return total


def _strip_dict_sidecar(batch: ColumnarBatch) -> ColumnarBatch:
    """Drop dictionary sidecars before D2H: the host rebuild reads only
    chars/lengths/validity, so the codes (full capacity) must never
    cross the link.  dict_len goes with them — it is jit-cache-keying
    aux (tree_flatten), and leaving it set on a column whose dictionary
    was just dropped would fragment the shrink/fetch program cache by
    the deleted dictionary's cardinality bucket."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch as _CB

    import dataclasses as _dc

    if not any(getattr(c, "codes", None) is not None
               for c in batch.columns):
        return batch

    def strip(c):
        if isinstance(c, StringColumn) and c.codes is not None:
            return _dc.replace(c, codes=None, dict_chars=None,
                               dict_lens=None, dict_len=None)
        if isinstance(c, Column) and c.codes is not None:
            return _dc.replace(c, codes=None, dict_values=None,
                               dict_len=None)
        return c

    return _CB([strip(c) for c in batch.columns], batch.num_rows,
               batch.schema)


def to_arrow(batch: ColumnarBatch) -> pa.Table:
    """Device ColumnarBatch -> host Arrow table (the D2H download).

    Every device component comes back in ONE batched jax.device_get:
    D2H pays a latency round per call, not per buffer, so sequential
    per-column reads would multiply the transfer latency by the column
    count.  The batch is first SHRUNK on device to its live row count
    (padding rows never cross the wire — a 1-row aggregate result in a
    million-row capacity bucket is a 1-row transfer, not a 100MB one)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch as _CB

    batch = _strip_dict_sidecar(batch)

    if not isinstance(batch.num_rows, int) \
            and _full_fetch_bytes(batch) <= _FUSED_FETCH_BYTES:
        # modest batch with a device-resident row count (aggregate
        # results, limits): fetch the count WITH the components in one
        # D2H round instead of syncing the count first — each round
        # pays full link latency, so up to the
        # bandwidth-breakeven size, shipping the padding is cheaper
        # than a second round trip.  Columns are pytrees, so one
        # device_get batches every leaf of every column (incl. nested).
        n_host, host_cols = jax.device_get(
            (batch.num_rows, list(batch.columns)))
        n = int(np.asarray(n_host).reshape(()))
    else:
        n = batch.concrete_num_rows()
        shrunk_cap = max(128, -(-n // 128) * 128)
        if shrunk_cap < batch.capacity:
            batch = batch.shrink_to_capacity(shrunk_cap)
            batch = _CB(batch.columns, n, batch.schema)
        host_cols = jax.device_get(list(batch.columns))

    arrays = []
    aschema = schema_to_arrow(batch.schema)
    for f, col, afield in zip(batch.schema.fields, host_cols, aschema):
        arrays.append(_host_col_to_arrow(col, f.dtype, n, afield.type))
    return pa.Table.from_arrays(arrays, schema=aschema)


def _host_col_to_arrow(col, dtype: T.DataType, n: int,
                       atype) -> pa.Array:
    """One HOST-resident (device_get) column -> pa.Array[:n]."""
    if isinstance(col, ListColumn):
        vals, lens = col.values[:n], col.lengths[:n]
        ev, rv = col.elem_validity[:n], col.validity[:n]
        pylist = []
        for i in range(n):
            if not rv[i]:
                pylist.append(None)
            else:
                m = int(lens[i])
                pylist.append([vals[i, j].item() if ev[i, j] else None
                               for j in range(m)])
        return pa.array(pylist, type=atype)
    if isinstance(col, StringColumn):
        chars, lens, valid = col.chars[:n], col.lengths[:n], \
            col.validity[:n]
        pylist = [bytes(chars[i, :lens[i]]).decode("utf-8")
                  if valid[i] else None for i in range(n)]
        return pa.array(pylist, type=atype)
    if isinstance(col, StructColumn):
        valid = np.asarray(col.validity[:n])
        kids = [_host_col_to_arrow(c, f.dtype, n, atype.field(i).type)
                for i, (c, f) in enumerate(zip(col.children,
                                               dtype.fields))]
        mask = pa.array(~valid) if (~valid).any() else None
        return pa.StructArray.from_arrays(
            kids, fields=list(atype), mask=mask)
    if isinstance(col, MapColumn):
        keys, vals = col.keys[:n], col.values[:n]
        ev, lens, rv = col.entry_validity[:n], col.lengths[:n], \
            col.validity[:n]
        pylist = []
        for i in range(n):
            if not rv[i]:
                pylist.append(None)
            else:
                m = int(lens[i])
                pylist.append([
                    (keys[i, j].item(),
                     vals[i, j].item() if ev[i, j] else None)
                    for j in range(m)])
        return pa.array(pylist, type=atype)
    # fixed-width
    vals, valid = col.data[:n], col.validity[:n]
    if isinstance(dtype, T.DecimalType):
        return _decimal_to_arrow(vals, np.asarray(valid, bool), dtype,
                                 atype)
    mask = ~valid if (~valid).any() else None
    if isinstance(dtype, T.DateType):
        return pa.array(vals.astype("int32"), pa.int32(),
                        mask=mask).cast(atype)
    if isinstance(dtype, T.TimestampType):
        return pa.array(vals.astype("int64"), pa.int64(),
                        mask=mask).cast(atype)
    return pa.array(vals, type=atype, mask=mask)
