"""Encoded H2D transfer: compact wire encodings + device-side decode.

The design premise is that the host-device link has limited sustained
bandwidth, so the bytes crossing the wire — not device compute — bound
scan-heavy queries (not measured on the chip yet, ROADMAP S3).  The reference sidesteps host bandwidth by decoding Parquet ON
the accelerator (ref: GpuParquetScan.scala:495-560 assembles one device
buffer and launches device decode kernels).  The TPU analog:

- the host (scan prefetch thread) re-encodes each decoded column into a
  compact wire form: bias-packed integers (uint8/uint16 deltas from a
  per-batch base), dictionary-encoded low-cardinality floats/strings
  (codes + values), raw bytes otherwise;
- all components upload in ONE batched `jax.device_put` call;
- a cached, jitted *decode program* (keyed by the static wire plan)
  reconstructs full-width padded device columns: gathers for dictionary
  decode, base adds for bias decode, and validity-mask synthesis
  (`iota < n_live`) so all-valid columns ship zero validity bytes.

Decode work thus moves from the wire to the VPU, where a gather over a
few million rows is microseconds.  Everything is astype/gather/compare —
deliberately NO bitcast_convert_type: the TPU X64 rewriter cannot
compile 64-bit bitcasts, so 64-bit columns ride the list as native
arrays and only sub-32-bit codes get widened on device.

Wire row counts bucket to <=8 sizes per capacity (compile-cache
stability) and live row count rides as a dynamic scalar, so one
compiled decode program serves every batch of the same plan.

When `spark.rapids.tpu.sql.wireCompression.enabled` is on, data-plane
components additionally ride COMPRESSED (columnar/compression/): the
host packs them through the codec chooser during scan-prefetch encode
and the decode program decompresses in HBM — shift/mask unpacking,
per-block cumsums, searchsorted run expansion — fused into the same
XLA program as the rest of the decode.  Off (the default) is
bit-for-bit the uncompressed wire format above.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (
    Column,
    StringColumn,
    pad_capacity,
    pad_width,
)

#: tapped H2D accounting: bytes actually crossing the wire through
#: THE batched upload below (compressed components count their packed
#: size) — the counter the wire-codec acceptance gate reads.
_upload_lock = threading.Lock()
_UPLOAD_STATS = {"batches": 0, "wire_bytes": 0}


def upload_stats() -> dict:
    with _upload_lock:
        return dict(_UPLOAD_STATS)


def reset_upload_stats() -> None:
    with _upload_lock:
        _UPLOAD_STATS["batches"] = 0
        _UPLOAD_STATS["wire_bytes"] = 0


def upload_components(comps):
    """THE batched H2D upload (one ``jax.device_put`` for the whole
    component list) with the ``transfer.upload`` fault seam in front
    and in-place recovery behind it: a retryable failure (injected, or
    a real device-side allocation failure materializing the upload)
    spills every unpinned store buffer and re-uploads once — the
    upload is restartable by construction (host components are still
    in hand).  A second failure propagates to the batch
    split-and-retry ladder / task retry.

    The `wire.put` span times the host's call of ``jax.device_put``
    (and a retry's spill, when there is one).  The call may return
    before the link is done with the bytes: the span is what the
    uploading thread pays, not the transfer's own duration."""
    from spark_rapids_tpu.execs.retry import absorb_once
    from spark_rapids_tpu.robustness import faults as _faults

    def attempt():
        _faults.fault_point("transfer.upload", n_comps=len(comps))
        return jax.device_put(comps)

    # count HOST array leaves only (tree_leaves: nested column pytrees
    # from the arrow.py fallback path count too): device-resident
    # components handed back through here (decode_now re-running a
    # wire-form batch) are a device_put no-op, and crediting them
    # would double-count bytes that never crossed the link
    host_bytes = sum(
        int(a.nbytes) for a in jax.tree_util.tree_leaves(comps)
        if isinstance(a, np.ndarray))
    with _trace.span("wire.put", bytes=host_bytes, comps=len(comps)):
        out = absorb_once(attempt, action="upload_retry")
    if host_bytes:
        with _upload_lock:
            _UPLOAD_STATS["batches"] += 1
            _UPLOAD_STATS["wire_bytes"] += host_bytes
    return out


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _wire_rows(n: int, cap: int) -> int:
    # <= 8 distinct wire lengths per capacity bucket (compile-cache
    # stability) at <= 12.5% padding waste on the wire
    return min(cap, _round_up(n, max(64, cap // 8)))


# ------------------------------------------------------------------ #
# Host-side encoding
# ------------------------------------------------------------------ #

_INT_KINDS = "iu"


def _decode_fixed_host(arr: pa.Array, dtype: T.DataType
                       ) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """One fixed-width pa.Array -> (values[n], validity[n] or None)."""
    from spark_rapids_tpu.columnar.arrow import _zero_value

    phys = T.to_numpy_dtype(dtype)
    if isinstance(dtype, T.DecimalType):
        # one int64 word a value (narrow: the encoder refuses wide),
        # read off the decimal128 buffer's low words
        from spark_rapids_tpu.columnar.arrow import decimal_words

        validity = np.asarray(arr.is_valid()) if arr.null_count else None
        vals = decimal_words(arr)[:, 0]
        if validity is not None:
            vals = np.where(validity, vals, 0)
        return np.ascontiguousarray(vals), validity
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
        arr = arr.fill_null(_zero_value(dtype))
    else:
        validity = None
    if isinstance(dtype, T.DateType):
        vals = arr.cast(pa.int32()).to_numpy(zero_copy_only=False)
    elif isinstance(dtype, T.TimestampType):
        vals = arr.cast(pa.int64()).to_numpy(zero_copy_only=False)
    else:
        vals = arr.to_numpy(zero_copy_only=False)
    return np.ascontiguousarray(vals.astype(phys, copy=False)), validity


def _sample_low_cardinality(vals: np.ndarray, limit: int = 1024) -> bool:
    """Cheap gate: does a strided sample look low-cardinality?"""
    n = len(vals)
    if n <= 8192:
        return True
    s = vals[:: max(1, n // 4096)]
    return len(np.unique(s)) <= min(limit, len(s) // 2)


def _try_dict(vals: np.ndarray) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """(codes, values) when dictionary encoding pays off, else None."""
    if vals.dtype.kind == "f" and np.isnan(vals).any():
        return None  # NaN payload bits would not round-trip the dict
    if not _sample_low_cardinality(vals):
        return None
    d = pa.array(vals).dictionary_encode()
    nvals = len(d.dictionary)
    if nvals > 0xFFFF or nvals * 2 > max(len(vals), 1):
        return None
    codes = d.indices.to_numpy(zero_copy_only=False)
    values = d.dictionary.to_numpy(zero_copy_only=False).astype(
        vals.dtype, copy=False)
    # bit-exactness gate (the contract is byte-identical round-trips):
    # Arrow's dictionary_encode unifies -0.0 with +0.0, which flips
    # sign bits downstream (1/x: -inf vs +inf) — verify reconstruction
    if vals.dtype.kind == "f":
        bits = np.int32 if vals.dtype.itemsize == 4 else np.int64
        if not np.array_equal(values[codes].view(bits),
                              vals.view(bits)):
            return None
    return codes, values


def _try_scaled(vals: np.ndarray) -> Optional[np.ndarray]:
    """int32 cents for decimal-valued doubles (prices, rates): data that
    entered the file as 2-decimal values reconstructs BIT-EXACTLY via
    round(v*100)/100.0, verified here before committing to the wire
    format — int32 halves the dominant float column's bytes."""
    if len(vals) == 0:
        return None
    lib = _native()
    if lib is not None:
        v = np.ascontiguousarray(vals)
        out = np.empty(len(v), np.int32)
        ok = lib.scaled_check_encode(v.ctypes.data, len(v),
                                     out.ctypes.data)
        return out if ok else None
    if not np.isfinite(vals).all():
        return None
    s = np.rint(vals * 100.0)
    if (np.abs(s) >= 2**31).any():
        return None
    s32 = s.astype(np.int32)
    r = s32.astype(np.float64) / 100.0
    if not np.array_equal(r.view(np.int64), vals.view(np.int64)):
        return None
    return s32


def _native():
    from spark_rapids_tpu import native

    return native.load()


def _int_range(vals: np.ndarray, phys: np.dtype):
    """(min, range, encode8, encode16) for an integer column, using the
    native codec's single-pass kernels for the common i32/i64 cases."""
    lib = _native()
    if lib is not None and phys in (np.dtype(np.int64),
                                    np.dtype(np.int32)):
        v = np.ascontiguousarray(vals)
        mnb = np.empty(1, np.int64)
        mxb = np.empty(1, np.int64)
        scan = lib.minmax_i64 if phys.itemsize == 8 else lib.minmax_i32
        scan(v.ctypes.data, len(v), mnb.ctypes.data, mxb.ctypes.data)
        mn = int(mnb[0])
        e8 = lib.bias_encode8_i64 if phys.itemsize == 8 \
            else lib.bias_encode8_i32
        e16 = lib.bias_encode16_i64 if phys.itemsize == 8 \
            else lib.bias_encode16_i32

        def enc8(x, base, _f=e8):
            x = np.ascontiguousarray(x)
            out = np.empty(len(x), np.uint8)
            _f(x.ctypes.data, len(x), base, out.ctypes.data)
            return out

        def enc16(x, base, _f=e16):
            x = np.ascontiguousarray(x)
            out = np.empty(len(x), np.uint16)
            _f(x.ctypes.data, len(x), base, out.ctypes.data)
            return out

        return mn, int(mxb[0]) - mn, enc8, enc16
    mn = int(vals.min())
    rng = int(vals.max()) - mn

    def enc8_np(x, base):
        return (x.astype(np.int64) - base).astype(np.uint8)

    def enc16_np(x, base):
        return (x.astype(np.int64) - base).astype(np.uint16)

    return mn, rng, enc8_np, enc16_np


def _padded(a: np.ndarray, wire: int) -> np.ndarray:
    """Zero-pad a 1-D/2-D per-row array to `wire` rows (zero-copy when
    it already fits exactly)."""
    if len(a) == wire:
        return np.ascontiguousarray(a)
    out = np.zeros((wire,) + a.shape[1:], a.dtype)
    out[: len(a)] = a
    return out


class _Comps:
    """Component accumulator producing the physical upload list.

    Each component rides as its OWN array in one batched
    ``jax.device_put`` call (PJRT moves the whole list in one transfer
    round).  An earlier design packed all sub-4-byte
    components into one uint8 buffer recovered with device slices +
    bitcast_convert_type; that was abandoned after XLA:TPU's layout
    pass was observed taking 100-500 SECONDS to compile decode programs
    whose big slices did not exactly tile the staging buffer (the
    multi-megabyte slice-of-uint8 copies defeat the bitcast-view
    recognition and send tiling assignment into a pathological search).
    Separate typed arrays compile in ~2s, need zero bitcasts, and make
    the X64-rewriter caveat moot.

    add() returns an opaque ref the plan stores; the decode program
    resolves refs against the uploaded list.  add_wire() is the
    data-plane variant: when wire compression is configured it routes
    the component through the codec chooser and returns a "comp" ref
    carrying the codec name + static meta — the decode program
    resolves those by running the codec's device decompress before
    (fused with) the rest of the decode.  With compression off,
    add_wire IS add, so the disabled wire format is bit-for-bit the
    historical one.
    """

    def __init__(self, wire_cfg: Optional[tuple] = None):
        self.arrays: list[np.ndarray] = []
        self.wire_cfg = wire_cfg  # (codec names, min_ratio, block_rows)

    def add(self, a: np.ndarray):
        self.arrays.append(np.ascontiguousarray(a))
        return ("arr", len(self.arrays) - 1)

    def add_wire(self, a: np.ndarray):
        a = np.ascontiguousarray(a)
        if self.wire_cfg is not None:
            from spark_rapids_tpu.columnar import compression as WC

            with _trace.span("wire.compress", nbytes=a.nbytes,
                             dtype=str(a.dtype)):
                enc = WC.choose_and_encode(a.reshape(-1),
                                           *self.wire_cfg)
            if enc is not None:
                name, arrays, meta = enc
                refs = tuple(self.add(x) for x in arrays)
                return ("comp", name, refs, meta, str(a.dtype),
                        a.shape)
        return self.add(a)

    def finish(self) -> list[np.ndarray]:
        return self.arrays


def encode_for_device(arrays: Sequence[pa.Array], schema: T.Schema,
                      n: int) -> Optional[tuple[list, tuple]]:
    """Encode decoded host Arrow columns into (components, plan).

    Returns None when a column type has no wire encoding yet (a decimal
    past precision 18, list) — callers fall back to the per-component
    padded upload path.  A decimal of one int64 word travels as that
    word, under the integer encodings.
    Traced as `wire.encode` on the encoding thread; its `wire_bytes`
    are what `upload_components` will count for these components.
    """
    if not _trace.TRACER.enabled:
        return _encode_components(arrays, schema, n)
    t0 = time.perf_counter_ns()
    enc = _encode_components(arrays, schema, n)
    _trace.record_complete(
        "wire.encode", t0, time.perf_counter_ns() - t0, rows=n,
        columns=len(schema.fields),
        host_bytes=sum(a.nbytes for a in arrays),
        wire_bytes=sum(int(c.nbytes) for c in enc[0]) if enc else 0)
    return enc


def _encode_components(arrays: Sequence[pa.Array], schema: T.Schema,
                       n: int) -> Optional[tuple[list, tuple]]:
    for f in schema.fields:
        if T.is_wide_decimal(f.dtype) or isinstance(
                f.dtype, (T.ListType, T.StructType, T.MapType)):
            return None
    if n == 0:
        return None

    cap = pad_capacity(n)
    wire = _wire_rows(n, cap)
    from spark_rapids_tpu.columnar.compression import wire_codec_config

    comps = _Comps(wire_codec_config())
    n_ref = comps.add(np.asarray(n, np.int32))  # dynamic live row count
    entries: list[tuple] = []

    for arr, f in zip(arrays, schema.fields):
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if isinstance(arr, pa.DictionaryArray):
            # dictionary came straight from the Parquet page (fastpar):
            # ship codes + values with no re-encode and, for strings,
            # no full-column materialization at all
            e = _encode_dict_direct(comps, arr, f.dtype, wire)
            if e is not None:
                entries.append(e)
                continue
            arr = arr.cast(arr.type.value_type)
        if isinstance(f.dtype, T.StringType):
            entries.append(_encode_string(comps, arr, wire))
            continue
        vals, validity = _decode_fixed_host(arr, f.dtype)
        if validity is not None and not validity.any():
            # all-NULL column: nothing crosses the wire at all (real
            # all-null data, and the scan's filter-only column
            # suppression which nulls columns no operator above the
            # elided filter reads)
            entries.append(("fixed", "null", -1, str(vals.dtype), (),
                            None, None))
            continue
        vref = None
        if validity is not None:
            vref = comps.add_wire(_padded(validity, wire))
        phys = vals.dtype
        kind = "raw"
        extra: tuple = ()
        dict_n = None  # bucketed dictionary entry bound, dict entries
        if phys.kind in _INT_KINDS and phys.itemsize > 1:
            mn, rng, enc8, enc16 = _int_range(vals, phys)
            if rng <= 0xFF:
                kind = "bias"
                extra = (comps.add(np.asarray(mn, np.int64)),)
                vals = enc8(vals, mn)
            elif phys.itemsize > 2 and rng <= 0xFFFF:
                kind = "bias"
                extra = (comps.add(np.asarray(mn, np.int64)),)
                vals = enc16(vals, mn)
            elif phys.itemsize > 4 and rng <= 0xFFFFFFFF:
                # 64-bit ints with a 32-bit range (join/order keys)
                # halve the dominant upload; base + zero-extended u32
                # round-trips exactly (vals-mn <= rng, no overflow)
                kind = "bias"
                extra = (comps.add(np.asarray(mn, np.int64)),)
                vals = (vals - mn).astype(np.uint32)
        elif phys.kind == "f":
            enc = _try_dict(vals)
            if enc is not None:
                codes, dvals = enc
                code_dt = np.uint8 if len(dvals) <= 0x100 else np.uint16
                nvp = max(8, pad_capacity(len(dvals)))
                kind = "dict"
                dict_n = _dict_len_bound(len(dvals), nvp)
                extra = (comps.add_wire(_padded(dvals, nvp)),)
                vals = codes.astype(code_dt)
            elif phys.itemsize == 8:
                scaled = _try_scaled(vals)
                if scaled is not None:
                    kind = "scaled"
                    # divisor rides as a RUNTIME scalar: a literal
                    # constant lets XLA strength-reduce /100.0 into
                    # *(1/100.0), which breaks the bit-exactness the
                    # host encoder verified
                    extra = (comps.add(np.asarray(100.0, np.float64)),)
                    vals = scaled
        dref = comps.add_wire(_padded(vals, wire))
        entries.append(("fixed", kind, dref, str(phys), extra, vref,
                        dict_n))

    plan = (cap, wire, n_ref, tuple(entries))
    return comps.finish(), plan


def _dict_len_bound(n: int, nvp: int) -> int:
    """Tight upper bound on a dictionary's true entry count, bucketed
    to a multiple of 16 (min 8) and clamped to the padded capacity.
    The bound rides in pytree aux data / the wire plan, both of which
    key jit compile caches — an EXACT per-row-group cardinality would
    mint a distinct program per dictionary size, while the full padded
    capacity (pow2) overestimates coded-key domains (compounding per
    group key).  The bucket keeps domains within 16 of tight and the
    program-variant count small."""
    return min(nvp, max(8, -(-n // 16) * 16))


def _encode_dict_direct(comps: _Comps, arr: pa.DictionaryArray,
                        dtype: T.DataType, wire: int) -> Optional[tuple]:
    """A pre-dictionary-encoded column -> wire dict/sdict entry, trusting
    the source dictionary (values came FROM it, so the round trip is
    exact by construction).  None = no dict wire form for this type."""
    dvals = arr.dictionary
    nvals = len(dvals)
    if nvals > 0xFFFF or dvals.null_count:
        # a null INSIDE the dictionary hides row nulls from
        # arr.is_valid() (index-level only): take the plain path,
        # which decodes through the value type and keeps the nulls
        return None
    validity = np.asarray(arr.is_valid()) if arr.null_count else None
    codes = arr.indices.to_numpy(zero_copy_only=False)
    if validity is not None:
        codes = np.where(validity, codes, 0)
    if isinstance(dtype, T.StringType):
        return _sdict_entry(comps, codes, dvals, validity, wire)
    if isinstance(dtype, (T.DecimalType, T.ListType, T.StructType,
                          T.MapType)):
        return None
    dnp, dvalid = _decode_fixed_host(dvals, dtype)
    if dvalid is not None:
        return None
    code_dt = np.uint8 if nvals <= 0x100 else np.uint16
    nvp = max(8, pad_capacity(max(nvals, 1)))
    vref = comps.add_wire(_padded(validity, wire)) \
        if validity is not None else None
    cref = comps.add_wire(_padded(codes.astype(code_dt), wire))
    extra = (comps.add_wire(_padded(dnp, nvp)),)
    return ("fixed", "dict", cref, str(dnp.dtype), extra, vref,
            _dict_len_bound(nvals, nvp))


def _sdict_entry(comps: _Comps, codes: np.ndarray, dvals: pa.Array,
                 validity: Optional[np.ndarray],
                 wire: int) -> Optional[tuple]:
    """Assemble one string-dictionary wire entry (shared by the direct
    DictionaryArray path and the host re-encode path); None when the
    dictionary exceeds the wire's uint16 length/size format."""
    nvals = len(dvals)
    if nvals > 0xFFFF:
        return None
    dchars, dlens = _chars_matrix(dvals.cast(pa.large_string()))
    if dlens.size and int(dlens.max()) > 0xFFFF:
        return None
    code_dt = np.uint8 if nvals <= 0x100 else np.uint16
    nvp = max(8, pad_capacity(max(nvals, 1)))
    vref = comps.add_wire(_padded(validity, wire)) \
        if validity is not None else None
    cref = comps.add_wire(_padded(codes.astype(code_dt), wire))
    dcref = comps.add_wire(_padded(dchars, nvp))
    dlref = comps.add_wire(_padded(dlens.astype(np.uint16), nvp))
    return ("sdict", cref, dcref, dlref, vref,
            _dict_len_bound(nvals, nvp))


def _encode_string(comps: _Comps, arr: pa.Array, wire: int) -> tuple:
    """Encode one string column; returns its plan entry."""
    sarr = arr.cast(pa.large_string())
    n = len(sarr)
    offsets = np.frombuffer(sarr.buffers()[1], dtype=np.int64,
                            count=n + 1, offset=sarr.offset * 8)
    validity = (np.asarray(arr.is_valid()) if arr.null_count
                else None)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    if validity is not None:
        lens = np.where(validity, lens, 0).astype(np.int32)

    # dictionary attempt: low-cardinality string columns ship codes only
    if _string_dict_gate(sarr):
        d = sarr.dictionary_encode()
        dvals = d.dictionary
        if (len(dvals) * 2 <= max(n, 1)
                and not dvals.null_count):
            codes = d.indices.to_numpy(zero_copy_only=False)
            if validity is not None:
                codes = np.where(validity, codes, 0)
            e = _sdict_entry(comps, codes, dvals, validity, wire)
            if e is not None:
                return e
            # >=64KB dictionary values would wrap the uint16 length
            # wire format: fall through to the raw layout (int32 lens)

    vref = None
    if validity is not None:
        vref = comps.add_wire(_padded(validity, wire))
    chars, _ = _chars_matrix(sarr, lens)
    cref = comps.add_wire(_padded(chars, wire))
    # lengths >= 64KiB would wrap uint16: widen the wire type (the
    # decode side reads whatever dtype the ref carries)
    len_dt = np.uint16 if (not lens.size or int(lens.max()) <= 0xFFFF) \
        else np.int32
    lref = comps.add_wire(_padded(lens.astype(len_dt), wire))
    return ("sraw", cref, lref, vref)


def _string_dict_gate(sarr: pa.Array) -> bool:
    n = len(sarr)
    if n <= 8192:
        return True
    d = sarr.slice(0, 4096).dictionary_encode()
    return len(d.dictionary) <= 1024


def _chars_matrix(sarr: pa.Array,
                  lens: Optional[np.ndarray] = None
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized fixed-width chars matrix for a large_string array:
    (chars[n, w], lengths[n])."""
    n = len(sarr)
    offsets = np.frombuffer(sarr.buffers()[1], dtype=np.int64,
                            count=n + 1, offset=sarr.offset * 8)
    data_buf = sarr.buffers()[2]
    raw = (np.frombuffer(data_buf, dtype=np.uint8)
           if data_buf is not None else np.zeros(1, np.uint8))
    if lens is None:
        lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    maxw = int(lens.max()) if n else 0
    w = pad_width(max(maxw, 1))
    if n == 0:
        return np.zeros((0, w), np.uint8), lens
    lib = _native()
    if lib is not None:
        chars = np.zeros((n, w), np.uint8)
        off = np.ascontiguousarray(offsets)
        cl = np.ascontiguousarray(np.minimum(lens, w).astype(np.int32))
        rb = np.ascontiguousarray(raw)
        lib.chars_fill(rb.ctypes.data, off.ctypes.data, cl.ctypes.data,
                       n, w, chars.ctypes.data)
        return chars, lens
    idx = offsets[:-1, None] + np.arange(w)[None, :]
    mask = np.arange(w)[None, :] < lens[:, None]
    safe = np.clip(idx, 0, max(len(raw) - 1, 0))
    chars = np.where(mask, raw[safe], 0).astype(np.uint8)
    return chars, lens


# ------------------------------------------------------------------ #
# Device-side decode program
# ------------------------------------------------------------------ #


def _make_decode(plan: tuple):
    cap, wire, n_ref, entries = plan
    pad = cap - wire

    def grow(a):
        if pad == 0:
            return a
        z = jnp.zeros((pad,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, z], axis=0)

    def decode(xs):
        def read(ref):
            if ref[0] == "arr":
                return xs[ref[1]]  # one typed array per component
            # ("comp", codec, refs, meta, dtype, shape): run the
            # codec's device decompress — it traces into THIS program,
            # so decompress+decode(+consumer transform) is one fused
            # XLA execution per batch
            from spark_rapids_tpu.columnar.compression import get_codec

            _, name, refs, meta, dt, shape = ref
            out = get_codec(name).decode_array(
                [xs[r[1]] for r in refs], meta, np.dtype(dt))
            return out.reshape(shape) if len(shape) > 1 else out

        n_live = read(n_ref)
        live_mask = jnp.arange(cap, dtype=jnp.int32) < n_live

        def validity_of(vref):
            if vref is None:
                return live_mask
            return grow(read(vref)) & live_mask

        out = []
        for e in entries:
            if e[0] == "fixed":
                _, kind, dref, physdt, extra, vref, _dict_n = e
                phys = np.dtype(physdt)
                if kind == "null":
                    out.append((jnp.zeros((cap,), phys),
                                jnp.zeros((cap,), jnp.bool_)))
                    continue
                vals = read(dref)
                if kind == "bias":
                    base = read(extra[0])
                    vals = (vals.astype(jnp.int64) + base).astype(phys)
                elif kind == "dict":
                    codes = vals.astype(jnp.int32)
                    vals = jnp.take(read(extra[0]), codes, axis=0)
                    # codes + dictionary ride along as the column's
                    # sidecar (grow pads dead rows with code 0): the
                    # coded group-by uses them as dense group ids for
                    # low-cardinality numeric keys, skipping the sort
                    out.append((grow(vals), validity_of(vref),
                                grow(codes), read(extra[0])))
                    continue
                elif kind == "scaled":
                    # same op the host exactness check performed
                    vals = vals.astype(phys) / read(extra[0])
                out.append((grow(vals), validity_of(vref)))
            elif e[0] == "sraw":
                _, cref, lref, vref = e
                v = validity_of(vref)
                out.append((grow(read(cref)),
                            grow(read(lref).astype(jnp.int32))
                            * v.astype(jnp.int32), v))
            elif e[0] == "sdict":
                _, cref, dcref, dlref, vref, _dict_n = e
                codes = read(cref).astype(jnp.int32)
                v = validity_of(vref)
                # invariant shared with every string kernel: chars are
                # zero for null rows and beyond each row's length — a
                # gathered dict[0] payload on null/padding rows would
                # break byte-wise comparators
                chars = grow(jnp.take(read(dcref), codes, axis=0)) \
                    * v[:, None].astype(jnp.uint8)
                lens = grow(jnp.take(read(dlref).astype(jnp.int32),
                                     codes, axis=0)) \
                    * v.astype(jnp.int32)
                # codes + dictionary ride along as the column's dict
                # sidecar: the group-by coded fast path uses codes as
                # dense group ids (no sort).  grow() pads dead rows
                # with code 0; consumers gate on validity/row masks.
                out.append((chars, lens, v, grow(codes), read(dcref),
                            read(dlref).astype(jnp.int32)))
        return out

    return decode


def _wrap_cols(parts, schema: T.Schema, entries=None):
    """Decode-program outputs -> AnyColumn list (traceable).  `entries`
    (the plan's per-column entry tuples) supplies the bucketed
    dictionary entry bound for dict-encoded columns — the device
    arrays are padded to pow2 capacity buckets, so consumers sizing
    code domains need the tighter bound carried separately."""
    cols = []
    for i, (f, p) in enumerate(zip(schema.fields, parts)):
        e = entries[i] if entries is not None else None
        dict_n = e[-1] if e is not None and e[0] in ("fixed",
                                                     "sdict") else None
        if isinstance(f.dtype, T.StringType):
            if len(p) == 6:  # sdict: dictionary sidecar rides along
                chars, lens, valid, codes, dchars, dlens = p
                cols.append(StringColumn(chars, lens, valid, f.dtype,
                                         codes, dchars, dlens, dict_n))
                continue
            chars, lens, valid = p
            cols.append(StringColumn(chars, lens, valid))
        else:
            if len(p) == 4:  # dict: numeric dictionary sidecar
                data, valid, codes, dvals = p
                cols.append(Column(data, valid, f.dtype, codes, dvals,
                                   dict_n))
                continue
            data, valid = p
            cols.append(Column(data, valid, f.dtype))
    return cols


def plan_codecs(plan: tuple) -> tuple:
    """Codec names appearing in a wire plan's comp refs (empty when the
    plan is uncompressed) — the host-side view the decompress stats and
    the wire.decompress span key off."""
    names = []
    for e in plan[3]:
        for ref in e:
            if isinstance(ref, tuple) and ref and ref[0] == "comp":
                names.append(ref[1])
            elif isinstance(ref, tuple) and ref and \
                    isinstance(ref[0], tuple):  # extra refs tuple
                names.extend(r[1] for r in ref if r[0] == "comp")
    return tuple(names)


def _record_decompress(names: tuple) -> None:
    """Bump the per-codec decompress stats for one wire-form batch
    (``names`` = plan_codecs(plan), computed once by the caller)."""
    if not names:
        return
    from spark_rapids_tpu.columnar import compression as WC

    for name in set(names):
        WC.record_decompress(name, names.count(name))


def decode_on_device(comps: list, plan: tuple, schema: T.Schema,
                     record: bool = True):
    """Upload the component list (one batched transfer round) and run
    the cached decode program.  Returns device columns in schema
    order.  The program is compiled through cached_jit under
    op="WireDecode", so the device ledger attributes decode (and
    decompress) device-time per program.

    ``record=False`` skips the per-codec decompress stat bump: callers
    whose batch was ALREADY counted at encode_batch (decode_now on a
    wire-form batch) must not count it twice — every encoded batch
    contributes exactly one decompress per codec use, whether its
    decode runs here eagerly or fused inside a consumer program."""
    from spark_rapids_tpu.execs.jit_cache import cached_jit

    # the compiled decode ignores dict_n (it is applied by _wrap_cols
    # OUTSIDE the program here): strip it from the cache key so row
    # groups differing only in dictionary cardinality bucket share one
    # program (the fused EncodedBatch path legitimately keys on it)
    key = ("wire.decode",) + plan[:3] + (tuple(
        e[:-1] if e[0] in ("fixed", "sdict") else e for e in plan[3]),)
    fn = cached_jit(key, lambda: _make_decode(plan), op="WireDecode")
    dev = upload_components(comps)
    codecs = plan_codecs(plan)
    if codecs:
        if record:
            _record_decompress(codecs)
        with _trace.span("wire.decompress", components=len(codecs),
                         codecs=",".join(sorted(set(codecs)))):
            parts = fn(dev)
    else:
        parts = fn(dev)
    return _wrap_cols(parts, schema, plan[3])


class ConsumedBatchError(RuntimeError):
    """A donated (consumed) batch was asked for its device buffers
    again.  Deliberately NON-retryable (no retryable marker in the
    text): re-running over freed HBM cannot succeed, so the failure
    must fail fast instead of burning the spill/split ladder —
    donation's contract is that consumers resume from the memoized
    program output (run_consuming), never re-execute."""


def run_consuming(fn, eb: "EncodedBatch"):
    """Execute a DONATING fused program over a wire-form batch exactly
    once.  The batch is marked consumed BEFORE the call (a failure
    mid-execution leaves device state unknown — conservatively gone)
    and the output is memoized on the batch, so a retry-ladder re-run
    of the same unit (e.g. a retire-side OOM after a successful
    update dispatch) RESUMES from the already-produced output instead
    of re-executing over donated buffers.  A re-run that finds the
    batch consumed with no memoized output (the program itself died)
    — or a memoized output whose buffers were since freed (spilled
    while registered, and the rollback's repair_donated_memo could
    not restore it) — raises ConsumedBatchError, non-retryable by
    design."""
    if eb.consumed:
        if eb.donated_out is None:
            raise ConsumedBatchError(
                "donated program died mid-execution; input buffers "
                "are gone and no output was memoized")
        if memo_is_dead(eb.donated_out):
            raise ConsumedBatchError(
                "memoized donated output was spilled and its device "
                "buffers freed before the re-run; input buffers are "
                "gone too, so the unit cannot be recovered")
        return eb.donated_out
    eb.consumed = True
    out = fn(eb)
    eb.donated_out = out
    return out


def memo_is_dead(out) -> bool:
    """True if any device-array leaf of a memoized program output has
    been deleted.  The spill store's device→host spill deletes the
    device arrays of the batch it holds (`_batch_to_host(delete=True)`)
    and restores into a NEW batch object — a raw reference memoized
    before the spill (EncodedBatch.donated_out) is not updated, so it
    must be liveness-checked before the resume path hands it
    downstream."""
    for x in jax.tree_util.tree_leaves(out):
        if isinstance(x, jax.Array):
            try:
                if x.is_deleted():
                    return True
            except Exception:
                return True
    return False


def repair_donated_memo(eb: "EncodedBatch", handle) -> bool:
    """Rollback seam for a donated unit (docs/fusion.md): retire
    registers the memoized update output with the spill store UNPINNED,
    so pressure may spill it — deleting the very device arrays
    ``eb.donated_out`` references.  A retry-ladder rollback about to
    close that registration (dropping the only surviving copy) calls
    this first: if the memo is dead, re-materialize through the handle
    and re-memoize, so the re-run's resume hands downstream a live
    batch instead of freed buffers — the recovery the memo exists for.
    Best-effort: a failed restore (e.g. OOM during the rollback
    itself) leaves the memo dead and run_consuming fails fast with
    ConsumedBatchError instead of an opaque deleted-array crash.
    Returns True when the memo was repaired."""
    out = eb.donated_out
    if out is None or not memo_is_dead(out):
        return False
    try:
        restored = handle.get()  # re-materialize on device (pins)
        handle.unpin()
    except Exception:
        return False  # rollback must proceed; resume will fail fast
    eb.donated_out = restored
    return True


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EncodedBatch:
    """A scan batch still in WIRE form: uploaded components + static
    decode plan.  Consumers that jit their per-batch work (the fusable
    pipeline driver, the hash aggregate's update phase) decode INSIDE
    their own program, so scan->filter->aggregate is one program
    execution per batch — every execution has a fixed dispatch cost,
    so collapsing decode+transform+update into one program saves it
    twice per batch
    (the reference gets the same effect by chaining cudf kernels inside
    one task, GpuParquetScan.scala:495-560 -> GpuFilterExec).

    `num_rows` is the host-known live count for metrics/accumulation
    bookkeeping; it deliberately does NOT survive tracing (the decode
    derives the traced count from the wire components), so one compiled
    consumer program serves every ragged tail.

    `consumed` / `donated_out`: donation bookkeeping
    (docs/fusion.md).  A consumer that donates the wire components
    into its fused program (cached_jit's `donate=`) marks the batch
    consumed FIRST and memoizes the program output — the retry/split
    ladder's re-run path then resumes from the memoized output instead
    of re-executing over donated (freed) buffers, and
    `retry.bisect_batch`/`_batch_rows` refuse to decode or split a
    consumed batch.  Neither field rides the pytree (flatten drops
    them): tracing sees only the wire components.
    """

    comps: list
    plan: tuple
    schema: T.Schema
    num_rows: Optional[int] = None
    consumed: bool = False
    donated_out: Optional[object] = None

    def tree_flatten(self):
        return (tuple(self.comps),), (self.plan, self.schema)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (comps,) = children
        return cls(list(comps), aux[0], aux[1], None)

    @property
    def capacity(self) -> int:
        return self.plan[0]

    @property
    def live_count(self):
        """The wire `n` component: a device scalar holding the live
        row count (the one place the plan's n-ref layout is decoded —
        consumers must not index comps/plan themselves)."""
        return self.comps[self.plan[2][1]]

    def decode(self):
        """Traceable: wire components -> ColumnarBatch with a traced
        live-row count (read off the wire's n component)."""
        from spark_rapids_tpu.columnar.batch import ColumnarBatch

        decode = _make_decode(self.plan)
        cols = _wrap_cols(decode(self.comps), self.schema, self.plan[3])
        return ColumnarBatch(cols,
                             jnp.asarray(self.live_count, jnp.int32),
                             self.schema)

    def decode_now(self):
        """Eager fallback for consumers that do not fuse the decode."""
        from spark_rapids_tpu.columnar.batch import ColumnarBatch

        if self.consumed:
            raise ConsumedBatchError(
                "wire components were donated into a fused program; "
                "the batch cannot be decoded again")
        # record=False: this batch's decompress was counted when
        # encode_batch shipped it
        cols = decode_on_device(self.comps, self.plan, self.schema,
                                record=False)
        n = self.num_rows
        if n is None:
            from spark_rapids_tpu.parallel.pipeline import device_read_int

            n = device_read_int(self.live_count, tag="transfer.decode")
        return ColumnarBatch(cols, n, self.schema)


def encode_batch(arrays: Sequence[pa.Array], schema: T.Schema,
                 n: int) -> Optional[EncodedBatch]:
    """Host Arrow columns -> EncodedBatch (one batched H2D upload), or
    None when a column type has no wire encoding."""
    enc = encode_for_device(arrays, schema, n)
    if enc is None:
        return None
    comps, plan = enc
    # a wire-form batch is decoded (decompressed) exactly once —
    # fused inside a consumer program or via decode_now — so the
    # per-codec decompress stat is counted HERE, where every such
    # batch passes once on the host (trace-time counting inside the
    # fused program would undercount on compile-cache hits)
    _record_decompress(plan_codecs(plan))
    return EncodedBatch(upload_components(comps), plan, schema, n)
