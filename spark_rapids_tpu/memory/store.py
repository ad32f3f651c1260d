"""Spill-tiered buffer store.

Maps the reference's architecture onto JAX/TPU:

- `StorageTier` DEVICE/HOST/DISK (ref: RapidsBuffer.scala:53-58; the GDS
  tier has no TPU analog and is dropped);
- `SpillableBatch` = SpillableColumnarBatch: a handle that lets the
  store move the batch down-tier while unused; `.get()` re-materializes
  on device (ref: SpillableColumnarBatch.scala:29);
- `BufferStore` = RapidsBufferCatalog + the per-tier stores: one
  priority-ordered registry with byte accounting per tier
  (ref: RapidsBufferStore.scala:145-207 synchronousSpill);
- `reserve()` replaces DeviceMemoryEventHandler.onAllocFailure: callers
  reserve device bytes *before* materializing, and the store spills
  lowest-priority resident buffers until the budget fits (proactive —
  XLA has no alloc-failure hook);
- spill priorities (ref: SpillPriorities.scala): exchange outputs spill
  first, active working batches last.

Device -> host movement is `jax.device_get` + explicit `.delete()` on
the device arrays (deterministic HBM release); host -> disk is a .npz
file in the configured spill directory."""

from __future__ import annotations

import dataclasses
import enum
import os
import tempfile
import threading
from typing import Optional

import jax
import numpy as np

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    MapColumn,
    StructColumn,
    AnyColumn,
    Column,
    ListColumn,
    StringColumn,
)
from spark_rapids_tpu.config import register


class StorageTier(enum.IntEnum):
    DEVICE = 0
    HOST = 1
    DISK = 2


class SpillPriorities:
    """Lower value spills first (ref: SpillPriorities.scala:26-60)."""

    OUTPUT_FOR_SHUFFLE = -100
    COALESCE_PENDING = 0
    #: cross-tenant shared-result entries (serving/work_share.py):
    #: pure cache — always rebuildable by re-running the query, and
    #: entirely host/disk-tier (Arrow-IPC frames, never device
    #: buffers) — so they yield host memory before any working data
    #: does; the disk hop is their designed pressure valve
    SHARED_RESULT = 10
    #: cached (df.cache) batches are re-served across queries but are
    #: rebuildable by re-running the subtree: spill them before the
    #: working set of the running query
    CACHED = 20
    AGGREGATE_PARTIAL = 50
    JOIN_BUILD = 80
    #: broadcast builds are shared across every stream partition, so
    #: respilling is paid many times over — spill them last (ref:
    #: GpuBroadcastExchangeExec keeping broadcast batches as catalog
    #: entries, GpuBroadcastExchangeExec.scala:237,271)
    BROADCAST = 90
    ACTIVE_ON_DECK = 100


HBM_BUDGET_BYTES = register(
    "spark.rapids.tpu.memory.hbm.budgetBytes", 12 << 30,
    "Device-memory budget the buffer store manages batches within "
    "(ref: spark.rapids.memory.gpu.pool sizing, RapidsConf.scala:413). "
    "Proactive: reservations beyond this trigger synchronous spill.  "
    "Left at its default on a TPU, the budget is memory.fraction of "
    "the HBM the chip reports (memory/device_manager.store_budget); "
    "the CPU backend and an explicit setting use this figure.")
HOST_SPILL_BYTES = register(
    "spark.rapids.tpu.memory.host.spillStorageSize", 4 << 30,
    "Host-memory bound for spilled batches before they continue to disk "
    "(ref: spark.rapids.memory.host.spillStorageSize, "
    "RapidsConf.scala:357).")
SPILL_DIR = register(
    "spark.rapids.tpu.memory.spill.dir", "",
    "Directory for disk-tier spill files (default: a temp dir).")
SPILL_HOST_COMPRESS = register(
    "spark.rapids.tpu.memory.spill.compressHostTier", False,
    "Serialize device->host spills through the spill codec "
    "(spark.rapids.tpu.memory.spill.compression.codec, shared "
    "wire-codec registry) so the HOST tier holds compressed frames: "
    "more batches fit under spillStorageSize before the disk tier "
    "engages, and a later host->disk spill writes the frame as-is "
    "(no recompression).  Costs a decompress on restore.  Snapshotted "
    "at store construction, like the codec itself.")


def _col_device_bytes(c) -> int:
    if isinstance(c, StringColumn):
        n = c.chars.size * 1 + c.lengths.size * 4 + c.validity.size
        if c.codes is not None:
            n += (c.codes.size * 4 + c.dict_chars.size
                  + c.dict_lens.size * 4)
        return n
    if isinstance(c, ListColumn):
        return (c.values.size * c.values.dtype.itemsize
                + c.lengths.size * 4 + c.elem_validity.size
                + c.validity.size)
    if isinstance(c, StructColumn):
        return sum(_col_device_bytes(k) for k in c.children) \
            + c.validity.size
    if isinstance(c, MapColumn):
        return (c.keys.size * c.keys.dtype.itemsize
                + c.values.size * c.values.dtype.itemsize
                + c.entry_validity.size + c.lengths.size * 4
                + c.validity.size)
    return c.data.size * c.data.dtype.itemsize + c.validity.size


def batch_device_bytes(batch: ColumnarBatch) -> int:
    total = sum(_col_device_bytes(c) for c in batch.columns)
    if not isinstance(batch.num_rows, int):
        total += 4
    return total


def _col_leaves(c, prefix: str) -> list[tuple[str, object]]:
    """(name, device array) leaves of one column (recursive)."""
    if isinstance(c, StringColumn):
        out = [(f"{prefix}_chars", c.chars),
               (f"{prefix}_lengths", c.lengths),
               (f"{prefix}_valid", c.validity)]
        if c.codes is not None:  # dict sidecar spills/restores with it
            out += [(f"{prefix}_codes", c.codes),
                    (f"{prefix}_dchars", c.dict_chars),
                    (f"{prefix}_dlens", c.dict_lens)]
            if c.dict_len is not None:
                # static entry-count bound: a host scalar leaf (passes
                # device_get untouched, skipped by _delete) — dropping
                # it would demote restored keys to padded-capacity
                # domains and fork the pytree aux
                out.append((f"{prefix}_dictlen",
                            np.asarray(c.dict_len, np.int64)))
        return out
    if isinstance(c, ListColumn):
        return [(f"{prefix}_lvalues", c.values),
                (f"{prefix}_lengths", c.lengths),
                (f"{prefix}_levalid", c.elem_validity),
                (f"{prefix}_valid", c.validity)]
    if isinstance(c, StructColumn):
        out = []
        for j, k in enumerate(c.children):
            out += _col_leaves(k, f"{prefix}_f{j}")
        return out + [(f"{prefix}_valid", c.validity)]
    if isinstance(c, MapColumn):
        return [(f"{prefix}_mkeys", c.keys),
                (f"{prefix}_mvalues", c.values),
                (f"{prefix}_mevalid", c.entry_validity),
                (f"{prefix}_lengths", c.lengths),
                (f"{prefix}_valid", c.validity)]
    out = [(f"{prefix}_data", c.data), (f"{prefix}_valid", c.validity)]
    if getattr(c, "codes", None) is not None:
        # numeric dict sidecar spills/restores with the column (as the
        # StringColumn sidecar does): dropping it would silently demote
        # a restored group-by key to the lexsort path
        out += [(f"{prefix}_codes", c.codes),
                (f"{prefix}_dvals", c.dict_values)]
        if c.dict_len is not None:
            out.append((f"{prefix}_dictlen",
                        np.asarray(c.dict_len, np.int64)))
    return out


#: leaf-name suffixes of DICTIONARY SIDECAR arrays.  gather/compact/
#: split pass the row-invariant dictionary through BY REFERENCE, so
#: every child batch of a dict-encoded column shares ONE device array —
#: spilling one registered child must not .delete() it out from under
#: its siblings (the "Array has been deleted" crash under a tight
#: budget).  Skipping the explicit delete only defers release to the
#: last Python reference dropping; dictionaries are bounded at 0xFFFF
#: entries, so the nondeterminism is a few KB, not a batch.
_SHARED_SIDECAR_SUFFIXES = ("_dchars", "_dlens", "_dvals")


def _batch_to_host(batch: ColumnarBatch,
                   delete: bool = True) -> dict:
    """Materialize to numpy; `delete` releases the device buffers
    (spill), False leaves them resident (host VIEW, e.g. serve_host)."""
    n = batch.concrete_num_rows()
    leaves: list[tuple[str, object]] = []
    for i, c in enumerate(batch.columns):
        leaves += _col_leaves(c, f"c{i}")
    # ONE batched D2H round for every leaf (per-leaf gets would pay
    # link latency per buffer)
    host = jax.device_get([a for _, a in leaves])
    arrays: dict[str, np.ndarray] = {
        name: np.asarray(h) for (name, _), h in zip(leaves, host)}
    if delete:
        for name, a in leaves:
            if not name.endswith(_SHARED_SIDECAR_SUFFIXES):
                _delete(a)
    arrays["__num_rows"] = np.asarray(n, np.int64)
    return arrays


def _delete(a) -> None:
    from spark_rapids_tpu.columnar.column import is_shared_array

    if isinstance(a, jax.Array) and not is_shared_array(a):
        try:
            a.delete()
        except Exception:
            pass  # already consumed/donated


def _host_to_col(arrays: dict, prefix: str, dtype: T.DataType):
    import jax.numpy as jnp

    if isinstance(dtype, T.StringType):
        codes = arrays.get(f"{prefix}_codes")
        return StringColumn(
            jnp.asarray(arrays[f"{prefix}_chars"]),
            jnp.asarray(arrays[f"{prefix}_lengths"]),
            jnp.asarray(arrays[f"{prefix}_valid"]), dtype,
            jnp.asarray(codes) if codes is not None else None,
            jnp.asarray(arrays[f"{prefix}_dchars"])
            if codes is not None else None,
            jnp.asarray(arrays[f"{prefix}_dlens"])
            if codes is not None else None,
            _restore_dict_len(arrays, prefix))
    if isinstance(dtype, T.ListType):
        return ListColumn(
            jnp.asarray(arrays[f"{prefix}_lvalues"]),
            jnp.asarray(arrays[f"{prefix}_lengths"]),
            jnp.asarray(arrays[f"{prefix}_levalid"]),
            jnp.asarray(arrays[f"{prefix}_valid"]), dtype)
    if isinstance(dtype, T.StructType):
        kids = tuple(_host_to_col(arrays, f"{prefix}_f{j}", cf.dtype)
                     for j, cf in enumerate(dtype.fields))
        return StructColumn(kids,
                            jnp.asarray(arrays[f"{prefix}_valid"]),
                            dtype)
    if isinstance(dtype, T.MapType):
        return MapColumn(
            jnp.asarray(arrays[f"{prefix}_mkeys"]),
            jnp.asarray(arrays[f"{prefix}_mvalues"]),
            jnp.asarray(arrays[f"{prefix}_mevalid"]),
            jnp.asarray(arrays[f"{prefix}_lengths"]),
            jnp.asarray(arrays[f"{prefix}_valid"]), dtype)
    codes = arrays.get(f"{prefix}_codes")
    return Column(jnp.asarray(arrays[f"{prefix}_data"]),
                  jnp.asarray(arrays[f"{prefix}_valid"]), dtype,
                  None if codes is None else jnp.asarray(codes),
                  None if codes is None
                  else jnp.asarray(arrays[f"{prefix}_dvals"]),
                  _restore_dict_len(arrays, prefix))


def _restore_dict_len(arrays: dict, prefix: str):
    v = arrays.get(f"{prefix}_dictlen")
    return None if v is None else int(np.asarray(v))


def _host_to_batch(arrays: dict, schema: T.Schema) -> ColumnarBatch:
    cols: list[AnyColumn] = [
        _host_to_col(arrays, f"c{i}", f.dtype)
        for i, f in enumerate(schema.fields)]
    n = int(np.asarray(arrays["__num_rows"]).reshape(-1)[0])
    return ColumnarBatch(cols, n, schema)


class _HostFrame:
    """A HOST-tier entry held as one compressed serde frame instead of
    a raw array dict (spill.compressHostTier): the host tier then
    stores what the disk tier would write, so host->disk spill is a
    plain file write and host occupancy accounts compressed bytes."""

    __slots__ = ("frame",)

    def __init__(self, frame: bytes):
        self.frame = frame


def _host_arrays(held) -> dict:
    """A HOST-tier entry's payload as a raw array dict (decompressing
    a _HostFrame through the serde/codec registry)."""
    if isinstance(held, _HostFrame):
        from spark_rapids_tpu.columnar.serde import deserialize_arrays

        return deserialize_arrays(held.frame)
    return held


def _host_bytes(held) -> int:
    if isinstance(held, _HostFrame):
        return len(held.frame)
    return int(sum(a.nbytes for a in held.values()))


@dataclasses.dataclass
class _Entry:
    buffer_id: int
    priority: int
    nbytes: int
    tier: StorageTier
    batch: Optional[ColumnarBatch]  # DEVICE tier
    host: Optional[dict]  # HOST tier
    path: Optional[str]  # DISK tier
    schema: T.Schema
    #: pin COUNT: entries in active use must not be evicted — an
    #: acquire() that spills an already-acquired sibling would delete
    #: device arrays the caller still holds.  A count (not a flag)
    #: because shared entries (broadcast builds) are acquired by many
    #: stream partitions concurrently; the first unpin must not make
    #: the entry evictable under the others.
    pins: int = 0
    #: host-bytes equivalent parked on the DISK tier (what disk_used
    #: credits back when the entry is restored or removed)
    disk_bytes: int = 0

    @property
    def pinned(self) -> bool:
        return self.pins > 0


class SpillableBatch:
    """Handle registering a device batch with the store so it may spill
    while not in active use.  `get()` returns a device-resident batch,
    re-materializing (and re-registering at DEVICE) if spilled.

    `mark_consumed()` is the donation seam (docs/fusion.md): a caller
    that donates the batch's device arrays into a fused XLA program
    must un-register them FIRST — a donated-then-spilled buffer is a
    use-after-free (`_batch_to_host` would device_get freed HBM).
    Consumed handles stay valid objects: `unpin`/`close` become
    no-ops (so retry-ladder rollbacks that sweep handle lists never
    re-park a donated batch) and `get()` fails fast."""

    def __init__(self, store: "BufferStore", buffer_id: int):
        self._store = store
        self.buffer_id = buffer_id
        self._consumed = False

    def get(self) -> ColumnarBatch:
        """Acquire device-resident (pins the buffer until unpin/close)."""
        if self._consumed:
            from spark_rapids_tpu.columnar.transfer import (
                ConsumedBatchError,
            )

            raise ConsumedBatchError(
                f"buffer {self.buffer_id} was donated into a fused "
                "program and cannot be re-materialized")
        return self._store.acquire(self.buffer_id)

    def mark_consumed(self) -> None:
        """Un-register: the device arrays are being donated into a
        fused program (XLA reuses their HBM for the outputs), so the
        store must never spill or account them again.  Idempotent;
        the entry is dropped WITHOUT deleting the arrays (XLA now
        owns that memory)."""
        if self._consumed:
            return
        self._consumed = True
        self._store.remove(self.buffer_id)

    @property
    def consumed(self) -> bool:
        return self._consumed

    def _raise_consumed(self, what: str) -> None:
        from spark_rapids_tpu.columnar.transfer import (
            ConsumedBatchError,
        )

        raise ConsumedBatchError(
            f"buffer {self.buffer_id} was donated into a fused "
            f"program; {what} is gone")

    def get_host(self) -> dict:
        """Read the batch as host arrays without materializing on device
        (pins; the out-of-core sort assembles buckets host-side)."""
        if self._consumed:
            self._raise_consumed("its host view")
        return self._store.acquire_host(self.buffer_id)

    def unpin(self) -> None:
        """Make the buffer spillable again (caller dropped its batch
        reference).  No-op on a consumed handle — a rollback sweep
        must never make a donated buffer spillable."""
        if self._consumed:
            return
        with self._store._lock:
            e = self._store._entries.get(self.buffer_id)
            if e is not None:
                e.pins = max(0, e.pins - 1)

    @property
    def tier(self) -> StorageTier:
        if self._consumed:
            self._raise_consumed("its storage tier")
        return self._store._entries[self.buffer_id].tier

    @property
    def nbytes(self) -> int:
        if self._consumed:
            self._raise_consumed("its byte accounting")
        return self._store._entries[self.buffer_id].nbytes

    def close(self) -> None:
        """No-op on a consumed handle (mark_consumed already dropped
        the entry; the arrays belong to XLA now)."""
        if self._consumed:
            return
        self._store.remove(self.buffer_id)


class BufferStore:
    def __init__(self, device_budget: Optional[int] = None,
                 host_budget: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        from spark_rapids_tpu.config import get_conf

        conf = get_conf()
        if device_budget is None:
            from spark_rapids_tpu.memory.device_manager import store_budget

            device_budget = store_budget(conf)
        self.device_budget = device_budget
        self.host_budget = host_budget if host_budget is not None \
            else conf.get(HOST_SPILL_BYTES)
        self._spill_dir = spill_dir or conf.get(SPILL_DIR) or None
        # snapshot at construction: spills run on worker threads whose
        # thread-local conf is not the user's session conf
        from spark_rapids_tpu.columnar.serde import spill_codec

        self._spill_codec = spill_codec()
        self._host_compress = conf.get_bool(SPILL_HOST_COMPRESS.key) \
            and self._spill_codec != "none"
        self._tmpdir: Optional[tempfile.TemporaryDirectory] = None
        self._entries: dict[int, _Entry] = {}  # guard: _lock
        self._next_id = 0           # guard: _lock
        self._lock = threading.RLock()
        self.device_used = 0        # guard: _lock
        self.host_used = 0          # guard: _lock
        #: observability (ref: spill metrics + memoryBytesSpilled)
        self.spilled_device_to_host = 0  # guard: _lock
        self.spilled_host_to_disk = 0    # guard: _lock
        #: gauge: host-bytes equivalent currently parked on disk (the
        #: telemetry sampler's third storage tier)
        self.disk_used = 0          # guard: _lock

    def spill_stats(self) -> dict[str, int]:
        """Point-in-time spill/occupancy accounting — the store's
        contribution to the event log's counter surface (the two
        ``spilled_*`` totals are monotonic; the ``*_used`` figures are
        gauges).  One locked read so the four values are mutually
        consistent."""
        with self._lock:
            return {
                "device_used": self.device_used,
                "host_used": self.host_used,
                "disk_used": self.disk_used,
                "spilled_device_to_host": self.spilled_device_to_host,
                "spilled_host_to_disk": self.spilled_host_to_disk,
            }

    # -- registration --------------------------------------------------- #

    def register(self, batch: ColumnarBatch,
                 priority: int = SpillPriorities.ACTIVE_ON_DECK
                 ) -> SpillableBatch:
        nbytes = batch_device_bytes(batch)
        with self._lock:
            self.reserve(nbytes)
            bid = self._next_id
            self._next_id += 1
            self._entries[bid] = _Entry(
                bid, priority, nbytes, StorageTier.DEVICE, batch, None,
                None, batch.schema)
            self.device_used += nbytes
            return SpillableBatch(self, bid)

    def register_host(self, arrays: dict, schema: T.Schema,
                      priority: int = SpillPriorities.ACTIVE_ON_DECK
                      ) -> SpillableBatch:
        """Register a batch already materialized as host arrays (the
        out-of-core sort's run storage: data that by design does not live
        on device).  Enters at HOST tier and participates in host->disk
        spill; `get()` re-materializes on device as usual."""
        with self._lock:
            bid = self._next_id
            self._next_id += 1
            # device-size estimate for when it is re-materialized
            nbytes = _host_bytes(arrays)
            self._entries[bid] = _Entry(
                bid, priority, nbytes, StorageTier.HOST, None, arrays,
                None, schema)
            self.host_used += nbytes
            while self.host_used > self.host_budget:
                if not self._spill_one_host_locked():
                    break
            return SpillableBatch(self, bid)

    def acquire(self, buffer_id: int) -> ColumnarBatch:
        with self._lock:
            e = self._entries[buffer_id]
            e.pins += 1  # before reserve(): a cascaded spill must
            # never select the entry being acquired (it could write a
            # disk file acquire would then orphan)
            try:
                if e.tier == StorageTier.DEVICE:
                    return e.batch  # type: ignore[return-value]
                with _trace.span("spill.restore", tier=e.tier.name,
                                 bytes=e.nbytes, buffer=e.buffer_id):
                    if e.tier == StorageTier.HOST:
                        arrays = _host_arrays(e.host)
                    else:
                        from spark_rapids_tpu.columnar.serde import (
                            read_spill_file,
                        )

                        arrays = read_spill_file(e.path)  # type: ignore
                    self.reserve(e.nbytes)
                    batch = _host_to_batch(arrays, e.schema)  # H2D
            except BaseException:
                # a failed acquire must not leak its pin (the entry
                # would be unevictable forever)
                e.pins = max(0, e.pins - 1)
                raise
            if e.tier == StorageTier.HOST:
                self.host_used -= _host_bytes(e.host)
            elif e.path:
                # unlink only after the upload succeeded: an exception
                # mid-acquire (cascaded spill, H2D failure) must not lose
                # the only copy while the entry still claims DISK tier
                try:
                    os.unlink(e.path)
                except OSError:
                    pass
                self.disk_used -= e.disk_bytes
                e.disk_bytes = 0
            e.batch, e.host, e.path = batch, None, None
            e.tier = StorageTier.DEVICE
            self.device_used += e.nbytes
            return batch

    def acquire_host(self, buffer_id: int) -> dict:
        """Host-array view of an entry at any tier (pins the entry; a
        DEVICE-tier entry is pulled D2H without changing tiers)."""
        with self._lock:
            e = self._entries[buffer_id]
            e.pins += 1
            try:
                if e.tier == StorageTier.HOST:
                    return _host_arrays(e.host)
                if e.tier == StorageTier.DISK:
                    from spark_rapids_tpu.columnar.serde import (
                        read_spill_file,
                    )

                    return read_spill_file(e.path)  # type: ignore
                # DEVICE: pull without deleting
                return _batch_to_host(e.batch, delete=False)
            except BaseException:
                e.pins = max(0, e.pins - 1)  # failed acquire: no leak
                raise

    def remove(self, buffer_id: int) -> None:
        with self._lock:
            e = self._entries.pop(buffer_id, None)
            if e is None:
                return
            if e.tier == StorageTier.DEVICE:
                self.device_used -= e.nbytes
            elif e.tier == StorageTier.HOST:
                self.host_used -= _host_bytes(e.host)  # type: ignore
            elif e.path:
                try:
                    os.unlink(e.path)
                except OSError:
                    pass
                self.disk_used -= e.disk_bytes
                e.disk_bytes = 0

    # -- budget / spill -------------------------------------------------- #

    def reserve(self, nbytes: int) -> None:
        """Make room for an nbytes device allocation, spilling if needed
        (the proactive analog of DeviceMemoryEventHandler.onAllocFailure
        -> synchronousSpill).  The alloc.device fault checkpoint sits in
        front: a (injected or real) RESOURCE_EXHAUSTED from admission is
        absorbed once by spilling EVERYTHING unpinned and re-admitting —
        the onAllocFailure -> synchronousSpill -> retry-the-alloc loop;
        a second failure propagates to the batch split-and-retry
        ladder."""
        from spark_rapids_tpu.memory.device_manager import (
            device_alloc_checkpoint,
        )

        with self._lock:
            try:
                device_alloc_checkpoint(nbytes)
            except BaseException as e:  # noqa: BLE001 - classified below
                from spark_rapids_tpu.execs.retry import is_retryable
                from spark_rapids_tpu.robustness import faults as _faults

                if not is_retryable(e):
                    raise
                while self._spill_one_device_locked():
                    pass
                device_alloc_checkpoint(nbytes)  # 2nd failure escalates
                _faults.note_recovered(e, action="alloc_spill_retry")
            while self.device_used + nbytes > self.device_budget:
                if not self._spill_one_device_locked():
                    break  # nothing spillable left; let XLA try anyway

    def leak_report(self) -> list[str]:
        """Still-registered buffers (the all-buffers-released invariant
        check SURVEY.md §5.2 calls for; the reference leans on cudf's
        RefCount debugging — here the store itself is the registry, so
        leak detection is a dictionary walk).  Healthy shutdown (and
        end-of-test) state: empty."""
        with self._lock:
            return [
                f"buffer {bid}: tier={e.tier.name} pins={e.pins} "
                f"bytes={e.nbytes}"
                for bid, e in self._entries.items()]

    def assert_all_released(self) -> None:
        leaks = self.leak_report()
        assert not leaks, (
            f"{len(leaks)} buffer(s) never released:\n  "
            + "\n  ".join(leaks))

    def spill_all_unpinned(self) -> int:
        """Evict every unpinned DEVICE buffer to host — the
        release-everything step between task retry attempts (ref:
        RmmRapidsRetryIterator's spill-before-retry).  Returns the
        number of buffers spilled."""
        n = 0
        with self._lock:
            while self._spill_one_device_locked():
                n += 1
        return n

    def _spill_one_device_locked(self) -> bool:
        candidates = [e for e in self._entries.values()
                      if e.tier == StorageTier.DEVICE and not e.pinned]
        if not candidates:
            return False
        victim = min(candidates, key=lambda e: (e.priority, e.buffer_id))
        self._spill_to_host_locked(victim)
        return True

    def _spill_to_host_locked(self, e: _Entry) -> None:
        with _trace.span("spill.device_to_host", tier="DEVICE",
                         bytes=e.nbytes, buffer=e.buffer_id):
            arrays = _batch_to_host(e.batch)  # type: ignore[arg-type]
            held: object = arrays
            if self._host_compress:
                from spark_rapids_tpu.columnar.serde import (
                    serialize_arrays,
                )

                held = _HostFrame(serialize_arrays(
                    arrays, self._spill_codec))
        e.batch = None
        e.tier = StorageTier.HOST
        e.host = held  # type: ignore[assignment]
        self.device_used -= e.nbytes
        hb = _host_bytes(held)
        self.host_used += hb
        self.spilled_device_to_host += e.nbytes
        while self.host_used > self.host_budget:
            if not self._spill_one_host_locked():
                break

    def _spill_one_host_locked(self) -> bool:
        candidates = [e for e in self._entries.values()
                      if e.tier == StorageTier.HOST and not e.pinned]
        if not candidates:
            return False
        victim = min(candidates, key=lambda e: (e.priority, e.buffer_id))
        held = victim.host
        path = os.path.join(self._dir(), f"spill-{victim.buffer_id}.tpub")
        from spark_rapids_tpu.columnar.serde import write_spill_file

        hb = _host_bytes(held)  # type: ignore[arg-type]
        with _trace.span("spill.host_to_disk", tier="HOST", bytes=hb,
                         buffer=victim.buffer_id):
            if isinstance(held, _HostFrame):
                # the host tier already holds the serde frame: write
                # it as-is — no recompression on the way to disk
                with open(path, "wb") as f:
                    f.write(held.frame)
            else:
                write_spill_file(path, held,  # type: ignore[arg-type]
                                 self._spill_codec)
        victim.host = None
        victim.path = path
        victim.tier = StorageTier.DISK
        victim.disk_bytes = hb
        self.host_used -= hb
        self.disk_used += hb
        self.spilled_host_to_disk += hb
        return True

    def _dir(self) -> str:
        if self._spill_dir:
            os.makedirs(self._spill_dir, exist_ok=True)
            return self._spill_dir
        if self._tmpdir is None:
            self._tmpdir = tempfile.TemporaryDirectory(
                prefix="spark_rapids_tpu_spill_")
        return self._tmpdir.name

    def close(self) -> None:
        with self._lock:
            for bid in list(self._entries):
                self.remove(bid)
            if self._tmpdir is not None:
                self._tmpdir.cleanup()
                self._tmpdir = None


_STORE: Optional[BufferStore] = None
_STORE_LOCK = threading.Lock()


def get_store() -> BufferStore:
    global _STORE
    with _STORE_LOCK:
        if _STORE is None:
            _STORE = BufferStore()
        return _STORE


def peek_store() -> Optional[BufferStore]:
    """The live store WITHOUT creating one.  A background probe (the
    telemetry sampler) must never construct the process singleton from
    its own thread's conf — the store snapshots budgets and the spill
    codec at __init__, and a sampler-thread default conf would pin
    them for the process lifetime."""
    with _STORE_LOCK:
        return _STORE


def reset_store(store: Optional[BufferStore] = None) -> None:
    global _STORE
    with _STORE_LOCK:
        if _STORE is not None:
            _STORE.close()
        _STORE = store
