"""Cross-tenant work sharing: one fleet, shared computation.

ROADMAP item #4's serving-scale layer (docs/work_sharing.md): N tenants
issuing the same dashboard query should cost ~1x device work, not Nx.
Three mechanisms, all process-wide, all dormant behind ONE conf read
when ``spark.rapids.tpu.serving.sharing.enabled`` is false (the
default — sharing is a serving-tier posture, opted into by the fleet):

- **result cache** (:class:`ResultCache`): completed query results
  keyed by ``plan structural identity x conf fingerprint``
  (plan/share_key.py) and invalidated by input-content digests.  A
  hit returns the cached Arrow result with ZERO plan/tag/lower/
  compile/scan work.  Entries hold their batches as Arrow-IPC frames
  registered with the process :class:`~spark_rapids_tpu.memory.store.
  BufferStore` at HOST tier (priority ``SHARED_RESULT``), so under
  memory pressure cached results spill to disk and restore
  transparently instead of pinning memory — the tiered-store
  economics of the reference applied to whole results.  Byte-budget
  LRU (``resultCache.budgetBytes``); oversized results are simply not
  cached.
- **shared scans** (:class:`ScanShareRegistry`): concurrent queries
  over the same file set + pushed filters ride ONE decode pass.  The
  first arrival is the LEADER and publishes each upload unit (the
  decoded host tables io/scan.py accumulates) as it produces them;
  later arrivals SUBSCRIBE and replay the buffered units, then follow
  live.  While consumers overlap, the leader's uploaded device batch
  is shared too (plain decoded batches only — wire-form EncodedBatch
  carries donation bookkeeping and is never shared); once every
  consumer finishes, device memos drop (host HBM must not stay
  pinned) and the completed entry's HOST tables stay in a bounded LRU
  so a later identical scan still skips the decode.  A leader that
  dies or abandons mid-scan aborts the entry; subscribers fall back
  to their own decode, skipping the units they already consumed
  (unit streams are deterministic by key construction).
- **admission-aware batching** lives in serving/scheduler.py: queued
  plans carrying the same template group are granted together so
  their scans overlap and the in-flight dedup above engages
  (``serving.batching.enabled``).

Sharing is bit-for-bit by construction: keys are structural and
content-complete (plan/share_key.py), results are stored as the exact
Arrow-IPC bytes of the first execution, and anything not provably
deterministic (nondeterministic expressions, UDFs, runtime-filtered
scans) never shares.  Shared objects are IMMUTABLE by contract —
consumers copy-on-write or re-materialize; tpulint SRC011 (error)
enforces this over serving//execs/ source.
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Iterator, Optional

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.config import register
from spark_rapids_tpu.robustness.lock_tracker import tracked_lock

SHARING_ENABLED = register(
    "spark.rapids.tpu.serving.sharing.enabled", False,
    "Master switch for cross-tenant work sharing (docs/"
    "work_sharing.md): the process-wide result cache and shared-scan "
    "dedup.  Off (default) = one conf read per query, no cache "
    "exists.")

RESULT_CACHE_BUDGET = register(
    "spark.rapids.tpu.serving.resultCache.budgetBytes", 256 << 20,
    "Byte budget of the process-wide result cache (LRU past it; a "
    "single result larger than a quarter of this is not cached).  "
    "Entries are registered with the spillable buffer store at HOST "
    "tier, so the budget bounds cache IDENTITY, while residency "
    "follows the store's host/disk spill policy "
    "(docs/work_sharing.md).",
    check=lambda v: v >= 0)

RESULT_MIN_HIT_RATE = register(
    "spark.rapids.tpu.serving.resultCache.health.minHitRate", 0.25,
    "HC012 (tools/history) flags a query window whose result-cache "
    "evictions exceed its hits while the hit rate sits under this "
    "floor — the cache is thrashing: its budget is too small for the "
    "fleet's working set (docs/work_sharing.md).")

SCAN_SHARE_ENABLED = register(
    "spark.rapids.tpu.serving.sharing.scans", True,
    "Shared scans under the sharing master switch: concurrent (and "
    "repeated) queries over one file set + pushed filters ride one "
    "decode pass via in-flight dedup (docs/work_sharing.md).")

SCAN_CACHE_BUDGET = register(
    "spark.rapids.tpu.serving.sharing.scanCache.budgetBytes", 128 << 20,
    "Byte budget for COMPLETED shared-scan entries retained (decoded "
    "host tables) so later identical scans skip the decode; in-flight "
    "entries are never evicted.  Device batches are shared only while "
    "consumers overlap and are dropped when the last one finishes "
    "(shared scans must not pin HBM).",
    check=lambda v: v >= 0)


def enabled(conf=None) -> bool:
    from spark_rapids_tpu.config import get_conf

    return bool((conf or get_conf()).get(SHARING_ENABLED))


def scan_sharing_enabled(conf=None) -> bool:
    from spark_rapids_tpu.config import get_conf

    conf = conf or get_conf()
    return bool(conf.get(SHARING_ENABLED)) \
        and bool(conf.get(SCAN_SHARE_ENABLED))


# ------------------------------------------------------------------ #
# Process-global counters (the `share.*` event-log surface)
# ------------------------------------------------------------------ #

_STATS_LOCK = threading.Lock()
_STATS = collections.Counter()


def tick(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def stats() -> dict:
    """Cumulative process-wide sharing counters.  Monotonic except the
    two gauges (``result_bytes``, ``result_entries``), which report
    the cache's CURRENT footprint."""
    with _STATS_LOCK:
        out = {k: _STATS.get(k, 0) for k in (
            "result_hits", "result_misses", "result_evictions",
            "result_invalidations", "result_inserts",
            "scan_leads", "scan_subscribes", "scan_units_shared",
            "scan_upload_shared", "scan_units_decoded",
            "scan_rows_decoded", "scan_overflows")}
    out["result_bytes"] = RESULT_CACHE.bytes_used()
    out["result_entries"] = len(RESULT_CACHE)
    total = out["result_hits"] + out["result_misses"]
    out["result_hit_rate"] = round(out["result_hits"] / total, 3) \
        if total else 0.0
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        _STATS.clear()


# ------------------------------------------------------------------ #
# Result cache
# ------------------------------------------------------------------ #


def _table_ipc(tbl: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        for b in tbl.combine_chunks().to_batches():
            w.write_batch(b)
    return sink.getvalue().to_pybytes()


def _ipc_table(buf: bytes) -> pa.Table:
    return pa.ipc.open_stream(pa.py_buffer(buf)).read_all()


class _ResultEntry:
    """One cached result: the Arrow-IPC frame of the exact first
    execution, registered with the buffer store at HOST tier (it
    spills to disk under pressure and restores on read), plus the
    input-content digests that invalidate it."""

    __slots__ = ("key", "digests", "handle", "nbytes", "rows")

    def __init__(self, key: str, digests: list, handle, nbytes: int,
                 rows: int):
        self.key = key
        self.digests = digests
        self.handle = handle
        self.nbytes = nbytes
        self.rows = rows


class ResultCache:
    """Process-wide byte-budget LRU over :class:`_ResultEntry` (see
    module doc).  All methods are lock-protected; the store handles
    entries hold close() on removal so evicted results release their
    host/disk footprint immediately."""

    def __init__(self):
        # guard: _mu
        self._entries: "collections.OrderedDict[str, _ResultEntry]" = \
            collections.OrderedDict()
        self._mu = tracked_lock("resultCache.mu")

    def bytes_used(self) -> int:
        with self._mu:
            return sum(e.nbytes for e in self._entries.values())

    def __len__(self) -> int:
        with self._mu:
            return len(self._entries)

    def lookup(self, key: str, digests: list) -> Optional[pa.Table]:
        """Get-and-touch.  The entry's stored input digests are
        verified against the CURRENT digests first: a mismatch (an
        input file changed content) invalidates the entry — counted,
        and observable to the mutation probes — and reads as a
        miss."""
        stale = None
        with self._mu:
            e = self._entries.get(key)
            if e is not None and e.digests != digests:
                stale = self._entries.pop(key)
                e = None
            elif e is not None:
                self._entries.move_to_end(key)
        if stale is not None:
            tick("result_invalidations")
            self._close(stale)
        if e is None:
            tbl = self._restore_persisted(key, digests)
            if tbl is not None:
                tick("result_hits")
                return tbl
            tick("result_misses")
            return None
        try:
            arrays = e.handle.get_host()  # HOST or DISK: restores
            try:
                tbl = _ipc_table(arrays["ipc"].tobytes())
            finally:
                e.handle.unpin()
        except Exception:
            # the backing entry died (store reset between phases, a
            # torn spill file): drop it and answer honestly with a
            # miss — never a broken hit
            with self._mu:
                self._entries.pop(key, None)
            tick("result_misses")
            return None
        tick("result_hits")
        return tbl

    def _restore_persisted(self, key: str,
                           digests: list) -> Optional[pa.Table]:
        """Lazy restore from the warm-start disk tier
        (spark_rapids_tpu/persist.py) on an in-memory miss — one conf
        read when persistence is off.  The persisted frame carries its
        own `plan_source_digests` stat triples; a mismatch against the
        CURRENT digests (a source file changed since the frame was
        written) deletes the entry and reads as an honest miss.  A
        valid restore re-enters the normal in-memory tier via
        insert(), so it re-registers with the buffer store and ages
        under the same LRU as a fresh result."""
        from spark_rapids_tpu import persist as _persist

        store = _persist.active()
        if store is None:
            return None
        rec = store.load_result(key)
        if rec is None:
            return None
        meta, payload = rec
        if meta.get("digests") != [list(t) for t in digests]:
            store.delete_result(key)
            tick("result_invalidations")
            return None
        try:
            tbl = _ipc_table(payload)
        except Exception:
            store.delete_result(key)
            _persist.tick("errors")
            return None
        _persist.tick("result_hits")
        self.insert(key, digests, tbl)
        return tbl

    def insert(self, key: str, digests: list, tbl: pa.Table) -> bool:
        """Cache one result (first writer wins); False when the result
        is too large for the budget.  The IPC frame is registered with
        the process store at HOST tier under the SHARED_RESULT spill
        priority, so pressure moves it host->disk through the normal
        spill machinery instead of pinning memory."""
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.config import get_conf
        from spark_rapids_tpu.memory.store import (
            SpillPriorities,
            get_store,
        )

        budget = int(get_conf().get(RESULT_CACHE_BUDGET))
        # cheap rejections BEFORE paying the IPC copy (insert runs on
        # the collect critical path): tbl.nbytes over-approximates the
        # compact frame, so a table bigger than the whole budget can
        # never pass the quarter rule; a present key never re-inserts
        if budget <= 0 or tbl.nbytes > budget:
            return False
        with self._mu:
            if key in self._entries:
                return False
        buf = _table_ipc(tbl)
        nbytes = len(buf)
        if nbytes > max(1, budget // 4):
            return False
        arrays = {"ipc": np.frombuffer(buf, np.uint8),
                  "__num_rows": np.asarray(tbl.num_rows, np.int64)}
        handle = get_store().register_host(
            arrays, T.Schema([]), SpillPriorities.SHARED_RESULT)
        entry = _ResultEntry(key, digests, handle, nbytes,
                             tbl.num_rows)
        evicted: list[_ResultEntry] = []
        with self._mu:
            if key in self._entries:
                handle.close()
                return False
            self._entries[key] = entry
            used = sum(e.nbytes for e in self._entries.values())
            while used > budget and len(self._entries) > 1:
                _k, old = self._entries.popitem(last=False)
                if old is entry:  # never evict the fresh insert
                    self._entries[_k] = old
                    self._entries.move_to_end(_k, last=False)
                    break
                evicted.append(old)
                used -= old.nbytes
        for old in evicted:
            tick("result_evictions")
            self._close(old)
        from spark_rapids_tpu import persist as _persist

        store = _persist.active()
        if store is not None:
            # reuse the IPC frame already computed for the store
            # registration; the write itself runs on the persist
            # writer thread, off the collect critical path, and a
            # restore-triggered re-insert skips it (file exists)
            store.save_result_async(
                key, {"digests": [list(t) for t in digests],
                      "rows": tbl.num_rows},
                buf, _persist.max_bytes())
        tick("result_inserts")
        return True

    @staticmethod
    def _close(e: _ResultEntry) -> None:
        try:
            e.handle.close()
        except Exception:
            pass

    def reset(self) -> None:
        with self._mu:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            self._close(e)


RESULT_CACHE = ResultCache()

#: bounded (id(plan) -> (weakref, conf_fp, key)) memo so a prepared
#: template's repeat executions never re-hash in-memory table content;
#: the weakref guards against a recycled id aliasing a DEAD plan's key
#: onto different work
_KEY_MEMO: dict[int, tuple] = {}
_KEY_MEMO_LOCK = threading.Lock()


def _plan_key(plan, conf) -> Optional[str]:
    from spark_rapids_tpu.eventlog import conf_fingerprint
    from spark_rapids_tpu.parallel.mesh import mesh_cache_suffix
    from spark_rapids_tpu.plan.share_key import plan_share_key

    # mesh suffix in BOTH the memo key and the result key: a cached
    # result's row ORDER is execution-shaped (mesh width changes
    # partition interleaving), so a result minted on one mesh must not
    # serve another
    mesh_sfx = mesh_cache_suffix()
    fp = conf_fingerprint(conf) + mesh_sfx
    pid = id(plan)
    with _KEY_MEMO_LOCK:
        memo = _KEY_MEMO.get(pid)
        if memo is not None and memo[0]() is plan and memo[1] == fp:
            return memo[2]
    key = plan_share_key(plan, conf)
    if key is not None and mesh_sfx:
        key = key + mesh_sfx
    try:
        ref = weakref.ref(plan)
    except TypeError:
        return key
    with _KEY_MEMO_LOCK:
        if len(_KEY_MEMO) > 256:
            _KEY_MEMO.clear()
        _KEY_MEMO[pid] = (ref, fp, key)
    return key


def lookup_result(plan, conf) -> tuple[Optional[pa.Table],
                                       Optional[str]]:
    """(cached result | None, verdict): verdict is ``"hit"`` /
    ``"miss"`` for shareable plans and None for plans the determinism
    gate excludes (those never consult the cache)."""
    key = _plan_key(plan, conf)
    if key is None:
        return None, None
    from spark_rapids_tpu.plan.share_key import plan_source_digests

    try:
        digests = plan_source_digests(plan)
    except OSError:
        return None, None  # a source vanished: let execution raise
    tbl = RESULT_CACHE.lookup(key, digests)
    return tbl, ("hit" if tbl is not None else "miss")


def offer_result(plan, conf, tbl: pa.Table) -> None:
    """Population hook for a just-completed collect: cache the result
    when the plan is shareable (misses and unshareable plans are both
    silent — offering is always safe)."""
    key = _plan_key(plan, conf)
    if key is None:
        return
    from spark_rapids_tpu.plan.share_key import plan_source_digests

    try:
        digests = plan_source_digests(plan)
    except OSError:
        return
    RESULT_CACHE.insert(key, digests, tbl)


# ------------------------------------------------------------------ #
# Shared scans: in-flight dedup + completed-entry reuse
# ------------------------------------------------------------------ #


class ScanShareAborted(RuntimeError):
    """The leader abandoned or failed the shared scan mid-stream;
    subscribers fall back to their own decode (skipping the units
    they already consumed — unit streams are deterministic)."""


def _unit_bytes(unit) -> int:
    if isinstance(unit, int):
        return 8
    return sum(t.nbytes for t in unit)


class ScanShareEntry:
    """One shared scan partition's published unit stream (see module
    doc).  Units are (host_unit, device_batch|None) pairs; host units
    are immutable Arrow tables (or bare int counts), device batches
    are shared only while consumers overlap."""

    def __init__(self, key: str, cap: int = 0):
        self.key = key
        self._cv = threading.Condition()
        self._units: list = []      # guard: _cv (publish order)
        self._device: dict = {}     # guard: _cv (idx -> shared batch)
        self._done = False          # guard: _cv
        self._aborted = False       # guard: _cv
        self.leader_thread = threading.get_ident()
        self._consumers = 1         # guard: _cv (starts at the leader)
        self.nbytes = 0             # guard: _cv
        #: in-flight footprint cap (scanCache.budgetBytes, 0 = none):
        #: an entry buffers its host units for the scan's LIFETIME, so
        #: without a cap one huge scan would materialize its whole
        #: decoded table set in host memory — past the cap the entry
        #: self-aborts (dropping the buffer; subscribers fall back to
        #: their own decode) rather than trade a decode for an OOM
        self._cap = int(cap)

    # -- leader side ------------------------------------------------ #

    def publish(self, unit, device_batch=None) -> None:
        overflowed = False
        with self._cv:
            if self._aborted:
                return
            if device_batch is not None:
                _mark_batch_shared(device_batch)
                self._device[len(self._units)] = device_batch
            self._units.append(unit)
            self.nbytes += _unit_bytes(unit)
            if self._cap and self.nbytes > self._cap:
                self._abort_locked()
                overflowed = True
            self._cv.notify_all()
        if overflowed:
            tick("scan_overflows")

    def complete(self) -> None:
        with self._cv:
            self._done = True
            self._cv.notify_all()

    def _abort_locked(self) -> None:
        self._aborted = True
        self._done = True
        # free the buffered footprint NOW — subscribers mid-replay
        # observe done+aborted and fall back on their consumed count,
        # never on the dropped buffer
        self._units.clear()
        self._device.clear()
        self._cv.notify_all()

    def abort(self) -> None:
        with self._cv:
            self._abort_locked()

    @property
    def done(self) -> bool:
        with self._cv:
            return self._done and not self._aborted

    # -- subscriber side -------------------------------------------- #

    def subscribe_units(self) -> Iterator[tuple]:
        """Yield (host_unit, shared_device_batch|None) in publish
        order: buffered units first, then live as the leader produces
        them.  Raises :class:`ScanShareAborted` when the leader
        abandons mid-stream (the consumer's fallback skips what it
        already received), or QueryCancelled when the SUBSCRIBER's own
        query is cancelled while waiting for the leader (the wait is
        bounded and cancel-aware — SRC012; the subscriber's release
        path runs normally, and a cancelled LEADER aborts the entry
        through its drain finally, waking everyone here)."""
        from spark_rapids_tpu.serving import cancel as _cancel

        i = 0
        while True:
            with self._cv:
                tok = _cancel.current_token()
                while i >= len(self._units) and not self._done:
                    self._cv.wait(_cancel.poll_timeout(tok))
                    if tok is not None:
                        tok.check()
                if i < len(self._units):
                    unit = self._units[i]
                    dev = self._device.get(i)
                else:
                    if self._aborted:
                        raise ScanShareAborted(self.key)
                    return
            yield unit, dev
            i += 1

    def _drop_device(self) -> None:
        with self._cv:
            self._device.clear()


def _mark_batch_shared(batch) -> None:
    """Register every device array of a shared batch with the
    shared-array registry: a consumer that parks it in the buffer
    store and spills it must copy, never ``.delete()`` — the other
    consumers still compute over the same HBM."""
    from spark_rapids_tpu.columnar.column import mark_shared_array
    from spark_rapids_tpu.memory.store import _col_leaves

    for i, c in enumerate(batch.columns):
        for _name, a in _col_leaves(c, f"c{i}"):
            mark_shared_array(a)
    n = batch.num_rows
    if not isinstance(n, int):
        mark_shared_array(n)


class ScanShareRegistry:
    """Process-wide registry of shared scan entries: in-flight dedup
    plus a byte-bounded LRU of completed entries (host units only —
    device memos drop with the last overlapping consumer)."""

    def __init__(self):
        # guard: _mu
        self._entries: "collections.OrderedDict[str, ScanShareEntry]" \
            = collections.OrderedDict()
        self._mu = tracked_lock("scanShare.mu")

    def begin(self, key: str) -> tuple[Optional[ScanShareEntry], bool]:
        """(entry, is_leader).  (None, False) means "do not share":
        the live entry's leader is THIS thread (a same-thread
        subscribe would deadlock — e.g. a self-join interleaving two
        scans of one table on one task thread)."""
        with self._mu:
            e = self._entries.get(key)
            if e is not None:
                with e._cv:
                    aborted = e._aborted
                if aborted:
                    e = None
                elif e.leader_thread == threading.get_ident() \
                        and not e.done:
                    return None, False
                else:
                    self._entries.move_to_end(key)
                    with e._cv:
                        e._consumers += 1
                    return e, False
            from spark_rapids_tpu.config import get_conf

            e = ScanShareEntry(
                key, cap=int(get_conf().get(SCAN_CACHE_BUDGET)))
            self._entries[key] = e
            tick("scan_leads")
            return e, True

    def release(self, entry: ScanShareEntry) -> None:
        """A consumer (leader or subscriber) finished with the entry;
        the last one out drops the shared device batches — HBM must
        not stay pinned by a cache — and aborted entries leave the
        registry entirely."""
        drop_key = None
        with entry._cv:
            entry._consumers -= 1
            last = entry._consumers <= 0
            aborted = entry._aborted
        if last:
            entry._drop_device()
            if aborted:
                drop_key = entry.key
        if drop_key is not None:
            with self._mu:
                cur = self._entries.get(drop_key)
                if cur is entry:
                    del self._entries[drop_key]
        self._enforce_budget()

    def _enforce_budget(self) -> None:
        from spark_rapids_tpu.config import get_conf

        budget = int(get_conf().get(SCAN_CACHE_BUDGET))
        with self._mu:
            # snapshot size + liveness per entry under ITS lock (a
            # leader thread grows nbytes under _cv concurrently; the
            # old unlocked sum could tear against publish and evict
            # on a stale total), then evict from the locked snapshot.
            # _mu -> _cv nesting matches begin()'s acquisition order.
            sizes: dict[str, int] = {}
            busy: dict[str, bool] = {}
            used = 0
            for key in list(self._entries):
                e = self._entries[key]
                with e._cv:
                    sizes[key] = e.nbytes
                    busy[key] = e._consumers > 0 or not e._done
                used += sizes[key]
            for key in list(self._entries):
                if used <= budget:
                    break
                if busy[key]:
                    continue  # in-flight entries are never evicted
                e = self._entries.pop(key)
                used -= sizes[key]
                e._drop_device()

    def __len__(self) -> int:
        with self._mu:
            return len(self._entries)

    def inflight(self) -> int:
        # _done is _cv-guarded state a leader flips concurrently;
        # snapshot the registry under _mu, then read each entry's
        # flag under its own lock instead of racing complete()/abort()
        with self._mu:
            entries = list(self._entries.values())
        n = 0
        for e in entries:
            with e._cv:
                if not e._done:
                    n += 1
        return n

    def reset(self) -> None:
        with self._mu:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            e.abort()
            e._drop_device()


SCAN_REGISTRY = ScanShareRegistry()


def record_scan_decode(rows: int) -> None:
    """Tapped decode counter (io/scan.py ticks it per decoded table):
    THE sub-linearity evidence — shared/cached executions leave it
    flat while unshared ones grow it linearly in sessions."""
    with _STATS_LOCK:
        _STATS["scan_units_decoded"] += 1
        _STATS["scan_rows_decoded"] += rows


def reset() -> None:
    """Tests / bench phase boundaries: drop every cache and counter."""
    RESULT_CACHE.reset()
    SCAN_REGISTRY.reset()
    with _KEY_MEMO_LOCK:
        _KEY_MEMO.clear()
    reset_stats()
