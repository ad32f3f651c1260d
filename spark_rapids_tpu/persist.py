"""Warm-start persistence: the on-disk tier under the process caches.

Steady state is a handful of fused programs per query with zero warm
jit misses (docs/fusion.md), but every process restart recompiles the
world — at fleet scale a rollout is a cold-start storm.  The reference
never pays this: cudf kernels are pre-compiled native code shipped in
the plugin jar.  The XLA analog is serialization of the compiled
artifacts themselves, and this module is the single validated store
for all three tiers (docs/warm_start.md):

- **AOT programs**: on a structural-key miss, ``execs/jit_cache``
  probes this store BEFORE tracing.  Entries are ``jax.export``
  serializations of the jitted program, one per (structural jit key x
  conf fingerprint x argument signature); restores dispatch through
  :class:`RestoredProgram` (still ledger-wrapped by the caller, so
  restored programs attribute dispatches like compiled ones), and the
  XLA persistent compilation cache is pointed at ``<dir>/xla`` on
  activation so the backend compile of a restored module is a disk
  hit too.  Fresh compiles serialize back ASYNCHRONOUSLY
  (:class:`AutoSave` captures each new argument signature off the
  critical path).
- **prepared-plan metadata**: ``serving/plan_cache`` entries rehydrate
  their template metadata from (structural plan key x conf
  fingerprint) — the lowered exec tree itself holds live closures and
  device buffers and is rebuilt, immediately hitting the AOT tier.
- **result frames**: ``serving/work_share`` result-cache entries (the
  exact Arrow-IPC frame plus the ``plan_source_digests`` stat-triple
  invalidation tokens) persist verbatim and restore lazily on first
  key probe, re-entering the BufferStore host tier.

Validation discipline — every failure mode is an HONEST MISS, never a
wrong answer: entries carry a magic prefix, a JSON header with the
payload length + sha256 checksum, and an environment stamp
(jax/jaxlib version + device fingerprint, checked for program
entries); writes go to a unique temp file then ``os.replace`` (atomic
on POSIX — a torn write or a concurrent-writer race leaves either the
old entry or a complete new one, and a truncated file fails the
checksum).  A byte-budget LRU sweep (``persist.maxBytes``, mtime
order, entries touched on hit) bounds the footprint.

Cost discipline: ``spark.rapids.tpu.persist.enabled=false`` (the
default) is ONE conf read at each probe site and nothing else — no
store object, no thread, behavior bit-identical to the non-persisting
engine (asserted by tests/test_persist.py).  tpulint SRC015 (error)
forbids raw ``open()``/``pickle`` writes of executables anywhere else
in the engine, so every disk artifact flows through this writer.
"""

from __future__ import annotations

import collections
import concurrent.futures as _cf
import hashlib
import json
import os
import tempfile
import threading
import time
import uuid
from typing import Any, Optional

from spark_rapids_tpu.config import register

PERSIST_ENABLED = register(
    "spark.rapids.tpu.persist.enabled", False,
    "Master switch for the on-disk warm-start cache "
    "(docs/warm_start.md): AOT program entries (jax.export "
    "serializations probed by the jit cache before tracing, written "
    "back asynchronously on compile), prepared-plan metadata and "
    "result-cache frames, plus the XLA persistent compilation cache "
    "pointed at <persist.dir>/xla.  Off (the default) = one conf "
    "read per probe site, dispatch pattern and results bit-identical "
    "to the non-persisting engine.")

PERSIST_DIR = register(
    "spark.rapids.tpu.persist.dir", "",
    "Root directory of the warm-start cache (programs/, plans/, "
    "results/, xla/ under it).  Empty (the default) resolves to a "
    "per-user directory under the system temp dir.  Processes "
    "sharing a dir share entries; concurrent writers are safe "
    "(unique temp file + atomic rename, checksum-validated reads).")

PERSIST_MAX_BYTES = register(
    "spark.rapids.tpu.persist.maxBytes", 512 << 20,
    "Byte budget of the warm-start cache's validated entries "
    "(programs + plans + results; the xla/ subdir is managed by "
    "jax's own compilation cache).  Past it, a least-recently-used "
    "sweep (mtime order; entries are touched on hit) deletes oldest "
    "entries after each write (docs/warm_start.md).",
    check=lambda v: v >= 0)

PERSIST_MIN_HIT_RATE = register(
    "spark.rapids.tpu.persist.health.minHitRate", 0.5,
    "HC017 (tools/history) flags a query window that probed the "
    "warm-start cache and paid real compiles while its persist hit "
    "rate sat under this floor — a cold process against a supposedly "
    "warm disk cache mostly missed: stale entries (jax/device/conf "
    "drift) or a wrong persist.dir (docs/warm_start.md).")

PERSIST_XLA_CACHE = register(
    "spark.rapids.tpu.persist.xlaCache.enabled", True,
    "Point jax's persistent XLA compilation cache at "
    "<persist.dir>/xla on activation, so the backend compilation of "
    "restored (and fresh) programs is itself a disk hit in later "
    "processes.  Process-global jax config: the first activating "
    "conf wins for the process lifetime (docs/warm_start.md).  A "
    "cache placed by JAX_COMPILATION_CACHE_DIR stays where it is.")

#: bump when the entry layout changes: old-format files read as
#: honest misses instead of parse errors
FORMAT_VERSION = 1
_MAGIC = b"TPUPERSIST1\n"
_SUFFIX = ".tpup"

#: cap on distinct argument signatures auto-saved per program key —
#: a shape-churning key (the thing program_census exists to catch)
#: must not fill the store with one entry per batch shape
MAX_SIGS_PER_KEY = 8

# ------------------------------------------------------------------ #
# Process-global counters (the `persist.*` event-log surface)
# ------------------------------------------------------------------ #

_STATS_LOCK = threading.Lock()
_STATS: "collections.Counter" = collections.Counter()

_STAT_KEYS = (
    "hits", "misses", "writes", "evictions", "errors",
    "plan_hits", "plan_writes", "result_hits", "result_writes",
    "fallback_compiles",
)


def tick(key: str, n: float = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def stats() -> dict:
    """Cumulative process-wide persist counters.  ``hits``/``misses``
    count PROGRAM store probes (the cold-start hit-rate surface);
    ``deserialize_ms``/``serialize_ms`` are cumulative milliseconds
    spent restoring / exporting program entries."""
    with _STATS_LOCK:
        out = {k: _STATS.get(k, 0) for k in _STAT_KEYS}
        out["deserialize_ms"] = round(_STATS.get("deserialize_ms", 0.0), 3)
        out["serialize_ms"] = round(_STATS.get("serialize_ms", 0.0), 3)
    total = out["hits"] + out["misses"]
    out["hit_rate"] = round(out["hits"] / total, 3) if total else 0.0
    return out


def reset_stats() -> None:
    with _STATS_LOCK:
        _STATS.clear()


# ------------------------------------------------------------------ #
# Fingerprints / signatures
# ------------------------------------------------------------------ #


def device_fingerprint() -> str:
    """Stable identity of the device set a program was compiled for:
    platform + device kind + count, hashed.  A serialized executable
    restored onto different hardware must read as a miss, not a
    wrong-target deserialize."""
    try:
        import jax

        devs = [(d.platform, getattr(d, "device_kind", ""))
                for d in jax.devices()]
    except Exception:
        devs = []
    return hashlib.sha256(repr(devs).encode()).hexdigest()[:16]


def env_stamp() -> dict:
    """The validated environment stamp written into every entry header
    (docs/warm_start.md key anatomy).  Program entries check all of
    it; plan/result entries (version-agnostic JSON / Arrow IPC) check
    only the format version."""
    out = {"format": FORMAT_VERSION, "device": device_fingerprint()}
    try:
        import jax

        out["jax"] = jax.__version__
    except Exception:
        out["jax"] = ""
    try:
        import jaxlib

        out["jaxlib"] = getattr(jaxlib, "__version__", "")
    except Exception:
        out["jaxlib"] = ""
    return out


def args_signature(args: tuple, kwargs: dict
                   ) -> tuple[Optional[str], Optional[tuple]]:
    """(signature digest, aval pytree) for one call's arguments, or
    (None, None) when any leaf lacks shape/dtype (Python scalars,
    opaque objects — such calls are never persisted).  The digest
    covers the tree structure plus every leaf's (shape, dtype): the
    per-signature identity under one structural jit key, stable
    across processes because structural keys carry no addresses."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    parts: list[str] = []
    avals = []
    for x in leaves:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return None, None
        try:
            shape = tuple(int(s) for s in shape)
        except TypeError:
            return None, None
        parts.append(f"{shape}:{dtype}")
        avals.append(jax.ShapeDtypeStruct(shape, dtype))
    payload = repr(treedef) + "|" + ";".join(parts)
    sig = hashlib.sha256(payload.encode()).hexdigest()[:16]
    return sig, jax.tree_util.tree_unflatten(treedef, avals)


_EXPORT_REG_LOCK = threading.Lock()
_EXPORT_REG_DONE = False


def _ensure_export_registrations() -> None:
    """Register jax.export (de)serialization for the engine's custom
    pytree node classes (ColumnarBatch, the column hierarchy,
    EncodedBatch): exported program calling conventions embed the
    in/out pytree structure, and jax refuses unregistered node types.
    Aux data is engine-owned static metadata (schemas, dtypes, decode
    plans — plain dataclasses/tuples), round-tripped via pickle; this
    module is the one blessed pickle surface for executables (SRC015).
    Must run in BOTH the exporting and the restoring process before
    the first serialize/deserialize — both store paths call it."""
    global _EXPORT_REG_DONE
    with _EXPORT_REG_LOCK:
        if _EXPORT_REG_DONE:
            return
        import pickle

        from jax import export as _export

        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import (
            Column,
            ListColumn,
            MapColumn,
            StringColumn,
            StructColumn,
        )
        from spark_rapids_tpu.columnar.transfer import EncodedBatch

        for cls in (ColumnarBatch, Column, StringColumn, ListColumn,
                    StructColumn, MapColumn, EncodedBatch):
            try:
                _export.register_pytree_node_serialization(
                    cls,
                    serialized_name=f"spark_rapids_tpu.{cls.__name__}",
                    serialize_auxdata=pickle.dumps,
                    deserialize_auxdata=pickle.loads)
            except ValueError:
                pass  # an earlier partial registration pass got it
        _EXPORT_REG_DONE = True


def _key_digest(key: Any) -> str:
    return hashlib.sha256(repr(key).encode()).hexdigest()[:20]


def _conf_fp(conf=None) -> str:
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.eventlog import conf_fingerprint

    return conf_fingerprint(conf or get_conf())


# ------------------------------------------------------------------ #
# The validated store
# ------------------------------------------------------------------ #

_KINDS = ("programs", "plans", "results")


class PersistStore:
    """One warm-start cache directory (see module doc).  All disk
    writes flow through :meth:`_write_entry` (unique temp file +
    ``os.replace``); all reads through :meth:`_read_entry` (magic +
    header + checksum + stamp validation — any failure deletes the
    entry and reads as None)."""

    def __init__(self, root: str):
        self.root = root
        for kind in _KINDS:
            os.makedirs(os.path.join(root, kind), exist_ok=True)

    # -- low-level entry format ------------------------------------- #

    def _write_entry(self, path: str, meta: dict, payload: bytes) -> bool:
        header = {
            "stamp": env_stamp(),
            "meta": meta,
            "len": len(payload),
            "sha256": hashlib.sha256(payload).hexdigest(),
        }
        blob = _MAGIC + json.dumps(header).encode() + b"\n" + payload
        d = os.path.dirname(path)
        tmp = os.path.join(
            d, f".tmp-{os.getpid()}-{uuid.uuid4().hex[:8]}")
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError:
            tick("errors")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        tick("writes")
        return True

    def _read_entry(self, path: str, check_env: bool
                    ) -> Optional[tuple[dict, bytes]]:
        """(meta, payload) or None — corrupt/stale/torn entries are
        deleted and read as honest misses."""
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        try:
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            rest = blob[len(_MAGIC):]
            nl = rest.index(b"\n")
            header = json.loads(rest[:nl])
            payload = rest[nl + 1:]
            if len(payload) != int(header["len"]):
                raise ValueError("truncated payload")
            if hashlib.sha256(payload).hexdigest() != header["sha256"]:
                raise ValueError("checksum mismatch")
            stamp = header.get("stamp") or {}
            if int(stamp.get("format", -1)) != FORMAT_VERSION:
                raise ValueError("format mismatch")
            if check_env:
                want = env_stamp()
                for k in ("jax", "jaxlib", "device"):
                    if stamp.get(k) != want[k]:
                        raise ValueError(f"stale {k} stamp")
        except (ValueError, KeyError, TypeError, json.JSONDecodeError):
            tick("errors")
            self._delete(path)
            return None
        self._touch(path)
        return header.get("meta") or {}, payload

    @staticmethod
    def _delete(path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    @staticmethod
    def _touch(path: str) -> None:
        try:
            os.utime(path)
        except OSError:
            pass

    # -- eviction / gauges ------------------------------------------ #

    def _entry_files(self) -> list[tuple[float, int, str]]:
        out: list[tuple[float, int, str]] = []
        for kind in _KINDS:
            d = os.path.join(self.root, kind)
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for name in names:
                if not name.endswith(_SUFFIX):
                    continue
                p = os.path.join(d, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                out.append((st.st_mtime, st.st_size, p))
        return out

    def evict_over_budget(self, max_bytes: int) -> int:
        """LRU sweep by mtime (hits touch entries): delete oldest
        validated entries until the footprint fits.  Returns the
        number evicted."""
        files = sorted(self._entry_files())
        total = sum(sz for _m, sz, _p in files)
        n = 0
        for _mtime, size, path in files:
            if total <= max_bytes:
                break
            self._delete(path)
            total -= size
            n += 1
        if n:
            tick("evictions", n)
        return n

    def bytes_used(self) -> int:
        """Total on-disk footprint (validated entries + the xla/
        compilation cache) — the `persist_cache.bytes` gauge."""
        total = 0
        for dirpath, _dirs, names in os.walk(self.root):
            for name in names:
                try:
                    total += os.stat(os.path.join(dirpath, name)).st_size
                except OSError:
                    continue
        return total

    # -- programs ---------------------------------------------------- #

    def _program_path(self, key: Any, conf_fp: str, sig: str) -> str:
        return os.path.join(
            self.root, "programs",
            f"{_key_digest(key)}-{conf_fp}-{sig}{_SUFFIX}")

    def load_programs(self, key: Any, conf_fp: str) -> dict:
        """{signature -> deserialized jax.export.Exported} for every
        valid entry under (key x conf fingerprint); {} is a miss.
        Ticks `persist.hits` per restored program or one
        `persist.misses`, plus cumulative `deserialize_ms`."""
        prefix = f"{_key_digest(key)}-{conf_fp}-"
        d = os.path.join(self.root, "programs")
        try:
            names = sorted(os.listdir(d))
        except OSError:
            names = []
        out: dict = {}
        t0 = time.perf_counter()
        candidates = [n for n in names
                      if n.startswith(prefix) and n.endswith(_SUFFIX)]
        if candidates:
            _ensure_export_registrations()
        for name in candidates:
            path = os.path.join(d, name)
            rec = self._read_entry(path, check_env=True)
            if rec is None:
                continue
            meta, payload = rec
            try:
                from jax import export as _export

                exp = _export.deserialize(payload)
            except Exception:
                tick("errors")
                self._delete(path)
                continue
            sig = str(meta.get("sig", ""))
            if sig:
                out[sig] = exp
        if out:
            tick("hits", len(out))
            tick("deserialize_ms", (time.perf_counter() - t0) * 1e3)
        else:
            tick("misses")
        return out

    def save_program_async(self, key: Any, conf_fp: str, sig: str,
                           jitted_fn, avals: tuple,
                           max_bytes: int) -> None:
        """Schedule one (key x conf x signature) export+write on the
        background writer — serialize-back stays off the critical
        path.  Export failures (unexportable program, donation quirks
        on exotic backends) are swallowed into `persist.errors`: the
        query already has its answer."""
        path = self._program_path(key, conf_fp, sig)
        if os.path.exists(path):
            return
        meta = {"sig": sig, "tag": key[0] if isinstance(key, tuple)
                and key and isinstance(key[0], str) else "prog"}
        _submit(self._save_program_job, path, meta, jitted_fn, avals,
                max_bytes)

    def _save_program_job(self, path: str, meta: dict, jitted_fn,
                          avals: tuple, max_bytes: int) -> None:
        t0 = time.perf_counter()
        try:
            from jax import export as _export

            _ensure_export_registrations()
            aval_args, aval_kwargs = avals
            blob = _export.export(jitted_fn)(
                *aval_args, **aval_kwargs).serialize()
        except Exception:
            tick("errors")
            return
        if self._write_entry(path, meta, blob):
            tick("serialize_ms", (time.perf_counter() - t0) * 1e3)
            self.evict_over_budget(max_bytes)

    # -- plans ------------------------------------------------------- #

    def _plan_path(self, key: str) -> str:
        return os.path.join(self.root, "plans", f"plan-{key}{_SUFFIX}")

    def load_plan(self, key: str) -> Optional[dict]:
        rec = self._read_entry(self._plan_path(key), check_env=False)
        if rec is None:
            return None
        tick("plan_hits")
        return rec[0]

    def save_plan_async(self, key: str, meta: dict,
                        max_bytes: int) -> None:
        _submit(self._save_small_job, self._plan_path(key), meta, b"",
                max_bytes, "plan_writes")

    # -- results ----------------------------------------------------- #

    def _result_path(self, key: str) -> str:
        return os.path.join(self.root, "results", f"res-{key}{_SUFFIX}")

    def load_result(self, key: str) -> Optional[tuple[dict, bytes]]:
        """(meta, Arrow-IPC payload) or None.  Digest verification
        against the CURRENT source stat triples is the CALLER's job
        (work_share) — this layer only proves the bytes are the bytes
        that were written."""
        return self._read_entry(self._result_path(key), check_env=False)

    def save_result_async(self, key: str, meta: dict, payload: bytes,
                          max_bytes: int) -> None:
        path = self._result_path(key)
        if os.path.exists(path):
            return
        _submit(self._save_small_job, path, meta, payload, max_bytes,
                "result_writes")

    def delete_result(self, key: str) -> None:
        self._delete(self._result_path(key))

    def _save_small_job(self, path: str, meta: dict, payload: bytes,
                        max_bytes: int, stat_key: str) -> None:
        if self._write_entry(path, meta, payload):
            tick(stat_key)
            self.evict_over_budget(max_bytes)


# ------------------------------------------------------------------ #
# Activation / the background writer
# ------------------------------------------------------------------ #

_STORES_LOCK = threading.Lock()
_STORES: dict[str, PersistStore] = {}
_XLA_CACHE_DIR: Optional[str] = None  # guard: _STORES_LOCK
#: jax compilation-cache config as it stood before activation, so
#: reset_for_tests restores an outer harness's cache dir (the test
#: suite points one at a shared tmp dir) instead of clobbering it
_XLA_PREV: Optional[tuple] = None  # guard: _STORES_LOCK
_WRITER: Optional[_cf.ThreadPoolExecutor] = None  # guard: _STORES_LOCK
_PENDING: "set[_cf.Future]" = set()
_PENDING_LOCK = threading.Lock()


def _default_dir() -> str:
    who = f"{os.getuid()}" if hasattr(os, "getuid") else "user"
    return os.path.join(tempfile.gettempdir(), f"tpu-persist-{who}")


def active(conf=None) -> Optional[PersistStore]:
    """The store for the current conf, or None when persistence is
    off — the disabled path is exactly ONE conf read (the cost
    contract every probe site inherits)."""
    from spark_rapids_tpu.config import get_conf

    conf = conf or get_conf()
    if not bool(conf.get(PERSIST_ENABLED)):
        return None
    root = str(conf.get(PERSIST_DIR) or "") or _default_dir()
    root = os.path.abspath(root)
    with _STORES_LOCK:
        store = _STORES.get(root)
        if store is None:
            try:
                store = PersistStore(root)
            except OSError:
                tick("errors")
                return None
            _STORES[root] = store
            _activate_xla_cache_locked(root, conf)
    return store


def _activate_xla_cache_locked(root: str, conf) -> None:
    """Point jax's persistent compilation cache at <root>/xla (first
    activating dir wins for the process — the config is jax-global).
    A cache placed from outside (JAX_COMPILATION_CACHE_DIR) stays
    where it was put.  Failures are non-fatal: the AOT tier still
    works, restored modules just pay a backend re-compile."""
    global _XLA_CACHE_DIR, _XLA_PREV
    if not bool(conf.get(PERSIST_XLA_CACHE)) or _XLA_CACHE_DIR \
            or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    xdir = os.path.join(root, "xla")
    try:
        os.makedirs(xdir, exist_ok=True)
        import jax

        prev = (
            getattr(jax.config, "jax_compilation_cache_dir", None),
            getattr(jax.config,
                    "jax_persistent_cache_min_compile_time_secs", 1.0),
            getattr(jax.config,
                    "jax_persistent_cache_min_entry_size_bytes", 0),
        )
        jax.config.update("jax_compilation_cache_dir", xdir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1)
        _XLA_PREV = prev
        _XLA_CACHE_DIR = xdir
    except Exception:
        tick("errors")


def _submit(fn, *args) -> None:
    global _WRITER
    with _STORES_LOCK:
        if _WRITER is None:
            _WRITER = _cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-persist")
        writer = _WRITER
    fut = writer.submit(fn, *args)
    with _PENDING_LOCK:
        _PENDING.add(fut)
    fut.add_done_callback(_discard_pending)


def _discard_pending(fut: "_cf.Future") -> None:
    with _PENDING_LOCK:
        _PENDING.discard(fut)


def flush(timeout: float = 30.0) -> bool:
    """Drain the background writer (bench/smoke/test barrier before a
    child process probes the store).  True when everything landed."""
    with _PENDING_LOCK:
        pending = list(_PENDING)
    if not pending:
        return True
    done, not_done = _cf.wait(pending, timeout=timeout)
    return not not_done


def cache_bytes() -> int:
    """The `persist_cache.bytes` telemetry gauge: total on-disk
    footprint of every store this process activated (0 without a
    single dir walk when persistence never activated)."""
    with _STORES_LOCK:
        stores = list(_STORES.values())
    return sum(s.bytes_used() for s in stores)


def max_bytes(conf=None) -> int:
    from spark_rapids_tpu.config import get_conf

    return int((conf or get_conf()).get(PERSIST_MAX_BYTES))


def reset_for_tests() -> None:
    """Tests / bench phase boundaries: drain writes, forget activated
    stores, release the process-global XLA cache pointer (so a later
    suite member is not writing compilation-cache files into a
    deleted temp dir), zero the counters."""
    global _XLA_CACHE_DIR, _XLA_PREV
    flush(timeout=10.0)
    with _STORES_LOCK:
        _STORES.clear()
        if _XLA_CACHE_DIR is not None:
            try:
                import jax

                prev = _XLA_PREV or (None, 1.0, 0)
                jax.config.update("jax_compilation_cache_dir", prev[0])
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs",
                    prev[1])
                jax.config.update(
                    "jax_persistent_cache_min_entry_size_bytes",
                    prev[2])
            except Exception:
                pass
            _XLA_CACHE_DIR = None
            _XLA_PREV = None
    reset_stats()


# ------------------------------------------------------------------ #
# Program wrappers (used by execs/jit_cache on the miss path)
# ------------------------------------------------------------------ #


class RestoredProgram:
    """A disk-restored program: dispatches by argument signature to
    ``jax.jit(exported.call)`` artifacts (trace/compile skipped; the
    backend compile of the exported module rides the XLA persistent
    cache).  An UNSEEN signature falls back to an honest compile via
    the original ``make_fn`` — counted as a real compile
    (jit_cache.note_external_compile) and auto-saved for the next
    process.  The caller wraps the whole object with the device
    ledger, so restored programs attribute dispatches and cost bytes
    exactly like compiled ones."""

    def __init__(self, key: Any, exported: dict, make_fn, jit_kwargs,
                 store: PersistStore, conf_fp: str):
        self._key = key
        self._exported = exported          # sig -> Exported (consumed)
        self._compiled: dict = {}          # sig -> callable
        self._make_fn = make_fn
        self._jit_kwargs = dict(jit_kwargs)
        self._store = store
        self._conf_fp = conf_fp
        self._fallback = None
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        sig, avals = args_signature(args, kwargs)
        fn = self._compiled.get(sig) if sig is not None else None
        if fn is None:
            fn = self._bind(sig, avals)
        return fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        """Cost-model seam (trace/ledger._capture_cost): delegate to
        the signature's bound executable so restored programs report
        flops / bytes accessed like compiled ones.  An unbound
        signature raises; the ledger records zero cost rather than
        compiling anything here."""
        sig, _ = args_signature(args, kwargs)
        fn = self._compiled.get(sig) if sig is not None else None
        if fn is None:
            raise AttributeError("lower: signature not bound")
        return fn.lower(*args, **kwargs)

    def _bind(self, sig: Optional[str], avals):
        import jax

        with self._lock:
            if sig is not None:
                fn = self._compiled.get(sig)
                if fn is not None:
                    return fn
                exp = self._exported.pop(sig, None)
                if exp is not None:
                    fn = jax.jit(exp.call)
                    self._compiled[sig] = fn
                    return fn
            # unseen (or unserializable) signature: the honest
            # compile path, once, shared across such signatures
            fn = self._fallback
            if fn is None:
                from spark_rapids_tpu.execs.jit_cache import (
                    note_external_compile,
                )

                note_external_compile()
                tick("fallback_compiles")
                fn = jax.jit(self._make_fn(), **self._jit_kwargs)
                fn = AutoSave(self._key, fn, self._store, self._conf_fp)
                self._fallback = fn
            if sig is not None:
                self._compiled[sig] = fn
            return fn


class AutoSave:
    """Serialize-back wrapper around a freshly compiled program: the
    first call per argument signature (capped at MAX_SIGS_PER_KEY)
    schedules an async ``jax.export`` + validated write, off the
    critical path.  The wrapped call itself is untouched — results
    are bit-identical with persistence on or off."""

    __slots__ = ("_key", "_fn", "_store", "_conf_fp", "_seen",
                 "_max_bytes")

    def __init__(self, key: Any, fn, store: PersistStore,
                 conf_fp: str):
        self._key = key
        self._fn = fn
        self._store = store
        self._conf_fp = conf_fp
        self._seen: set = set()
        self._max_bytes = max_bytes()

    def __getattr__(self, name):
        # non-call attribute access (the ledger cost model's .lower)
        # passes through to the jitted fn
        return getattr(object.__getattribute__(self, "_fn"), name)

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        if len(self._seen) < MAX_SIGS_PER_KEY:
            sig, avals = args_signature(args, kwargs)
            if sig is not None and sig not in self._seen:
                self._seen.add(sig)
                self._store.save_program_async(
                    self._key, self._conf_fp, sig, self._fn, avals,
                    self._max_bytes)
        return out
