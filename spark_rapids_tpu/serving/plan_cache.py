"""Prepared-plan cache: lowered exec trees keyed by structural plan
identity, so a repeated query template never re-pays parse/plan/tag/
lower.

The reference never needs this layer — its per-batch kernels are
pre-compiled native code and Spark re-plans cheaply — but this engine's
query setup is real work (plan tagging, runtime-filter injection,
pipeline planning) and its programs key on structural expression trees
(execs/jit_cache.py).  The cache extends that idea one level up: the
whole LOWERED exec tree is the cached object, keyed by

- the **structural plan key**: a deterministic serialization of the
  logical plan — node class names plus every attribute, expressions via
  ``jit_cache.expr_key`` (the same ordinal/dtype/literal-complete
  serialization compiled programs key on), in-memory tables via their
  content digest (an id-based key could alias a recycled address to a
  DIFFERENT table — a stale hit that answers the wrong query);
- the **conf fingerprint** (eventlog.conf_fingerprint): lowering reads
  conf (pipeline depth, runtime filters, shuffle partitions), so two
  conf epochs must never share a lowered tree;
- the **parameter binding** for SQL templates: literal values are burned
  into the lowered programs (that IS the jit key design), so each bound
  value set is its own entry — repeats of a binding hit, new bindings
  lower once.

Exec trees are re-drainable by construction (close() returns join
builds / shuffle registrations to their pre-execute state — asserted by
tests/test_serving.py), so a hit simply re-drains the cached tree.
Operator metrics on the LIVE cached tree accumulate across executions
(the tree is the long-lived object), but every record derived from a
re-drain — explain("analyze"), the history event, the event-log
operator tree — reports per-EXECUTION deltas: the collect paths
snapshot the settled pre-drain totals and subtract
(session._collect_tpu_admitted / tools.profiling.snapshot_delta).

Eviction: LRU bounded by ``spark.rapids.tpu.serving.planCache.capacity``
— entries pin their source data (ArrowSourceExec tables), so the bound
is also a memory bound.  Hit/miss/evict counters are process-global
(:func:`stats`), surfaced in ``explain("analyze")``'s counter footer
and (per query, via the serving context) in the event-log record.
"""

from __future__ import annotations

import collections
import hashlib
import threading
from typing import Any, Optional

from spark_rapids_tpu.robustness.lock_tracker import tracked_lock
from spark_rapids_tpu.serving import PLAN_CACHE_CAPACITY

# ------------------------------------------------------------------ #
# Structural keys
# ------------------------------------------------------------------ #


def _value_key(v: Any, seen: dict) -> str:
    """Serialize one logical-plan attribute value deterministically.
    Correctness rule: two plans that could EXECUTE differently must
    never share a key — when in doubt, serialize more, not less."""
    from spark_rapids_tpu.exprs.base import Expression
    from spark_rapids_tpu.plan.logical import LogicalPlan

    import pyarrow as pa

    if isinstance(v, Expression):
        from spark_rapids_tpu.execs.jit_cache import expr_key

        try:
            return expr_key(v)
        except TypeError:
            return repr(v)
    from spark_rapids_tpu.exprs.aggregates import (
        AggregateFunction,
        NamedAgg,
    )

    if isinstance(v, NamedAgg):
        return (f"NamedAgg({_value_key(v.fn, seen)},"
                f"{v.out_name!r})")
    if isinstance(v, AggregateFunction):
        # no custom __repr__: the default falls back to the object
        # address, which would mint a fresh key per plan INSTANCE and
        # defeat every structural-identity consumer (prepared-plan
        # cache across template objects, the cross-tenant result
        # cache) — serialize class + attributes instead
        parts = [f"{k}={_value_key(x, seen)}"
                 for k, x in sorted(vars(v).items())]
        return f"{type(v).__name__}({','.join(parts)})"
    if isinstance(v, LogicalPlan):
        return plan_structural_key(v, seen)
    if isinstance(v, pa.Table):
        # content digest, not id(): a recycled address must not alias
        # a dead table's key onto different data
        from spark_rapids_tpu.eventlog import table_digest

        return f"table:{table_digest(v)}"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_value_key(x, seen) for x in v) + "]"
    if isinstance(v, (set, frozenset)):
        return "{" + ",".join(sorted(_value_key(x, seen)
                                     for x in v)) + "}"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{_value_key(k, seen)}:{_value_key(x, seen)}"
            for k, x in sorted(v.items(), key=lambda kv: repr(kv[0]))
        ) + "}"
    if callable(v):
        # UDFs and pandas functions have no structural form; identity
        # keys them (the PreparedQuery holds the plan alive, so the id
        # cannot be recycled while the entry is reachable via its key
        # holder — see PreparedQuery, which keeps the DataFrame)
        return f"fn:{getattr(v, '__qualname__', '?')}@{id(v)}"
    return repr(v)


def plan_structural_key(plan, seen: Optional[dict] = None) -> str:
    """Deterministic structural serialization of a LOGICAL plan tree:
    class names + every instance attribute (expressions via the
    jit_cache structural serialization), recursing into children.
    A node visited twice (a DAG: `a.union(b).union(a)` shares `a`)
    serializes as ``ref:N`` — its first-visit ordinal, assigned in
    deterministic traversal order — so WHICH node repeats is part of
    the key; a class-name-only marker would collide plans that share
    different subtrees of one class."""
    if seen is None:
        seen = {}
    import pyarrow as pa

    ref = seen.get(id(plan))
    if ref is not None:
        return f"ref:{ref}"
    seen[id(plan)] = len(seen)
    digester = getattr(plan, "content_digest", None)
    parts = [type(plan).__name__]
    for k, v in sorted(vars(plan).items()):
        if k.startswith("_") and k != "_schema":
            continue
        if isinstance(v, pa.Table) and digester is not None:
            # the node memoizes its own content digest
            # (InMemoryRelation.content_digest): same digest-keyed
            # identity as _value_key's table branch, hashed once per
            # relation instead of once per prepare()
            parts.append(f"{k}=table:{digester()}")
            continue
        parts.append(f"{k}={_value_key(v, seen)}")
    return f"{parts[0]}[{','.join(parts[1:])}]"


def template_key(plan, conf) -> str:
    """The cache key for a native (DataFrame) template: structural plan
    key x conf fingerprint x the active mesh identity (a plan lowered
    against an 8-device mesh must re-key, not rehit, on 4), hashed."""
    from spark_rapids_tpu.eventlog import conf_fingerprint
    from spark_rapids_tpu.parallel.mesh import mesh_cache_suffix

    payload = (plan_structural_key(plan) + "|" + conf_fingerprint(conf)
               + mesh_cache_suffix())
    return hashlib.sha256(payload.encode()).hexdigest()[:24]


def _normalize_sql(text: str) -> str:
    """Whitespace-normalize a SQL template WITHOUT reaching inside
    string literals: token texts joined by one space.  A naive
    ``" ".join(text.split())`` would collapse ``'a  b'`` and ``'a b'``
    onto one key — a stale hit answering the wrong query.  Templates
    the tokenizer rejects key on their raw text (the parse error
    surfaces at lowering, never as a wrong cache hit)."""
    from spark_rapids_tpu.frontends.sql import SqlError, _tokenize

    try:
        return " ".join(tok[1] for tok in _tokenize(text))
    except SqlError:
        return text


def binding_key(params: Optional[dict]) -> str:
    """Canonical serialization of one parameter binding — THE single
    definition (the PreparedQuery key memo and sql_template_key must
    agree to the bit, or a memoized key could alias a different
    binding onto one entry)."""
    if not params:
        return ""
    return repr(sorted((str(k), repr(v)) for k, v in params.items()))


def sql_template_key(text: str, conf,
                     params: Optional[dict] = None) -> str:
    """The cache key for a SQL template: normalized text x conf
    fingerprint x the parameter BINDING (values are burned into the
    lowered programs, so each binding is its own entry) x the active
    mesh identity."""
    from spark_rapids_tpu.eventlog import conf_fingerprint
    from spark_rapids_tpu.parallel.mesh import mesh_cache_suffix

    payload = (_normalize_sql(text) + "|" + conf_fingerprint(conf)
               + "|" + binding_key(params) + mesh_cache_suffix())
    return hashlib.sha256(payload.encode()).hexdigest()[:24]


# ------------------------------------------------------------------ #
# Process-global counters (per-cache caches, one counter surface)
# ------------------------------------------------------------------ #

_STATS_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0
_EVICTIONS = 0


def stats() -> dict:
    """Cumulative process-wide plan-cache counters {hits, misses,
    evictions, hit_rate} (every session's cache ticks the same surface;
    bench and the analyze footer diff before/after for windows)."""
    with _STATS_LOCK:
        total = _HITS + _MISSES
        return {
            "hits": _HITS,
            "misses": _MISSES,
            "evictions": _EVICTIONS,
            "hit_rate": round(_HITS / total, 3) if total else 0.0,
        }


def reset_stats() -> None:
    global _HITS, _MISSES, _EVICTIONS
    with _STATS_LOCK:
        _HITS = 0
        _MISSES = 0
        _EVICTIONS = 0


# ------------------------------------------------------------------ #
# The cache
# ------------------------------------------------------------------ #


class DrainLock:
    """Non-reentrant drain mutex with same-thread deadlock DETECTION.

    A partially consumed ``execute_stream()`` holds its entry's drain
    lock across yields ON THE CONSUMER THREAD; if that thread then
    re-executes the same template, a plain Lock would block forever
    with no diagnostic.  Re-entry by the owning thread raises
    immediately instead — drain or close the open stream first.
    Cross-thread acquisition blocks normally (that is the serializing
    contract).  The owner check is race-free: another thread's ident
    never equals ours, and our own owner writes happen-before our own
    reads."""

    __slots__ = ("_lock", "_owner")

    def __init__(self):
        self._lock = threading.Lock()
        self._owner = None

    def acquire(self, blocking: bool = True) -> bool:
        if self._owner == threading.get_ident():
            raise RuntimeError(
                "this prepared template is still draining on this "
                "thread (an execute_stream() not yet drained or "
                "closed?); finish or close() the open stream before "
                "re-executing it")
        ok = self._lock.acquire(blocking)
        if ok:
            self._owner = threading.get_ident()
        return ok

    def release(self) -> None:
        self._owner = None
        self._lock.release()

    def __enter__(self) -> "DrainLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False


class CacheEntry:
    """One cached lowered plan (plus the DataFrame that lowered to it —
    the CPU-degrade ladder and the structural key's identity-keyed
    parts need the logical plan kept alive).  ``lock`` serializes
    re-drains of the shared exec tree — a single session re-executing
    one template from two threads must not interleave two drains of one
    tree."""

    __slots__ = ("exec_", "meta", "plan_hash", "df", "lock",
                 "rehydrated")

    def __init__(self, exec_, meta, plan_hash: str, df=None):
        self.exec_ = exec_
        self.meta = meta
        self.plan_hash = plan_hash
        self.df = df
        self.lock = DrainLock()
        #: metadata restored from the warm-start disk tier
        #: (spark_rapids_tpu/persist.py) for this key, when a prior
        #: process prepared the same template — None otherwise.  The
        #: lowered exec tree itself is LIVE state (closures, device
        #: buffers) and is rebuilt, immediately hitting the persisted
        #: AOT program tier; this slot carries the cross-process
        #: prepare lineage (docs/warm_start.md).
        self.rehydrated: Optional[dict] = None


class PlanCache:
    """Per-session LRU of :class:`CacheEntry` (see module doc)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from spark_rapids_tpu.config import get_conf

            capacity = int(get_conf().get(PLAN_CACHE_CAPACITY))
        self.capacity = max(1, int(capacity))
        # guard: _mu
        self._entries: "collections.OrderedDict[str, CacheEntry]" = \
            collections.OrderedDict()
        # guard: _mu — persisted-plan metadata restored on a miss,
        # consumed by the insert() that follows it (prepared._resolve
        # always inserts after a miss)
        self._rehydrated: dict[str, dict] = {}
        self._mu = tracked_lock("planCache.mu")

    def lookup(self, key: str) -> Optional[CacheEntry]:
        """Get-and-touch; ticks the global hit/miss counters.  An
        in-memory miss additionally probes the warm-start disk tier
        (one conf read when persistence is off): a valid persisted
        entry for this (structural plan key x conf fingerprint) stashes
        its metadata for the insert() that follows the re-lowering."""
        global _HITS, _MISSES
        with self._mu:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
        with _STATS_LOCK:
            if e is None:
                _MISSES += 1
            else:
                _HITS += 1
        if e is None:
            from spark_rapids_tpu import persist as _persist

            store = _persist.active()
            if store is not None:
                meta = store.load_plan(key)
                if meta is not None:
                    with self._mu:
                        self._rehydrated[key] = meta
        return e

    def insert(self, key: str, entry: CacheEntry) -> CacheEntry:
        """Insert (first writer wins under a race) and evict past
        capacity; evicted exec trees are close()d so they release any
        held resources."""
        global _EVICTIONS
        evicted: list[CacheEntry] = []
        with self._mu:
            cur = self._entries.get(key)
            if cur is not None:
                self._entries.move_to_end(key)
                return cur
            self._entries[key] = entry
            entry.rehydrated = self._rehydrated.pop(key, None)
            while len(self._entries) > self.capacity:
                _k, old = self._entries.popitem(last=False)
                evicted.append(old)
        from spark_rapids_tpu import persist as _persist

        store = _persist.active()
        if store is not None:
            # write-back (async, off the prepare path): next process's
            # lookup() rehydrates this metadata instead of starting its
            # prepare lineage from zero
            prev = int((entry.rehydrated or {}).get("prepares", 0))
            store.save_plan_async(
                key, {"plan_hash": entry.plan_hash,
                      "prepares": prev + 1},
                _persist.max_bytes())
        if evicted:
            with _STATS_LOCK:
                _EVICTIONS += len(evicted)
            for old in evicted:
                self._close_entry(old)
        return entry

    @staticmethod
    def _close_entry(old: CacheEntry) -> None:
        """Best-effort teardown of an evicted entry: only under its
        drain lock (closing DURING a drain tears state out from under
        the iterator), and only if the lock is free — an in-flight
        drain closes its own tree when it finishes (stream_exec /
        collect_exec close in their finally), so a busy entry needs no
        close from here, and blocking (or raising, if the evicting
        thread itself holds the lock via an open stream) would stall
        an innocent prepare()."""
        try:
            if not old.lock.acquire(blocking=False):
                return
        except RuntimeError:
            return  # this thread's own open stream owns the drain
        try:
            old.exec_.close()
        except Exception:
            pass
        finally:
            old.lock.release()

    def invalidate(self) -> None:
        """Drop every entry (conf epoch changes key entries out
        naturally; this is the explicit hammer for tests/operators)."""
        with self._mu:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            self._close_entry(e)

    def __len__(self) -> int:
        with self._mu:
            return len(self._entries)
