"""Per-program device-utilization ledger: which compiled program burns
the chip's time, and how close each runs to its roofline.

ROADMAP open item #2 is judged on ``hbm_roofline_fraction``, but until
this module that number was a single coarse quotient in bench.py
(wall-clock rows/s x row bytes / HBM bandwidth) — nothing could say
WHICH XLA program the time went to, how much of a query was dispatch
overhead versus device compute, or what a program's achieved bytes/s
and FLOPs/s are against the chip peaks.  The reference stack leans on
exactly this attribution (per-exec GpuMetrics feeding the Profiling /
Qualification tools); this is the XLA analog:

- every compiled program already flows through ONE chokepoint —
  :func:`spark_rapids_tpu.execs.jit_cache.cached_jit` — keyed by a
  structural program key.  The cache wraps each jitted callable with a
  ledger hook: when the ledger is ON, each dispatch bumps an invocation
  counter and hands the program's output to a settlement worker (the
  metric-reaper pattern: poll ``is_ready`` off the critical path, then
  credit the dispatch its EXCLUSIVE busy interval — completion stamps
  are monotone across the settle queue, so overlapping async-dispatch
  windows never double-count the one chip and the per-query sum is a
  true device-busy time bounded by the wall);
- on a program's FIRST ledger-observed dispatch, XLA's own cost model
  is captured (``fn.lower(*args).compile().cost_analysis()`` on the
  settlement worker): flops and bytes accessed per execution;
- from (dispatches, device wall, cost model) the ledger computes the
  ATTRIBUTED roofline per program — achieved bytes/s and flops/s
  against the chip peaks — plus dispatch-overhead ratios, surfaced in
  ``explain("analyze")`` (per-operator roofline column + top-program
  footer), bench.py (``q*_device_busy_ms`` / ``q*_roofline_attributed``
  / top-program fields), the event log (the per-query ``programs``
  section) and ``tools/history`` (per-program compare deltas, health
  rules HC010/HC011).

Cost discipline: with ``spark.rapids.tpu.trace.ledger.enabled=false``
(the default) the per-dispatch cost is ONE attribute read in the
cached_jit wrapper — no entry exists, no lock is taken, behavior is
bit-identical.  Enabled, the hot loop pays one counter bump under a
per-entry lock; everything else (completion wait, cost analysis)
settles on the ledger's worker thread.  Docs: ``docs/device_ledger.md``.
"""

from __future__ import annotations

import hashlib
import queue
import threading
import time
import weakref
from typing import Any, Optional

from spark_rapids_tpu.config import register

LEDGER_ENABLED = register(
    "spark.rapids.tpu.trace.ledger.enabled", False,
    "Enable the per-program device-utilization ledger: every program "
    "dispatched through the jit cache records invocation count, "
    "device wall time (settled off the critical path) and XLA's own "
    "cost model (flops, bytes accessed), from which per-program and "
    "per-operator ATTRIBUTED roofline fractions are computed — "
    "surfaced in explain('analyze'), bench.py and the event log's "
    "per-query `programs` section (docs/device_ledger.md).  Off (the "
    "default) the only per-dispatch cost is one attribute read.")

#: Published peaks of one chip, keyed by the ``device_kind`` JAX
#: reports: (HBM bandwidth in bytes/s, bf16 matrix throughput in
#: FLOP/s).  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16, 16 GB of HBM at 819 GB/s per chip).  A device that is not in
#: the table has no roofline: its fractions are None, never another
#: chip's.
DEVICE_PEAKS = {
    "TPU v5 lite": (819e9, 197e12),
}

LEDGER_ROOFLINE_FLOOR = register(
    "spark.rapids.tpu.trace.ledger.health.rooflineFloor", 0.001,
    "HC011 health-rule budget: a query whose ATTRIBUTED roofline "
    "fraction (device-time-weighted, from the event log's per-query "
    "programs section) falls below this while its programs burned "
    "real device time is flagged — the chip ran far under its "
    "roofline for that plan (docs/device_ledger.md).",
    check=lambda v: 0 <= v <= 1)

LEDGER_OCCUPANCY_FLOOR = register(
    "spark.rapids.tpu.trace.ledger.health.occupancyFloor", 0.5,
    "HC015 health-rule budget: a query whose aggregate live-rows over "
    "padded-capacity ratio (from the event log's per-query programs "
    "section) falls below this while its programs burned real device "
    "time is flagged — the chip mostly processed padding; coalesce "
    "small batches (sql.coalesce.enabled) or switch the capacity "
    "policy (sql.capacity.policy=pow2x3) to densify "
    "(docs/occupancy.md).",
    check=lambda v: 0 <= v <= 1)

def device_peaks() -> tuple[Optional[float], Optional[float]]:
    """(HBM bytes/s, FLOP/s) of this process's device from
    DEVICE_PEAKS; (None, None) for a device the table does not hold."""
    from spark_rapids_tpu.memory.device_manager import select_device

    return DEVICE_PEAKS.get(select_device().device_kind, (None, None))


def roofline_fraction(bytes_per_s: float,
                      hbm_bytes_per_s: Optional[float] = None
                      ) -> Optional[float]:
    """THE roofline formula: achieved bytes/s over the chip's HBM
    bandwidth (this device's, from DEVICE_PEAKS, unless given).  One
    definition shared by bench.py's coarse cold/warm quotients and the
    ledger's per-program attribution.  None on a device without a
    published peak."""
    if hbm_bytes_per_s is None:
        hbm_bytes_per_s = device_peaks()[0]
    if hbm_bytes_per_s is None:
        return None
    return bytes_per_s / hbm_bytes_per_s


def key_tag(key: Any) -> str:
    """THE key-tag rule: the leading string of a structural jit key
    (every cached_jit key starts with one), "prog" otherwise.  One
    definition shared by ProgramEntry, program_key_str and
    jit_cache.program_census — the census the fusion smoke diffs and
    the ledger footer must bucket keys identically or key churn gets
    pinned to the wrong tag."""
    return key[0] if isinstance(key, tuple) and key \
        and isinstance(key[0], str) else "prog"


def program_key_str(key: Any) -> str:
    """Stable, compact cross-run identity for a structural jit key:
    the key's leading tag (every cached_jit key starts with one) plus a
    hash of the full structural serialization.  Structural keys contain
    only expression trees / capacities / schemas — no addresses — so
    the same program hashes identically across runs, which is what
    lets tools/history line programs up between event logs."""
    h = hashlib.sha256(repr(key).encode()).hexdigest()[:12]
    return f"{key_tag(key)}#{h}"


class ProgramEntry:
    """Cumulative counters for one compiled program (one jit key)."""

    __slots__ = ("key_str", "tag", "op", "gen", "donated", "meta",
                 "dispatches", "dispatch_ns", "device_ns", "flops",
                 "bytes_accessed", "live_rows", "capacity_rows",
                 "cost_state", "lock")

    #: cost_state values
    COST_NONE, COST_PENDING, COST_DONE = 0, 1, 2

    def __init__(self, key: Any, op: Optional[str], gen: int,
                 donated: bool = False,
                 meta: Optional[dict] = None):
        self.key_str = program_key_str(key)
        self.tag = key_tag(key)
        self.op = op
        self.gen = gen
        self.donated = donated
        #: static program attributes from the compile site — a
        #: PARTITIONED (SPMD) program records its mesh device count
        #: (`devices`) and in-program collective round count
        #: (`rounds`), so snapshots can attribute per-device busy time
        #: (device_ms spans the whole mesh: the per-device figure IS
        #: device_ms, and the mesh burns devices x device_ms of chip
        #: capacity) and the multichip bench can report how many
        #: exchange rounds each stage folded into one dispatch
        self.meta = dict(meta) if meta else None
        self.dispatches = 0     # guard: lock
        self.dispatch_ns = 0    # guard: lock (host-side dispatch wall)
        self.device_ns = 0      # guard: lock (exclusive busy, settled)
        self.flops = 0.0        # guard: lock (XLA cost analysis)
        self.bytes_accessed = 0.0  # guard: lock (per execution)
        self.live_rows = 0      # guard: lock (occupancy accounting)
        self.capacity_rows = 0  # guard: lock (occupancy accounting)
        self.cost_state = self.COST_NONE  # guard: lock
        self.lock = threading.Lock()


# ------------------------------------------------------------------ #
# Occupancy accounting (live rows vs padded capacity per dispatch)
# ------------------------------------------------------------------ #

#: per-thread occupancy hint: dispatch sites whose batch row counts are
#: device-resident (the fused pipelines promote num_rows to a device
#: scalar before dispatch) state the host-known live/capacity pair just
#: before calling the wrapped program; the very next ledger dispatch on
#: that thread consumes it.  Sites that don't hint fall back to the
#: argument scan below.
_OCC_TLS = threading.local()

#: batch classes recognized by the argument scan; resolved lazily (the
#: columnar package imports config only, but ledger loads very early)
_BATCH_TYPES: Optional[tuple] = None


def note_occupancy(live_rows: Any, capacity_rows: Any) -> None:
    """Record the live/capacity row counts for the NEXT cached_jit
    dispatch on this thread.  No-op when the ledger is off (one
    attribute read), so call sites need no guard of their own; counts
    that aren't host ints (traced values) are ignored."""
    if not LEDGER.enabled:
        return
    try:
        live, cap = int(live_rows), int(capacity_rows)
    except Exception:
        return
    if cap > 0:
        _OCC_TLS.occ = (live, cap)


def _batch_types() -> Optional[tuple]:
    global _BATCH_TYPES
    if _BATCH_TYPES is None:
        try:
            from spark_rapids_tpu.columnar.batch import ColumnarBatch
            from spark_rapids_tpu.columnar.transfer import EncodedBatch

            _BATCH_TYPES = (ColumnarBatch, EncodedBatch)
        except Exception:
            return None
    return _BATCH_TYPES


def observe_occupancy(args: tuple) -> tuple[int, int]:
    """(live_rows, capacity_rows) summed over every batch argument
    whose row count is host-known.  Batches carrying device-resident
    counts are skipped (reading them would force a sync on the hot
    path) — their dispatch sites use :func:`note_occupancy` instead.
    Scans one level of tuple/list nesting, bounded, never throws."""
    types_ = _batch_types()
    if types_ is None:
        return (0, 0)
    batch_cls, encoded_cls = types_
    live = cap = 0
    stack = list(args)
    budget = 64
    while stack and budget > 0:
        budget -= 1
        a = stack.pop()
        try:
            if isinstance(a, batch_cls):
                n = a.num_rows
                if type(n) is int:
                    live += n
                    cap += a.capacity
            elif isinstance(a, encoded_cls):
                if a.num_rows is not None:
                    live += int(a.num_rows)
                    cap += int(a.capacity)
            elif isinstance(a, (tuple, list)):
                stack.extend(a)
        except Exception:
            continue
    return (live, cap)


def derive_sentinels(out: Any) -> list:
    """The zero-row sentinel slices that settle a program output pytree:
    at most ONE per distinct device set, and none for a device set
    whose live leaves are all complete (see :func:`sentinels_of`)."""
    return sentinels_of(out)[0]


def sentinels_of(out: Any) -> tuple[list, int]:
    """``(sentinels, live_leaves)`` for a program output pytree.

    A sentinel is an eager ``x[:0]``: itself a program, enqueued on its
    leaf's devices after everything dispatched there before it, so its
    completion bounds ALL of that work (the device runs programs in
    order) — one per device set is enough.  A device set whose live
    leaves are all ready needs none: its work has retired and the
    settle time is the host's, known now.  One that holds an unfinished
    leaf is sliced at its LAST live leaf of rank one or more (a 0-d
    leaf, the last only where it is alone, takes a reshape and a slice:
    two programs), whichever leaf is unfinished: the slice's shape then
    follows the output's structure and not the timing, so warm-up
    compiles every slice a run needs (a slice of whichever leaf
    happened to be unfinished compiled new shapes inside
    `tpch-sf10.scan`'s measured window: PERF.md section 6, PR 39).  A
    mesh array is one leaf over all its chips, so a stage output takes
    one sentinel (its eager slice, as ever, lands on the first shard's
    device).  The settle worker / metric reaper exclusively owns the
    sentinels, so polling never races the spill store's .delete()
    (``is_ready`` on a deleted buffer segfaults, which is why readiness
    is read here, on the producing thread, after ``is_deleted``).

    Fidelity: host-to-device transfers (an upload, a batch rebuilt from
    a spill) run beside the programs, leaf by leaf, and need not finish
    in order; such a region settles on its last leaf's transfer plus
    everything queued before it, not on the last transfer to finish.

    PER-LEAF fault isolation: under buffer donation a fused program's
    output can mix live leaves with leaves the caller already consumed
    (donated into the next program, or passed through from a donated
    input) — one dead leaf is skipped, never fatal, or the donated
    fused program silently settles \"as host\" and its device-busy
    time vanishes from the ledger."""
    import jax

    try:
        leaves = jax.tree_util.tree_leaves(out)
    except Exception:
        return [], 0
    groups: dict = {}  # device set -> [the leaf to slice, any unfinished]
    live = 0
    for x in leaves:
        if not isinstance(x, jax.Array):
            continue
        try:
            if x.is_deleted():
                continue
            group = groups.setdefault(frozenset(x.sharding.device_set),
                                      [x, False])
            if x.ndim > 0 or group[0].ndim == 0:
                group[0] = x
            group[1] = group[1] or not x.is_ready()
            live += 1
        except Exception:
            continue  # this leaf is gone; the survivors still settle
    sentinels = []
    for x, unfinished in groups.values():
        if not unfinished:
            continue
        try:
            sentinels.append(x[:0] if x.ndim > 0
                             else x.reshape((1,))[:0])
        except Exception:
            continue
    return sentinels, live


class _SettleWorker:
    """Off-critical-path settlement, mirroring the metric reaper:
    dispatch sites derive zero-row SENTINELS from the program output on
    the producing thread (the sentinel's completion implies the
    program finished; polling the output arrays themselves would race
    the spill store's .delete()) and this daemon polls readiness, then
    credits dispatch-to-completion time to the entry.  Cost-analysis
    capture (lower+compile+cost_analysis, once per program) also runs
    here — it can take tens of ms and must never sit on the hot
    loop."""

    def __init__(self) -> None:
        self._q: "queue.Queue" = queue.Queue()
        self._unfinished = 0    # guard: _cv
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        #: completion stamp of the previously settled dispatch: each
        #: dispatch is credited its EXCLUSIVE interval
        #: [max(t0, prev_done), done] — async dispatch lets
        #: dispatch-to-completion windows overlap (program k+1 is
        #: launched while k still runs), and crediting overlapping
        #: wall to both would double-count one chip.  The device runs
        #: programs in order, the worker settles them in order, so the
        #: credited intervals are disjoint and their sum is a true
        #: BUSY time, bounded by the query wall (the run_ledger_smoke
        #: acceptance bound) — queue wait inherited from the previous
        #: program is excluded by construction.
        self._last_done_ns = 0

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="tpu-ledger-settle", daemon=True)
            self._thread.start()

    def submit(self, entry: ProgramEntry, t0: int, out: Any,
               cost_req: Optional[tuple]) -> None:
        sentinels = derive_sentinels(out)
        with self._cv:
            self._ensure_thread()
            self._unfinished += 1
        self._q.put((entry, t0, sentinels, cost_req))

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait (bounded) until every submitted dispatch has settled;
        returns False on timeout.  Bounded because callers sit at query
        boundaries — a wedged settle must degrade the ledger, not hang
        the query epilogue."""
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            while self._unfinished:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._cv.wait(remaining)
        return True

    def _task_done(self) -> None:
        with self._cv:
            self._unfinished -= 1
            if not self._unfinished:
                self._cv.notify_all()

    def _run(self) -> None:
        while True:
            entry, t0, sentinels, cost_req = self._q.get()
            try:
                for x in sentinels:
                    while not x.is_ready():
                        time.sleep(0.001)
                done = time.perf_counter_ns()
                start = max(t0, self._last_done_ns)
                self._last_done_ns = done
                with entry.lock:
                    entry.device_ns += max(0, done - start)
                if cost_req is not None:
                    self._capture_cost(entry, cost_req)
            except Exception:
                pass  # diagnostics must never take the engine down
            finally:
                self._task_done()

    @staticmethod
    def _capture_cost(entry: ProgramEntry, cost_req: tuple) -> None:
        """XLA cost model for one program: lower+compile at the first
        observed argument signature, read flops / bytes accessed.  A
        backend without cost analysis (or an unlowerable signature)
        marks the entry DONE with zeros — retried never."""
        fn, args, kwargs = cost_req
        flops = nbytes = 0.0
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                flops = max(0.0, float(ca.get("flops", 0.0) or 0.0))
                nbytes = max(0.0, float(
                    ca.get("bytes accessed", 0.0) or 0.0))
        except Exception:
            pass
        with entry.lock:
            entry.flops = flops
            entry.bytes_accessed = nbytes
            entry.cost_state = ProgramEntry.COST_DONE


class DeviceLedger:
    """Process-wide program ledger.  ``enabled`` is THE fast-path
    guard (the cached_jit wrapper reads this one attribute and does
    nothing else when the ledger is off); ``forced`` marks a
    programmatic :func:`enable` that :func:`sync_conf` must not
    override — the tracer's ownership discipline exactly."""

    def __init__(self) -> None:
        self.enabled = False
        self.forced = False
        self.gen = 0  # bumped by reset(); stale wrapper cells re-key
        self._entries: dict[Any, ProgramEntry] = {}  # guard: _lock
        self._lock = threading.Lock()
        self._enabled_by: Optional[weakref.ref] = None
        self._settle = _SettleWorker()

    # -- recording (fed by the cached_jit wrapper) ------------------- #

    def entry(self, key: Any, op: Optional[str],
              donated: bool = False,
              meta: Optional[dict] = None) -> ProgramEntry:
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = ProgramEntry(key, op, self.gen,
                                                      donated, meta)
            elif e.op is None and op is not None:
                e.op = op
            return e

    def wrap(self, key: Any, fn, op: Optional[str] = None,
             donated: bool = False, meta: Optional[dict] = None):
        """Wrap one jitted callable with ledger accounting.  The
        disabled path is one attribute read + the passthrough call —
        bit-identical results either way (the wrapper never touches
        arguments or output).  `donated` marks programs compiled with
        buffer donation so snapshots/footers can say which programs
        reuse input HBM; `meta` carries static partitioned-program
        attributes (mesh devices, in-program collective rounds)."""
        cell: list = [None]
        ledger = self

        def dispatch(*args, **kwargs):
            if not ledger.enabled:
                return fn(*args, **kwargs)
            e = cell[0]
            if e is None or e.gen != ledger.gen:
                e = cell[0] = ledger.entry(key, op, donated, meta)
            occ = getattr(_OCC_TLS, "occ", None)
            if occ is not None:
                _OCC_TLS.occ = None
            t0 = time.perf_counter_ns()
            out = fn(*args, **kwargs)
            t1 = time.perf_counter_ns()
            if occ is None:
                occ = observe_occupancy(args)
            cost_req = None
            with e.lock:
                e.dispatches += 1
                e.dispatch_ns += t1 - t0
                if occ[1] > 0:
                    e.live_rows += occ[0]
                    e.capacity_rows += occ[1]
                if e.cost_state == ProgramEntry.COST_NONE:
                    e.cost_state = ProgramEntry.COST_PENDING
                    # args are immutable jax values: safe to hold for
                    # the worker's one-time lower+compile
                    cost_req = (fn, args, kwargs)
            ledger._settle.submit(e, t0, out, cost_req)
            return out

        dispatch.__wrapped__ = fn
        return dispatch

    # -- lifecycle --------------------------------------------------- #

    def enable(self, forced: bool = True) -> None:
        self.enabled = True
        self.forced = forced

    def disable(self) -> None:
        self.enabled = False
        self.forced = False
        self._enabled_by = None

    def reset(self) -> None:
        """Drop every entry (bench resets between queries, tests
        between cases).  Wrapper cells holding stale entries re-key on
        their next dispatch via the generation check."""
        with self._lock:
            self.gen += 1
            self._entries = {}

    def flush(self, timeout: Optional[float] = None) -> bool:
        return self._settle.flush(timeout)

    # -- reading ----------------------------------------------------- #

    def snapshot(self) -> dict[str, dict]:
        """Point-in-time cumulative counters per program (key_str ->
        plain dict).  Callers wanting per-query figures snapshot
        before/after and :func:`delta`."""
        with self._lock:
            entries = list(self._entries.values())
        out: dict[str, dict] = {}
        for e in entries:
            with e.lock:
                rec = {
                    "tag": e.tag,
                    "op": e.op,
                    "donated": e.donated,
                    "dispatches": e.dispatches,
                    "dispatch_ms": round(e.dispatch_ns / 1e6, 3),
                    "device_ms": round(e.device_ns / 1e6, 3),
                    "flops": e.flops,
                    "bytes_accessed": e.bytes_accessed,
                    "live_rows": e.live_rows,
                    "capacity_rows": e.capacity_rows,
                    "live_capacity_ratio": round(
                        e.live_rows / e.capacity_rows, 4)
                    if e.capacity_rows else None,
                }
                if e.meta:
                    # partitioned-program attribution: device_ms spans
                    # the mesh, so per-device busy IS device_ms and the
                    # stage burned devices x device_ms of chip capacity
                    rec.update(e.meta)
                out[e.key_str] = rec
        return out


#: THE process-wide ledger; the cached_jit wrapper guards on
#: ``LEDGER.enabled``
LEDGER = DeviceLedger()


def is_enabled() -> bool:
    return LEDGER.enabled


def enable() -> None:
    """Force the ledger on (tests, bench): survives sync_conf."""
    LEDGER.enable(forced=True)


def disable() -> None:
    LEDGER.disable()


def reset_stats() -> None:
    LEDGER.reset()


def snapshot() -> dict[str, dict]:
    return LEDGER.snapshot()


def sync_conf(conf=None) -> None:
    """Align the ledger with the session conf at a query boundary —
    same ownership rule as the tracer: a programmatic enable() wins,
    and only the conf that ENABLED the ledger may turn it off (a
    concurrent session's defaults-only conf must not kill another
    session's capture mid-query)."""
    if LEDGER.forced:
        return
    from spark_rapids_tpu.config import get_conf

    conf = conf or get_conf()
    want = bool(conf.get(LEDGER_ENABLED))
    if want:
        if not LEDGER.enabled:
            LEDGER.enable(forced=False)
        LEDGER._enabled_by = weakref.ref(conf)
    elif LEDGER.enabled and LEDGER._enabled_by is not None \
            and LEDGER._enabled_by() is conf:
        LEDGER.disable()


# ------------------------------------------------------------------ #
# Analytics over snapshots
# ------------------------------------------------------------------ #


def delta(before: dict[str, dict],
          after: dict[str, dict]) -> dict[str, dict]:
    """Per-query attribution: after - before on the monotonic
    counters, cost-model fields carried from `after` (they are
    per-execution constants).  Programs that did not dispatch in the
    window are dropped."""
    out: dict[str, dict] = {}
    for k, a in after.items():
        b = before.get(k, {})
        d = a["dispatches"] - b.get("dispatches", 0)
        if d <= 0:
            continue
        live = a.get("live_rows", 0) - b.get("live_rows", 0)
        cap = a.get("capacity_rows", 0) - b.get("capacity_rows", 0)
        rec = {
            "tag": a["tag"],
            "op": a["op"],
            "donated": a.get("donated", False),
            "dispatches": d,
            "dispatch_ms": round(
                a["dispatch_ms"] - b.get("dispatch_ms", 0.0), 3),
            "device_ms": round(
                a["device_ms"] - b.get("device_ms", 0.0), 3),
            "flops": a["flops"],
            "bytes_accessed": a["bytes_accessed"],
            "live_rows": live,
            "capacity_rows": cap,
            "live_capacity_ratio": round(live / cap, 4)
            if cap > 0 else None,
        }
        for mk in ("devices", "rounds"):
            if mk in a:
                rec[mk] = a[mk]
        out[k] = rec
    return out


def summarize(programs: dict[str, dict], top_n: int = 5,
              hbm_bytes_per_s: Optional[float] = None,
              peak_flops: Optional[float] = None) -> dict:
    """Enrich a snapshot/delta with attributed rooflines and totals —
    the ``programs`` section the event log persists and bench/analyze
    render.  Per program: achieved bytes/s and flops/s (cost model x
    dispatches over settled device time) against the chip peaks, and
    the dispatch-overhead ratio (host dispatch ms per device ms).
    Totals: device-time totals, a device-time-WEIGHTED roofline
    fraction, and the top-N programs by device time with their
    share."""
    if hbm_bytes_per_s is None or peak_flops is None:
        table_hbm, table_flops = device_peaks()
        if hbm_bytes_per_s is None:
            hbm_bytes_per_s = table_hbm
        if peak_flops is None:
            peak_flops = table_flops
    enriched: dict[str, dict] = {}
    total_device_ms = 0.0
    total_dispatch_ms = 0.0
    total_dispatches = 0
    total_live = 0
    total_capacity = 0
    weighted_roofline = 0.0
    weighted_known_ms = 0.0
    for k, p in programs.items():
        device_s = p["device_ms"] / 1e3
        e = dict(p)
        total_live += p.get("live_rows", 0)
        total_capacity += p.get("capacity_rows", 0)
        if device_s > 0 and p["bytes_accessed"] > 0:
            bps = p["bytes_accessed"] * p["dispatches"] / device_s
            fps = p["flops"] * p["dispatches"] / device_s
            e["bytes_per_s"] = round(bps, 1)
            e["flops_per_s"] = round(fps, 1)
            e["roofline"] = round(bps / hbm_bytes_per_s, 6) \
                if hbm_bytes_per_s else None
            e["flops_fraction"] = round(fps / peak_flops, 9) \
                if peak_flops else None
            if e["roofline"] is not None:
                weighted_roofline += e["roofline"] * p["device_ms"]
                weighted_known_ms += p["device_ms"]
        else:
            e["bytes_per_s"] = e["flops_per_s"] = None
            e["roofline"] = e["flops_fraction"] = None
        e["dispatch_overhead"] = round(
            p["dispatch_ms"] / p["device_ms"], 3) \
            if p["device_ms"] > 0 else None
        enriched[k] = e
        total_device_ms += p["device_ms"]
        total_dispatch_ms += p["dispatch_ms"]
        total_dispatches += p["dispatches"]
    top = sorted(enriched.items(),
                 key=lambda kv: -kv[1]["device_ms"])[:top_n]
    totals = {
        "programs": len(enriched),
        "dispatches": total_dispatches,
        "dispatch_ms": round(total_dispatch_ms, 3),
        "device_ms": round(total_device_ms, 3),
        "roofline": round(weighted_roofline / weighted_known_ms, 6)
        if weighted_known_ms else None,
        "live_rows": total_live,
        "capacity_rows": total_capacity,
        "live_capacity_ratio": round(total_live / total_capacity, 4)
        if total_capacity else None,
        "top": [{
            "key": k,
            "op": p["op"],
            "dispatches": p["dispatches"],
            "device_ms": p["device_ms"],
            "share": round(p["device_ms"] / total_device_ms, 3)
            if total_device_ms else 0.0,
            "live_capacity_ratio": p.get("live_capacity_ratio"),
        } for k, p in top],
    }
    return {"programs": enriched, "totals": totals}


def per_op(programs: dict[str, dict],
           hbm_bytes_per_s: Optional[float] = None) -> dict[str, dict]:
    """Aggregate an (un-enriched or enriched) program delta by the
    operator that compiled it (cached_jit's `op=`), for the
    explain('analyze') per-operator roofline column: per op —
    dispatches, device_ms, and the attributed roofline over the op's
    own device time (cost-model bytes x dispatches / device time)."""
    if hbm_bytes_per_s is None:
        hbm_bytes_per_s = device_peaks()[0]
    acc: dict[str, dict] = {}
    for p in programs.values():
        op = p.get("op")
        if not op:
            continue
        a = acc.setdefault(op, {"dispatches": 0, "device_ms": 0.0,
                                "bytes_total": 0.0, "live_rows": 0,
                                "capacity_rows": 0})
        a["dispatches"] += p["dispatches"]
        a["device_ms"] += p["device_ms"]
        a["bytes_total"] += p["bytes_accessed"] * p["dispatches"]
        a["live_rows"] += p.get("live_rows", 0)
        a["capacity_rows"] += p.get("capacity_rows", 0)
    out: dict[str, dict] = {}
    for op, a in acc.items():
        device_s = a["device_ms"] / 1e3
        roof = None
        if device_s > 0 and a["bytes_total"] > 0 and hbm_bytes_per_s:
            roof = round(a["bytes_total"] / device_s / hbm_bytes_per_s, 6)
        out[op] = {"dispatches": a["dispatches"],
                   "device_ms": round(a["device_ms"], 3),
                   "roofline": roof,
                   "live_capacity_ratio": round(
                       a["live_rows"] / a["capacity_rows"], 4)
                   if a["capacity_rows"] else None}
    return out
