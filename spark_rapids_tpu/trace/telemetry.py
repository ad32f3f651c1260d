"""Live serving telemetry: a conf-gated sampling thread snapshotting
the engine's occupancy gauges into Chrome-trace counter tracks and
periodic event-log records.

The PR8 serving tier made the process multi-tenant, but every signal
so far is per-QUERY (spans, settled metrics, counter deltas) — nothing
shows what the fleet looks like BETWEEN query boundaries: how full the
device store is while six sessions contend, how many semaphore permits
are held, how deep the admission queue runs, whether the pipeline
stages sit full or starved.  This module is that view:

- :func:`sample_now` — one consistent gauge snapshot: device-store
  bytes by tier (device/host/disk), device-semaphore permits in use,
  serving admission queue occupancy (running/waiting), and pipeline
  stage occupancy (item-weighted);
- :class:`TelemetrySampler` — a daemon thread sampling at
  ``spark.rapids.tpu.telemetry.hz``; each sample is recorded as
  Chrome-trace COUNTER events (``ph="C"``) when the tracer is on —
  Perfetto renders them as stacked counter tracks above the span
  timeline — and every ``telemetry.eventLogEvery``-th sample appends a
  ``telemetry`` record to each attached session's event log, so
  ``tools/history`` can replay fleet load offline;
- ownership mirrors the tracer: a programmatic :func:`start` (tests)
  survives :func:`sync_conf`; conf-driven starts are owned by the
  enabling conf, and only that conf's "off" stops the thread —
  concurrent sessions attach their event-log writers to the ONE
  process sampler instead of racing thread lifecycles.

Cost discipline: disabled (the default), the per-query cost is one
enabled-flag read plus one conf read in :func:`sync_conf`; no thread
exists.  Docs: ``docs/device_ledger.md`` (live telemetry section).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Optional

from spark_rapids_tpu import trace as _tr
from spark_rapids_tpu.config import register

TELEMETRY_ENABLED = register(
    "spark.rapids.tpu.telemetry.enabled", False,
    "Enable the live telemetry sampler: a background thread "
    "snapshotting device-store bytes by tier, semaphore permits in "
    "use, serving admission queue depth and pipeline stage occupancy "
    "at telemetry.hz — into Chrome-trace counter tracks (when tracing "
    "is on) and periodic `telemetry` event-log records "
    "(docs/device_ledger.md).  Off (the default) no thread exists.")

TELEMETRY_HZ = register(
    "spark.rapids.tpu.telemetry.hz", 4.0,
    "Sampling frequency of the live telemetry thread (samples per "
    "second).  Each sample is a handful of in-process gauge reads — "
    "no device traffic — so tens of Hz are safe; the default stays "
    "low because the event-log records accumulate.",
    check=lambda v: 0.1 <= v <= 1000)

TELEMETRY_LOG_EVERY = register(
    "spark.rapids.tpu.telemetry.eventLogEvery", 4,
    "Append a `telemetry` event-log record every Nth sample (per "
    "attached session log).  Counter tracks in the trace buffer get "
    "EVERY sample; the persisted record rate is divided so long runs "
    "do not bloat their logs.",
    check=lambda v: v >= 1)


def sample_now() -> dict:
    """One flat gauge snapshot (all host-side reads, no device sync):
    the fleet-monitoring surface the sampler records.  Usable directly
    by tests and ad-hoc probes; keys are stable (the event-log
    `telemetry` record persists exactly this dict)."""
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.memory.store import peek_store
    from spark_rapids_tpu.parallel.pipeline import (
        live_stage_threads,
        stage_snapshot,
    )
    from spark_rapids_tpu.serving import cancel as _cancel
    from spark_rapids_tpu.serving import work_share as _ws
    from spark_rapids_tpu.serving.scheduler import queue_gauges

    # peek, never create: the singleton store snapshots budgets + the
    # spill codec from the CONSTRUCTING thread's conf, and this may be
    # the sampler thread holding defaults
    store = peek_store()
    ss = store.spill_stats() if store is not None else {
        "device_used": 0, "host_used": 0, "disk_used": 0}
    sem = TpuSemaphore.usage_now()
    adm = queue_gauges()
    weighted = items = 0.0
    for s in stage_snapshot().values():
        n = s.get("items", 0)
        if n:
            weighted += s.get("occupancy_fraction", 0.0) * n
            items += n
    return {
        "store.device_bytes": ss["device_used"],
        "store.host_bytes": ss["host_used"],
        "store.disk_bytes": ss["disk_used"],
        "semaphore.permits": sem["permits"],
        "semaphore.in_use": sem["in_use"],
        "admission.running": adm["running"],
        "admission.waiting": adm["waiting"],
        "pipeline.occupancy": round(weighted / items, 3)
        if items else 0.0,
        "pipeline.items": int(items),
        # the cancellation tier's live-serving gauges: in-flight
        # tokens, live stage producer threads and in-flight shared
        # scans — all must return to baseline after a cancellation
        # storm (docs/robustness.md)
        "cancel.active": _cancel.active_count(),
        "pipeline.stage_threads": live_stage_threads(),
        "scan.inflight": _ws.SCAN_REGISTRY.inflight(),
        # the ops-plane surfaces (docs/ops_plane.md): live registered
        # queries (0 whenever the obs plane is off — REGISTRY.count()
        # is a plain len, no lock, no conf read) and the shared
        # result-cache residency the /metrics scrape reports
        "queries.in_flight": _obs_inflight(),
        "result_cache.bytes": _ws.RESULT_CACHE.bytes_used(),
        # warm-start disk-cache footprint (docs/warm_start.md): 0 with
        # no dir walk when persistence never activated in this process
        "persist_cache.bytes": _persist_bytes(),
    }


def _obs_inflight() -> int:
    from spark_rapids_tpu.obs import REGISTRY
    return REGISTRY.count()


def _persist_bytes() -> int:
    from spark_rapids_tpu.persist import cache_bytes
    return cache_bytes()


#: Chrome counter TRACKS: one ph="C" event per track per sample, the
#: series within a track stacked by Perfetto (name -> sample keys)
_COUNTER_TRACKS = (
    ("telemetry.store_bytes", (("device", "store.device_bytes"),
                               ("host", "store.host_bytes"),
                               ("disk", "store.disk_bytes"))),
    ("telemetry.semaphore", (("in_use", "semaphore.in_use"),)),
    ("telemetry.admission", (("running", "admission.running"),
                             ("waiting", "admission.waiting"))),
    ("telemetry.pipeline_occupancy",
     (("occupancy", "pipeline.occupancy"),)),
    ("telemetry.queries", (("in_flight", "queries.in_flight"),)),
    ("telemetry.result_cache_bytes",
     (("bytes", "result_cache.bytes"),)),
    ("telemetry.persist_cache_bytes",
     (("bytes", "persist_cache.bytes"),)),
)


class TelemetrySampler:
    """The process sampler (see module doc).  ``enabled`` is the
    fast-path guard; writers are held by WEAKREF so a session going
    away never leaks its log into the sampler."""

    def __init__(self) -> None:
        self.enabled = False
        self.forced = False
        self.hz = float(TELEMETRY_HZ.default)
        self.log_every = int(TELEMETRY_LOG_EVERY.default)
        self.samples = 0
        self._enabled_by: Optional[weakref.ref] = None
        self._writers: list[weakref.ref] = []
        self._lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --------------------------------------------------- #

    def start(self, hz: Optional[float] = None,
              log_every: Optional[int] = None,
              forced: bool = True) -> None:
        with self._lock:
            if hz is not None:
                self.hz = float(hz)
            if log_every is not None:
                self.log_every = int(log_every)
            self.forced = self.forced or forced
            if self.enabled:
                return
            self.enabled = True
            self._stop_evt = threading.Event()
            self._thread = threading.Thread(
                target=self._run, args=(self._stop_evt,),
                name="tpu-telemetry", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        """Stop and JOIN the sampler thread — leak-free by contract:
        after stop() returns, no telemetry thread exists (the
        concurrent-sessions test counts threads across start/stop
        cycles)."""
        with self._lock:
            if not self.enabled:
                self.forced = False
                self._enabled_by = None
                return
            self.enabled = False
            self.forced = False
            self._enabled_by = None
            evt, t = self._stop_evt, self._thread
            self._thread = None
        evt.set()
        if t is not None:
            t.join()

    def attach_writer(self, writer) -> None:
        """Register one session's event-log writer for periodic
        `telemetry` records (weakref; dedup; dead refs pruned on each
        emit)."""
        if writer is None:
            return
        with self._lock:
            for r in self._writers:
                if r() is writer:
                    return
            self._writers.append(weakref.ref(writer))

    # -- sampling loop ----------------------------------------------- #

    def _run(self, stop_evt: threading.Event) -> None:
        n = 0
        while not stop_evt.wait(1.0 / max(self.hz, 0.1)):
            try:
                sample = sample_now()
            except Exception:
                continue  # a torn gauge read must not kill the thread
            n += 1
            with self._lock:
                self.samples += 1
            self._emit_counters(sample)
            if n % max(1, self.log_every) == 0:
                self._emit_eventlog(sample)

    @staticmethod
    def _emit_counters(sample: dict) -> None:
        if not _tr.TRACER.enabled:
            return
        ts = time.perf_counter_ns()
        for track, series in _COUNTER_TRACKS:
            _tr.TRACER.record(
                track, ts, 0,
                {label: sample[key] for label, key in series},
                ph="C")

    def _emit_eventlog(self, sample: dict) -> None:
        with self._lock:
            refs = list(self._writers)
        live = []
        for r in refs:
            w = r()
            if w is None:
                continue
            live.append(r)
            try:
                w.log_telemetry(sample)
            except Exception:
                pass  # a full disk must not kill the sampler
        if len(live) != len(refs):
            with self._lock:
                self._writers = [r for r in self._writers
                                 if r() is not None]


#: THE process sampler
SAMPLER = TelemetrySampler()


def is_enabled() -> bool:
    return SAMPLER.enabled


def start(hz: Optional[float] = None,
          log_every: Optional[int] = None) -> None:
    """Force the sampler on (tests): survives sync_conf."""
    SAMPLER.start(hz=hz, log_every=log_every, forced=True)


def stop() -> None:
    SAMPLER.stop()


def sync_conf(conf=None, writer=None) -> None:
    """Query-boundary alignment with the session conf (tracer
    ownership discipline): the conf that enables the sampler owns it;
    another session's defaults-only conf cannot stop it mid-flight; a
    forced start() wins over confs entirely.  `writer` (the session's
    event-log writer, may be None) is attached so the sampler's
    periodic `telemetry` records land in every enabled session's
    log."""
    from spark_rapids_tpu.config import get_conf

    conf = conf or get_conf()
    if SAMPLER.forced:
        if SAMPLER.enabled:
            SAMPLER.attach_writer(writer)
        return
    want = bool(conf.get(TELEMETRY_ENABLED))
    if want:
        if not SAMPLER.enabled:
            SAMPLER.start(hz=float(conf.get(TELEMETRY_HZ)),
                          log_every=int(conf.get(TELEMETRY_LOG_EVERY)),
                          forced=False)
        SAMPLER._enabled_by = weakref.ref(conf)
        SAMPLER.attach_writer(writer)
    elif SAMPLER.enabled and SAMPLER._enabled_by is not None \
            and SAMPLER._enabled_by() is conf:
        SAMPLER.stop()
