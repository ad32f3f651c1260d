"""Software-pipelining layer: bounded background stages + deferred
device->host readbacks.

The engine's latency profile has two serialization points:

1. host-side stage work (Parquet decode, table accumulation, the final
   Arrow fetch) running inline with device dispatch, where the
   reference overlaps them on a reader thread pool (ref:
   GpuParquetScan.scala:882-895 MultiFileCloudParquetPartitionReader);
2. blocking per-batch device->host syncs (`int(jax.device_get(total))`
   in the join stream loop, per-partial sizing syncs in the aggregate,
   split counts in the exchange) that stop the stream loop cold — JAX
   dispatch is asynchronous, so the COMPUTE for batch k+1 could already
   be in flight while batch k's scalar is fetched; only the readback
   ordering serializes it.

Two primitives fix both, shared by every exec:

- :func:`prefetch` — run a generator on a background thread behind a
  bounded queue (a pipeline *stage*).  Condition-variable handshake:
  no poll loops, clean cancellation (closing the consumer closes the
  producer's generator on the producer thread and joins it),
  exceptions propagate in stream order, and the caller's thread-local
  conf snapshot is installed on the producer thread (conf is
  thread-local; a bare thread would silently read defaults).
- :func:`pipelined` + :func:`device_read` — a software-pipelined
  stream loop: ``dispatch(item)`` launches batch k+1's device work
  BEFORE ``retire`` performs batch k's one blocking readback, so the
  readback wait overlaps real compute.  ``device_read*`` is the single
  blessed blocking-sync helper (the tpulint SRC005 rule flags raw
  ``jax.device_get`` in exec bodies) and is traceable in tests via
  :func:`trace_events`.
- :func:`device_read_async` + :class:`ReadbackFuture` — the
  future-style sibling for SPECULATIVE sizing (parallel/speculation.py,
  docs/speculation.md): the exec dispatches work at a predicted
  capacity and the true count is harvested off-thread; ``result()``
  one batch later is free in steady state, so even the deferred
  readback leaves the critical path.

Per-stage occupancy and wait counters feed the event log, the
telemetry sampler and the docs/pipeline.md tuning guide.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

from spark_rapids_tpu import trace as _tr
from spark_rapids_tpu.config import get_conf, register, set_conf
from spark_rapids_tpu.robustness.lock_tracker import tracked_lock

PIPELINE_ENABLED = register(
    "spark.rapids.tpu.sql.pipeline.enabled", True,
    "Enable the software-pipelined executor: scan decode/upload run as "
    "bounded background stages and per-batch device->host readbacks "
    "(join probe counts, aggregate partial sizing, exchange split "
    "counts, the final result fetch) are deferred one batch behind "
    "dispatch so they overlap device compute (the reader-thread-pool + "
    "JoinGatherer overlap of the reference, GpuParquetScan.scala:882).")

PIPELINE_DEPTH = register(
    "spark.rapids.tpu.sql.pipeline.depth", 2,
    "Bounded-queue depth of each pipeline stage, and (depth - 1) the "
    "lookahead window for deferred readbacks.  Higher values smooth "
    "jittery stages at the cost of one extra in-flight batch of host "
    "(stage queues) or device (readback window) memory per step.",
    check=lambda v: v >= 1)


def stage_depth(conf=None) -> int:
    """Queue depth for pipeline stages; 0 = pipelining disabled."""
    conf = conf or get_conf()
    if not conf.get(PIPELINE_ENABLED):
        return 0
    return int(conf.get(PIPELINE_DEPTH))


def readback_lookahead(conf=None) -> int:
    """How many batches a stream loop dispatches ahead of its blocking
    readback (0 = retire immediately, the unpipelined order)."""
    d = stage_depth(conf)
    return max(0, d - 1) if d else 0


# ------------------------------------------------------------------ #
# Stage metrics
# ------------------------------------------------------------------ #


class StageMetrics:
    """Counters for one named stage, accumulated across queries: item
    count, queue-occupancy samples (taken at each consumer pop), and
    the time each side spent blocked on the other."""

    __slots__ = ("name", "depth", "items", "occupancy_sum", "samples",
                 "producer_wait_ns", "consumer_wait_ns", "readbacks",
                 "async_readbacks", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.depth = 0              # guard: _lock
        self.items = 0              # guard: _lock
        self.occupancy_sum = 0      # guard: _lock
        self.samples = 0            # guard: _lock
        self.producer_wait_ns = 0   # guard: _lock
        self.consumer_wait_ns = 0   # guard: _lock
        self.readbacks = 0          # guard: _lock
        self.async_readbacks = 0    # guard: _lock
        self._lock = threading.Lock()

    def snapshot(self) -> dict:
        with self._lock:
            occ = (self.occupancy_sum / self.samples) if self.samples \
                else 0.0
            return {
                "depth": self.depth,
                "items": self.items,
                "avg_occupancy": round(occ, 3),
                "occupancy_fraction": round(occ / self.depth, 3)
                if self.depth else 0.0,
                "producer_wait_s": round(self.producer_wait_ns / 1e9, 4),
                "consumer_wait_s": round(self.consumer_wait_ns / 1e9, 4),
                "readbacks": self.readbacks,
                "async_readbacks": self.async_readbacks,
            }


_STAGES: dict[str, StageMetrics] = {}
_STAGES_LOCK = tracked_lock("pipeline.stages")


def _stage_metrics(name: str) -> StageMetrics:
    with _STAGES_LOCK:
        m = _STAGES.get(name)
        if m is None:
            m = _STAGES[name] = StageMetrics(name)
        return m


def stage_snapshot(prefix: Optional[str] = None) -> dict[str, dict]:
    """Point-in-time counters for every stage seen so far.  `prefix`
    filters to one stage family — e.g. ``stage_snapshot("serve.stream")``
    isolates the serving tier's streaming-fetch backpressure counters
    from the scan stages."""
    with _STAGES_LOCK:
        stages = list(_STAGES.values())
    return {m.name: m.snapshot() for m in stages
            if prefix is None or m.name.startswith(prefix)}


def reset_stage_counters() -> None:
    """Clear every stage's counters, so occupancy read after it
    reports one query instead of accumulating across queries."""
    with _STAGES_LOCK:
        _STAGES.clear()


def live_stage_threads() -> int:
    """Gauge: pipeline stage PRODUCER threads alive right now (the
    ``tpu-pipe-<stage>`` family; the persistent readback harvester
    pool is excluded).  Zero between queries — a nonzero reading after
    a query unwound is a leaked stage, the cancellation tests' and
    HC013's leak surface."""
    return sum(1 for t in threading.enumerate()
               if t.name.startswith("tpu-pipe-")
               and not t.name.startswith("tpu-pipe-harvest"))


# ------------------------------------------------------------------ #
# Readback tracing (test instrumentation)
# ------------------------------------------------------------------ #

_TRACE: Optional[list] = None
_TRACE_LOCK = threading.Lock()


@contextmanager
def trace_events():
    """Capture ("dispatch"|"readback", tag) events from pipelined() and
    device_read*() — the acceptance-test hook verifying that batch
    k+1's dispatch precedes batch k's readback."""
    global _TRACE
    events: list[tuple[str, Optional[str]]] = []
    with _TRACE_LOCK:
        prev, _TRACE = _TRACE, events
    try:
        yield events
    finally:
        with _TRACE_LOCK:
            _TRACE = prev


def _trace(kind: str, tag: Optional[str]) -> None:
    t = _TRACE
    if t is not None:
        with _TRACE_LOCK:
            if _TRACE is t:
                t.append((kind, tag))


# ------------------------------------------------------------------ #
# Deferred readback helpers (the SRC005-blessed sync points)
# ------------------------------------------------------------------ #


def device_read(x, tag: Optional[str] = None):
    """THE blocking device->host readback.  Host scalars pass through
    free.  Stream loops must not call this inline per batch — route the
    loop through :func:`pipelined` so the next batch's dispatch is
    already in flight when this blocks (tpulint SRC005 flags raw
    ``jax.device_get`` in exec bodies for exactly that reason)."""
    if isinstance(x, (int, float, bool)):
        return x
    import jax

    _trace("readback", tag)
    if tag is not None:
        m = _stage_metrics(tag)
        with m._lock:
            m.readbacks += 1
    if _tr.TRACER.enabled:
        with _tr.span("pipe.readback", tag=tag or ""):
            return jax.device_get(x)
    return jax.device_get(x)


def device_read_int(x, tag: Optional[str] = None) -> int:
    v = device_read(x, tag)
    return v if isinstance(v, int) else int(v)


def device_read_many(xs: Sequence, tag: Optional[str] = None) -> list:
    """Fetch MANY device scalars in ONE transfer round (a per-item
    device_get pays a blocking round trip each)."""
    xs = list(xs)
    host = [x for x in xs if isinstance(x, (int, float, bool))]
    if len(host) == len(xs):
        return xs
    import jax

    _trace("readback", tag)
    if tag is not None:
        m = _stage_metrics(tag)
        with m._lock:
            m.readbacks += 1
    if _tr.TRACER.enabled:
        with _tr.span("pipe.readback", tag=tag or "", n=len(xs)):
            return list(jax.device_get(xs))
    return list(jax.device_get(xs))


#: how long ReadbackFuture.result() waits for the harvester before the
#: wait counts as a BLOCKING sizing sync: scheduling jitter on a local
#: backend — including GC pauses and harvester-thread preemption under
#: a loaded process, which full-suite runs showed can exceed 5ms — is
#: under this, so the counter measures critical-path stalls, not
#: thread-scheduling noise
_HARVEST_GRACE_S = 0.025

_HARVESTER = None
_HARVESTER_LOCK = threading.Lock()


def _harvester():
    """ONE process-wide harvest pool (the readbacks it runs serialize on
    the device link anyway; per-call threads would leak)."""
    global _HARVESTER
    with _HARVESTER_LOCK:
        if _HARVESTER is None:
            from concurrent.futures import ThreadPoolExecutor

            _HARVESTER = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="tpu-pipe-harvest")
        return _HARVESTER


class ReadbackFuture:
    """A device->host readback in flight on the harvester thread — the
    speculative-sizing counterpart of :func:`device_read`: the exec
    dispatches work sized by a PREDICTION and reconciles with the true
    count when this resolves, so the sizing sync leaves the critical
    path entirely.  ``result()`` only counts as a blocking readback
    (trace event + stage counter) when the harvest genuinely was not
    finished — the zero-blocking-sync acceptance tests key off that."""

    __slots__ = ("_fut", "_value", "_tag", "_resolved")

    def __init__(self, fut, tag: Optional[str], value=None):
        self._fut = fut
        self._tag = tag
        self._value = value
        self._resolved = fut is None

    def done(self) -> bool:
        return self._resolved or self._fut.done()

    def result(self):
        if self._resolved:
            return self._value
        fut = self._fut
        if fut.done():
            v = fut.result()
        else:
            import concurrent.futures as _cf

            try:
                v = fut.result(timeout=_HARVEST_GRACE_S)
            except _cf.TimeoutError:
                # a real critical-path stall: account it like an inline
                # device_read so host_sync_count stays honest
                _trace("readback", self._tag)
                if self._tag is not None:
                    m = _stage_metrics(self._tag)
                    with m._lock:
                        m.readbacks += 1
                if _tr.TRACER.enabled:
                    with _tr.span("pipe.readback", tag=self._tag or "",
                                  blocking=True):
                        v = fut.result()
                else:
                    v = fut.result()
        self._value = v
        self._resolved = True
        self._fut = None
        return v


def device_read_async(x, tag: Optional[str] = None) -> ReadbackFuture:
    """Submit a device->host readback to the harvester thread and return
    a :class:`ReadbackFuture` — the future-style sibling of
    :func:`device_read` for speculative stream loops: dispatch at the
    predicted size NOW, reconcile with ``result()`` (usually already
    harvested) one batch later.  Host scalars resolve immediately."""
    if isinstance(x, (int, float, bool)):
        return ReadbackFuture(None, tag, value=x)
    import jax

    _trace("readback_async", tag)
    if tag is not None:
        m = _stage_metrics(tag)
        with m._lock:
            m.async_readbacks += 1
    return ReadbackFuture(_harvester().submit(jax.device_get, x), tag)


def pipelined(items: Iterable, dispatch: Callable[[Any], Any],
              retire: Callable[[Any], Optional[Iterable]],
              depth: Optional[int] = None,
              tag: Optional[str] = None) -> Iterator:
    """Software-pipeline a stream loop: ``dispatch(item)`` launches
    (async) device work and returns its in-flight state; ``retire``
    performs the blocking readback + output for the OLDEST state.  With
    depth >= 1, item k+1 is dispatched before item k retires, so JAX's
    async dispatch overlaps k+1's compute with k's readback wait.
    retire may return an iterable of outputs (yielded in stream order)
    or None.  depth defaults to the conf lookahead; 0 degenerates to
    the serial dispatch-then-retire order."""
    if depth is None:
        depth = readback_lookahead()
    depth = max(0, int(depth))
    pending: deque = deque()
    for item in items:
        pending.append(dispatch(item))
        _trace("dispatch", tag)
        while len(pending) > depth:
            out = retire(pending.popleft())
            if out is not None:
                yield from out
    while pending:
        out = retire(pending.popleft())
        if out is not None:
            yield from out


def _stage_checkpoint(stage: str) -> None:
    """The ``pipeline.stage`` fault seam, hit once per produced item ON
    the producer thread, with in-place bounded recovery: an INJECTED
    stage fault releases pressure and re-checks instead of tearing the
    stage down — only a persistent one re-raises at the consumer in
    stream order (the prefetch contract).  Real failures from the
    producer's own work (`gen`) keep that contract untouched: they
    re-raise at the consumer, whose recovery ladder owns them (the
    producer cannot re-run a generator it does not control).
    Disarmed, this is one global read per item."""
    from spark_rapids_tpu.robustness import faults as _faults

    attempts = 3
    caught = []
    for attempt in range(attempts):
        try:
            _faults.fault_point("pipeline.stage", stage=stage)
        except BaseException as e:  # noqa: BLE001 - classified below
            from spark_rapids_tpu.execs.retry import (
                is_retryable,
                release_pressure,
            )

            if not is_retryable(e) or attempt == attempts - 1:
                raise
            caught.append(e)
            release_pressure()
            continue
        for e in caught:
            _faults.note_recovered(e, action="stage_retry")
        return


# ------------------------------------------------------------------ #
# Bounded background stage
# ------------------------------------------------------------------ #


class _Chan:
    """Bounded channel with a condition-variable handshake (no poll
    loops anywhere): producer blocks in put() while full, consumer
    blocks in pop() while empty, and abort() wakes both sides
    immediately."""

    __slots__ = ("depth", "buf", "lock", "not_full", "not_empty",
                 "done", "aborted", "error")

    def __init__(self, depth: int):
        self.depth = depth
        self.buf: deque = deque()   # guard: lock
        self.lock = threading.Lock()
        # both conditions share the ONE channel lock (an alias group:
        # holding either holds `lock`; they differ only in who waits)
        self.not_full = threading.Condition(self.lock)
        self.not_empty = threading.Condition(self.lock)
        self.done = False           # guard: lock
        self.aborted = False        # guard: lock
        # `error` is deliberately NOT guarded: written under the lock
        # in finish(), read by the consumer only after pop() returned
        # (None, False) — the lock release/acquire pair orders the two
        self.error: Optional[BaseException] = None

    # producer side ---------------------------------------------------- #

    def put(self, item, m: StageMetrics) -> bool:
        """False once the consumer aborted (producer should stop).
        The full-queue wait is bounded and cancel-aware (SRC012): a
        cancelled query's producer raises out of the wait instead of
        blocking until a consumer that already unwound drains it."""
        from spark_rapids_tpu.serving import cancel as _cancel

        with self.not_full:
            if len(self.buf) >= self.depth and not self.aborted:
                t0 = time.perf_counter_ns()
                tok = _cancel.current_token()
                while len(self.buf) >= self.depth and not self.aborted:
                    self.not_full.wait(_cancel.poll_timeout(tok))
                    if tok is not None:
                        tok.check()
                dt = time.perf_counter_ns() - t0
                with m._lock:
                    m.producer_wait_ns += dt
                if _tr.TRACER.enabled:  # reuse the wait already timed
                    _tr.record_complete(f"pipe.{m.name}.wait_full",
                                        t0, dt, stage=m.name)
            if self.aborted:
                return False
            self.buf.append(item)
            if _tr.TRACER.enabled:
                _tr.event(f"pipe.{m.name}.enqueue", stage=m.name,
                          qlen=len(self.buf))
            self.not_empty.notify()
            return True

    def finish(self, error: Optional[BaseException]) -> None:
        with self.not_empty:
            self.error = self.error or error
            self.done = True
            self.not_empty.notify_all()

    # consumer side ---------------------------------------------------- #

    def pop(self, m: StageMetrics):
        """(item, True) or (None, False) when the stream ended."""
        with self.not_empty:
            # occupancy sampled BEFORE waiting, so an empty queue (a
            # starved stage) counts as 0 — sampling after the wait
            # would floor the metric at 1/depth and a fully serial
            # pipeline would read as half-full
            with m._lock:
                m.occupancy_sum += len(self.buf)
                m.samples += 1
            if not self.buf and not self.done:
                from spark_rapids_tpu.serving import cancel as _cancel

                t0 = time.perf_counter_ns()
                tok = _cancel.current_token()
                while not self.buf and not self.done:
                    # bounded, cancel-aware wait (SRC012): a cancelled
                    # consumer raises here; the enclosing prefetch's
                    # finally then aborts the stage and joins the
                    # producer, so nothing leaks
                    self.not_empty.wait(_cancel.poll_timeout(tok))
                    if tok is not None:
                        tok.check()
                dt = time.perf_counter_ns() - t0
                with m._lock:
                    m.consumer_wait_ns += dt
                if _tr.TRACER.enabled:
                    _tr.record_complete(f"pipe.{m.name}.wait_empty",
                                        t0, dt, stage=m.name)
            if self.buf:
                with m._lock:
                    m.items += 1
                item = self.buf.popleft()
                if _tr.TRACER.enabled:
                    _tr.event(f"pipe.{m.name}.dequeue", stage=m.name,
                              qlen=len(self.buf))
                self.not_full.notify()
                return item, True
            return None, False

    def abort(self) -> None:
        with self.lock:
            self.aborted = True
            self.buf.clear()
            self.not_full.notify_all()
            self.not_empty.notify_all()


def prefetch(gen: Iterable, depth: Optional[int] = None,
             stage: str = "stage") -> Iterator:
    """Run `gen` on a background thread behind a bounded queue so the
    producer's work overlaps the consumer's (one pipeline *stage*).

    Contracts:
    - order preserved; items should stay HOST-side unless the caller
      owns the device-memory budget for `depth` in-flight batches;
    - a producer exception is re-raised at the consumer, after the
      items produced before it;
    - closing the consumer generator (or leaving it via break/raise)
      aborts the stage: the producer wakes from any blocked put, its
      generator is closed ON the producer thread (finally blocks run
      there), and the thread is joined — a sentinel handshake, not a
      poll-drain;
    - the caller's thread-local conf snapshot is installed on the
      producer thread.

    depth defaults to the conf stage depth; depth <= 0 yields from
    `gen` inline (pipelining disabled)."""
    if depth is None:
        depth = stage_depth()
    if depth <= 0:
        yield from gen
        return
    from spark_rapids_tpu.serving import cancel as _cancel

    m = _stage_metrics(stage)
    with m._lock:
        m.depth = max(m.depth, depth)
    chan = _Chan(depth)
    conf = get_conf()
    # trace correlation (query_id, ...) is thread-local and does NOT
    # follow the generator onto the stage thread: capture here, attach
    # there — the same hop the conf snapshot makes.  The query's
    # cancel token rides the same capture/attach channel, so the
    # producer observes cancellation mid-decode, not only at the
    # channel boundary
    tctx = _tr.current_context()
    ctok = _cancel.current_token()

    def produce() -> None:
        err: Optional[BaseException] = None
        set_conf(conf)
        with _tr.attach_context(tctx), _cancel.attach_token(ctok), \
                _tr.span(f"pipe.{stage}.run", stage=stage):
            try:
                try:
                    for item in gen:
                        _stage_checkpoint(stage)
                        _cancel.check_point()
                        if not chan.put(item, m):
                            return
                except BaseException as e:  # noqa: BLE001 — re-raised at consumer
                    err = e
            finally:
                close = getattr(gen, "close", None)
                if close is not None:
                    try:
                        close()
                    except BaseException as e:  # noqa: BLE001
                        err = err or e
                chan.finish(err)

    t = threading.Thread(target=produce, daemon=True,
                         name=f"tpu-pipe-{stage}")
    t.start()
    try:
        while True:
            item, ok = chan.pop(m)
            if not ok:
                break
            yield item
        if chan.error is not None:
            raise chan.error
    finally:
        chan.abort()
        t.join()
