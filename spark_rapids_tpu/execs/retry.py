"""Task failure detection and recovery.

The reference leans on two layers the TPU runtime must reproduce
itself (SURVEY.md §5.3): Spark's task re-execution (deterministic
lineage — a failed task re-runs from its inputs) and the plugin's
OOM-retry framework (ref: RmmRapidsRetryIterator.scala `withRetry` /
`withRetryNoSplit` — release what the task holds, spill, and
split-and-retry the input batch on GPU OOM).

TPU analog — an ESCALATION LADDER, cheapest rung first:

1. `run_with_oom_retry(fn)`: spill every unpinned device buffer and
   re-run the closure (the withRetryNoSplit shape, for restartable
   non-streaming work: a merge drain, an H2D upload, a compile).
2. `with_split_retry(run, batch)`: the batch-granular rung threaded
   through the join/aggregate/sort/exchange stream loops — on a
   retryable failure, spill + re-run the batch; on a second failure,
   BISECT the batch (via SpillableBatch, down to
   `spark.rapids.tpu.task.retry.minSplitRows`) and process the halves
   recursively (the withRetry + splitSpillableInHalfByRows shape).
3. `with_task_retries(fn)`: whole-task re-run from lineage (the
   spark.task.maxFailures analog), with jittered doubling backoff so
   concurrent sessions retrying the same pressure event don't
   stampede in lockstep.
4. `should_cpu_fallback(exc)`: per-query degrade to the CPU engine
   (the sick-executor blacklisting analog, applied in session.py).

- `classify(exc)` / `is_retryable(exc)`: device/transient failures
  (a RESOURCE_EXHAUSTED allocation at run time, UNAVAILABLE/
  DEADLINE_EXCEEDED link hiccups, connection resets, our own
  reservation failures) are RETRYABLE; everything else (assertion,
  user error, and a RESOURCE_EXHAUSTED raised while COMPILING — the
  same program overruns the same memory every time) fails fast.  tpulint SRC008
  flags broad `except` clauses in execs//io//shuffle/ that swallow
  exceptions without consulting this gate.
- every rung reports absorbed injected faults to
  robustness.faults.note_recovered, and process-global counters
  (`retry_stats()`) feed the bench `*_retry_splits` /
  `*_spills_under_pressure` fields.
- tasks that produce shuffle output buffer it locally and COMMIT
  atomically at task end (exchange.py) so a failed attempt leaves no
  partial blocks behind — the MapStatus commit protocol.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Iterator, Optional, TypeVar

from spark_rapids_tpu.config import register, get_conf

TASK_MAX_FAILURES = register(
    "spark.rapids.tpu.task.maxFailures", 3,
    "Attempts per deterministic task before the failure propagates "
    "(the spark.task.maxFailures analog).")

CPU_FALLBACK_ON_DEVICE_ERROR = register(
    "spark.rapids.tpu.sql.recovery.cpuFallbackOnDeviceError", True,
    "After task retries are exhausted on a DEVICE/transient error, "
    "re-run the whole query on the CPU engine instead of failing it "
    "(the sick-executor blacklisting analog).")

RETRY_BACKOFF_S = register(
    "spark.rapids.tpu.task.retryBackoffSeconds", 0.2,
    "Base sleep between task attempts (doubles per attempt, with "
    "+-50% jitter so concurrent sessions retrying the same pressure "
    "event spread out instead of stampeding in lockstep).")

SPLIT_RETRY_ENABLED = register(
    "spark.rapids.tpu.task.retry.splitEnabled", True,
    "On a second OOM for the same stream batch (after one "
    "spill-and-retry), bisect the batch and process the halves "
    "recursively instead of failing the task (the split-and-retry of "
    "the reference's RmmRapidsRetryIterator.withRetry).")

SPLIT_MIN_ROWS = register(
    "spark.rapids.tpu.task.retry.minSplitRows", 1024,
    "Floor for batch bisection: a batch at or below this many rows is "
    "never split further — the failure escalates to the whole-task "
    "retry (and ultimately the per-query CPU fallback) instead.",
    check=lambda v: v >= 1)

#: substrings that mark a failure raised WHILE COMPILING a program: an
#: XLA or Mosaic compile that overruns HBM or VMEM also says
#: RESOURCE_EXHAUSTED, but the same program overruns the same memory on
#: every attempt — spilling, splitting and backing off change nothing,
#: and a CPU degrade would answer the query while the chip did none of
#: it.  Checked BEFORE the retryable markers; such an error is fatal
#: and propagates.  On a v5e (libtpu 0.0.34) a Pallas kernel over its
#: VMEM says "RESOURCE_EXHAUSTED: Ran out of memory in memory space
#: vmem while allocating on stack for %tpu_custom_call..."; XLA:TPU
#: prefixes its own with "XLA:TPU compile permanent error".  What an
#: allocation that fails at RUN time says there is "Error allocating
#: device buffer: Attempting to allocate 1.00G. That was not
#: possible.", which matches neither.
_COMPILE_MARKERS = (
    "compile permanent error",
    "Ran out of memory in memory space",
)

#: substrings of device/transient error text that justify a retry.
#: Deliberately NOT "INTERNAL": compiler/unsupported-HLO failures are
#: deterministic INTERNAL errors — retrying them wastes backoff and a
#: CPU degrade would hide the bug from users and CI.
_RETRYABLE_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "OutOfMemory",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "Socket closed",
    "connection reset",
    "Connection reset",
    "ECONNRESET",
)

T = TypeVar("T")

#: jittered backoff RNG — deliberately unseeded state per process (the
#: whole point is that two processes sleep different amounts)
_JITTER = random.Random()

# -- recovery observability ------------------------------------------- #

_STATS_LOCK = threading.Lock()
_STATS = {"splits": 0, "spill_retries": 0, "task_retries": 0,
          "cpu_fallbacks": 0}


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def retry_stats() -> dict:
    """Process-global recovery counters: {splits, spill_retries,
    task_retries, cpu_fallbacks}."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_retry_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def is_retryable(exc: BaseException) -> bool:
    """Device / transient failure (retry may succeed) vs logic error
    (fail fast)."""
    from spark_rapids_tpu.serving.cancel import QueryCancelled

    if isinstance(exc, QueryCancelled):
        # cancellation/deadline is a VERDICT, not a fault: no retry,
        # no split, no CPU degrade — the query unwinds (its message
        # must never be marker-matched into a retry)
        return False
    if isinstance(exc, MemoryError):
        return True
    from spark_rapids_tpu.shuffle.net import FetchFailedError

    if isinstance(exc, FetchFailedError):
        # remote shuffle peer died mid-fetch: the retried attempt
        # re-resolves peers (the FetchFailedException contract)
        return True
    if isinstance(exc, RuntimeError):  # XlaRuntimeError subclasses it
        text = str(exc)
        if any(m in text for m in _COMPILE_MARKERS):
            return False
        return any(m in text for m in _RETRYABLE_MARKERS)
    return False


def classify(exc: BaseException) -> str:
    """'retryable' | 'fatal' — the single classification gate every
    recovery path must consult before absorbing an exception (tpulint
    SRC008 flags broad except clauses in execs//io//shuffle/ that
    swallow without routing through here)."""
    return "retryable" if is_retryable(exc) else "fatal"


def _release_pressure() -> None:
    """Free what this process can before a retry attempt — the
    spill-everything step of the reference's retry framework."""
    try:
        from spark_rapids_tpu.memory import get_store

        get_store().spill_all_unpinned()
    except Exception as e:  # noqa: BLE001 — best-effort pressure relief
        classify(e)  # a failed spill never masks the original error
    import gc

    gc.collect()


#: public alias for the fault sites that recover in place
release_pressure = _release_pressure


def _sleep_backoff(base: float, attempt: int) -> None:
    """Doubling backoff with +-50% jitter (decorrelates concurrent
    sessions retrying the same pressure event)."""
    if base <= 0:
        return
    time.sleep(base * (2 ** attempt) * (0.5 + _JITTER.random()))


def _note_recovered_all(caught: list, action: str) -> None:
    from spark_rapids_tpu.robustness import faults as _faults

    for e in caught:
        _faults.note_recovered(e, action=action)


def absorb_once(fn: Callable[[], T], action: str) -> T:
    """THE in-place recovery shape shared by the fault seams (upload,
    compile): run the restartable closure; on ONE retryable failure
    release pressure (spill everything unpinned), re-run, and credit
    the absorbed fault; a second failure escalates to the ladder /
    task retry / CPU degrade."""
    try:
        return fn()
    except BaseException as e:  # noqa: BLE001 - classified below
        if not is_retryable(e):
            raise
        _release_pressure()
        out = fn()
        from spark_rapids_tpu.robustness import faults as _faults

        _faults.note_recovered(e, action=action)
        return out


def _retry_loop(fn: Callable[[], T], stat_key: str, action: str,
                attempts: Optional[int] = None) -> T:
    """The one release-pressure retry loop behind both the spill rung
    and the whole-task rung: classify, count, spill everything
    unpinned, jittered doubling backoff, credit absorbed injected
    faults on eventual success."""
    from spark_rapids_tpu.serving.cancel import check_point

    conf = get_conf()
    attempts = attempts if attempts is not None \
        else max(1, conf.get(TASK_MAX_FAILURES))
    backoff = conf.get(RETRY_BACKOFF_S)
    caught: list[BaseException] = []
    for attempt in range(attempts):
        try:
            out = fn()
        except BaseException as e:  # noqa: BLE001 - classified below
            if not is_retryable(e) or attempt == attempts - 1:
                raise
            # a cancelled query must not burn backoff sleeps and
            # re-attempts on work nobody will consume
            check_point()
            caught.append(e)
            _bump(stat_key)
            _release_pressure()
            _sleep_backoff(backoff, attempt)
            continue
        if caught:
            _note_recovered_all(caught, action)
        return out
    raise caught[-1]  # unreachable; keeps type checkers honest


def with_task_retries(fn: Callable[[], T], desc: str = "task") -> T:
    """Run a deterministic task closure with device-error retries.
    The closure must be safe to re-run from scratch (lineage: pure
    function of its exec-tree inputs)."""
    return _retry_loop(fn, "task_retries", f"task_retry:{desc}")


def run_with_oom_retry(fn: Callable[[], T], desc: str = "op",
                       attempts: Optional[int] = None) -> T:
    """Spill-and-retry a RESTARTABLE closure (rung 1 of the ladder, the
    withRetryNoSplit shape): on a retryable failure, release pressure
    (spill every unpinned buffer) and re-run.  The closure must have no
    partial externally-visible effects — callers keep their own state
    in closures so a re-run resumes instead of redoing (see the
    aggregate's merge drain)."""
    return _retry_loop(fn, "spill_retries", f"spill_retry:{desc}",
                       attempts)


# -- batch bisection --------------------------------------------------- #


def _desharded(batch):
    """Re-place a batch whose leaves are mesh-sharded (or scattered
    across devices) onto ONE device before the ladder's row-indexed
    gathers: bisection slices leaf-by-leaf with plain `gather`/`slice`
    ops that assume fully-addressable single-device arrays, and a
    multi-device leaf would either fail the trace or silently gather a
    single shard's rows.  The move routes through
    parallel/placement.adopt_batch — the single device_put choke point
    (SRC016) — so it shows up in the placement counters instead of
    vanishing into an untracked transfer."""
    import jax

    target = None
    for c in getattr(batch, "columns", ()):
        for leaf in jax.tree_util.tree_leaves(c):
            if isinstance(leaf, jax.Array):
                try:
                    devs = leaf.devices()
                except Exception:
                    continue
                if len(devs) > 1:
                    target = sorted(devs, key=lambda d: d.id)[0]
                    break
        if target is not None:
            break
    if target is None:
        return batch
    from spark_rapids_tpu.parallel import placement as _placement

    return _placement.adopt_batch(batch, target)


def bisect_batch(batch):
    """Split a device batch into (first_half, second_half) along the
    row axis.  Runs only on the failure path (after a spill), so the
    sizing sync and the eager gathers are off the happy path by
    construction.  EncodedBatch inputs decode first (splitting wire
    components is plan-specific; the decoded form is universal).

    A COALESCED batch (TpuCoalesceBatchesExec output, carrying
    `coalesce_seams`) splits at the seam boundary nearest the midpoint
    instead of n//2, and each half inherits its side's seams: the retry
    ladder walks a coalesced batch back down the producer's original
    batch granularity, so the bucket shapes the recovery dispatches at
    are ones the compile cache has already seen."""
    import dataclasses

    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import pad_capacity
    from spark_rapids_tpu.columnar.transfer import EncodedBatch

    seams = getattr(batch, "coalesce_seams", None)
    if isinstance(batch, EncodedBatch):
        # a consumed (donated) batch has no device buffers left to
        # split; decode_now refuses it with ConsumedBatchError
        # (non-retryable) — callers gate on _batch_rows first, so the
        # ladder escalates instead of bisecting freed HBM
        batch = batch.decode_now()
    batch = _desharded(batch)
    n = batch.concrete_num_rows()
    assert n >= 2, f"cannot bisect a {n}-row batch"
    batch = dataclasses.replace(batch, num_rows=n)
    lo = n // 2
    first_seams = second_seams = None
    if seams and len(seams) >= 2 and sum(seams) == n:
        offs, acc = [], 0
        for s in seams[:-1]:
            acc += s
            offs.append(acc)
        cut = min(offs, key=lambda o: abs(o - lo))
        if 0 < cut < n:
            lo = cut
            k = offs.index(cut) + 1
            first_seams, second_seams = seams[:k], seams[k:]
    first = batch.slice_prefix(lo).shrink_to_capacity(pad_capacity(lo))
    cap = batch.capacity
    # gather DIRECTLY at the half's padded capacity: this path runs
    # precisely because the device is out of memory, so a full-capacity
    # gather followed by a shrink (an up-to-2x transient per column)
    # could re-OOM the recovery rung itself
    out_cap = pad_capacity(n - lo)
    idx = jnp.minimum(jnp.arange(out_cap, dtype=jnp.int32) + lo,
                      cap - 1)
    cols = [c.gather(idx) for c in batch.columns]
    live = jnp.arange(out_cap, dtype=jnp.int32) < (n - lo)
    cols = [c.with_validity(c.validity & live) for c in cols]
    second = ColumnarBatch(cols, n - lo, batch.schema)
    if first_seams and len(first_seams) >= 2:
        first.coalesce_seams = first_seams
    if second_seams and len(second_seams) >= 2:
        second.coalesce_seams = second_seams
    return first, second


def _batch_rows(batch) -> Optional[int]:
    """Concrete row count for split decisions; None when even the
    readback fails (then splitting is off the table anyway).
    EncodedBatch (the encoded scan path — the aggregate's primary
    input) carries a host-known count, or exposes it as its wire `n`
    component."""
    try:
        from spark_rapids_tpu.columnar.transfer import EncodedBatch

        if isinstance(batch, EncodedBatch):
            if batch.consumed:
                # donated into a fused program: its buffers are gone,
                # so bisection is off the table — rows=None keeps the
                # ladder on the retry/escalate rungs, which resume
                # from the memoized program output (run_consuming)
                # without touching the consumed buffer
                return None
            if batch.num_rows is not None:
                return int(batch.num_rows)
            from spark_rapids_tpu.parallel.pipeline import (
                device_read_int,
            )

            return device_read_int(batch.live_count, tag="retry.size")
        return batch.concrete_num_rows()
    except Exception as e:  # noqa: BLE001 — split gating only
        classify(e)
        return None


def with_split_retry(run, batch, desc: str = "batch",
                     first_attempt=None, initial_error=None,
                     _depth: int = 0) -> Iterator:
    """THE batch-granular escalation ladder (generator), threaded
    through the streaming loops of join/aggregate/sort/exchange.

    ``run(batch)`` processes one input batch and returns an iterable of
    output chunks (or an empty iterable for sink-style loops); it must
    roll back its own partial side effects when it raises, so a re-run
    is clean.  On a retryable failure with nothing yielded yet:

    1. spill every unpinned device buffer and re-run the batch;
    2. on a second failure, BISECT the batch and recurse on the halves
       (each parked spillably while the other runs), down to
       spark.rapids.tpu.task.retry.minSplitRows;
    3. at the floor (or once output already streamed downstream, where
       a re-run would duplicate rows), re-raise — the whole-task retry
       and per-query CPU fallback rungs take over.

    ``first_attempt`` lets a software-pipelined caller hand in the
    already-dispatched in-flight state for attempt zero (PR4's
    speculative dispatch): if that attempt fails, the speculated chunk
    is discarded and retries RE-DISPATCH from the input batch — at the
    split size after a bisect — so no predictor entry leaks.
    ``initial_error`` seeds the ladder with a failure that happened at
    dispatch time, before any attempt could run here."""
    from spark_rapids_tpu.robustness import faults as _faults
    from spark_rapids_tpu.serving.cancel import check_point

    conf = get_conf()
    attempts = max(1, conf.get(TASK_MAX_FAILURES))
    backoff = conf.get(RETRY_BACKOFF_S)
    min_rows = conf.get(SPLIT_MIN_ROWS)
    split_on = conf.get(SPLIT_RETRY_ENABLED)
    caught: list[BaseException] = []
    failures = 0
    action = "spill_retry"
    if initial_error is not None:
        caught.append(initial_error)
        failures = 1
        _bump("spill_retries")
        _release_pressure()
        _sleep_backoff(backoff, 0)  # same decorrelation as every rung
    while True:
        emitted = False
        try:
            _faults.fault_point("exec.batch", desc=desc)
            it = first_attempt() if first_attempt is not None \
                else run(batch)
            first_attempt = None
            if it is not None:
                for out in it:
                    emitted = True
                    yield out
            break  # success
        except BaseException as e:  # noqa: BLE001 - classified below
            first_attempt = None
            if not is_retryable(e) or emitted:
                # output already streamed downstream: a re-run would
                # duplicate rows — escalate to the task/query rungs
                raise
            failures += 1
            caught.append(e)
            # between rungs: a cancelled query escalates OUT of the
            # ladder instead of spilling/splitting for nobody
            check_point()
            if failures == 1:
                # rung 1: release pressure, retry at full size
                _bump("spill_retries")
                _release_pressure()
                _sleep_backoff(backoff, 0)
                continue
            rows = _batch_rows(batch) if split_on else None
            if rows is not None and rows >= 2 and rows > min_rows \
                    and _depth < 32:
                # rung 2: bisect and recurse — each half re-enters the
                # ladder with its own spill/split budget
                _bump("splits")
                action = "split"
                _release_pressure()
                for half in _split_spillable(batch):
                    yield from with_split_retry(
                        run, half, desc=desc, _depth=_depth + 1)
                break
            if failures < attempts:
                _release_pressure()
                _sleep_backoff(backoff, failures - 1)
                continue
            raise
    if caught:
        _note_recovered_all(caught, f"{action}:{desc}")


def _split_spillable(batch):
    """Bisect, parking the second half as a SpillableBatch while the
    first half processes (under the very pressure that forced the
    split, holding both halves device-resident un-spillably would
    defeat the point).  Registration failures degrade to processing
    the half directly — the split itself must never make things
    worse."""
    first, second = bisect_batch(batch)
    handle = None
    try:
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        handle = get_store().register(
            second, SpillPriorities.ACTIVE_ON_DECK)
        handle.unpin()
    except Exception as e:  # noqa: BLE001 — parking is best-effort
        classify(e)
        handle = None
    try:
        yield first
        if handle is not None:
            try:
                second = handle.get()
            finally:
                # close AFTER get: the entry may have spilled; get()
                # re-materialized it and the batch now owns the arrays
                handle.close()
            handle = None
        yield second
    finally:
        # abandoned between yields (first half's ladder re-raised, or
        # a LIMIT stopped consuming): the parked registration must not
        # outlive the generator in the process-global store
        if handle is not None:
            handle.close()


def guarded_pipeline(dispatch, retire, desc: str, after=None):
    """Wire a pipelined dispatch/retire stream loop into the split
    ladder: returns (dispatch_guarded, retire_guarded) for
    parallel.pipeline.pipelined.  A dispatch-time retryable failure is
    carried into the ladder as its first failure; a retire-time
    failure discards the in-flight entry and re-dispatches from the
    input batch (at the split size after a bisect).  `retire` must
    roll back its own partial side effects when it raises.  `after`,
    when given, runs once per input batch after its ladder unit
    completes (the exchange's opportunistic in-flight drain — work
    that must stay OUTSIDE the ladder because its items are their own
    retry transactions)."""
    def dispatch_guarded(batch):
        try:
            return ("ok", dispatch(batch), batch, None)
        except BaseException as e:  # noqa: BLE001 - classified below
            if not is_retryable(e):
                raise
            return ("failed", None, batch, e)

    def rerun(b):
        return retire(dispatch(b))

    def retire_guarded(tagged):
        kind, entry, batch, err = tagged
        if kind == "ok":
            gen = with_split_retry(rerun, batch, desc=desc,
                                   first_attempt=lambda: retire(entry))
        else:
            gen = with_split_retry(rerun, batch, desc=desc,
                                   initial_error=err)
        if after is None:
            return gen

        def with_after():
            yield from gen
            after()

        return with_after()

    return dispatch_guarded, retire_guarded


def note_cpu_fallback(exc: BaseException) -> None:
    """Account a query-level CPU degrade (the ladder's last rung):
    ticks the public cpu_fallbacks counter and credits an injected
    fault's site if one is in the cause chain."""
    _bump("cpu_fallbacks")
    from spark_rapids_tpu.robustness import faults as _faults

    _faults.note_recovered(exc, action="cpu_fallback")


def should_cpu_fallback(exc: BaseException) -> bool:
    """After retries: degrade the query to the CPU engine?"""
    return get_conf().get(CPU_FALLBACK_ON_DEVICE_ERROR) \
        and is_retryable(exc)
