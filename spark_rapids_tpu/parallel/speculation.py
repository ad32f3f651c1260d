"""Speculative output sizing: predict data-dependent output counts so
the aggregate's and the exchange's stream loops do not block on a
per-batch sizing readback.

The grouped Q1 and every hash exchange keep one structural
serialization: the per-batch device->host SIZING sync (aggregate
partial row count, exchange split counts) that the software pipeline
can only defer by a single batch.  The OOM-retry framework
(RmmRapidsRetryIterator.scala ``withRetry``, mirrored by
``execs/retry.py``) is the repo's blessed "guess, then recover" shape,
and this module is that pattern for sizing:

- :class:`SizePredictor` — per program key, the largest of the last
  observed output counts (keyed by the same structural key
  ``jit_cache.cached_jit`` uses), scaled by a safety factor and
  clamped to pow2 capacity buckets, with a conservative
  sync-on-first-batches warm-up;
- the aggregate registers a big partial unshrunk, runs its merge
  bookkeeping on the predicted estimate and harvests the true count
  asynchronously (``parallel.pipeline.device_read_async``); the drain
  reconciles.  The exchange's map loop harvests its split counts the
  same way and registers slices as they arrive.

The JOIN does not speculate (PR 35): it reads each stream batch's pair
count behind the next batch's probe and expands at the count's own
bucket (``execs/join.py``).  An expansion costs by the capacity it runs
at, and a bucket guessed at 1.5 x the count was one too large for every
count over two thirds of its bucket: 0.4-1.3 s a join on a v5e against
some 2 ms a readback.

Hit/overflow counters feed ``bench.py``'s
``q*_speculation_hit_rate`` fields and the aggregate's
``specHits``/``specOverflows`` metrics shown by
``df.explain("analyze")``; ``speculation.hit``/``speculation.overflow``
instants land on the structured trace timeline.  Docs:
``docs/speculation.md``.
"""

from __future__ import annotations

import collections
import threading
from typing import Optional

from spark_rapids_tpu import trace as _tr
from spark_rapids_tpu.config import get_conf, register
from spark_rapids_tpu.parallel import pipeline as _P

SPECULATION_ENABLED = register(
    "spark.rapids.tpu.sql.speculation.enabled", True,
    "Enable speculative output sizing: aggregates keep a big partial "
    "unshrunk and run their merge bookkeeping on a predicted row count "
    "(per program key, from the largest recent count), exchanges "
    "register their slices as split counts arrive; both harvest the "
    "true count asynchronously instead of blocking on a per-batch "
    "device->host sizing readback.  An overshoot costs dead padded "
    "rows until the drain, an undershoot one merge a batch late.")

SPECULATION_SAFETY_FACTOR = register(
    "spark.rapids.tpu.sql.speculation.safetyFactor", 1.5,
    "Multiplier applied to the largest recent output count before pow2 "
    "bucket clamping.  Larger values trade dead padded rows in an "
    "aggregate's pending partials for fewer undershoots (a merge "
    "triggered a batch late).",
    check=lambda v: v >= 1.0)

SPECULATION_WARMUP_BATCHES = register(
    "spark.rapids.tpu.sql.speculation.warmupBatches", 1,
    "Observed batches per program key before the predictor speculates; "
    "warm-up batches pay the conservative blocking sizing sync and "
    "seed the predictor.",
    check=lambda v: v >= 1)

SPECULATION_TEST_FORCE_CAPACITY = register(
    "spark.rapids.tpu.sql.speculation.testForceCapacity", 0,
    "Test aid: when > 0, a warmed-up predictor returns exactly this "
    "capacity bucket instead of its observed one (forces the "
    "under-/over-speculation paths deterministically).",
    internal=True)

SPECULATION_ADAPTIVE_MIN_HIT_RATE = register(
    "spark.rapids.tpu.sql.speculation.adaptive.minHitRate", 0.0,
    "Adaptive kill-switch: when > 0, a predictor TAG (agg.size) "
    "whose rolling hit rate over the last "
    "speculation.adaptive.window outcomes falls below this is "
    "auto-DISABLED for the rest of the process (or until "
    "reset_stats) — its execs revert to the conservative blocking "
    "sizing sync.  BISECT_q3_r07's conviction: a workload whose output "
    "counts the predictor cannot track pays for every miss, and turning "
    "speculation off recovered 1.294x on q3 (through the join, which "
    "has since stopped guessing).  The "
    "disable lands as a speculation.disabled event-log counter and a "
    "speculation.disabled trace instant; 0.0 = never disable.",
    check=lambda v: 0.0 <= v <= 1.0)

SPECULATION_ADAPTIVE_WINDOW = register(
    "spark.rapids.tpu.sql.speculation.adaptive.window", 16,
    "Rolling outcome-window length per predictor tag for the adaptive "
    "kill-switch: the hit rate is judged only once this many "
    "speculative dispatches (hits + overflows) have been observed, so "
    "one unlucky warm-up batch cannot convict a tag.",
    check=lambda v: v >= 2)

#: observations a predictor remembers.  It predicts from the LARGEST
#: of them: a function of which counts were seen and not of the order
#: they came in, so a query run again predicts what it predicted the
#: round before whatever order its tasks ran in.  (An average leaning
#: to the newest count moves with that order, and crosses a bucket's
#: edge from round to round where two tasks' counts lie either side of
#: it.)  Long enough to hold a round of one operator's batches, short
#: enough to follow a shift of selectivity
_WINDOW = 16


def speculation_enabled(conf=None) -> bool:
    conf = conf or get_conf()
    return bool(conf.get(SPECULATION_ENABLED))


class SizePredictor:
    """The last `_WINDOW` observed output counts of ONE program key,
    and their largest.  Thread-safe: an aggregate's tasks observe
    concurrently."""

    __slots__ = ("key", "recent", "observations", "_lock")

    def __init__(self, key):
        self.key = key
        self.recent: "collections.deque" = collections.deque(
            maxlen=_WINDOW)
        self.observations = 0
        self._lock = threading.Lock()

    def observe(self, n: int) -> None:
        with self._lock:
            self.observations += 1
            self.recent.append(int(n))

    def predict(self, conf=None,
                cap_ceiling: Optional[int] = None) -> Optional[int]:
        """Speculated pow2 capacity bucket, or None during warm-up (the
        caller then pays the conservative blocking sizing sync)."""
        from spark_rapids_tpu.columnar.column import pad_capacity

        conf = conf or get_conf()
        with self._lock:
            obs = self.observations
            largest = max(self.recent, default=0)
        if obs < int(conf.get(SPECULATION_WARMUP_BATCHES)):
            return None
        forced = int(conf.get(SPECULATION_TEST_FORCE_CAPACITY))
        if forced > 0:
            cap = pad_capacity(forced)
        else:
            est = largest * float(conf.get(SPECULATION_SAFETY_FACTOR))
            cap = pad_capacity(max(1, int(est)))
        if cap_ceiling is not None:
            cap = min(cap, cap_ceiling)
        return cap


#: LRU like jit_cache's MAX_ENTRIES: a long-lived process serving many
#: distinct ad-hoc query shapes must not pin one predictor per key
#: forever (the key space is the compile-cache key space)
_PREDICTORS: "collections.OrderedDict" = collections.OrderedDict()
MAX_PREDICTORS = 512
_PRED_LOCK = threading.Lock()


def predictor(key) -> SizePredictor:
    """Get-or-create the process-global predictor for a structural
    program key (the jit_cache key discipline: two execs whose sizing
    is determined by equal expression trees/specs share one)."""
    with _PRED_LOCK:
        p = _PREDICTORS.get(key)
        if p is None:
            p = _PREDICTORS[key] = SizePredictor(key)
            while len(_PREDICTORS) > MAX_PREDICTORS:
                _PREDICTORS.popitem(last=False)
        else:
            _PREDICTORS.move_to_end(key)
        return p


def reset_predictors() -> None:
    """Drop every predictor (test isolation)."""
    with _PRED_LOCK:
        _PREDICTORS.clear()


# ------------------------------------------------------------------ #
# Hit/overflow accounting (bench.py + explain("analyze") source)
# ------------------------------------------------------------------ #

_STATS: dict[str, dict] = {}
_STATS_LOCK = threading.Lock()

#: per-tag rolling outcome window (True = hit) for the adaptive
#: kill-switch, plus the set of convicted tags
_WINDOWS: dict[str, "collections.deque"] = {}
_DISABLED: set[str] = set()
_DISABLED_TOTAL = 0


def _stat(tag: str) -> dict:
    s = _STATS.get(tag)
    if s is None:
        s = _STATS[tag] = {"hits": 0, "overflows": 0, "synced": 0}
    return s


def _observe_outcome_locked(tag: str, hit: bool) -> bool:
    """Feed the tag's rolling window; returns True when this outcome
    just convicted the tag (caller emits the events OUTSIDE the
    lock).  Caller holds _STATS_LOCK."""
    global _DISABLED_TOTAL
    conf = get_conf()
    min_rate = float(conf.get(SPECULATION_ADAPTIVE_MIN_HIT_RATE))
    if min_rate <= 0.0 or tag in _DISABLED:
        return False
    window = int(conf.get(SPECULATION_ADAPTIVE_WINDOW))
    w = _WINDOWS.get(tag)
    if w is None or w.maxlen != window:
        w = _WINDOWS[tag] = collections.deque(w or (), maxlen=window)
    w.append(hit)
    if len(w) < window:
        return False
    if sum(w) / float(window) >= min_rate:
        return False
    _DISABLED.add(tag)
    _DISABLED_TOTAL += 1
    return True


def _note_disabled(tag: str, rate: float) -> None:
    _P._trace("spec_disabled", tag)
    if _tr.TRACER.enabled:
        _tr.event("speculation.disabled", tag=tag, hit_rate=rate)


def record_hit(tag: str, cap: int = 0, actual: int = 0) -> None:
    """The speculated capacity covered the true count: the batch ran
    with ZERO blocking sizing syncs."""
    with _STATS_LOCK:
        _stat(tag)["hits"] += 1
        tripped = _observe_outcome_locked(tag, True)
    _P._trace("spec_hit", tag)
    if _tr.TRACER.enabled:
        _tr.event("speculation.hit", tag=tag, cap=cap, actual=actual)
    if tripped:
        _note_disabled(tag, hit_rate((tag,)))


def record_overflow(tag: str, cap: int = 0, actual: int = 0) -> None:
    """Undershoot: the true count passed the estimate the exec ran
    on (no rollback: the drain reconciles)."""
    with _STATS_LOCK:
        _stat(tag)["overflows"] += 1
        tripped = _observe_outcome_locked(tag, False)
    _P._trace("spec_overflow", tag)
    if _tr.TRACER.enabled:
        _tr.event("speculation.overflow", tag=tag, cap=cap,
                  actual=actual)
    if tripped:
        _note_disabled(tag, hit_rate((tag,)))


def record_sync(tag: str) -> None:
    """A conservative blocking sizing sync (warm-up batch)."""
    with _STATS_LOCK:
        _stat(tag)["synced"] += 1


def tag_enabled(tag: str) -> bool:
    """False once the adaptive kill-switch convicted this tag — the
    exec should skip predictor creation / speculation and pay the
    blocking sizing sync (which the kill-switch has just proven
    cheaper than the misses)."""
    with _STATS_LOCK:
        return tag not in _DISABLED


def disabled_tags() -> list[str]:
    """Tags the adaptive kill-switch has disabled, sorted (bench.py's
    ``q*_speculation_disabled`` field)."""
    with _STATS_LOCK:
        return sorted(_DISABLED)


def disabled_total() -> int:
    """Cumulative count of kill-switch trips this process (the
    ``speculation.disabled`` event-log counter; monotonic across
    reset_stats like every other eventlog counter source is NOT —
    this one survives reset_stats precisely so per-query deltas in
    the event log attribute the trip to the query that caused it)."""
    with _STATS_LOCK:
        return _DISABLED_TOTAL


def stats() -> dict[str, dict]:
    """Per-tag {hits, overflows, synced} counters since the last
    reset."""
    with _STATS_LOCK:
        return {k: dict(v) for k, v in _STATS.items()}


def reset_stats() -> None:
    """bench.py resets between benchmark queries so hit rates report
    PER QUERY (the reset_stage_counters discipline).  Also re-arms the
    adaptive kill-switch (windows + convicted tags) so one query's
    conviction does not bleed into the next query's measurement; the
    cumulative ``disabled_total`` survives so event-log deltas stay
    monotonic."""
    with _STATS_LOCK:
        _STATS.clear()
        _WINDOWS.clear()
        _DISABLED.clear()


def hit_rate(tags=None) -> float:
    """Fraction of speculative dispatches whose capacity covered the
    true count, over `tags` (default: all)."""
    snap = stats()
    hits = ovf = 0
    for tag, s in snap.items():
        if tags is not None and tag not in tags:
            continue
        hits += s["hits"]
        ovf += s["overflows"]
    total = hits + ovf
    return round(hits / total, 3) if total else 0.0
