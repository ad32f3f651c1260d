"""Multi-tenant query-serving tier: concurrent sessions, one device.

Everything below this package was one-session-one-query; this is the
production front the ROADMAP's "heavy traffic from millions of users"
north star asks for (open item #4), built from four pieces:

- **admission control** (:mod:`serving.scheduler`): a
  :class:`~spark_rapids_tpu.serving.scheduler.QueryScheduler` gating
  query execution on the device's concurrency budget — the same permit
  count :class:`~spark_rapids_tpu.memory.semaphore.TpuSemaphore` guards
  batch residency with — using per-tenant weighted-fair + priority
  queues, a bounded admission queue with rejection, and the admission
  wait recorded as a ``serve.admit`` span plus per-query event-log
  counters;
- a **prepared-statement / plan cache** (:mod:`serving.plan_cache`,
  :mod:`serving.prepared`): ``session.prepare(df)`` /
  ``SqlSession.prepare(sql)`` return a
  :class:`~spark_rapids_tpu.serving.prepared.PreparedQuery` keyed on
  the event log's plan-fingerprint idea + the jit_cache structural
  expression keys, so a repeated template with bound parameters skips
  parse -> plan -> tag -> lower entirely and re-drains the cached
  lowered exec tree;
- **streaming result fetch**
  (:meth:`~spark_rapids_tpu.serving.prepared.PreparedQuery.execute_stream`):
  Arrow record batches yielded incrementally off the pipelined collect
  path, with backpressure tied to the prefetch stage depth
  (parallel/pipeline.py).

Cost discipline: with ``spark.rapids.tpu.serving.maxConcurrent`` at its
default of 0 the whole tier is dormant — a collect performs one conf
lookup and nothing else; no scheduler exists, no lock is taken.
Docs: ``docs/serving.md``.
"""

from __future__ import annotations

import threading
from typing import Any, Optional

from spark_rapids_tpu.config import register

MAX_CONCURRENT = register(
    "spark.rapids.tpu.serving.maxConcurrent", 0,
    "Maximum queries executing concurrently under the serving tier's "
    "admission control (0 = serving admission disabled; collects run "
    "unscheduled).  The effective limit is additionally clamped to the "
    "device semaphore's permit count "
    "(spark.rapids.tpu.sql.concurrentTpuTasks) — admission rides the "
    "same budget that caps device batch residency (docs/serving.md).")

QUEUE_DEPTH = register(
    "spark.rapids.tpu.serving.queueDepth", 32,
    "Bounded admission-queue depth: a query arriving while maxConcurrent "
    "queries run and this many already wait is REJECTED with "
    "AdmissionRejected instead of queuing unboundedly (load shedding; "
    "the rejection is counted in the scheduler stats).",
    check=lambda v: v >= 0)

DEFAULT_PRIORITY = register(
    "spark.rapids.tpu.serving.defaultPriority", 1,
    "Weighted-fair share for tenants that do not set an explicit "
    "priority: a tenant with priority P receives P times the admission "
    "share of a priority-1 tenant under contention (start-time fair "
    "queuing; docs/serving.md).",
    check=lambda v: v >= 1)

PLAN_CACHE_CAPACITY = register(
    "spark.rapids.tpu.serving.planCache.capacity", 32,
    "Per-session LRU capacity of the prepared-plan cache (lowered exec "
    "trees keyed by structural plan key + conf fingerprint + parameter "
    "binding).  Cached entries pin their plan's source data (e.g. "
    "in-memory tables), so the bound is a memory bound too.",
    check=lambda v: v >= 1)

BATCHING_ENABLED = register(
    "spark.rapids.tpu.serving.batching.enabled", True,
    "Admission-aware batching (docs/work_sharing.md): when granting "
    "slots, the scheduler prefers queued queries whose template group "
    "(the prepared-statement identity, independent of parameter "
    "bindings) matches one already running — compatible plans run "
    "together, so the work-sharing tier's in-flight scan dedup and "
    "result cache engage instead of the same scan being paid once per "
    "slot generation.  A deliberate, bounded throughput-over-strict-"
    "WFQ-order tradeoff; disable for strict weighted-fair order.  "
    "Inert unless serving.maxConcurrent > 0.")

ADMIT_WAIT_BUDGET_MS = register(
    "spark.rapids.tpu.serving.health.admitWaitBudgetMs", 250.0,
    "Admission-wait budget per query for the HC009 health rule "
    "(tools/history): a recorded query whose serve.admit_wait_ms "
    "counter exceeds this is flagged — the serving tier is saturated "
    "for its traffic (docs/serving.md).")

# ------------------------------------------------------------------ #
# Per-query serving context (thread-local)
# ------------------------------------------------------------------ #
#
# Admission happens BEFORE the event-log writer's query_begin counter
# snapshot and plan-cache lookups happen before plan_query — so neither
# is attributable through the monotonic-counter delta mechanism.  The
# scheduler and PreparedQuery instead deposit their per-query facts
# here, and EventLogWriter.query_end (which runs on the calling thread,
# inside the admitted region) folds them into the query record.

_TL = threading.local()


def update_serving_context(**kv: Any) -> None:
    ctx = getattr(_TL, "ctx", None)
    if ctx is None:
        ctx = _TL.ctx = {}
    ctx.update(kv)


def current_serving_context() -> Optional[dict]:
    """The calling thread's serving facts for the query in flight
    (tenant, priority, admit_wait_ms, plan_cache hit/miss), or None."""
    ctx = getattr(_TL, "ctx", None)
    return dict(ctx) if ctx else None


def clear_serving_context() -> None:
    _TL.ctx = None
