"""Deterministic fault injection: a registry of named injection points
planted at the engine's recovery-critical seams.

The reference proves its OOM/spill/retry machinery with the
`RmmRapidsRetryIterator` test harness (forced split-and-retry, forced
OOM on the Nth allocation); nothing equivalent existed here — the
recovery paths (spill-on-pressure, shuffle refetch, task retry, CPU
degrade) only ran when real hardware happened to misbehave.  This
module makes every one of them exercisable *deterministically*, in
tier-1.

Sites (each planted at exactly one seam):

- ``alloc.device``    — memory/device_manager.device_alloc_checkpoint,
  called by BufferStore.reserve before admitting a device reservation;
- ``transfer.upload`` — columnar/transfer.upload_components, the single
  batched H2D ``jax.device_put`` the scan/serde paths route through;
- ``shuffle.fetch``   — shuffle/net.fetch_blocks, per fetch attempt;
- ``jit.compile``     — execs/jit_cache.cached_jit, on a cache miss;
- ``pipeline.stage``  — parallel/pipeline.prefetch, per produced item
  on the producer thread (recovered in place, stage never torn down);
- ``exec.batch``      — execs/retry.with_split_retry, once per guarded
  batch attempt in the join/aggregate/sort/exchange stream loops (the
  drill site for the OOM escalation ladder);
- ``cancel.check``    — serving/cancel.check_point, once per
  cooperative cancellation checkpoint WHEN a query token is attached;
  an injected hit cancels the current token, so chaos schedules drive
  deterministic cancellations through the real unwind path
  (docs/robustness.md).

Policies are conf-driven (``spark.rapids.tpu.robustness.faults.spec``)
and fully deterministic: fail-the-Nth-call (optionally N consecutive
calls), fail-every-Nth, seeded per-site probability, injected latency.
Counters per site (calls / injected / recovered) feed the chaos parity
tests;
``fault.inject`` / ``fault.recovered`` trace events land on the
correlated timeline (docs/observability.md).

Disabled (the default) every checkpoint is one module-global read —
the subsystem asserts behavior-identical to the un-instrumented engine
(tests/test_chaos.py).
"""

from __future__ import annotations

import random
import threading
import time
import weakref
from typing import Optional

from spark_rapids_tpu import trace as _tr
from spark_rapids_tpu.config import register, get_conf

FAULTS_ENABLED = register(
    "spark.rapids.tpu.robustness.faults.enabled", False,
    "Arm the deterministic fault-injection registry for queries run "
    "with this conf (chaos mode).  Sites and policies come from "
    "spark.rapids.tpu.robustness.faults.spec; disabled, every "
    "injection point is a single global read.")

FAULTS_SPEC = register(
    "spark.rapids.tpu.robustness.faults.spec", "",
    "Semicolon-separated per-site fault policies: "
    "'site:key=val,key=val;site2:...'.  Sites: alloc.device, "
    "transfer.upload, shuffle.fetch, jit.compile, pipeline.stage, "
    "exec.batch, cancel.check.  Keys: nth=N (fail the Nth call, "
    "1-based), times=K "
    "(with nth: fail K consecutive calls from the Nth; default 1), "
    "every=N (fail every Nth call), prob=P (seeded per-call "
    "probability), seed=S (per-site RNG seed for prob), latency=MS "
    "(sleep MS milliseconds per call, injected without failing), "
    "marker=TEXT (override the error text; the default per site is a "
    "retryable marker like RESOURCE_EXHAUSTED).")

#: the registered sites (a checkpoint at an unknown site is a no-op so
#: schedules stay forward-compatible, but tests assert against this)
SITES = ("alloc.device", "transfer.upload", "shuffle.fetch",
         "jit.compile", "pipeline.stage", "exec.batch",
         "cancel.check")

#: default injected-error text per site — every default carries a
#: marker execs/retry.is_retryable classifies as transient, so the
#: engine's real recovery ladder (not a test-only path) handles it
_DEFAULT_MARKERS = {
    "alloc.device":
        "RESOURCE_EXHAUSTED: injected device allocation failure",
    "transfer.upload":
        "UNAVAILABLE: injected H2D transfer fault",
    "shuffle.fetch":
        "injected shuffle fetch fault: connection reset by peer",
    "jit.compile":
        "UNAVAILABLE: injected compile fault",
    "pipeline.stage":
        "RESOURCE_EXHAUSTED: injected pipeline stage fault",
    "exec.batch":
        "RESOURCE_EXHAUSTED: injected batch processing fault",
    # deliberately NO retryable marker: an injected cancellation is
    # converted by check_point into a real token cancel and must fail
    # fast through the ladder, exactly like a user cancel
    "cancel.check":
        "injected cancellation at a cancel.check checkpoint",
}


class InjectedFault(RuntimeError):
    """An error raised by a fault_point.  Subclasses RuntimeError so
    the standard marker classification (execs/retry.is_retryable) sees
    it exactly like a real XlaRuntimeError; carries its site so
    recovery paths can attribute the save (note_recovered)."""

    def __init__(self, site: str, message: str):
        super().__init__(message)
        self.site = site


class _SiteState:
    __slots__ = ("site", "nth", "times", "every", "prob", "latency_s",
                 "marker", "rng", "calls", "injected", "recovered",
                 "lock")

    def __init__(self, site: str, nth: int = 0, times: int = 1,
                 every: int = 0, prob: float = 0.0, seed: int = 0,
                 latency_s: float = 0.0, marker: Optional[str] = None):
        self.site = site
        self.nth = nth
        self.times = max(1, times)
        self.every = every
        self.prob = prob
        self.latency_s = latency_s
        self.marker = marker or _DEFAULT_MARKERS.get(
            site, "RESOURCE_EXHAUSTED: injected fault")
        # seeded per site so a multi-site schedule stays deterministic
        # regardless of cross-site call interleaving; crc32, NOT
        # hash() — string hashing is salted per process, which would
        # make a prob= schedule irreproducible across runs
        import zlib

        self.rng = random.Random(zlib.crc32(site.encode()) ^ seed)
        self.calls = 0
        self.injected = 0
        self.recovered = 0
        self.lock = threading.Lock()

    def snapshot(self) -> dict:
        with self.lock:
            return {"calls": self.calls, "injected": self.injected,
                    "recovered": self.recovered}


def parse_spec(spec: str) -> dict[str, _SiteState]:
    """'site:nth=3,times=2;site2:prob=0.5,seed=7' -> site states.
    Malformed entries raise ValueError (a chaos schedule that silently
    no-ops would report green recovery coverage that never ran)."""
    out: dict[str, _SiteState] = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise ValueError(f"fault spec entry {part!r} missing ':'")
        site, _, body = part.partition(":")
        site = site.strip()
        if site not in SITES:
            # a typo'd site would arm a schedule no checkpoint ever
            # matches — the run would read as "recovery survives" when
            # nothing was injected
            raise ValueError(
                f"unknown fault site {site!r}; sites: {SITES}")
        kw: dict = {}
        for item in body.split(","):
            item = item.strip()
            if not item:
                continue
            k, _, v = item.partition("=")
            k = k.strip()
            v = v.strip()
            if k in ("nth", "times", "every", "seed"):
                kw[k] = int(v)
            elif k == "prob":
                kw["prob"] = float(v)
            elif k == "latency":
                kw["latency_s"] = float(v) / 1e3
            elif k == "marker":
                kw["marker"] = v
            else:
                raise ValueError(
                    f"unknown fault policy key {k!r} for site {site!r}")
        out[site] = _SiteState(site, **kw)
    return out


# process-global armed state (like the tracer: injection points run on
# producer/map-pool threads whose thread-local conf is a snapshot; the
# schedule itself must be one per process)
_ARMED = False
_FORCED = False
_SITES_STATE: dict[str, _SiteState] = {}
_SPEC_STR: Optional[str] = None
_OWNER: Optional["weakref.ref"] = None
_LOCK = threading.Lock()


def install(spec: str, forced: bool = False) -> None:
    """Arm the registry with a schedule (fresh counters).  ``forced``
    installs (tests, bench --chaos) survive sync_conf."""
    global _ARMED, _FORCED, _SITES_STATE, _SPEC_STR
    with _LOCK:
        _SITES_STATE = parse_spec(spec)
        _SPEC_STR = spec
        _ARMED = True
        _FORCED = forced


def disarm() -> None:
    global _ARMED, _FORCED, _SPEC_STR, _OWNER, _SITES_STATE
    with _LOCK:
        _ARMED = False
        _FORCED = False
        _SPEC_STR = None
        _OWNER = None
        _SITES_STATE = {}


def sync_conf(conf=None) -> None:
    """Align the process registry with the session conf at a query
    boundary (mirrors trace.sync_conf): a conf that enables faults arms
    its schedule; only the conf that armed may disarm; a programmatic
    forced install wins."""
    global _OWNER
    if _FORCED:
        return
    conf = conf or get_conf()
    want = bool(conf.get(FAULTS_ENABLED))
    if want:
        spec = str(conf.get(FAULTS_SPEC))
        with _LOCK:
            reinstall = not _ARMED or spec != _SPEC_STR
        if reinstall:
            install(spec)
        with _LOCK:
            _OWNER = weakref.ref(conf)
    elif _ARMED and _OWNER is not None and _OWNER() is conf:
        disarm()


def fault_point(site: str, **ctx) -> None:
    """The injection checkpoint.  Disabled: one global read.  Armed:
    evaluate the site's policy — maybe sleep (latency), maybe raise an
    InjectedFault whose text carries a retryable marker."""
    if not _ARMED:
        return
    st = _SITES_STATE.get(site)
    if st is None:
        return
    with st.lock:
        st.calls += 1
        call_no = st.calls
        fire = False
        if st.nth and st.nth <= call_no < st.nth + st.times:
            fire = True
        elif st.every and call_no % st.every == 0:
            fire = True
        elif st.prob and st.rng.random() < st.prob:
            fire = True
        if fire:
            st.injected += 1
        latency = st.latency_s
    if latency:
        time.sleep(latency)
    if fire:
        if _tr.TRACER.enabled:
            _tr.event("fault.inject", site=site, call=call_no, **ctx)
        raise InjectedFault(
            site, f"{st.marker} (site={site}, call #{call_no})")


def _injected_in_chain(exc: BaseException) -> Optional[InjectedFault]:
    seen = 0
    e: Optional[BaseException] = exc
    while e is not None and seen < 16:
        if isinstance(e, InjectedFault):
            return e
        e = e.__cause__ or e.__context__
        seen += 1
    return None


def note_recovered(exc: BaseException, action: str = "") -> None:
    """A recovery path absorbed ``exc`` (spill+retry, batch split, task
    re-run, fetch re-attempt, CPU degrade).  If an InjectedFault is in
    its cause chain, credit the site's recovered counter and emit the
    ``fault.recovered`` trace event; real (non-injected) failures pass
    through untouched — their recoveries are counted by the retry-layer
    stats instead (execs/retry.retry_stats)."""
    if not _ARMED:
        return
    inj = _injected_in_chain(exc)
    if inj is None:
        return
    st = _SITES_STATE.get(inj.site)
    if st is None:
        return
    with st.lock:
        st.recovered += 1
    if _tr.TRACER.enabled:
        _tr.event("fault.recovered", site=inj.site, action=action)


def fault_stats() -> dict[str, dict]:
    """{site: {calls, injected, recovered}} for the armed schedule."""
    with _LOCK:
        states = list(_SITES_STATE.values())
    return {st.site: st.snapshot() for st in states}


def recovered_total() -> int:
    return sum(s["recovered"] for s in fault_stats().values())


def injected_total() -> int:
    return sum(s["injected"] for s in fault_stats().values())


def reset_stats() -> None:
    """Zero every site's counters (the schedule itself stays armed),
    so nth-call policies re-fire."""
    with _LOCK:
        states = list(_SITES_STATE.values())
    for st in states:
        with st.lock:
            st.calls = 0
            st.injected = 0
            st.recovered = 0
