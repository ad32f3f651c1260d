"""Process-global compiled-program cache.

Every collect() builds a fresh exec tree, and jax.jit's compile cache is
per-wrapper — so without sharing, each query run re-traces and
re-compiles XLA programs identical to the last run's.  The reference
never pays this: cudf kernels are pre-compiled native code invoked per
batch (SURVEY.md L0).  The XLA analog is a *structural program key*: two
execs whose compute is determined by equal expression trees / specs share
one jit wrapper, so the second query (and every query after) hits the
compile cache at trace level.

Keys must capture everything the traced function reads that is not part
of the input pytree: bound expression trees (ordinals, dtypes, literal
values), agg specs, static capacities, output schemas.  Input batch
shape/dtype/schema ride the pytree and are keyed by jax itself.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import re
import threading
import warnings
from typing import Callable, Optional, Sequence

import jax

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu.config import register
from spark_rapids_tpu.trace import ledger as _ledger
from spark_rapids_tpu.exprs.base import Expression

DONATION_ENABLED = register(
    "spark.rapids.tpu.sql.fusion.donation.enabled", False,
    "Donate per-batch WIRE-form decode inputs (fresh single-use "
    "uploads) into fused XLA programs via cached_jit's `donate=` arg, "
    "so XLA reuses their HBM for the program's outputs instead of "
    "allocating fresh buffers.  Donated inputs are CONSUMED — the "
    "engine marks them (EncodedBatch.consumed via "
    "transfer.run_consuming) so the retry/split ladder never touches "
    "a donated buffer again; a future donation site over "
    "store-registered batches must first un-register them via "
    "SpillableBatch.mark_consumed (the seam exists and is tested, "
    "but no engine path donates store-registered batches today — "
    "decoded batches carry process-shared arrays and are never "
    "donated).  Off (the default): donate= is ignored and behavior "
    "is bit-for-bit identical to the non-donating engine "
    "(docs/fusion.md).  Read at program-compile time; the "
    "compile-cache key carries the donation state, so flipping it "
    "mid-session compiles fresh programs rather than corrupting "
    "cached ones.")

#: CPU/METAL backends implement donation as a no-op and warn per
#: compile; the engine treats donation as best-effort HBM reuse (the
#: consumed-state bookkeeping is what matters for correctness), so the
#: warning is noise in every non-TPU test run
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

_LOCK = threading.Lock()
#: LRU: a long-lived process serving many distinct ad-hoc query shapes
#: must not pin every query's exec tree (cached closures retain the exec
#: instance that created them) and jax executable forever.
_CACHE: "collections.OrderedDict" = collections.OrderedDict()
MAX_ENTRIES = 512
#: lookup counters (under _LOCK): a low hit rate on a steady workload
#: means keys are unstable (per-query state leaking into them) and
#: every query is paying trace+compile again — surfaced by
#: cache_stats() in explain("analyze") next to the per-miss
#: jit.cache_miss trace events
_HITS = 0
_MISSES = 0
#: real XLA trace+compiles (under _LOCK): a MISS that restores a
#: persisted AOT program (spark_rapids_tpu/persist.py) is not a
#: compile, so the warm-start smoke's "zero compilations in a warm
#: child" assert taps THIS counter, not _MISSES.  Bumped at a fresh
#: wrapper's FIRST INVOCATION (see _CompileLatch), never at wrapper
#: creation — jax.jit is lazy, and several call sites mint wrappers
#: speculatively that are never dispatched.  compiles <= misses
#: always; the gap is exactly those phantom wrappers.
_COMPILES = 0


def _field_key(v) -> str:
    """Serialize one dataclass field value; recurses into tuples so nested
    containers of Expressions (CaseWhen's branch pairs) serialize
    structurally instead of through Expression.__repr__ (which is
    name-only and would collide across ordinals/dtypes)."""
    if isinstance(v, Expression):
        return expr_key(v)
    if isinstance(v, tuple):
        return "(" + ",".join(_field_key(x) for x in v) + ")"
    return repr(v)


def expr_key(e) -> str:
    """Deterministic structural serialization of a bound expression tree:
    class names plus every dataclass field (ordinals, dtypes, literal
    values) — everything eval() reads."""
    if not isinstance(e, Expression):
        return repr(e)
    if dataclasses.is_dataclass(e):
        parts = [_field_key(getattr(e, f.name))
                 for f in dataclasses.fields(e)]
        return f"{type(e).__name__}[{','.join(parts)}]"
    # a non-dataclass Expression subclass with state would silently share
    # one compiled program across different states — refuse instead of
    # returning a bare class name (cache correctness depends entirely on
    # key completeness)
    raise TypeError(
        f"expression {type(e).__name__} is not a dataclass; expression "
        "classes must be dataclasses so their state serializes into "
        "compile-cache keys")


def exprs_key(es: Sequence) -> tuple:
    return tuple(expr_key(e) for e in es)


def donation_enabled() -> bool:
    """Is buffer donation into fused programs on for this thread's
    conf?  One conf read — callers gate their consumed-state
    bookkeeping on the same value they pass programs through with."""
    from spark_rapids_tpu.config import get_conf

    return bool(get_conf().get(DONATION_ENABLED))


def _validate_donate(donate) -> tuple:
    """Normalize/validate a donate= spec: a tuple of distinct
    non-negative argnums.  Validated HERE, not at jax call time —
    a malformed spec must fail at the compile chokepoint with the
    caller's key in hand, not deep inside jax's pytree plumbing."""
    if isinstance(donate, bool):
        # bool IS int in Python: a natural-looking donate=True would
        # silently normalize to argnum 1 and donate the WRONG buffer
        raise TypeError(
            "cached_jit donate= takes argnums, not a flag; use "
            "donate=(0,) to donate the first argument")
    if isinstance(donate, int):
        donate = (donate,)
    donate = tuple(donate)
    if not donate:
        return ()
    if not all(isinstance(i, int) and not isinstance(i, bool)
               and i >= 0 for i in donate) \
            or len(set(donate)) != len(donate):
        raise TypeError(
            f"cached_jit donate= must be distinct non-negative "
            f"argnums, got {donate!r}")
    return donate


def _shardings_key(in_shardings, out_shardings) -> tuple:
    """Serialize a sharding spec pair for the cache key.  reprs carry
    mesh axis names/sizes and the PartitionSpec but NOT device
    identity — partitioned callers additionally fold
    parallel.mesh.mesh_key(mesh) into their own key (the SPMD stage
    builders do), so two same-shaped meshes over different devices
    never share an executable."""
    def one(s):
        if s is None:
            return None
        if isinstance(s, (tuple, list)):
            return tuple(one(x) for x in s)
        return repr(s)
    return (one(in_shardings), one(out_shardings))


class _CompileLatch:
    """jax.jit compiles LAZILY: wrapper creation traces nothing; the
    first invocation pays trace+compile.  Some call sites mint
    wrappers speculatively (sort's full-sort program when the
    augmented path supersedes it, agg merge/final phases in
    single-partition complete mode) and never dispatch them — no XLA
    compilation ever happens for those keys.  Counting at creation
    would charge these phantom compiles to every fresh process and
    break the warm-start smoke's zero-compiles assert, so _COMPILES
    bumps HERE, once, at the first real call.  Attribute access (the
    ledger cost model's ``.lower``) passes through to the wrapped
    fn."""

    __slots__ = ("_fn", "_fired")

    def __init__(self, fn):
        self._fn = fn
        self._fired = False

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)

    def __call__(self, *args, **kwargs):
        if not self._fired:
            global _COMPILES
            with _LOCK:
                if not self._fired:
                    self._fired = True
                    _COMPILES += 1
        return self._fn(*args, **kwargs)


#: process-wide ENQUEUE gate for PARTITIONED (sharded) programs.
#: XLA's CPU collectives rendezvous per-device participant threads
#: that drain per-device execution queues in FIFO order, so two
#: threads enqueueing two multi-device programs can interleave the
#: per-device queue orders — device 0 queues A-then-B while device 1
#: queues B-then-A, each program's rendezvous waits on participants
#: parked BEHIND the other program, and both stall forever (the
#: `collective_ops_utils` "waiting for all participants" deadlock).
#: Concurrent sessions on one mesh are exactly this shape.  Holding
#: the lock across the (async) call makes every device see the same
#: program order — sufficient, IF
#: every multi-device launch goes through the gate: cached_jit is the
#: only place one is compiled (parallel/spmd.py's stage builders all
#: end in it), and the eager side doors (a sharded array's
#: `__getitem__`, an eager `jnp.max` on a sharded leaf) are closed in
#: exchange.take_piece and the stage-exit device_get fetches.
#: Single-threaded/mesh-off callers never contend, and
#: program-to-program pipelining is untouched.
_SHARDED_DISPATCH_LOCK = threading.RLock()


class _SerializedDispatch:
    """Wrap a compiled partitioned program so concurrent callers
    ENQUEUE atomically (see _SHARDED_DISPATCH_LOCK): the runtime's
    per-device execution queues drain FIFO, so as long as every
    collective program lands on every device queue in the same order,
    the per-device worker threads reach each program's rendezvous
    together and no program waits on participants parked behind it.
    The call itself stays async — program-to-program overlap and
    host/device overlap are preserved; only the enqueue interleaving
    (the thing two threads can scramble) is serialized.  The eager
    side doors are closed separately (exchange.take_piece, stage-exit
    device_get fetches) — an UNGUARDED multi-device launch between
    two gated ones reintroduces the scramble.  Attribute access
    (``.lower`` for the ledger cost model) passes through."""

    __slots__ = ("_fn",)

    def __init__(self, fn):
        self._fn = fn

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)

    def __call__(self, *args, **kwargs):
        with _SHARDED_DISPATCH_LOCK:
            return self._fn(*args, **kwargs)


_NOT_IN_A_NAME = re.compile(r"[^A-Za-z0-9_]")


def named_program(fn: Callable, op: Optional[str], tag: str) -> Callable:
    """`fn` under the name `tpu__<op>__<tag>`, for `jax.jit` to call:
    JAX names the XLA module after the function (`jit_<name>`), so a
    profiler capture then says which exec's program ran, and whatever
    lacks the `jit_tpu__` prefix was dispatched outside `cached_jit`.
    `op` is the owning exec's name (`none` where the site has none),
    `tag` the key's leading string (`trace.ledger.key_tag`) or
    `unkeyed`; both are cut to `[A-Za-z0-9_]`.

    Always a thin wrapper, never a rename: partials, bound methods and
    callable objects take no `__name__`, JAX reads the name only when
    it traces, and one function may serve several keys.  The wrapper
    runs at trace time alone; a compiled dispatch never enters it."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = "tpu__%s__%s" % (
        _NOT_IN_A_NAME.sub("_", op or "none"),
        _NOT_IN_A_NAME.sub("_", tag))
    return program


def cached_jit(key: tuple, make_fn: Callable[[], Callable],
               op: Optional[str] = None,
               donate: "int | Sequence[int] | None" = None,
               in_shardings=None, out_shardings=None,
               meta: Optional[dict] = None):
    """Return a jitted callable shared by every caller presenting `key`.
    `make_fn` is invoked (once) only on a cache miss.

    `op` (the owning exec's name, when the caller has one) labels the
    program in the device-utilization ledger (trace/ledger.py) so
    explain("analyze") can attribute per-operator roofline fractions;
    the cached callable is the ledger's dispatch hook — with the
    ledger off the wrapper is one attribute read and a passthrough
    call, bit-identical to the raw jitted function.

    `donate` (argnums) marks input args whose buffers XLA may reuse
    for the program's outputs (the pjit donate_argnums plumbing —
    SNIPPETS [1][2]).  Honored only when
    spark.rapids.tpu.sql.fusion.donation.enabled is on; the caller
    owns the CONSUMED-state bookkeeping for whatever it donates
    (EncodedBatch.consumed / SpillableBatch.mark_consumed) — a
    donated-then-spilled buffer is a use-after-free.  The donation
    state folds into the cache key, so donating and non-donating
    callers of the same logical program never share a compiled
    executable.

    `in_shardings` / `out_shardings` thread jax.sharding specs
    (NamedSharding pytrees) into the compiled program — the pjit/GSPMD
    plumbing for partitioned SPMD stage programs (SNIPPETS [1][2][3]).
    Sharding is PART of the executable (GSPMD partitions the program
    around it), so the spec pair folds into the cache key; donation
    composes (a donated sharded input's per-device buffers are reused
    for the partitioned outputs).  `meta` attaches static program
    attributes (mesh device count, in-program collective round count)
    to the ledger entry so partitioned programs attribute per-device
    busy time in snapshots/bench."""
    global _HITS, _MISSES, _COMPILES
    donate = _validate_donate(donate) if donate is not None else ()
    if donate and donation_enabled():
        key = key + ("donate", donate)
    else:
        donate = ()
    if in_shardings is not None or out_shardings is not None:
        key = key + ("shardings",
                     _shardings_key(in_shardings, out_shardings))
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is None:
            _MISSES += 1
            if _trace.TRACER.enabled:
                # a miss means a fresh trace+compile is coming for this
                # program shape: the timeline shows WHICH key paid it
                _trace.event("jit.cache_miss", key=repr(key)[:200],
                             cache_size=len(_CACHE))
            # the jit.compile fault seam sits on the miss path only (a
            # cache hit compiles nothing), with in-place recovery
            # (absorb_once) for INJECTED compile faults: spill
            # unpinned buffers, re-check once.  Real XLA compilation
            # happens lazily at the wrapper's first invocation — a
            # real compile OOM therefore surfaces at the CALLER, and
            # retry.classify calls it fatal: it propagates
            from spark_rapids_tpu.execs.retry import absorb_once
            from spark_rapids_tpu.robustness import faults as _faults

            absorb_once(
                lambda: _faults.fault_point("jit.compile",
                                            key=repr(key)[:80]),
                action="compile_retry")
            # every program the engine compiles flows through here:
            # the ledger wrapper is the single metering point feeding
            # per-program dispatch counts + device time + cost-model
            # attribution (tpulint SRC009 flags raw jax.jit in exec
            # modules for exactly this reason)
            jit_kwargs: dict = {"donate_argnums": donate}
            if in_shardings is not None:
                jit_kwargs["in_shardings"] = in_shardings
            if out_shardings is not None:
                jit_kwargs["out_shardings"] = out_shardings
            # warm-start probe BEFORE tracing (docs/warm_start.md):
            # with persistence on, a structural-key miss first asks the
            # disk store for jax.export artifacts under (key x conf
            # fingerprint); a hit dispatches restored executables and
            # compiles nothing.  Sharded programs are excluded: their
            # sharding specs bind live device objects that don't
            # round-trip a serialize.  Off = one conf read in active(),
            # then the identical compile path as ever.
            from spark_rapids_tpu import persist as _persist

            sharded = (in_shardings is not None
                       or out_shardings is not None)
            store = None if sharded else _persist.active()
            def make_named() -> Callable:
                return named_program(make_fn(), op, _ledger.key_tag(key))

            restored = None
            conf_fp = ""
            if store is not None:
                conf_fp = _persist._conf_fp()[:12]
                exported = store.load_programs(key, conf_fp)
                if exported:
                    restored = _persist.RestoredProgram(
                        key, exported, make_named, jit_kwargs, store,
                        conf_fp)
            if restored is not None:
                fn = _ledger.LEDGER.wrap(
                    key, restored, op=op, donated=bool(donate),
                    meta={**(meta or {}), "persist_restored": True})
            else:
                jitted = jax.jit(make_named(), **jit_kwargs)
                if store is not None:
                    jitted = _persist.AutoSave(key, jitted, store,
                                               conf_fp)
                fn = _ledger.LEDGER.wrap(
                    key, _CompileLatch(jitted), op=op,
                    donated=bool(donate), meta=meta)
            if sharded:
                # outside the ledger wrapper: lock WAIT (another
                # session's enqueue) must not inflate this program's
                # attributed dispatch time
                fn = _SerializedDispatch(fn)
            _CACHE[key] = fn
            while len(_CACHE) > MAX_ENTRIES:
                _CACHE.popitem(last=False)
        else:
            _HITS += 1
            _CACHE.move_to_end(key)
        return fn


def cache_size() -> int:
    with _LOCK:
        return len(_CACHE)


def cache_stats() -> dict:
    """Cumulative lookup counters: {hits, misses, size, hit_rate}.
    Callers wanting PER-QUERY figures (explain("analyze")) snapshot
    before/after and diff."""
    with _LOCK:
        total = _HITS + _MISSES
        return {
            "hits": _HITS,
            "misses": _MISSES,
            "compiles": _COMPILES,
            "size": len(_CACHE),
            "hit_rate": round(_HITS / total, 3) if total else 0.0,
        }


def reset_cache_stats() -> None:
    """Zero the lookup counters (the cache itself is untouched)."""
    global _HITS, _MISSES, _COMPILES
    with _LOCK:
        _HITS = 0
        _MISSES = 0
        _COMPILES = 0


def note_external_compile() -> None:
    """A compile happened OUTSIDE the miss path: a RestoredProgram
    saw an argument signature with no persisted artifact and fell
    back to an honest jax.jit.  Bumped so the compiles counter (and
    the warm-start smoke's zero-compiles assert) stays truthful."""
    global _COMPILES
    with _LOCK:
        _COMPILES += 1


def program_census() -> dict[str, int]:
    """Distinct compiled programs per key TAG (the leading string of
    every structural key): the jit-key audit surface behind ROADMAP
    #2's bucketing work.  A steady workload whose census GROWS run
    over run has non-structural values (literals, per-batch counts)
    leaking into its keys — the fusion smoke and
    tests/test_fusion.py's re-key stability test diff this figure
    across identical collects to pin key churn to the tag that minted
    it."""
    with _LOCK:
        keys = list(_CACHE)
    census: dict[str, int] = {}
    for k in keys:
        tag = _ledger.key_tag(k)
        census[tag] = census.get(tag, 0) + 1
    return census


def clear() -> None:
    with _LOCK:
        _CACHE.clear()
