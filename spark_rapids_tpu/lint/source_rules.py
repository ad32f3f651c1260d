"""Engine-source linter: AST pass over spark_rapids_tpu/ flagging
host-device sync hazards inside traced (jit) regions.

The JAX/TPU analog of a race/sanitizer pass: inside a `jax.jit` trace,
`.item()`, `float(arr)`, `np.asarray(traced)` and Python `if` on a
traced boolean either fail at trace time or — far worse, when they
happen to run on concrete values during warmup paths — silently insert
a blocking device->host transfer into a hot loop (each stalls the
dispatching thread until the device has drained; see execs/base.py's
deferred-metric design for how much the codebase works to avoid
exactly this).

Traced-region discovery (per module, purely syntactic):
- functions decorated with jit / jax.jit / partial(jax.jit, ...)
- functions passed by name to jit()/jax.jit()/pjit()/cached_jit()
  (including `cached_jit(key, lambda: fn)` thunks)
- Expression.eval methods (signature `eval(self, ctx)`) — they run
  inside the fused pipeline's trace
- inner functions returned by `make_*_fn`/`_make_decode` factories —
  the fusion machinery jits them

Taint: a region's parameters (minus self/cls) are traced values;
assignments propagate taint; reads through shape/ndim/dtype/size,
len(), isinstance() etc. are static and clear it.

Rules
-----
- SRC001 (error): .item() inside a traced region
- SRC002 (warning): host materialization of a traced value
  (np.asarray/np.array/jax.device_get/.tolist()/.block_until_ready())
- SRC003 (error): Python scalar conversion float()/int()/bool() of a
  traced value
- SRC004 (warning): Python if/while branching on a traced boolean
- SRC005 (warning): raw blocking device->host readback
  (jax.device_get / .item()) in an exec module (execs/) instead of the
  software pipeline's deferred-readback helper
  (parallel.pipeline.device_read / device_read_many) — an inline sync
  in a stream loop stalls the loop for a full link round trip per
  batch where the pipelined form overlaps it with the next batch's
  dispatch.  Intentional syncs (metric settlement, ANSI error polls)
  are baselined, not suppressed inline.
- SRC006 (warning): raw wall-clock timing (time.time /
  time.perf_counter / time.perf_counter_ns / time.monotonic) in an
  exec or pipeline module (execs/, parallel/) instead of MetricTimer
  (device-aware, feeds the metric tree) or trace.span (lands on the
  correlated timeline).  Ad-hoc timing is invisible to profile_query,
  EXPLAIN ANALYZE and the Chrome-trace export; the timing
  INFRASTRUCTURE itself (MetricTimer, the metric reaper, the pipeline
  wait counters) is baselined, mirroring SRC005's posture.
- SRC007 (warning): `.block_until_ready()` or `np.asarray(...)` /
  `np.array(...)` on a (potential) device value inside an exec or ops
  module (execs/, ops/) — the sync hazards SRC005's
  `device_get`/`.item()` patterns miss.  Both force a blocking
  device->host wait when handed a device array; a stream loop must
  route the sync through parallel.pipeline.device_read* /
  device_read_async instead (np.asarray of a device_read* RESULT is
  exempt — the value is already host memory).  Intentional
  infrastructure sites (metric settlement in execs/base.py, the
  split-count conversion in ops/partition.py) are baselined.
- SRC008 (warning): a broad `except` clause (bare / Exception /
  BaseException / RuntimeError) in an exec, io, or shuffle module
  that SWALLOWS the exception — no re-raise anywhere in the handler
  and no routing through the retry classification gate
  (execs/retry.classify / is_retryable / should_cpu_fallback /
  note_recovered).  A bare `except Exception: pass` in those layers
  can eat a retryable device error (XlaRuntimeError subclasses
  RuntimeError), silently skipping the spill/split/task-retry
  escalation ladder AND the chaos-mode fault accounting.  Intentional
  fall-back-to-slow-path sites (the fastpar decoder's per-column
  bailouts) are baselined, not suppressed inline.  execs/retry.py
  itself — the classification gate — is exempt by construction.
- SRC010 (error): source-level use-after-donate.  In execs//ops/
  modules, a local assigned from ``cached_jit(..., donate=...)`` is a
  DONATING program: the locals passed at its donated argnum positions
  are consumed by the call (XLA reuses their buffers for the outputs
  — docs/fusion.md), so any later reference to those locals in the
  same function is a use-after-free waiting for a TPU backend.  The
  direct-call spelling ``cached_jit(..., donate=...)(x)`` is covered
  too.  Deliberately narrow (local names, source order within one
  function): donation routed through the blessed consuming helper
  (``transfer.run_consuming``, which memoizes the output and marks
  the batch consumed) is exempt by construction — that is the
  spelling engine code is supposed to use.  Intentional raw sites,
  if any ever appear, are baselined, not suppressed inline.
- SRC011 (error): direct mutation of a shared-cache object in a
  serving-path module (serving/, execs/, io/).  Cross-tenant work
  sharing (serving/work_share.py, docs/work_sharing.md) hands the
  SAME objects — a shared scan's published units and device batches
  (``subscribe_units``), a cached query result (``lookup_result``) —
  to every concurrent consumer: an in-place mutation (item/attribute
  assignment, ``append``/``update``/``sort``/... on the object or
  anything reached through it) corrupts OTHER tenants' in-flight
  queries and the cache itself.  Consumers must copy-on-write or
  re-materialize.  Taint is local-name based (assignments from the
  accessor calls, loop targets iterating them, and propagation
  through attribute/subscript reads); serving/work_share.py — the
  cache's own bookkeeping — is exempt by construction.
- SRC009 (error): raw ``jax.jit`` in an exec or ops module (execs/,
  ops/) bypassing ``execs/jit_cache.cached_jit``.  Every program the
  engine compiles is supposed to flow through the structural-key
  cache: a raw jit is UNMETERED — it escapes the jit-cache hit/miss
  stats that explain("analyze") reports, AND the device-utilization
  ledger (trace/ledger.py) that attributes per-program dispatches,
  device time and roofline fractions — and it re-traces per exec
  instance where the cache would share one compiled program across
  every query presenting the same key.  Sites with no stable
  structural key (the fused-pipeline fallback when a chain member has
  no fuse key, the module-level Pallas kernel wrappers) are
  baselined, not suppressed inline.  execs/jit_cache.py — the cache
  itself — is exempt by construction.
- SRC013 (error): host syncs inside collective step functions /
  shard_map bodies (parallel/exchange.py, parallel/spmd.py,
  execs/collective.py).  The SPMD whole-stage contract (docs/spmd.md)
  defers per-round host syncs to stage exit: a
  ``concrete_num_rows()`` / ``.block_until_ready()`` /
  ``np.asarray`` / ``jax.device_get`` / ``.item()`` inside a step
  builder's nested body, a function passed to ``shard_map``, or a
  collective-exec method handed to a builder either fails at trace
  time or silently re-inserts the per-round host round-trip the
  partitioned stage architecture exists to remove.  The host driver
  code in the same modules (round staging, stage-exit
  ``stage_counts``/``fetch``) is out of scope by construction.
- SRC012 (error): unbounded blocking waits in serving/ and parallel/.
  Every wait on the serving path must be INTERRUPTIBLE — the
  cancellation substrate (serving/cancel.py) can only unwind a query
  whose blocked seams wake up to poll the token, so a
  ``Condition.wait()`` / ``Event.wait()`` / ``queue.get()`` /
  ``Thread.join()`` with no timeout is a query that session.cancel()
  and the deadline cannot reach.  Syntactic: zero-argument
  ``.wait()`` / ``.get()`` / ``.join()`` calls without a ``timeout=``
  keyword (``dict.get`` always takes a key, so a bare ``.get()`` is a
  queue read — except ``ClassName.get()`` singleton accessors, which
  are exempt by the leading-capital convention; a bare ``.join()`` is
  a thread join — ``str.join`` takes an iterable).  The deliberate
  sites (prefetch's
  abort-then-join teardown, whose wake-up is the channel abort, not a
  poll) are baselined with their justification in
  tests/test_lint.py's coverage contract.
- SRC014 (error): wire-facing handler discipline in connect/.  A
  frame length read off the wire (``struct.unpack``) must be clamped
  by an ``if``-raise guard BEFORE it feeds any allocation or read —
  an 8-byte hostile length must cost an error frame, never a giant
  bytearray; and nothing under connect/ may call ``.collect()`` /
  ``collect_exec()`` / ``execute_cpu()`` directly — every wire query
  routes through the admission-controlled serving seam
  (PreparedQuery.execute_stream → _stream_tpu) so deadline/cancel
  propagation and the per-query ``connect`` record engage
  (docs/connect.md).
- SRC015 (error): raw executable persistence outside the warm-start
  module.  Serialized program artifacts (``.serialize()`` products —
  jax.export blobs) and ``pickle`` writes of engine objects MUST flow
  through spark_rapids_tpu/persist.py's validated writer (magic +
  checksummed header + env stamp + temp-file-and-rename atomicity —
  docs/warm_start.md): a raw ``open().write(blob)`` or
  ``pickle.dump`` elsewhere produces files with no torn-write
  protection and no staleness stamp, which a later process would
  deserialize blind.  Syntactic: ``pickle.dump``/``dumps``/
  ``Pickler`` calls, and ``.write(x)`` where x is a ``.serialize()``
  result (directly or through a local).  persist.py IS the writer —
  exempt by construction — and python_worker/ (the UDF pipe
  protocol, pickled function frames over stdin, never files) is out
  of scope.
- SRC016 (error): raw ``jax.device_put`` in execs/ and parallel/
  outside parallel/placement.py.  Stage-input placement has ONE choke
  point (docs/spmd.md): placement.place_piece /
  placement.adopt_batch classify every move (host upload vs
  device-born vs device-to-device) into the ``placement.*`` counters
  — a raw ``device_put`` elsewhere is an untracked transfer that
  moves bytes past the meter.
  Syntactic and module-wide: any ``jax.device_put(...)`` call (or
  bare ``device_put`` imported from jax) in scope.  placement.py IS
  the choke point — exempt by construction.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, Optional

from spark_rapids_tpu.lint.diagnostic import Diagnostic

#: attribute reads that yield static (trace-time) values — includes the
#: codebase's shape-derived properties (Column.capacity/width/max_len
#: are all static functions of array shapes)
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "name", "names",
                "fields", "itemsize", "kind", "capacity", "width",
                "max_len", "num_cols"}
#: calls whose results are static regardless of argument taint
STATIC_CALLS = {"len", "isinstance", "hasattr", "getattr", "type",
                "repr", "str", "range", "enumerate", "zip", "id"}
JIT_NAMES = {"jit", "pjit", "cached_jit"}
FACTORY_NAMES = {"_make_decode"}


def _terminal_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _is_jit_decorator(dec: ast.expr) -> bool:
    name = _terminal_name(dec)
    if name in JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fname = _terminal_name(dec.func)
        if fname in JIT_NAMES:
            return True
        if fname == "partial" and dec.args \
                and _terminal_name(dec.args[0]) in JIT_NAMES:
            return True
    return False


def _static_params(fn: ast.FunctionDef) -> set[str]:
    """Parameter names a jit decorator declares static
    (static_argnames / static_argnums): host values, never traced."""
    out: set[str] = set()
    all_args = fn.args.posonlyargs + fn.args.args
    for dec in fn.decorator_list:
        if not (isinstance(dec, ast.Call) and _is_jit_decorator(dec)):
            continue
        for kw in dec.keywords:
            v = kw.value
            items = v.elts if isinstance(v, (ast.Tuple, ast.List)) \
                else [v]
            if kw.arg == "static_argnames":
                out |= {x.value for x in items
                        if isinstance(x, ast.Constant)
                        and isinstance(x.value, str)}
            elif kw.arg == "static_argnums":
                for x in items:
                    if isinstance(x, ast.Constant) \
                            and isinstance(x.value, int) \
                            and x.value < len(all_args):
                        out.add(all_args[x.value].arg)
    return out


def _is_factory(name: str) -> bool:
    return name in FACTORY_NAMES or (
        name.startswith("make_") and name.endswith("_fn")) or (
        name.startswith("_make_") and name.endswith("_fn"))


class _RegionFinder(ast.NodeVisitor):
    """Collect (FunctionDef, why) traced regions in one module."""

    def __init__(self):
        self.by_name: dict[str, list[ast.FunctionDef]] = {}
        self.regions: dict[int, tuple[ast.FunctionDef, str]] = {}
        self.jit_referenced: set[str] = set()
        self._parent_fn: list[ast.FunctionDef] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.by_name.setdefault(node.name, []).append(node)
        if any(_is_jit_decorator(d) for d in node.decorator_list):
            self.regions[id(node)] = (node, "@jit")
        elif node.name == "eval" and len(node.args.args) >= 2 \
                and node.args.args[0].arg == "self" \
                and node.args.args[1].arg == "ctx":
            self.regions[id(node)] = (node, "Expression.eval")
        elif self._parent_fn and _is_factory(self._parent_fn[-1].name):
            self.regions[id(node)] = (
                node, f"returned by {self._parent_fn[-1].name}")
        self._parent_fn.append(node)
        self.generic_visit(node)
        self._parent_fn.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        if _terminal_name(node.func) in JIT_NAMES:
            for a in node.args:
                if isinstance(a, ast.Name):
                    self.jit_referenced.add(a.id)
                elif isinstance(a, ast.Lambda) \
                        and isinstance(a.body, ast.Name):
                    self.jit_referenced.add(a.body.id)
        self.generic_visit(node)

    def finish(self) -> list[tuple[ast.FunctionDef, str]]:
        for name in self.jit_referenced:
            for fn in self.by_name.get(name, []):
                self.regions.setdefault(id(fn), (fn, "passed to jit()"))
        return list(self.regions.values())


class _Taint:
    def __init__(self, params: set[str]):
        self.names = set(params)

    def expr(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.names
        if isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return False
            return self.expr(e.value)
        if isinstance(e, ast.Call):
            fname = _terminal_name(e.func)
            if fname in STATIC_CALLS:
                return False
            parts = [e.func] + list(e.args) \
                + [k.value for k in e.keywords]
            return any(self.expr(x) for x in parts)
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                return False  # identity tests are static
            return any(self.expr(x) for x in [e.left] + e.comparators)
        if isinstance(e, ast.Lambda):
            return False
        return any(self.expr(c) for c in ast.iter_child_nodes(e)
                   if isinstance(c, ast.expr))


class _RegionChecker(ast.NodeVisitor):
    def __init__(self, region: ast.FunctionDef, why: str, path: str,
                 out: list[Diagnostic]):
        self.path = path
        self.why = why
        self.qual = region.name
        params = {a.arg for a in (region.args.posonlyargs
                                  + region.args.args
                                  + region.args.kwonlyargs)}
        params.discard("self")
        params.discard("cls")
        params -= _static_params(region)
        self.taint = _Taint(params)
        self.out = out

    def _loc(self) -> str:
        return f"{self.path}::{self.qual}"

    def _emit(self, rule: str, severity: str, node: ast.AST,
              message: str, hint: str = "") -> None:
        self.out.append(Diagnostic(
            rule, severity, self._loc(),
            f"{message} (traced region: {self.why})", hint=hint,
            line=getattr(node, "lineno", 0)))

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        if self.taint.expr(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.taint.names.add(t.id)
                elif isinstance(t, ast.Tuple):
                    for el in t.elts:
                        if isinstance(el, ast.Name):
                            self.taint.names.add(el.id)

    def visit_Call(self, node: ast.Call) -> None:
        fname = _terminal_name(node.func)
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args \
                    and self.taint.expr(node.func.value):
                self._emit(
                    "SRC001", "error", node,
                    "`.item()` forces a blocking device->host sync",
                    hint="keep the value on device, or move the read "
                         "outside the traced region")
            elif node.func.attr in ("tolist", "block_until_ready") \
                    and self.taint.expr(node.func.value):
                self._emit(
                    "SRC002", "warning", node,
                    f"`.{node.func.attr}()` materializes a traced "
                    "value on the host")
            elif node.func.attr in ("asarray", "array") \
                    and _terminal_name(node.func.value) in ("np",
                                                            "numpy") \
                    and any(self.taint.expr(a) for a in node.args):
                self._emit(
                    "SRC002", "warning", node,
                    "np.asarray/np.array on a traced value forces a "
                    "host transfer (or fails at trace time)",
                    hint="use jnp.asarray, or hoist the conversion "
                         "out of the traced region")
            elif node.func.attr == "device_get" \
                    and _terminal_name(node.func.value) == "jax":
                self._emit(
                    "SRC002", "warning", node,
                    "jax.device_get inside a traced region blocks on "
                    "the device")
        elif fname in ("float", "int", "bool") and len(node.args) == 1 \
                and self.taint.expr(node.args[0]):
            self._emit(
                "SRC003", "error", node,
                f"{fname}() of a traced value fails at trace time "
                "(ConcretizationTypeError) or hides a host sync",
                hint="keep the computation in jnp, or compute the "
                     "scalar before tracing")
        self.generic_visit(node)

    def _check_branch(self, node, kind: str) -> None:
        if self.taint.expr(node.test):
            self._emit(
                "SRC004", "warning", node,
                f"Python `{kind}` on a traced boolean: the branch is "
                "resolved at TRACE time, not per batch",
                hint="use jnp.where / lax.cond, or branch on static "
                     "metadata (shape/dtype) only")

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node, "while")
        self.generic_visit(node)


#: call names whose results are the BLESSED readback path — calls inside
#: parallel/pipeline.py itself, and call sites routed through it
_PIPELINE_HELPERS = {"device_read", "device_read_int", "device_read_many"}


class _ExecSyncChecker(ast.NodeVisitor):
    """SRC005: raw blocking device->host readbacks inside exec modules.

    Exec `execute`/stream-loop bodies must route their syncs through
    parallel.pipeline.device_read* so the software pipeline can defer
    the readback behind the next batch's dispatch (and so tests can
    trace readback ordering).  Scope is syntactic and module-wide for
    execs/: a raw sync in ANY exec helper ends up in some per-batch
    driver path."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def _loc(self) -> str:
        qual = self._fn_stack[-1] if self._fn_stack else "<module>"
        return f"{self.path}::{qual}"

    def _emit(self, node: ast.AST, what: str) -> None:
        self.out.append(Diagnostic(
            "SRC005", "warning", self._loc(),
            f"{what} is a raw blocking device->host readback in an "
            "exec body",
            hint="route it through parallel.pipeline.device_read / "
                 "device_read_many (pipelined stream loops defer it "
                 "behind the next batch's dispatch); baseline it only "
                 "if the sync is intentional",
            line=getattr(node, "lineno", 0)))

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "device_get" \
                    and _terminal_name(node.func.value) == "jax":
                self._emit(node, "jax.device_get")
            elif node.func.attr == "item" and not node.args:
                self._emit(node, ".item()")
        self.generic_visit(node)


#: numpy module aliases seen in engine code
_NP_NAMES = {"np", "numpy", "_np"}


class _HostMaterializeChecker(ast.NodeVisitor):
    """SRC007: `.block_until_ready()` / `np.asarray` / `np.array` on
    potential device values in execs/ and ops/ modules.

    SRC005 catches the explicit sync spellings (`jax.device_get`,
    `.item()`); these two are the quiet ones — `np.asarray(device_arr)`
    is a full blocking transfer that LOOKS like a free host-side cast.
    The rule is syntactic and module-wide like SRC005; converting the
    RESULT of a blessed `device_read*` call is exempt (that value is
    already host memory), and intentional infrastructure conversions
    are baselined, not suppressed inline."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def _emit(self, node: ast.AST, what: str) -> None:
        qual = self._fn_stack[-1] if self._fn_stack else "<module>"
        self.out.append(Diagnostic(
            "SRC007", "warning", f"{self.path}::{qual}",
            f"{what} on a device value blocks on the device in an "
            "engine hot path",
            hint="route the sync through parallel.pipeline.device_read"
                 " / device_read_async (speculative sizing harvests it "
                 "off the critical path); np.asarray of a device_read* "
                 "result is already exempt; baseline only intentional "
                 "infrastructure sites",
            line=getattr(node, "lineno", 0)))

    @staticmethod
    def _is_blessed(arg: ast.expr) -> bool:
        return isinstance(arg, ast.Call) \
            and _terminal_name(arg.func) in _PIPELINE_HELPERS

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "block_until_ready" and not node.args:
                self._emit(node, "`.block_until_ready()`")
            elif node.func.attr in ("asarray", "array") \
                    and _terminal_name(node.func.value) in _NP_NAMES \
                    and node.args \
                    and not self._is_blessed(node.args[0]):
                self._emit(node,
                           f"`np.{node.func.attr}(...)`")
        self.generic_visit(node)


#: time-module attributes whose call is a raw wall-clock measurement
_TIMING_ATTRS = {"time", "perf_counter", "perf_counter_ns",
                 "monotonic", "monotonic_ns"}


class _RawTimingChecker(ast.NodeVisitor):
    """SRC006: raw time.* readings inside exec/pipeline modules.

    Engine timing must flow through MetricTimer (settled, device-aware,
    visible to profile_query/EXPLAIN ANALYZE) or trace.span (on the
    correlated timeline); a bare perf_counter in an exec body produces
    numbers no tool can see or correlate.  Like SRC005, the rule is
    syntactic and module-wide; the blessed timing infrastructure
    (MetricTimer itself, the reaper, the pipeline wait counters) lives
    in these modules too and is baselined rather than special-cased."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _TIMING_ATTRS \
                and _terminal_name(node.func.value) == "time":
            qual = self._fn_stack[-1] if self._fn_stack else "<module>"
            self.out.append(Diagnostic(
                "SRC006", "warning", f"{self.path}::{qual}",
                f"raw `time.{node.func.attr}()` timing in an engine "
                "module bypasses MetricTimer/span",
                hint="time the region with MetricTimer (device-aware "
                     "metrics) or trace.span (correlated timeline); "
                     "baseline only timing-infrastructure sites",
                line=getattr(node, "lineno", 0)))
        self.generic_visit(node)


#: SRC012: blocking-wait method names.  `wait` covers Condition/Event,
#: `get` covers queue.Queue (dict.get always takes a key, so the
#: zero-arg form is a queue read), `join` covers Thread/Queue
#: (str.join takes an iterable, so the zero-arg form is a thread join)
_WAIT_ATTRS = {"wait", "get", "join"}


class _UnboundedWaitChecker(ast.NodeVisitor):
    """SRC012: unbounded blocking waits on the serving path (serving/
    and parallel/ modules).

    The cancellation substrate is COOPERATIVE: a cancelled query
    unwinds only when its blocked seams wake up and poll the token, so
    a timeout-less wait anywhere on the serving path is a query that
    session.cancel(), PreparedQuery.cancel() and the per-query
    deadline cannot reach — it blocks until some other party happens
    to notify.  Every wait must pass a timeout (the
    serving/cancel.poll_timeout cadence) and re-check the token, or be
    baselined with its wake-up justification (docs/robustness.md)."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @staticmethod
    def _is_class_accessor(node: ast.Call) -> bool:
        """`TpuSemaphore.get()` / `_MetricReaper.get()` are singleton
        ACCESSORS, not blocking reads: skip zero-arg `.get()` whose
        receiver follows the ClassName convention (leading capital,
        optionally underscore-prefixed)."""
        if node.func.attr != "get":  # type: ignore[union-attr]
            return False
        recv = _terminal_name(node.func.value)  # type: ignore[union-attr]
        return bool(recv) and recv.lstrip("_")[:1].isupper()

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _WAIT_ATTRS \
                and not node.args \
                and not any(kw.arg == "timeout"
                            for kw in node.keywords) \
                and not self._is_class_accessor(node):
            qual = self._fn_stack[-1] if self._fn_stack else "<module>"
            self.out.append(Diagnostic(
                "SRC012", "error", f"{self.path}::{qual}",
                f"unbounded blocking `.{node.func.attr}()` on the "
                "serving path cannot be interrupted by "
                "cancellation/deadline",
                hint="wait with a timeout on the "
                     "serving/cancel.poll_timeout cadence and "
                     "re-check the cancel token each wake-up; "
                     "baseline only sites with a guaranteed "
                     "non-poll wake-up",
                line=getattr(node, "lineno", 0)))
        self.generic_visit(node)


#: SRC014: engine entry points a wire-facing handler must NOT call
#: directly — the connect ingress routes every query through the
#: admission-controlled serving seam (PreparedQuery.execute_stream /
#: _stream_tpu), never a bare collect
_WIRE_FORBIDDEN_CALLS = {"collect_exec", "execute_cpu"}


class _WireHandlerChecker(ast.NodeVisitor):
    """SRC014: wire-facing code under connect/ must (a) clamp a frame
    length read off the wire BEFORE allocating with it, and (b) never
    call collect()/collect_exec()/execute_cpu() directly.

    (a) syntactically: a function that assigns from ``struct.unpack``
    (the length-prefix read) and then passes one of those names to any
    call (``recv``/``_recv_exact``/``bytearray`` — the allocation)
    must also contain an ``if``-guard comparing that name and raising.
    Without the clamp, an 8-byte hostile length becomes an arbitrary
    allocation — the server must reject oversized frames, not die
    trying to honor them (docs/connect.md).

    (b) a direct collect bypasses admission control, the deadline/
    cancellation substrate and the per-query serving record; the
    blessed path is the prepared-statement streaming seam."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def _qual(self) -> str:
        return self._fn_stack[-1] if self._fn_stack else "<module>"

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self._check_unclamped_lengths(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        name = _terminal_name(node.func)
        is_collect_attr = isinstance(node.func, ast.Attribute) \
            and node.func.attr == "collect"
        if is_collect_attr or name in _WIRE_FORBIDDEN_CALLS:
            what = (f".{node.func.attr}()" if is_collect_attr
                    else f"{name}()")
            self.out.append(Diagnostic(
                "SRC014", "error", f"{self.path}::{self._qual()}",
                f"wire-facing handler calls {what} directly, "
                "bypassing the admission-controlled serving seam",
                hint="route wire queries through "
                     "PreparedQuery.execute_stream/_stream_tpu so "
                     "admission, deadline/cancel propagation and the "
                     "per-query connect record all engage "
                     "(docs/connect.md)",
                line=getattr(node, "lineno", 0)))
        self.generic_visit(node)

    # -- (a): unpack-then-allocate without a clamp ------------------- #

    @staticmethod
    def _assigned_names(target: ast.expr) -> set[str]:
        return {n.id for n in ast.walk(target)
                if isinstance(n, ast.Name)}

    @classmethod
    def _own_nodes(cls, node: ast.AST):
        """This function's own statements/expressions — nested defs
        are excluded (they get their own visit)."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda)):
                continue
            yield child
            yield from cls._own_nodes(child)

    def _check_unclamped_lengths(self, fn: ast.FunctionDef) -> None:
        unpacked: dict[str, int] = {}  # name -> lineno
        guarded: set[str] = set()
        used: dict[str, int] = {}
        for node in self._own_nodes(fn):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _terminal_name(node.value.func) == "unpack":
                for t in node.targets:
                    for nm in self._assigned_names(t):
                        unpacked[nm] = node.lineno
            if isinstance(node, ast.If):
                has_raise = any(isinstance(x, ast.Raise)
                                for x in ast.walk(node))
                if has_raise:
                    for x in ast.walk(node.test):
                        if isinstance(x, ast.Name):
                            guarded.add(x.id)
            if isinstance(node, ast.Call) \
                    and _terminal_name(node.func) != "unpack":
                for a in list(node.args) \
                        + [k.value for k in node.keywords]:
                    for x in ast.walk(a):
                        if isinstance(x, ast.Name):
                            used.setdefault(x.id, node.lineno)
        for nm, line in sorted(unpacked.items()):
            if nm in used and nm not in guarded:
                self.out.append(Diagnostic(
                    "SRC014", "error",
                    f"{self.path}::{fn.name}",
                    f"wire frame length {nm!r} (struct.unpack) is "
                    "used to allocate/read without a clamp guard",
                    hint="validate the length against "
                         "spark.rapids.tpu.connect.maxFrameBytes and "
                         "raise BEFORE any allocation — an 8-byte "
                         "hostile length must never become a giant "
                         "bytearray (docs/connect.md)",
                    line=used[nm]))


def _is_wire_module(path: str) -> bool:
    """SRC014 scope: the wire-facing connect ingress package."""
    parts = path.replace("\\", "/").split("/")
    return "connect" in parts


#: SRC013: attribute-call spellings that force a device->host sync —
#: fatal inside a collective step / shard_map body, where they either
#: fail at trace time or silently serialize the partitioned program
_STEP_SYNC_ATTRS = {"concrete_num_rows", "block_until_ready", "item",
                    "tolist"}
#: builder-function name prefixes whose NESTED defs are traced step
#: bodies (make_exchange_scan_stage's shard_fn/step, make_stage_tail's
#: shard_fn, ...)
_STEP_BUILDER_PREFIXES = ("make_", "spmd_")


class _CollectiveStepSyncChecker(ast.NodeVisitor):
    """SRC013: host syncs inside collective step functions / shard_map
    bodies (parallel/exchange.py, parallel/spmd.py,
    execs/collective.py).

    The SPMD whole-stage contract (docs/spmd.md) is that per-round
    host syncs are DEFERRED to stage exit: everything inside a stage
    program — the shard_map body, the lax.scan round body, the fused
    pre/merge/finalize phases — must stay traceable.  A
    `concrete_num_rows()` / `.block_until_ready()` / `np.asarray` /
    `jax.device_get` / `.item()` in one of those bodies either fails
    at trace time or, on a warm-up path handed concrete values,
    silently re-inserts the per-round host round-trip the whole
    architecture exists to remove.

    Traced bodies, syntactically:
    - any function nested inside a step/stage BUILDER (a function
      whose name starts with ``make_`` or ``spmd_``);
    - any function passed by name to ``shard_map``/``_shard_map``;
    - in execs/collective.py: methods handed to a builder as a bound
      reference or called from a lambda passed to a builder
      (``make_join_scan_stage(mesh, key, lambda s, b:
      self._join_local(s, b, cap), n)`` makes ``_join_local`` a traced
      body).

    The host DRIVER code in the same modules (round staging,
    stage-exit counts fetches) legitimately syncs and is out of
    scope."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[ast.FunctionDef] = []
        #: method names referenced as `self._x` in builder-call args
        self.traced_methods: set[str] = set()

    # -- pass 1: find traced bodies --------------------------------- #

    @staticmethod
    def _is_builder_call(node: ast.Call) -> bool:
        name = _terminal_name(node.func)
        return bool(name) and name.startswith(_STEP_BUILDER_PREFIXES)

    @staticmethod
    def _self_attrs(e: ast.expr) -> list[str]:
        """`self._x` attribute names referenced anywhere under `e`."""
        out = []
        for n in ast.walk(e):
            if isinstance(n, ast.Attribute) \
                    and isinstance(n.value, ast.Name) \
                    and n.value.id == "self":
                out.append(n.attr)
        return out

    def collect_traced(self, tree: ast.Module) -> tuple[set[int],
                                                        set[str]]:
        """(ids of traced FunctionDef nodes, traced method names)."""
        traced: set[int] = set()
        methods: set[str] = set()
        parents: list[ast.FunctionDef] = []

        def visit(node):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                if any(p.name.startswith(_STEP_BUILDER_PREFIXES)
                       for p in parents):
                    traced.add(id(node))
                parents.append(node)
                for child in ast.iter_child_nodes(node):
                    visit(child)
                parents.pop()
                return
            if isinstance(node, ast.Call):
                if self._is_builder_call(node):
                    for a in list(node.args) \
                            + [k.value for k in node.keywords]:
                        methods.update(self._self_attrs(a))
                        if isinstance(a, ast.Name):
                            methods.add(a.id)
                fname = _terminal_name(node.func)
                if fname in ("shard_map", "_shard_map"):
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            methods.add(a.id)  # resolved by name below
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(tree)
        return traced, methods

    # -- pass 2: flag syncs inside traced bodies --------------------- #

    def _emit(self, node: ast.AST, what: str) -> None:
        qual = self._fn_stack[-1].name if self._fn_stack else "<module>"
        self.out.append(Diagnostic(
            "SRC013", "error", f"{self.path}::{qual}",
            f"{what} is a host sync inside a collective step / "
            "shard_map body — the SPMD stage contract defers syncs "
            "to stage exit (docs/spmd.md)",
            hint="keep the body traceable (jnp/lax only); read counts "
                 "once at stage exit via parallel.spmd.stage_counts / "
                 "fetch",
            line=getattr(node, "lineno", 0)))

    def check_body(self, fn: ast.FunctionDef) -> None:
        self._fn_stack.append(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in _STEP_SYNC_ATTRS \
                        and not node.args:
                    self._emit(node, f"`.{node.func.attr}()`")
                elif node.func.attr in ("asarray", "array") \
                        and _terminal_name(node.func.value) \
                        in _NP_NAMES:
                    self._emit(node, f"`np.{node.func.attr}(...)`")
                elif node.func.attr == "device_get" \
                        and _terminal_name(node.func.value) == "jax":
                    self._emit(node, "`jax.device_get`")
        self._fn_stack.pop()

    def run(self, tree: ast.Module) -> None:
        traced, method_names = self.collect_traced(tree)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if id(node) in traced or node.name in method_names:
                self.check_body(node)


class _RawJitChecker(ast.NodeVisitor):
    """SRC009: raw ``jax.jit`` calls (or decorators, including
    ``partial(jax.jit, ...)``) in execs//ops/ modules instead of
    ``cached_jit``.

    Scope is syntactic and module-wide like SRC005: a raw jit
    ANYWHERE in an exec/ops module produces a program the ledger and
    the compile-cache stats cannot see.  ``pjit`` is out of scope (the
    collective tier's partitioned programs have their own lifecycle);
    ``cached_jit`` itself obviously passes."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        # bare decorator forms (`@jax.jit`, `@jit`) are plain
        # Attribute/Name nodes — no Call for visit_Call to see;
        # `@partial(jax.jit, ...)` IS a Call and lands there
        for dec in node.decorator_list:
            if not isinstance(dec, ast.Call) and self._is_raw_jit(dec):
                self._emit(dec, "a raw `@jax.jit` decorator")
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def _emit(self, node: ast.AST, what: str) -> None:
        qual = self._fn_stack[-1] if self._fn_stack else "<module>"
        self.out.append(Diagnostic(
            "SRC009", "error", f"{self.path}::{qual}",
            f"{what} bypasses the jit cache — the compiled program is "
            "unmetered (no ledger attribution, no cache stats, no "
            "cross-query sharing)",
            hint="route it through execs.jit_cache.cached_jit with a "
                 "structural key (and op= for per-operator roofline "
                 "attribution); baseline only sites that genuinely "
                 "have no stable key",
            line=getattr(node, "lineno", 0)))

    @staticmethod
    def _is_raw_jit(e: ast.expr) -> bool:
        """A reference to jax.jit / bare jit (imported from jax)."""
        if isinstance(e, ast.Attribute):
            return e.attr == "jit" and _terminal_name(e.value) == "jax"
        return isinstance(e, ast.Name) and e.id == "jit"

    def visit_Call(self, node: ast.Call) -> None:
        if self._is_raw_jit(node.func):
            self._emit(node, "raw `jax.jit(...)`")
        elif _terminal_name(node.func) == "partial" and node.args \
                and self._is_raw_jit(node.args[0]):
            self._emit(node, "`partial(jax.jit, ...)`")
        self.generic_visit(node)


class _UseAfterDonateChecker(ast.NodeVisitor):
    """SRC010: a local passed at a donated argnum of a
    ``cached_jit(..., donate=...)`` program, referenced after the call
    site.

    Per-function, source-order analysis: assignments like
    ``fn = cached_jit(key, mk, donate=(0,))`` register ``fn`` as a
    donating callable with its (constant) argnums; a later ``fn(b)``
    marks ``b`` consumed at that line; any LOAD of ``b`` on a later
    line in the same function is flagged.  A re-assignment of the
    consumed name clears it (the local now holds something else).
    When the donate spec is not a constant tuple/int, every positional
    arg of the call is treated as donated — conservative, loud.
    ``transfer.run_consuming`` is the blessed escape hatch and is not
    tracked (it owns the consumed-state bookkeeping)."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out

    @staticmethod
    def _donate_spec(call: ast.Call):
        """The donate= keyword of a cached_jit call: a tuple of
        argnums, None when absent/disabled, or "all" when not
        statically known."""
        if _terminal_name(call.func) != "cached_jit":
            return None
        for kw in call.keywords:
            if kw.arg != "donate":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and v.value is None:
                return None
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                nums = []
                for el in v.elts:
                    if isinstance(el, ast.Constant) \
                            and isinstance(el.value, int):
                        nums.append(el.value)
                    else:
                        return "all"
                return tuple(nums) if nums else None
            return "all"
        return None

    @staticmethod
    def _own_nodes(fn: ast.FunctionDef):
        """Walk a function body WITHOUT descending into nested
        function definitions — each function is its own scope and is
        checked by its own visit (no double reports)."""
        stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(node))

    def _check_function(self, fn: ast.FunctionDef) -> None:
        consumed: dict[str, tuple[int, str]] = {}  # name -> (line, fn)
        rebound: dict[str, int] = {}  # name -> earliest later rebind

        def consume_args(call: ast.Call, spec, via: str) -> None:
            args = call.args
            idxs = range(len(args)) if spec == "all" else spec
            for i in idxs:
                if i < len(args) and isinstance(args[i], ast.Name):
                    consumed[args[i].id] = (call.lineno, via)

        # pass 0: EVERY assignment to each name, in source order (the
        # walk itself is not source ordered) — a call site then
        # resolves against the latest assignment at or before its own
        # line, so re-binding a donating name to a plain callable (or
        # vice versa) is honored for straight-line code
        assigns: dict[str, list[tuple[int, object]]] = {}
        for node in self._own_nodes(fn):
            if isinstance(node, ast.Assign):
                spec = self._donate_spec(node.value) \
                    if isinstance(node.value, ast.Call) else None
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        assigns.setdefault(t.id, []).append(
                            (node.lineno, spec))
        for history in assigns.values():
            history.sort()

        def spec_at(name: str, line: int):
            """The donate spec of `name`'s latest assignment at or
            before `line` (None = plain / not assigned yet)."""
            spec = None
            for lineno, s in assigns.get(name, ()):
                if lineno > line:
                    break
                spec = s
            return spec

        for node in self._own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name):
                spec = spec_at(node.func.id, node.lineno)
                if spec is not None:
                    consume_args(node, spec, node.func.id)
            elif isinstance(node.func, ast.Call):
                spec = self._donate_spec(node.func)
                if spec is not None:
                    consume_args(node, spec, "cached_jit(...)")
        if not consumed:
            return
        for node in self._own_nodes(fn):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Store) \
                    and node.id in consumed \
                    and node.lineno >= consumed[node.id][0]:
                rebound[node.id] = min(
                    node.lineno, rebound.get(node.id, node.lineno))
        # lambda parameters SHADOW: a Load of a consumed name inside a
        # lambda whose own params bind that name refers to the
        # parameter, not the donated local — exempt those Loads
        shadowed: set[int] = set()
        for node in self._own_nodes(fn):
            if not isinstance(node, ast.Lambda):
                continue
            params = {a.arg for a in (node.args.posonlyargs
                                      + node.args.args
                                      + node.args.kwonlyargs)}
            if not params & set(consumed):
                continue
            for sub in ast.walk(node.body):
                if isinstance(sub, ast.Name) and sub.id in params:
                    shadowed.add(id(sub))
        for node in self._own_nodes(fn):
            if not (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)) \
                    or id(node) in shadowed:
                continue
            hit = consumed.get(node.id)
            if hit is None or node.lineno <= hit[0] \
                    or node.lineno >= rebound.get(node.id, 1 << 30):
                continue  # before the donate, or after a rebind
            line, via = hit
            self.out.append(Diagnostic(
                "SRC010", "error", f"{self.path}::{fn.name}",
                f"`{node.id}` was donated into `{via}` at line {line} "
                "and referenced afterwards — its device buffers "
                "belong to the program's outputs now (use-after-free "
                "on a TPU backend)",
                hint="route donation through "
                     "transfer.run_consuming (memoizes the output, "
                     "marks the batch consumed) or stop referencing "
                     "the donated local; baseline only intentional "
                     "sites",
                line=node.lineno))

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_function(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


#: SRC011: accessor calls whose results are SHARED cache objects
#: (serving/work_share.py) — every concurrent consumer sees the same
#: Python objects, so mutating them corrupts other tenants' queries
_SHARED_ACCESSORS = {"subscribe_units", "lookup_result"}
#: method names that mutate their receiver in place
_MUTATOR_METHODS = {"append", "extend", "insert", "pop", "remove",
                    "clear", "update", "sort", "reverse",
                    "setdefault", "popitem", "add", "discard"}


def _base_name(node: ast.expr) -> Optional[str]:
    """The root Name of an attribute/subscript chain
    (``x.cols[0].data`` -> ``x``), or None."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


class _SharedMutationChecker(ast.NodeVisitor):
    """SRC011: in-place mutation of shared-cache objects (see module
    doc).  Per function: pass 1 collects tainted local names —
    assignments from the shared accessors, loop targets iterating
    them, and propagation through plain / attribute / subscript
    reads; pass 2 flags item/attribute assignment, ``del``, augmented
    assignment, and mutator-method calls whose receiver chain roots
    in a tainted name.  Conservative within one function body (taint
    is not flow-sensitive): shared-cache consumers are expected to
    copy before touching, which never taints."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out

    # -- pass 1: taint ---------------------------------------------- #

    @staticmethod
    def _names_in_target(t: ast.expr) -> list[str]:
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, (ast.Tuple, ast.List)):
            out = []
            for e in t.elts:
                out.extend(_SharedMutationChecker._names_in_target(e))
            return out
        return []

    @staticmethod
    def _is_shared_source(v: ast.expr, tainted: set) -> bool:
        if isinstance(v, ast.Call):
            return _terminal_name(v.func) in _SHARED_ACCESSORS
        return _base_name(v) in tainted

    def _collect_taint(self, fn: ast.FunctionDef) -> set:
        tainted: set = set()
        # iterate to a fixpoint so `b = dev; c = b.columns` taints c
        # regardless of statement visit order (bounded: names only
        # ever get ADDED)
        while True:
            before = len(tainted)
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    if self._is_shared_source(node.value, tainted):
                        for t in node.targets:
                            tainted.update(self._names_in_target(t))
                elif isinstance(node, (ast.For, ast.AsyncFor)):
                    if self._is_shared_source(node.iter, tainted):
                        tainted.update(
                            self._names_in_target(node.target))
            if len(tainted) == before:
                return tainted

    # -- pass 2: mutations ------------------------------------------ #

    def _flag(self, name: str, node: ast.AST, what: str) -> None:
        self.out.append(Diagnostic(
            "SRC011", "error", self.path,
            f"{what} mutates `{name}`, a shared-cache object "
            "(serving/work_share.py) — other tenants' in-flight "
            "queries and the cache itself see the same Python "
            "object, so in-place mutation corrupts their results",
            hint="cached results are immutable by contract: copy "
                 "first (table.combine_chunks(), list(...), a fresh "
                 "batch) or re-materialize, then mutate the copy "
                 "(docs/work_sharing.md)",
            line=getattr(node, "lineno", 0)))

    def _check_function(self, fn: ast.FunctionDef) -> None:
        tainted = self._collect_taint(fn)
        if not tainted:
            return
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        name = _base_name(t)
                        if name in tainted:
                            self._flag(name, node,
                                       "item/attribute assignment")
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target,
                              (ast.Attribute, ast.Subscript)):
                    name = _base_name(node.target)
                    if name in tainted:
                        self._flag(name, node, "augmented assignment")
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        name = _base_name(t)
                        if name in tainted:
                            self._flag(name, node, "del")
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _MUTATOR_METHODS:
                name = _base_name(node.func.value)
                if name in tainted:
                    self._flag(name, node,
                               f"`.{node.func.attr}()`")

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_function(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


#: handler-body calls that prove the exception was CLASSIFIED before
#: being absorbed (the execs/retry gate + the fault-accounting hooks)
_CLASSIFY_CALLS = {"classify", "is_retryable", "should_cpu_fallback",
                   "note_recovered"}
#: broad exception type names whose swallow can eat a retryable device
#: error (XlaRuntimeError subclasses RuntimeError)
_BROAD_EXC = {"Exception", "BaseException", "RuntimeError"}


class _SwallowChecker(ast.NodeVisitor):
    """SRC008: broad except clauses that swallow without consulting
    the retry classification gate in recovery-critical modules
    (execs/, io/, shuffle/).

    A handler is CLEAN when its body re-raises anywhere (`raise`,
    bare or not) or calls one of the classification/fault-accounting
    helpers; everything else absorbing Exception/BaseException/
    RuntimeError (or a bare except) is flagged.  Narrow catches
    (OSError, ValueError, a project error type) are out of scope —
    they cannot eat an XlaRuntimeError."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True  # bare except
        types = t.elts if isinstance(t, ast.Tuple) else [t]
        return any(_terminal_name(x) in _BROAD_EXC for x in types)

    @staticmethod
    def _routes(handler: ast.ExceptHandler) -> bool:
        for n in ast.walk(handler):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                if _terminal_name(n.func) in _CLASSIFY_CALLS:
                    return True
                # FORWARDING the caught exception object as a call's
                # SOLE argument (queue.put(e), chan.finish(e),
                # callback(e)) is propagation, not a swallow — the
                # consumer re-raises it.  Deliberately narrow: a
                # logging call (`log.warning("failed: %s", e)`) passes
                # the exception among other args and IS a swallow.
                if handler.name and len(n.args) == 1 \
                        and not n.keywords \
                        and isinstance(n.args[0], ast.Name) \
                        and n.args[0].id == handler.name:
                    return True
        return False

    def visit_Try(self, node: ast.Try) -> None:
        for handler in node.handlers:
            if self._is_broad(handler) and not self._routes(handler):
                qual = self._fn_stack[-1] if self._fn_stack \
                    else "<module>"
                caught = "bare except" if handler.type is None else \
                    f"except {ast.unparse(handler.type)}"
                self.out.append(Diagnostic(
                    "SRC008", "warning", f"{self.path}::{qual}",
                    f"`{caught}` swallows without routing through "
                    "retry.classify — it can eat a retryable device "
                    "error and skip the recovery ladder",
                    hint="re-raise, or consult execs/retry.classify / "
                         "is_retryable before absorbing (and "
                         "note_recovered for absorbed injected "
                         "faults); baseline only intentional "
                         "fall-back-to-slow-path sites",
                    line=getattr(handler, "lineno", 0)))
        self.generic_visit(node)


class _PersistWriteChecker(ast.NodeVisitor):
    """SRC015: raw persistence of serialized executables outside
    spark_rapids_tpu/persist.py (see Rules).  Taint is local-name
    based: a name assigned from a ``.serialize()`` call (or from an
    already-tainted name) is a serialized artifact; any ``.write()``
    taking it — or taking a ``.serialize()`` call directly — is a raw
    unvalidated write.  ``pickle.dump``/``dumps``/``Pickler`` are
    flagged outright (the engine has exactly one blessed pickle
    surface, the python_worker pipe protocol, which is out of
    scope)."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []
        self._tainted: set[str] = set()

    def _qual(self) -> str:
        return self._fn_stack[-1] if self._fn_stack else "<module>"

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        saved = self._tainted
        self._tainted = set()
        self.generic_visit(node)
        self._tainted = saved
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @staticmethod
    def _is_serialize_call(v: ast.expr) -> bool:
        return isinstance(v, ast.Call) \
            and _terminal_name(v.func) == "serialize"

    def _is_tainted(self, v: ast.expr) -> bool:
        if self._is_serialize_call(v):
            return True
        return isinstance(v, ast.Name) and v.id in self._tainted

    def visit_Assign(self, node: ast.Assign) -> None:
        if self._is_tainted(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._tainted.add(t.id)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        name = _terminal_name(node.func)
        if name in ("dump", "dumps", "Pickler") \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "pickle":
            self.out.append(Diagnostic(
                "SRC015", "error", f"{self.path}::{self._qual()}",
                f"raw `pickle.{name}` outside the persist module — "
                "engine artifacts written to disk must go through "
                "persist.py's validated writer (magic + checksum + "
                "env stamp + atomic rename)",
                hint="route the write through "
                     "spark_rapids_tpu/persist.py, or keep the data "
                     "in memory",
                line=node.lineno))
        elif name == "write" and node.args \
                and self._is_tainted(node.args[0]):
            self.out.append(Diagnostic(
                "SRC015", "error", f"{self.path}::{self._qual()}",
                "raw `.write()` of a serialized executable — a file "
                "written outside persist.py's validated writer has "
                "no torn-write protection and no staleness stamp",
                hint="route the artifact through "
                     "spark_rapids_tpu/persist.py's save_* APIs",
                line=node.lineno))
        self.generic_visit(node)


class _RawDevicePutChecker(ast.NodeVisitor):
    """SRC016: raw ``jax.device_put`` calls in execs//parallel/
    modules instead of the placement choke point.

    Scope is syntactic and module-wide like SRC009: a raw device_put
    anywhere in these layers moves a stage-input leaf without
    classifying it into the ``placement.*`` counters, so they
    silently stop covering that transfer.  parallel/placement.py IS
    the choke point — exempt by construction."""

    def __init__(self, path: str, out: list[Diagnostic]):
        self.path = path
        self.out = out
        self._fn_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node.name)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @staticmethod
    def _is_raw_device_put(e: ast.expr) -> bool:
        """A reference to jax.device_put / bare device_put."""
        if isinstance(e, ast.Attribute):
            return e.attr == "device_put" \
                and _terminal_name(e.value) == "jax"
        return isinstance(e, ast.Name) and e.id == "device_put"

    def visit_Call(self, node: ast.Call) -> None:
        if self._is_raw_device_put(node.func):
            qual = self._fn_stack[-1] if self._fn_stack else "<module>"
            self.out.append(Diagnostic(
                "SRC016", "error", f"{self.path}::{qual}",
                "raw `jax.device_put` bypasses the stage-input "
                "placement choke point — the transfer is unclassified "
                "(no placement.* counter)",
                hint="route the move through parallel/placement."
                     "place_piece (per-shard pieces) or "
                     "placement.adopt_batch (whole batches)",
                line=node.lineno))
        self.generic_visit(node)


def _is_exec_module(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "execs" in parts


def _is_timed_module(path: str) -> bool:
    """SRC006 scope: exec bodies and the pipeline layer."""
    parts = path.replace("\\", "/").split("/")
    return "execs" in parts or "parallel" in parts


def _is_sync_hazard_module(path: str) -> bool:
    """SRC007 scope: exec bodies and the device kernels under ops/."""
    parts = path.replace("\\", "/").split("/")
    return "execs" in parts or "ops" in parts


def _is_program_module(path: str) -> bool:
    """SRC009 scope: the modules that compile device programs.
    execs/jit_cache.py IS the cache — exempt by construction."""
    norm = path.replace("\\", "/")
    if norm.endswith("execs/jit_cache.py"):
        return False
    parts = norm.split("/")
    return "execs" in parts or "ops" in parts


def _is_sharing_module(path: str) -> bool:
    """SRC011 scope: the layers that consume shared-cache objects
    (the serving tier, exec stream loops, the scan subscribers).
    serving/work_share.py IS the cache — its own bookkeeping mutates
    its own lists by construction — so it is exempt."""
    norm = path.replace("\\", "/")
    if norm.endswith("serving/work_share.py"):
        return False
    parts = norm.split("/")
    return any(p in parts for p in ("serving", "execs", "io"))


def _is_collective_step_module(path: str) -> bool:
    """SRC013 scope: the modules that define collective step /
    shard_map bodies — the exchange program builders, the SPMD stage
    builders, and the collective execs whose methods trace into
    them."""
    norm = path.replace("\\", "/")
    return norm.endswith(("parallel/exchange.py", "parallel/spmd.py",
                          "execs/collective.py"))


def _is_wait_module(path: str) -> bool:
    """SRC012 scope: the serving tier and the parallel substrate — the
    layers whose blocking waits sit on the serving path a cancelled
    query must be able to unwind through."""
    parts = path.replace("\\", "/").split("/")
    return "serving" in parts or "parallel" in parts


def _is_persist_scope_module(path: str) -> bool:
    """SRC015 scope: the whole engine EXCEPT persist.py (it IS the
    validated writer) and python_worker/ (its pickle use is the UDF
    pipe protocol — function frames over stdin, never disk files)."""
    norm = path.replace("\\", "/")
    if norm.endswith("spark_rapids_tpu/persist.py") \
            or norm == "persist.py":
        return False
    return "python_worker" not in norm.split("/")


def _is_placement_scope_module(path: str) -> bool:
    """SRC016 scope: exec bodies and the parallel substrate — the
    layers that feed stage inputs — EXCEPT parallel/placement.py (it
    IS the classified mover)."""
    norm = path.replace("\\", "/")
    if norm.endswith("parallel/placement.py"):
        return False
    parts = norm.split("/")
    return "execs" in parts or "parallel" in parts


def _is_recovery_module(path: str) -> bool:
    """SRC008 scope: the layers whose exceptions feed the recovery
    ladder.  execs/retry.py IS the classification gate — exempt."""
    norm = path.replace("\\", "/")
    parts = norm.split("/")
    if norm.endswith("execs/retry.py"):
        return False
    return any(p in parts for p in ("execs", "io", "shuffle"))


def lint_source_text(src: str, path: str) -> list[Diagnostic]:
    """Lint one module's source text (unit-test entry point)."""
    out: list[Diagnostic] = []
    try:
        tree = ast.parse(src)
    except SyntaxError as exc:
        out.append(Diagnostic(
            "SRC000", "error", path, f"syntax error: {exc}",
            line=exc.lineno or 0))
        return out
    finder = _RegionFinder()
    finder.visit(tree)
    for region, why in finder.finish():
        _RegionChecker(region, why, path, out).visit(region)
    if _is_exec_module(path):
        _ExecSyncChecker(path, out).visit(tree)
    if _is_timed_module(path):
        _RawTimingChecker(path, out).visit(tree)
    if _is_sync_hazard_module(path):
        _HostMaterializeChecker(path, out).visit(tree)
    if _is_program_module(path):
        _RawJitChecker(path, out).visit(tree)
        _UseAfterDonateChecker(path, out).visit(tree)
    if _is_recovery_module(path):
        _SwallowChecker(path, out).visit(tree)
    if _is_sharing_module(path):
        _SharedMutationChecker(path, out).visit(tree)
    if _is_wait_module(path):
        _UnboundedWaitChecker(path, out).visit(tree)
    if _is_collective_step_module(path):
        _CollectiveStepSyncChecker(path, out).run(tree)
    if _is_wire_module(path):
        _WireHandlerChecker(path, out).visit(tree)
    if _is_persist_scope_module(path):
        _PersistWriteChecker(path, out).visit(tree)
    if _is_placement_scope_module(path):
        _RawDevicePutChecker(path, out).visit(tree)
    return out


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def iter_source_files(root: Optional[str] = None) -> Iterable[str]:
    root = root or _package_root()
    for dirpath, dirnames, filenames in sorted(os.walk(root)):
        dirnames[:] = sorted(d for d in dirnames
                             if not d.startswith(("_", ".")))
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def check_sources(root: Optional[str] = None) -> list[Diagnostic]:
    """Lint every engine source file under spark_rapids_tpu/."""
    root = root or _package_root()
    base = os.path.dirname(root)
    out: list[Diagnostic] = []
    for path in iter_source_files(root):
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, base)
        out.extend(lint_source_text(src, rel))
    return out
