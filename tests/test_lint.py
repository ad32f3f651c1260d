"""tpulint: seeded-violation fixtures per analyzer + the repo-clean
gate that hooks the linter into the tier-1 test run."""

from __future__ import annotations

import dataclasses

import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs import base as B
from spark_rapids_tpu.lint import evaluate, lint_exec_tree, run_lint
from spark_rapids_tpu.lint.source_rules import lint_source_text
from spark_rapids_tpu.session import TpuSession, col


@pytest.fixture
def session():
    return TpuSession()


def rules(diags):
    return {d.rule for d in diags}


# -- dtype-flow checker ------------------------------------------------- #

def test_dtype_flow_flags_prefix_union_truncation(session):
    """The round-5 UNION bug, reconstructed: an INT member unioned with
    a DOUBLE member.  DataFrame.union now widens, so the mismatched
    plan is built from raw L.Union — the hand-built-plan class the
    checker exists to backstop.  It must flag it WITHOUT executing."""
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.plan.planner import plan_query

    a = session.create_dataframe(
        pa.table({"x": pa.array([1, 2], pa.int32())}))
    b = session.create_dataframe(pa.table({"x": [1.5, 2.5]}))
    root, _meta = plan_query(L.Union([a._plan, b._plan]), session.conf)
    diags = lint_exec_tree(root)
    dt = [d for d in diags if d.rule == "DT001"]
    assert dt, f"DT001 expected, got {diags}"
    assert dt[0].severity == "error"
    assert "double" in dt[0].message and "int" in dt[0].message
    # ... and the seeded violation makes the evaluation gate fail
    assert evaluate(diags)[2] != 0


def test_dtype_flow_clean_union_is_silent(session):
    from spark_rapids_tpu.plan.planner import plan_query

    a = session.create_dataframe(pa.table({"x": [1, 2]}))
    b = session.create_dataframe(pa.table({"x": [3, 4]}))
    root, _ = plan_query(a.union(b)._plan, session.conf)
    assert "DT001" not in rules(lint_exec_tree(root))


def test_dtype_flow_flags_stale_bound_reference(session):
    """Seed a DT002: a projection whose BoundReference declares DOUBLE
    over an INT input column (the stale-binding class)."""
    from spark_rapids_tpu.execs.basic import (
        TpuBatchSourceExec,
        TpuProjectExec,
    )

    schema = T.Schema([T.Field("x", T.INT, True)])
    src = TpuBatchSourceExec([], schema)
    stale = B.BoundReference(0, T.DOUBLE, True, "x")
    root = TpuProjectExec([stale], src)
    diags = lint_exec_tree(root)
    assert "DT002" in rules(diags)
    assert evaluate(diags)[2] != 0


def test_dtype_flow_flags_nonboolean_filter(session):
    from spark_rapids_tpu.execs.basic import (
        TpuBatchSourceExec,
        TpuFilterExec,
    )

    schema = T.Schema([T.Field("x", T.LONG, True)])
    src = TpuBatchSourceExec([], schema)
    root = TpuFilterExec(col("x") + 1, src)  # long-typed "condition"
    assert "DT004" in rules(lint_exec_tree(root))


def test_explain_surfaces_lint_findings(session):
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.session import DataFrame

    a = session.create_dataframe(
        pa.table({"x": pa.array([1, 2], pa.int32())}))
    b = session.create_dataframe(pa.table({"x": [1.5, 2.5]}))
    # raw L.Union: DataFrame.union would widen the mismatch away
    out = DataFrame(L.Union([a._plan, b._plan]), session).explain()
    assert "Lint:" in out and "DT001" in out


# -- plan linter -------------------------------------------------------- #

@dataclasses.dataclass(repr=False)
class _Opaque(B.Expression):
    """Deliberately unregistered expression: tagging must fall back."""

    child: B.Expression

    @property
    def dtype(self) -> T.DataType:
        return self.child.dtype

    def eval(self, ctx):  # pragma: no cover - never executed
        return self.child.eval(ctx)


def test_plan_lint_flags_fallback_island(session):
    """TPU filter over a CPU-falling-back project over a TPU project:
    the classic device->host->device bounce."""
    from spark_rapids_tpu.plan.planner import CpuFallbackExec, plan_query

    df = session.create_dataframe(pa.table({"v": [1.0, 2.0, 3.0]}))
    mid = df.select((col("v") * 2).alias("v2"))
    island = mid.select(_Opaque(col("v2")).alias("u"))
    top = island.filter(col("u") > 2.0)
    root, meta = plan_query(top._plan, session.conf)
    # precondition: the plan really contains a sandwiched fallback
    assert any(isinstance(n, CpuFallbackExec) for n in root._walk())
    diags = lint_exec_tree(root)
    pl = [d for d in diags if d.rule == "PL001"]
    assert pl, f"PL001 expected, got {diags}"
    assert "device->host->device" in pl[0].message
    assert evaluate(diags, strict=True)[2] != 0


def test_plan_lint_flags_sort_under_sort(session):
    df = session.create_dataframe(pa.table({"a": [3, 1], "b": [1, 2]}))
    from spark_rapids_tpu.plan.planner import plan_query

    double_sorted = df.order_by(col("a")).order_by(col("b"))
    root, _ = plan_query(double_sorted._plan, session.conf)
    diags = lint_exec_tree(root)
    assert "PL004" in rules(diags)
    assert evaluate(diags, strict=True)[2] != 0


def test_plan_lint_nondeterministic_above_exchange(session):
    from spark_rapids_tpu.execs.basic import (
        TpuBatchSourceExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.exprs.nondeterministic import Rand
    from spark_rapids_tpu.ops.partition import HashPartitioning

    schema = T.Schema([T.Field("k", T.LONG, True)])
    src = TpuBatchSourceExec([], schema)
    ex = TpuShuffleExchangeExec(
        HashPartitioning([col("k")], 2), src)
    root = TpuProjectExec([col("k"), B.Alias(Rand(seed=7), "r")], ex)
    diags = lint_exec_tree(root)
    assert "PL003" in rules(diags)
    assert "PL002" in rules(diags)  # raw batches straight into shuffle
    assert evaluate(diags, strict=True)[2] != 0


def test_corpus_lowering_failure_is_a_finding(monkeypatch):
    """A planner regression that breaks a corpus query must surface as
    PL000 instead of silently shrinking lint coverage."""
    from spark_rapids_tpu.plan import planner as PL

    def boom(plan, conf=None):
        raise RuntimeError("planner regression")

    monkeypatch.setattr(PL, "plan_query", boom)
    diags = run_lint(source=False, registry=False)
    assert any(d.rule == "PL000" and "planner regression" in d.message
               for d in diags)
    assert evaluate(diags, strict=True)[2] != 0


# -- registry checker --------------------------------------------------- #

def test_registry_flags_unregistered_evaluator(monkeypatch):
    from spark_rapids_tpu.exprs.hashing import Md5
    from spark_rapids_tpu.lint.registry import check_registries
    from spark_rapids_tpu.plan import planner as PL

    monkeypatch.delitem(PL.SUPPORTED_EXPRS, Md5)
    diags = check_registries()
    hits = [d for d in diags
            if d.rule == "REG004" and "Md5" in d.message]
    assert hits, f"REG004 for Md5 expected, got {diags}"
    assert evaluate(diags, strict=True)[2] != 0


def test_registry_flags_missing_typesig(monkeypatch):
    from spark_rapids_tpu.exprs.hashing import Md5
    from spark_rapids_tpu.lint.registry import check_registries
    from spark_rapids_tpu.plan import planner as PL

    monkeypatch.delitem(PL.EXPR_SIGS, Md5)
    assert any(d.rule == "REG001" and "Md5" in d.message
               for d in check_registries())


def test_registry_flags_missing_agg_sig(monkeypatch):
    from spark_rapids_tpu.exprs.aggregates import PivotFirst
    from spark_rapids_tpu.lint.registry import check_registries
    from spark_rapids_tpu.plan import planner as PL

    monkeypatch.delitem(PL.AGG_SIGS, PivotFirst)
    assert any(d.rule == "REG006" and "PivotFirst" in d.message
               for d in check_registries())


def test_registry_flags_bad_wire_codec():
    """REG007: a codec registered without a decoder program key, or
    absent from the round-trip test matrix, is a hard error."""
    from spark_rapids_tpu.columnar import compression as WC
    from spark_rapids_tpu.lint.registry import check_wire_codecs

    class PhantomCodec(WC.Codec):
        name = "phantom"
        decoder_program_key = ""  # nothing names its decoder
        supports_arrays = True

    WC.register_codec(PhantomCodec())
    try:
        diags = check_wire_codecs()
        assert any(d.rule == "REG007" and "decoder_program_key"
                   in d.message and "phantom" in d.message
                   for d in diags), diags
        assert any(d.rule == "REG007" and "round-trip" in d.message
                   and "phantom" in d.message for d in diags), diags
        assert all(d.severity == "error" for d in diags)
    finally:
        WC.unregister_codec("phantom")
    # the live registry itself must be clean
    assert check_wire_codecs() == []


def test_registry_flags_missing_wire_matrix(tmp_path):
    """REG007 with no test matrix file at all: the registry-wide
    coverage contract is itself enforced."""
    from spark_rapids_tpu.lint.registry import check_wire_codecs

    diags = check_wire_codecs(tests_dir=str(tmp_path))
    assert any(d.rule == "REG007" and "matrix is missing"
               in d.message for d in diags), diags


def test_registry_flags_missing_doc_row(tmp_path):
    from spark_rapids_tpu.lint.registry import check_registries

    # an empty docs dir: every registered entry lacks its row
    (tmp_path / "supported_ops.md").write_text("# nothing\n")
    diags = check_registries(docs_dir=str(tmp_path))
    assert sum(d.rule == "REG003" for d in diags) > 100


def test_api_validation_drift_is_hard(monkeypatch):
    from spark_rapids_tpu.tools import api_validation as AV

    monkeypatch.setitem(AV._EXEC_MAP, "FilterExec",
                        ("spark_rapids_tpu.execs.basic", "Gone", ""))
    with pytest.raises(AssertionError, match="FilterExec"):
        AV.assert_no_drift()
    from spark_rapids_tpu.lint.registry import check_registries

    assert any(d.rule == "REG005" and "FilterExec" in d.message
               for d in check_registries())


# -- engine-source linter ----------------------------------------------- #

_ITEM_FIXTURE = """
import jax

@jax.jit
def hot(x):
    return x.sum().item()
"""

_BRANCH_FIXTURE = """
import jax.numpy as jnp
from functools import partial
import jax

@partial(jax.jit, static_argnames=("flag",))
def f(x, flag):
    if flag:            # static: fine
        x = x + 1
    if x.sum() > 0:     # traced: SRC004
        return float(x[0])   # SRC003
    return x

def make_batch_fn(self):
    import numpy as np

    def fn(batch):
        return np.asarray(batch)  # SRC002 inside the jitted inner fn
    return fn
"""


def test_source_lint_flags_item_in_jit_region():
    diags = lint_source_text(_ITEM_FIXTURE, "fixture.py")
    hits = [d for d in diags if d.rule == "SRC001"]
    assert hits and hits[0].severity == "error"
    assert hits[0].line == 6
    assert evaluate(diags)[2] != 0


def test_source_lint_taint_and_static_args():
    got = rules(lint_source_text(_BRANCH_FIXTURE, "fixture.py"))
    assert {"SRC002", "SRC003", "SRC004"} <= got
    # exactly one SRC004: the static-arg branch must NOT be flagged
    diags = lint_source_text(_BRANCH_FIXTURE, "fixture.py")
    assert sum(d.rule == "SRC004" for d in diags) == 1


def test_source_lint_eval_methods_are_regions():
    src = """
class Thing:
    def eval(self, ctx):
        v = ctx.batch.columns[0]
        return v.data.item()
"""
    assert "SRC001" in rules(lint_source_text(src, "fixture.py"))


def test_source_lint_static_shape_reads_are_clean():
    src = """
import jax

@jax.jit
def f(x):
    if x.ndim > 1 and x.shape[0] > 4:
        return x[:4]
    if x is None:
        return x
    n = len(x)
    if n:
        return x
    return x
"""
    assert rules(lint_source_text(src, "fixture.py")) == set()


_SYNC_FIXTURE = """
import jax

class FakeExec:
    def _join_stream(self, batches):
        for b in batches:
            n = int(jax.device_get(b.total))      # SRC005
            m = b.count.item()                    # SRC005
            yield n + m
"""


def test_source_lint_flags_raw_sync_in_exec_module():
    """SRC005: raw device_get/.item() in execs/ must route through the
    pipeline's deferred-readback helper (parallel.pipeline.device_read),
    so stream loops can overlap the sync with the next dispatch."""
    diags = lint_source_text(_SYNC_FIXTURE,
                             "spark_rapids_tpu/execs/fake.py")
    hits = [d for d in diags if d.rule == "SRC005"]
    assert len(hits) == 2, diags
    assert all(h.severity == "warning" for h in hits)
    assert "_join_stream" in hits[0].location
    # strict mode (the repo gate) fails on the seeded violation
    assert evaluate(diags, strict=True)[2] != 0


def test_source_lint_sync_rule_scoped_to_exec_modules():
    """The same code OUTSIDE execs/ (e.g. the pipeline helper itself,
    the metrics layer) is not SRC005's business."""
    diags = lint_source_text(_SYNC_FIXTURE,
                             "spark_rapids_tpu/parallel/fake.py")
    assert "SRC005" not in rules(diags)


_TIMING_FIXTURE = """
import time

class FakeExec:
    def execute(self, batches):
        t0 = time.perf_counter_ns()           # SRC006
        out = [b for b in batches]
        self.elapsed = time.time() - t0       # SRC006
        return out

    def untimed(self):
        time.sleep(0.1)                       # not a clock read
"""


def test_source_lint_flags_raw_timing_in_engine_modules():
    """SRC006: bare time.* readings in execs/ and parallel/ bypass
    MetricTimer (settled metrics) and trace.span (the correlated
    timeline) — nobody can see the number they produce."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/parallel/fake.py"):
        diags = lint_source_text(_TIMING_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC006"]
        assert len(hits) == 2, (path, diags)
        assert all(h.severity == "warning" for h in hits)
        assert "execute" in hits[0].location
    # strict mode (the repo gate) fails on the seeded violation
    assert evaluate(lint_source_text(
        _TIMING_FIXTURE, "spark_rapids_tpu/execs/fake.py"),
        strict=True)[2] != 0


def test_source_lint_timing_rule_scoped_to_engine_modules():
    """The same code elsewhere (io/, tools/, bench drivers) is not
    SRC006's business."""
    diags = lint_source_text(_TIMING_FIXTURE,
                             "spark_rapids_tpu/io/fake.py")
    assert "SRC006" not in rules(diags)


_MATERIALIZE_FIXTURE = """
import numpy as np

class FakeExec:
    def _drain(self, batches):
        out = []
        for b in batches:
            b.total.block_until_ready()          # SRC007
            out.append(np.asarray(b.counts))     # SRC007
        return out

    def blessed(self, counts):
        from spark_rapids_tpu.parallel.pipeline import device_read

        return np.asarray(device_read(counts))   # exempt: host already
"""


def test_source_lint_flags_host_materialization_in_engine_modules():
    """SRC007: raw `.block_until_ready()` / `np.asarray` on device
    values in execs/ AND ops/ (the sync spellings SRC005 misses) must
    route through device_read*/device_read_async; converting a
    device_read* RESULT is exempt (already host memory)."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/ops/fake.py"):
        diags = lint_source_text(_MATERIALIZE_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC007"]
        assert len(hits) == 2, (path, diags)
        assert all(h.severity == "warning" for h in hits)
        assert "_drain" in hits[0].location
    # strict mode (the repo gate) fails on the seeded violation
    assert evaluate(lint_source_text(
        _MATERIALIZE_FIXTURE, "spark_rapids_tpu/ops/fake.py"),
        strict=True)[2] != 0


def test_source_lint_materialize_rule_scoped_to_engine_modules():
    """The same code elsewhere (io/, the pipeline helper itself) is
    not SRC007's business."""
    for path in ("spark_rapids_tpu/io/fake.py",
                 "spark_rapids_tpu/parallel/fake.py"):
        assert "SRC007" not in rules(
            lint_source_text(_MATERIALIZE_FIXTURE, path))


_SWALLOW_FIXTURE = """
class FakeExec:
    def execute(self, batches):
        for b in batches:
            try:
                yield self._process(b)
            except Exception:
                pass                         # SRC008: eats OOM too

    def narrow(self, path):
        try:
            return open(path)
        except OSError:
            return None                      # narrow: not SRC008

    def routed(self, b):
        from spark_rapids_tpu.execs.retry import classify
        try:
            return self._process(b)
        except Exception as e:
            if classify(e) == "retryable":
                return None                  # classified: clean
            raise

    def reraised(self, b):
        try:
            return self._process(b)
        except BaseException:
            self.cleanup()
            raise                            # propagates: clean

    def forwarded(self, q, b):
        try:
            return self._process(b)
        except Exception as e:
            q.put(e)                         # forwarded: clean

    def logged(self, log, b):
        try:
            return self._process(b)
        except Exception as e:
            log.warning("failed: %s", e)     # SRC008: logging a
                                             # swallow is a swallow
"""


def test_source_lint_flags_swallowed_exceptions():
    """SRC008: a broad except in execs//io//shuffle/ that swallows
    without routing through retry.classify can eat a retryable device
    error — the recovery ladder (and chaos-mode fault accounting)
    never sees it.  Forwarding the exception as a call's SOLE argument
    is propagation; passing it among other args (logging) is not."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/io/fake.py",
                 "spark_rapids_tpu/shuffle/fake.py"):
        diags = lint_source_text(_SWALLOW_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC008"]
        assert len(hits) == 2, (path, diags)
        assert all(h.severity == "warning" for h in hits)
        assert "execute" in hits[0].location
        assert "logged" in hits[1].location
    # strict mode (the repo gate) fails on the seeded violation
    assert evaluate(lint_source_text(
        _SWALLOW_FIXTURE, "spark_rapids_tpu/execs/fake.py"),
        strict=True)[2] != 0


def test_source_lint_swallow_rule_scoped_and_exempt():
    """SRC008 does not police modules outside the recovery layers,
    nor execs/retry.py itself (it IS the classification gate)."""
    for path in ("spark_rapids_tpu/parallel/fake.py",
                 "spark_rapids_tpu/ops/fake.py",
                 "spark_rapids_tpu/execs/retry.py"):
        assert "SRC008" not in rules(
            lint_source_text(_SWALLOW_FIXTURE, path)), path


_RAW_JIT_FIXTURE = """
import functools
import jax
from jax import jit
from spark_rapids_tpu.execs.jit_cache import cached_jit


class FakeExec:
    def _compile(self, fn):
        return jax.jit(fn)                   # SRC009: unmetered

    def _compile_bare(self, fn):
        return jit(fn)                       # SRC009: unmetered


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel(x, interpret=False):              # SRC009: decorator form
    return x


@jax.jit
def bare_kernel(x):                          # SRC009: bare decorator
    return x


def metered(key, fn, name):
    return cached_jit(key, lambda: fn, op=name)   # blessed path
"""


def test_source_lint_flags_raw_jit_in_program_modules():
    """SRC009: raw jax.jit/jit/partial(jax.jit) in execs//ops/ is an
    ERROR — the program escapes the jit cache's stats AND the device
    ledger's per-program attribution (docs/device_ledger.md);
    cached_jit is the blessed path."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/ops/fake.py"):
        diags = lint_source_text(_RAW_JIT_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC009"]
        assert len(hits) == 4, (path, diags)
        assert all(h.severity == "error" for h in hits)
        locs = " ".join(h.location for h in hits)
        assert "_compile" in locs and "kernel" in locs \
            and "bare_kernel" in locs
    # an ERROR fails even the non-strict repo gate
    assert evaluate(lint_source_text(
        _RAW_JIT_FIXTURE, "spark_rapids_tpu/execs/fake.py"))[2] != 0


def test_source_lint_raw_jit_rule_scoped_and_exempt():
    """SRC009 does not police modules outside execs//ops/, nor
    execs/jit_cache.py itself (it IS the metered chokepoint)."""
    for path in ("spark_rapids_tpu/parallel/fake.py",
                 "spark_rapids_tpu/columnar/fake.py",
                 "spark_rapids_tpu/execs/jit_cache.py"):
        assert "SRC009" not in rules(
            lint_source_text(_RAW_JIT_FIXTURE, path)), path


_RAW_PERSIST_FIXTURE = """
import pickle

from spark_rapids_tpu import persist


def leak_program(exported, path):
    blob = exported.serialize()
    with open(path, "wb") as f:
        f.write(blob)                        # SRC015: raw blob write


def leak_direct(exported, f):
    f.write(exported.serialize())            # SRC015: direct form


def leak_pickle(batch, path):
    with open(path, "wb") as f:
        pickle.dump(batch, f)                # SRC015: raw pickle
    return pickle.dumps(batch)               # SRC015: raw pickle


def blessed(store, key, conf_fp, sig, fn, avals, budget):
    # the validated writer is the only sanctioned path
    store.save_program_async(key, conf_fp, sig, fn, avals, budget)


def harmless(log, line):
    log.write(line)                          # untainted .write is fine
"""


def test_source_lint_flags_raw_executable_persistence():
    """SRC015: `.write()` of a `.serialize()` product (direct or via a
    local) and `pickle.dump/dumps` outside persist.py are ERRORS — a
    raw file has no magic/checksum/env-stamp/atomic-rename protection
    and a later process would deserialize it blind
    (docs/warm_start.md)."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/serving/fake.py",
                 "spark_rapids_tpu/tools/fake.py"):
        diags = lint_source_text(_RAW_PERSIST_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC015"]
        assert len(hits) == 4, (path, diags)
        assert all(h.severity == "error" for h in hits)
        locs = " ".join(h.location for h in hits)
        assert "leak_program" in locs and "leak_direct" in locs \
            and "leak_pickle" in locs
        assert "blessed" not in locs and "harmless" not in locs
    # an ERROR fails even the non-strict repo gate
    assert evaluate(lint_source_text(
        _RAW_PERSIST_FIXTURE, "spark_rapids_tpu/execs/fake.py"))[2] != 0


def test_source_lint_persist_rule_scoped_and_exempt():
    """SRC015 exempts persist.py itself (it IS the validated writer)
    and python_worker/ (pipe-protocol pickle, never disk files)."""
    for path in ("spark_rapids_tpu/persist.py",
                 "persist.py",
                 "spark_rapids_tpu/python_worker/worker.py"):
        assert "SRC015" not in rules(
            lint_source_text(_RAW_PERSIST_FIXTURE, path)), path


_RAW_DEVICE_PUT_FIXTURE = """
import jax
from jax import device_put

from spark_rapids_tpu.parallel import placement


def leak_put(piece, dev):
    return jax.device_put(piece, dev)        # SRC016: raw move


def leak_bare(piece, dev):
    return device_put(piece, dev)            # SRC016: imported form


def blessed(piece, dev):
    return placement.place_piece(piece, dev)


def blessed_batch(batch, dev):
    return placement.adopt_batch(batch, dev)
"""


def test_source_lint_flags_raw_device_put():
    """SRC016: a raw `jax.device_put` (or bare imported `device_put`)
    in execs//parallel/ is an ERROR — the transfer bypasses the
    placement choke point's host-upload/device-born/d2d classification
    and so moves bytes past its counters (docs/spmd.md)."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/parallel/fake.py"):
        diags = lint_source_text(_RAW_DEVICE_PUT_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC016"]
        assert len(hits) == 2, (path, diags)
        assert all(h.severity == "error" for h in hits)
        locs = " ".join(h.location for h in hits)
        assert "leak_put" in locs and "leak_bare" in locs
        assert "blessed" not in locs
    assert evaluate(lint_source_text(
        _RAW_DEVICE_PUT_FIXTURE, "spark_rapids_tpu/execs/fake.py"))[2] != 0


def test_source_lint_device_put_rule_scoped_and_exempt():
    """SRC016 exempts parallel/placement.py (it IS the classified
    mover) and does not police layers outside execs//parallel/ (the
    columnar upload path and memory tier have their own counters)."""
    for path in ("spark_rapids_tpu/parallel/placement.py",
                 "spark_rapids_tpu/columnar/fake.py",
                 "spark_rapids_tpu/memory/fake.py"):
        assert "SRC016" not in rules(
            lint_source_text(_RAW_DEVICE_PUT_FIXTURE, path)), path


_DONATE_FIXTURE = """
from spark_rapids_tpu.columnar.transfer import run_consuming
from spark_rapids_tpu.execs.jit_cache import cached_jit


class FakeExec:
    def _use_after_donate(self, key, mk, batch):
        fn = cached_jit(key, mk, donate=(0,))
        out = fn(batch)
        return out, batch.num_rows            # SRC010: batch donated

    def _direct_call_form(self, key, mk, batch):
        out = cached_jit(key, mk, donate=(0,))(batch)
        n = batch.capacity                    # SRC010: batch donated
        return out, n

    def _clean_last_use(self, key, mk, batch):
        fn = cached_jit(key, mk, donate=(0,))
        return fn(batch)                      # clean: no use after

    def _clean_rebound(self, key, mk, batch):
        fn = cached_jit(key, mk, donate=(0,))
        batch = fn(batch)                     # rebound: fresh value
        return batch.num_rows                 # clean

    def _clean_no_donate(self, key, mk, batch):
        fn = cached_jit(key, mk)
        out = fn(batch)
        return out, batch.num_rows            # clean: nothing donated

    def _blessed_helper(self, fn, batch):
        out = run_consuming(fn, batch)
        return out, batch.num_rows            # clean: helper owns it

    def _donating_after_plain(self, key, mk, plain, batch):
        fn = plain(key)
        fn = cached_jit(key, mk, donate=(0,))
        out = fn(batch)
        return out, batch.num_rows            # SRC010: latest assign
                                              # wins, batch donated

    def _plain_after_donating(self, key, mk, plain, b, c):
        fn = cached_jit(key, mk, donate=(0,))
        out = fn(c)
        fn = plain(key)
        out2 = fn(b)
        return out, out2, b.num_rows          # clean: b hit the
                                              # PLAIN rebinding

    def _lambda_param_shadows(self, key, mk, batch, rows):
        fn = cached_jit(key, mk, donate=(0,))
        out = fn(batch)
        return out, sorted(rows,
                           key=lambda batch: batch.ordinal)  # clean:
                                              # the lambda's own param
"""


def test_source_lint_flags_use_after_donate():
    """SRC010: referencing a local after it was passed at a donated
    argnum of a cached_jit(donate=...) call is an ERROR in execs//ops/
    — its buffers belong to the program's outputs now.  The
    run_consuming helper, plain cached_jit, last-use and rebound
    shapes all pass."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/ops/fake.py"):
        diags = lint_source_text(_DONATE_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC010"]
        assert len(hits) == 3, (path, [d.render() for d in hits])
        assert all(h.severity == "error" for h in hits)
        locs = " ".join(h.location for h in hits)
        assert "_use_after_donate" in locs \
            and "_direct_call_form" in locs \
            and "_donating_after_plain" in locs
    assert evaluate(lint_source_text(
        _DONATE_FIXTURE, "spark_rapids_tpu/execs/fake.py"))[2] != 0


def test_source_lint_donate_rule_scoped():
    """SRC010 polices execs//ops/ only (jit_cache.py exempt, like
    SRC009)."""
    for path in ("spark_rapids_tpu/parallel/fake.py",
                 "spark_rapids_tpu/columnar/fake.py",
                 "spark_rapids_tpu/execs/jit_cache.py"):
        assert "SRC010" not in rules(
            lint_source_text(_DONATE_FIXTURE, path)), path


_SHARED_MUTATION_FIXTURE = """
from spark_rapids_tpu.serving.work_share import lookup_result


class FakeConsumer:
    def _mutate_subscribed(self, share):
        for unit, dev in share.subscribe_units():
            unit.columns[0] = None            # SRC011: shared unit
            yield dev

    def _mutate_cached_result(self, plan, conf):
        tbl, verdict = lookup_result(plan, conf)
        tbl.append(None)                      # SRC011: cached result
        return tbl

    def _mutate_through_alias(self, share):
        for unit, dev in share.subscribe_units():
            b = dev
            cols = b.columns
            cols.append(None)                 # SRC011: alias chain
            yield b

    def _clean_copy_first(self, share):
        for unit, dev in share.subscribe_units():
            cols = list(unit.columns)
            cols.append(None)                 # clean: list() copied
            yield cols

    def _clean_read_only(self, share):
        for unit, dev in share.subscribe_units():
            yield unit.num_rows               # clean: reads only

    def _clean_unrelated(self, batch):
        batch.columns.append(None)            # clean: not shared
        return batch
"""


def test_source_lint_flags_shared_cache_mutation():
    """SRC011: in-place mutation of a shared-cache object (a
    subscribed scan unit, a cached result, or anything reached
    through one) is an ERROR in serving//execs//io/ — every
    concurrent consumer holds the same Python object.  Copy-first and
    read-only consumers pass, as do mutations of unrelated locals."""
    for path in ("spark_rapids_tpu/serving/fake.py",
                 "spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/io/fake.py"):
        diags = lint_source_text(_SHARED_MUTATION_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC011"]
        assert len(hits) == 3, (path, [d.render() for d in hits])
        assert all(h.severity == "error" for h in hits)
    assert evaluate(lint_source_text(
        _SHARED_MUTATION_FIXTURE,
        "spark_rapids_tpu/serving/fake.py"))[2] != 0


def test_source_lint_shared_mutation_rule_scoped_and_exempt():
    """SRC011 polices serving//execs//io/ only, and
    serving/work_share.py itself — the cache's own bookkeeping — is
    exempt by construction."""
    for path in ("spark_rapids_tpu/parallel/fake.py",
                 "spark_rapids_tpu/columnar/fake.py",
                 "spark_rapids_tpu/serving/work_share.py"):
        assert "SRC011" not in rules(
            lint_source_text(_SHARED_MUTATION_FIXTURE, path)), path


_WAIT_FIXTURE = """
import queue
import threading


class Stage:
    def pump(self, cv, ev, q, t):
        cv.wait()                         # SRC012: unbounded Condition
        ev.wait()                         # SRC012: unbounded Event
        item = q.get()                    # SRC012: unbounded queue get
        t.join()                          # SRC012: unbounded join

    def clean(self, cv, ev, q, t, d, parts):
        cv.wait(0.05)                     # bounded: ok
        ev.wait(timeout=0.05)             # bounded: ok
        item = q.get(timeout=0.05)        # bounded: ok
        t.join(0.1)                       # bounded: ok
        v = d.get("key")                  # dict get: takes a key
        s = ",".join(parts)               # str join: takes an iterable
        reaper = _MetricReaper.get()      # singleton accessor: exempt
        return item, v, s, reaper
"""


def test_source_lint_flags_unbounded_serving_waits():
    """SRC012: timeout-less Condition/Event waits, queue gets and
    thread joins in serving/ and parallel/ are ERRORS — a wait the
    cancel token cannot interrupt is a query session.cancel() and the
    deadline cannot reach.  Bounded waits, dict gets, string joins
    and ClassName.get() singleton accessors all pass."""
    for path in ("spark_rapids_tpu/serving/fake.py",
                 "spark_rapids_tpu/parallel/fake.py"):
        diags = lint_source_text(_WAIT_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC012"]
        assert len(hits) == 4, (path, [d.render() for d in hits])
        assert all(h.severity == "error" for h in hits)
        assert {"wait", "get", "join"} == {
            h.message.split("`.")[1].split("()")[0] for h in hits} \
            | {"wait"}
    assert evaluate(lint_source_text(
        _WAIT_FIXTURE, "spark_rapids_tpu/serving/fake.py"))[2] != 0


def test_source_lint_wait_rule_scoped_to_serving_path():
    """SRC012 polices serving/ and parallel/ only: the reaper's
    queue.get() in execs/ and arbitrary waits elsewhere are other
    rules' (or nobody's) business."""
    for path in ("spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/io/fake.py",
                 "tools/fake.py"):
        assert "SRC012" not in rules(
            lint_source_text(_WAIT_FIXTURE, path)), path


_STEP_SYNC_FIXTURE = """
import numpy as np


def make_route_step(mesh, pid_fn):
    def shard_fn(stacked):
        n = stacked.concrete_num_rows()     # SRC013: sync in step body
        h = np.asarray(stacked.data)        # SRC013: host materialize
        return stacked
    return shard_fn


def local_sort_fn(b):
    b.block_until_ready()                   # SRC013: passed to builder
    return b


class TpuCollectiveFooExec:
    def _route(self, b):
        got = jax.device_get(b.data)        # SRC013: traced method
        return b

    def _drive(self):
        step = make_route_step(self.mesh, lambda b: self._route(b))
        final = make_local_step(self.mesh, local_sort_fn)
        counts = out.concrete_num_rows()    # host driver: out of scope
        host = np.asarray(counts)           # host driver: out of scope
        return counts, host
"""


def test_source_lint_flags_syncs_in_collective_step_bodies():
    """SRC013: host syncs (`concrete_num_rows`, `.block_until_ready`,
    `np.asarray`, `jax.device_get`) inside collective step functions /
    shard_map bodies are ERRORS — the SPMD stage contract defers every
    sync to stage exit (docs/spmd.md).  The host DRIVER code in the
    same modules (round staging, stage-exit counts fetches) stays out
    of scope."""
    for path in ("spark_rapids_tpu/parallel/exchange.py",
                 "spark_rapids_tpu/parallel/spmd.py",
                 "spark_rapids_tpu/execs/collective.py"):
        diags = lint_source_text(_STEP_SYNC_FIXTURE, path)
        hits = [d for d in diags if d.rule == "SRC013"]
        assert len(hits) == 4, (path, [d.render() for d in hits])
        assert all(h.severity == "error" for h in hits)
        assert not any("_drive" in h.location for h in hits)
    assert evaluate(lint_source_text(
        _STEP_SYNC_FIXTURE,
        "spark_rapids_tpu/parallel/spmd.py"))[2] != 0


def test_source_lint_step_sync_rule_scoped_to_collective_modules():
    """SRC013 polices the collective step modules only — the same
    spellings in scan/exec driver modules are SRC005/SRC007's
    business (different severity, different contract)."""
    for path in ("spark_rapids_tpu/io/scan.py",
                 "spark_rapids_tpu/parallel/pipeline.py",
                 "spark_rapids_tpu/execs/aggregate.py"):
        assert "SRC013" not in rules(
            lint_source_text(_STEP_SYNC_FIXTURE, path)), path


_WIRE_FIXTURE = """
import json
import struct


def bad_recv(sock):
    (n,) = struct.unpack("<Q", sock.recv(8))
    return sock.recv(n)                     # SRC014: unclamped length


def good_recv(sock, max_frame):
    (n,) = struct.unpack("<Q", sock.recv(8))
    if n > max_frame:
        raise ValueError("oversized frame")
    return sock.recv(n)                     # clamped: clean


def bad_handler(df, exec_):
    out = df.collect(engine="tpu")          # SRC014: bypasses serving
    tbl = collect_exec(exec_)               # SRC014: bypasses serving
    return out, tbl


def good_handler(pq):
    return list(pq.execute_stream())        # the blessed seam
"""


def test_source_lint_wire_handler_rules():
    """SRC014: under connect/, a wire frame length read via
    struct.unpack must be clamp-guarded before it feeds any
    allocation, and nothing may call .collect()/collect_exec()/
    execute_cpu() directly — wire queries route through the
    admission-controlled serving seam (docs/connect.md)."""
    diags = lint_source_text(
        _WIRE_FIXTURE, "spark_rapids_tpu/connect/fake.py")
    hits = [d for d in diags if d.rule == "SRC014"]
    assert len(hits) == 3, [d.render() for d in hits]
    assert all(h.severity == "error" for h in hits)
    assert any("bad_recv" in h.location for h in hits)
    assert not any("good_recv" in h.location for h in hits)
    assert sum("bad_handler" in h.location for h in hits) == 2
    assert not any("good_handler" in h.location for h in hits)
    assert evaluate(lint_source_text(
        _WIRE_FIXTURE, "spark_rapids_tpu/connect/fake.py"))[2] != 0


def test_source_lint_wire_rule_scoped_to_connect():
    """SRC014 polices connect/ only — shuffle/net.py's framing and
    exec-layer collects are other contracts."""
    for path in ("spark_rapids_tpu/shuffle/net.py",
                 "spark_rapids_tpu/execs/fake.py",
                 "spark_rapids_tpu/tools/fake.py"):
        assert "SRC014" not in rules(
            lint_source_text(_WIRE_FIXTURE, path)), path


def test_shipped_connect_package_is_src014_clean():
    """The shipped wire server/client pass their own rule with ZERO
    baseline entries (the clamp lives in client.recv_frame, shared by
    both ends)."""
    import os

    import spark_rapids_tpu

    root = os.path.dirname(spark_rapids_tpu.__file__)
    for fn in ("server.py", "client.py", "__init__.py"):
        path = os.path.join(root, "connect", fn)
        with open(path) as f:
            diags = lint_source_text(
                f.read(), f"spark_rapids_tpu/connect/{fn}")
        assert "SRC014" not in rules(diags), fn


# -- metric-registry checker (MET001) ----------------------------------- #

_MET_UNSETTLED = """
TOTAL_TIME = "totalTime"

class FooExec:
    def additional_metrics(self):
        return [("fooTime", "MODERATE"), ("fooRows", "ESSENTIAL")]

    def execute(self, batches):
        for b in batches:
            self.metrics["fooRows"].add_lazy(b.num_rows)
            with MetricTimer(self.metrics[TOTAL_TIME]):
                yield b
"""

_MET_UNREGISTERED = """
class BarExec:
    def additional_metrics(self):
        return [("barTime", "MODERATE")]

    def execute(self, batches):
        for b in batches:
            self.metrics["barTime"].add(1)
            self.metrics["barRowz"].add(b.num_rows)  # typo: never reg
            yield b
"""

_MET_DYNAMIC = """
class DynExec:
    def additional_metrics(self):
        return super().additional_metrics() + [("dynTime", "DEBUG")]

    def execute(self, b):
        self.metrics["somethingInherited"].add(1)
"""


def test_met001_flags_registered_but_never_settled():
    from spark_rapids_tpu.lint.metric_rules import check_metric_sources

    diags = check_metric_sources(
        {"spark_rapids_tpu/execs/fake.py": _MET_UNSETTLED})
    hits = [d for d in diags if d.rule == "MET001"]
    assert len(hits) == 1, diags
    assert hits[0].severity == "error"
    assert "fooTime" in hits[0].message
    assert "FooExec" in hits[0].location
    # TOTAL_TIME resolved through the module constant: no false
    # positive on the standard names, and fooRows is settled


def test_met001_flags_settled_but_unregistered():
    from spark_rapids_tpu.lint.metric_rules import check_metric_sources

    diags = check_metric_sources(
        {"spark_rapids_tpu/execs/fake.py": _MET_UNREGISTERED})
    hits = [d for d in diags if d.rule == "MET001"]
    assert len(hits) == 1, diags
    assert "barRowz" in hits[0].message


def test_met001_cross_module_settles_count():
    """Registration in one exec module, settle site in another (the
    scan registers what planner-side helpers tick): no finding."""
    from spark_rapids_tpu.lint.metric_rules import check_metric_sources

    reg = ("class AExec:\n"
           "    def additional_metrics(self):\n"
           "        return [(\"sharedRows\", \"ESSENTIAL\")]\n")
    use = ("def tick(node, n):\n"
           "    node.metrics[\"sharedRows\"].add(n)\n")
    diags = check_metric_sources({
        "spark_rapids_tpu/execs/a.py": reg,
        "spark_rapids_tpu/io/b.py": use,
    })
    assert [d for d in diags if d.rule == "MET001"] == [], diags


def test_met001_dynamic_registration_is_exempt():
    """A computed additional_metrics (super() + extras) cannot be
    enumerated statically — the class is exempt instead of guessed
    at, on BOTH sides of the check."""
    from spark_rapids_tpu.lint.metric_rules import check_metric_sources

    diags = check_metric_sources(
        {"spark_rapids_tpu/execs/fake.py": _MET_DYNAMIC})
    assert [d for d in diags if d.rule == "MET001"] == [], diags


def test_met001_repo_is_clean():
    """The live exec registry has no rot (MET001's first run caught
    ParquetScanExec's never-settled scanTime — now settled around the
    upload in io/scan.py; this pins that it stays settled)."""
    from spark_rapids_tpu.lint.metric_rules import check_metric_registry

    assert check_metric_registry() == []


def test_repo_baseline_covers_only_intentional_syncs():
    """The checked-in baseline holds exactly the intentional execs/
    base.py syncs (metric settlement + ANSI error poll), the SRC006
    timing-infrastructure sites (MetricTimer + reaper, the coalesce
    fetch-wait metric, the pipeline wait counters), the SRC007
    host-conversion infrastructure (metric settlement's np.asarray of
    already-fetched values in execs/base.py, the split-count
    conversion in ops/partition.py) and the SRC008 intentional
    broad-except sites (the metric reaper's drop-the-sample guards,
    the fastpar/pa_filter/scan fall-back-to-slow-path bailouts, the
    shuffle server's bad-request guards and the heartbeat chain's
    keep-alive swallow) plus (since SRC009) the keyless raw-jit
    sites — the fused-pipeline fallback in execs/base.py when a chain
    member has no fuse key, and the module-level Pallas kernel
    wrappers — plus (since SRC012) the ONE intentional unbounded wait:
    prefetch's producer-thread join, whose guaranteed wake-up is the
    channel abort() the same finally issued one line earlier (a
    timeout there would return with the producer still running — the
    exact leaked-stage-thread outcome the cancellation tier forbids).
    Nothing may hide behind the baseline silently."""
    from spark_rapids_tpu.lint.diagnostic import load_baseline

    keys = load_baseline()
    assert keys, "baseline should hold the intentional findings"
    timing_infra = ("spark_rapids_tpu/execs/base.py",
                    "spark_rapids_tpu/execs/coalesce.py",
                    "spark_rapids_tpu/parallel/pipeline.py")
    sync_infra = ("spark_rapids_tpu/execs/base.py",
                  "spark_rapids_tpu/ops/partition.py")
    swallow_infra = ("spark_rapids_tpu/execs/base.py",
                     "spark_rapids_tpu/io/fastpar.py",
                     "spark_rapids_tpu/io/pa_filter.py",
                     "spark_rapids_tpu/io/scan.py",
                     "spark_rapids_tpu/shuffle/net.py")
    rawjit_infra = ("spark_rapids_tpu/execs/base.py",
                    "spark_rapids_tpu/ops/pallas_kernels.py")
    metric_infra = ("spark_rapids_tpu/execs/", "spark_rapids_tpu/io/")
    for k in keys:
        if k.startswith("SRC005::"):
            assert k.startswith(
                "SRC005::spark_rapids_tpu/execs/base.py::"), k
        elif k.startswith("SRC009::"):
            assert any(k.startswith(f"SRC009::{p}::")
                       for p in rawjit_infra), k
        elif k.startswith("SRC010::"):
            # intentional use-after-donate sites (none today: engine
            # donation routes through transfer.run_consuming) may be
            # baselined only inside the program modules the rule scans
            assert any(k.startswith(f"SRC010::spark_rapids_tpu/{p}/")
                       for p in ("execs", "ops")), k
        elif k.startswith("SRC011::"):
            # intentional shared-cache mutation sites (none today:
            # consumers copy-on-write by contract) may be baselined
            # only inside the serving-path modules the rule scans
            assert any(k.startswith(f"SRC011::spark_rapids_tpu/{p}/")
                       for p in ("serving", "execs", "io")), k
        elif k.startswith("SRC012::"):
            # intentional unbounded waits may be baselined only inside
            # the serving-path modules the rule scans, and only where
            # a non-poll wake-up is guaranteed (today: prefetch's
            # abort-then-join teardown)
            assert k == ("SRC012::spark_rapids_tpu/parallel/"
                         "pipeline.py::prefetch::unbounded blocking "
                         "`.join()` on the serving path cannot be "
                         "interrupted by cancellation/deadline"), k
        elif k.startswith("MET001::"):
            # intentional metric-registry placeholders may be
            # baselined, but only inside the exec layers the rule
            # scans (none today: scanTime was fixed, not baselined)
            assert any(k.startswith(f"MET001::{p}")
                       for p in metric_infra), k
        elif k.startswith("SRC007::"):
            assert any(k.startswith(f"SRC007::{p}::")
                       for p in sync_infra), k
        elif k.startswith("SRC008::"):
            assert any(k.startswith(f"SRC008::{p}::")
                       for p in swallow_infra), k
        elif k.startswith("SRC013::"):
            # intentional host syncs inside collective step bodies
            # (none today: the SPMD stage contract defers every sync
            # to stage exit) may be baselined only inside the step
            # modules the rule scans
            assert any(k.startswith(f"SRC013::spark_rapids_tpu/{p}")
                       for p in ("parallel/exchange.py",
                                 "parallel/spmd.py",
                                 "execs/collective.py")), k
        else:
            assert k.startswith("SRC006::"), k
            assert any(k.startswith(f"SRC006::{p}::")
                       for p in timing_infra), k


# -- the repo gate (tier-1 hook) ---------------------------------------- #

def test_repo_is_clean_or_baselined():
    """The scripts/lint.sh contract, in-process: the full lint pass over
    the repo must produce no NEW findings even in --strict mode."""
    diags = run_lint()
    new, _accepted, code = evaluate(diags, strict=True)
    assert code == 0, "new lint findings:\n" + "\n".join(
        d.render() for d in new)


def test_cli_exits_zero_on_repo():
    from spark_rapids_tpu.tools.lint import main

    # source+registry only: the plan corpus ran in the previous test;
    # keep the CLI check cheap inside the tier-1 run
    assert main(["--strict", "--no-plans"]) == 0


def test_baseline_diff_repo_baseline_is_not_stale(capsys):
    """The shipped baseline audits clean: every accepted key still
    fires at HEAD (a stale suppression would silently mask the next
    regression landing on its key).  --no-plans is safe here — every
    baseline entry is an SRC* source finding."""
    from spark_rapids_tpu.tools.lint import main

    assert main(["--baseline-diff", "--no-plans"]) == 0
    out = capsys.readouterr().out
    assert "0 stale" in out and "tpulint: OK" in out


def test_baseline_diff_stale_entry_is_an_error(tmp_path, capsys):
    """A baselined key whose site no longer fires must FAIL the diff
    (and be listed), while keys that still fire stay silent."""
    import json as _json

    from spark_rapids_tpu.lint import load_baseline
    from spark_rapids_tpu.tools.lint import main

    dead = "SRC005::spark_rapids_tpu/gone.py::deleted long ago"
    keys = sorted(load_baseline()) + [dead]
    p = tmp_path / "baseline.json"
    p.write_text(_json.dumps({"accepted": keys}))
    assert main(["--baseline-diff", "--no-plans",
                 "--baseline", str(p)]) == 1
    out = capsys.readouterr().out
    assert f"STALE (baselined, no longer firing): {dead}" in out
    assert "1 stale" in out and "tpulint: FAIL" in out


def test_baseline_diff_added_is_informational(tmp_path, capsys):
    """Findings not yet baselined report as `added` but do NOT fail
    the diff — the strict gate owns failing on new findings; the diff
    subcommand's error condition is exclusively staleness."""
    import json as _json

    from spark_rapids_tpu.tools.lint import main

    p = tmp_path / "empty.json"
    p.write_text(_json.dumps({"accepted": []}))
    assert main(["--baseline-diff", "--no-plans", "--json",
                 "--baseline", str(p)]) == 0
    payload = _json.loads(capsys.readouterr().out)
    assert payload["stale"] == [] and payload["exit"] == 0
    # the repo's intentional (normally-baselined) findings surface
    assert payload["added"], "expected the SRC* intentional findings"
    assert all("::" in k for k in payload["added"])


# -- layering ----------------------------------------------------------- #

#: modules below the serving tier: the compile chokepoint, the memory
#: tier, the SPMD stage layer and the collective execs
_BELOW_SERVING = ("execs/jit_cache.py", "memory", "parallel/spmd.py",
                  "execs/collective.py")


def test_lower_layers_do_not_import_serving():
    """The layer map's arrows point down: nothing in `_BELOW_SERVING`
    imports spark_rapids_tpu.serving, at module level or inside a
    function."""
    import ast
    import pathlib

    import spark_rapids_tpu

    root = pathlib.Path(spark_rapids_tpu.__file__).parent
    files = []
    for rel in _BELOW_SERVING:
        p = root / rel
        files += sorted(p.rglob("*.py")) if p.is_dir() else [p]
    assert len(files) > len(_BELOW_SERVING)
    hits = []
    for f in files:
        for node in ast.walk(ast.parse(f.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                names = [mod] + [f"{mod}.{a.name}" for a in node.names]
            else:
                continue
            if any(n == "spark_rapids_tpu.serving"
                   or n.startswith("spark_rapids_tpu.serving.")
                   for n in names):
                hits.append(f"{f.relative_to(root)}:{node.lineno}")
    assert hits == [], hits
