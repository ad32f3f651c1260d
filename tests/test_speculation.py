"""Speculative output sizing (parallel/speculation.py): predictor
contracts, CPU-parity of speculative joins across every join type at
forced under/over-speculated capacities, speculative aggregate and
exchange sizing, and THE acceptance test — zero blocking sizing
readbacks on the steady-state portion of an inner join stream."""

from __future__ import annotations

from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.arrow import to_arrow
from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.parallel import pipeline as P
from spark_rapids_tpu.parallel import speculation as SP
from spark_rapids_tpu.session import TpuSession, col, sum_

ENABLED = "spark.rapids.tpu.sql.speculation.enabled"
WARMUP = "spark.rapids.tpu.sql.speculation.warmupBatches"
FORCE = "spark.rapids.tpu.sql.speculation.testForceCapacity"


@pytest.fixture(autouse=True)
def _fresh_speculation_state():
    """Predictors are process-global and keyed structurally: a join
    warmed by one test must not pre-warm the identical join in the
    next (warm-up assertions depend on it)."""
    SP.reset_predictors()
    SP.reset_stats()
    yield
    SP.reset_predictors()
    SP.reset_stats()


@pytest.fixture
def session():
    return TpuSession()


# -- predictor unit contracts ------------------------------------------- #

def test_predictor_warms_up_then_buckets():
    p = SP.predictor(("t", "k1"))
    assert p.predict() is None  # warm-up: no observations
    p.observe(100)
    cap = p.predict()
    # pow2 bucket of 100 * safetyFactor(1.5) = 150 -> 256
    assert cap == 256
    # ceiling clamp
    assert p.predict(cap_ceiling=64) == 64


def test_predictor_warmup_conf_respected():
    get_conf().set(WARMUP, 3)
    p = SP.predictor(("t", "k2"))
    p.observe(10)
    p.observe(10)
    assert p.predict() is None
    p.observe(10)
    assert p.predict() is not None


def test_predictor_force_capacity_override():
    get_conf().set(FORCE, 20)
    p = SP.predictor(("t", "k3"))
    assert p.predict() is None  # force does not bypass warm-up
    p.observe(100000)
    assert p.predict() == 32  # pad_capacity(20), not the observed bucket


@pytest.mark.parametrize("order", [(30, 55, 30, 30, 55, 55),
                                   (55, 30, 55, 55, 30, 30),
                                   (30, 55, 55, 30, 30, 55)])
def test_predictor_answers_alike_whatever_order_the_counts_came_in(order):
    """Two tasks whose counts lie either side of a bucket's edge over
    the safety factor (64 / 1.5 = 42.7): an average that leans to the
    newest count answers 64 after two 30s and 128 after two 55s, and
    each answer it had not given before is a new expansion program.
    Once a round has shown both counts, the largest of those seen
    answers 128 after any order."""
    p = SP.predictor(("t", "order", order))
    p.observe(order[0])
    p.observe(order[1])
    said = set()
    for n in order[2:]:
        said.add(p.predict())
        p.observe(n)
    assert said == {128}


def test_predictor_forgets_counts_older_than_its_window():
    p = SP.predictor(("t", "window"))
    p.observe(1000)
    assert p.predict() == 2048
    for _ in range(SP._WINDOW - 1):
        p.observe(10)
    assert p.predict() == 2048  # the 1000 is the oldest it still holds
    p.observe(10)
    assert p.predict() == 16


def test_predictor_shared_by_key():
    assert SP.predictor(("a", 1)) is SP.predictor(("a", 1))
    assert SP.predictor(("a", 1)) is not SP.predictor(("a", 2))


# -- join fixtures ------------------------------------------------------ #

def _join_tables(n_stream=200, dup=2, with_nulls=True):
    rng = np.random.default_rng(11)
    k = rng.integers(0, 50, n_stream).astype(np.int64).tolist()
    if with_nulls:
        for i in range(0, n_stream, 17):
            k[i] = None  # NULL keys never match
    left = pa.table({
        "k": pa.array(k, pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n_stream), pa.int64()),
    })
    right = pa.table({
        # keys 10..59: some stream keys match nothing, some build rows
        # match nothing (exercises every outer path)
        "k": np.repeat(np.arange(10, 60, dtype=np.int64), dup),
        "w": np.arange(50 * dup, dtype=np.int64),
    })
    return left, right


def _join_exec(join_type, left, right, batch_rows=32):
    from spark_rapids_tpu.execs.join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.io.scan import ArrowSourceExec

    lsrc = ArrowSourceExec(left, batch_rows=batch_rows)
    rsrc = ArrowSourceExec(right)
    return TpuShuffledHashJoinExec([col("k")], [col("k")], join_type,
                                   lsrc, rsrc)


def _rows(exec_) -> Counter:
    """Multiset of result rows (joins pass values through bit-exact,
    so exact equality is safe; Counter sidesteps None-sort issues)."""
    out = Counter()
    for b in exec_.execute():
        t = to_arrow(b)
        out.update(zip(*[c.to_pylist() for c in t.columns]))
    return out


ALL_JOIN_TYPES = ("inner", "left_outer", "right_outer", "full_outer",
                  "left_semi", "left_anti", "cross")


@pytest.mark.parametrize("join_type", ALL_JOIN_TYPES)
def test_join_speculative_parity_all_types(join_type):
    """Speculation on == speculation off, every join type, multi-batch
    stream (warm-up batch + steady state in one run)."""
    n = 60 if join_type == "cross" else 200
    left, right = _join_tables(n_stream=n)
    get_conf().set(ENABLED, True)
    on = _rows(_join_exec(join_type, left, right))
    get_conf().set(ENABLED, False)
    off = _rows(_join_exec(join_type, left, right))
    assert on == off
    assert sum(on.values()) > 0 or join_type == "left_anti" \
        or sum(off.values()) == 0


@pytest.mark.parametrize("join_type", ("inner", "left_outer",
                                       "full_outer"))
def test_join_forced_under_speculation_continuation(join_type):
    """testForceCapacity far below the true pair count: every
    speculated batch overflows and must emit continuation chunks from
    offset=cap — same rows as speculation off."""
    left, right = _join_tables(n_stream=128, dup=8)
    get_conf().set(ENABLED, True)
    get_conf().set(FORCE, 8)  # each 32-row batch matches ~32*8 pairs
    ex = _join_exec(join_type, left, right)
    on = _rows(ex)
    assert ex.metrics["specOverflows"].value > 0, \
        "forced under-speculation never took the continuation path"
    get_conf().set(ENABLED, False)
    off = _rows(_join_exec(join_type, left, right))
    assert on == off


def test_join_forced_over_speculation_masked_rows_trimmed():
    """testForceCapacity far above the true count: every batch hits,
    and the dead padded rows never reach the output."""
    left, right = _join_tables(n_stream=128)
    get_conf().set(ENABLED, True)
    get_conf().set(FORCE, 1 << 14)
    ex = _join_exec("inner", left, right)
    on = _rows(ex)
    assert ex.metrics["specHits"].value > 0
    assert ex.metrics["specOverflows"].value == 0
    get_conf().set(ENABLED, False)
    off = _rows(_join_exec("inner", left, right))
    assert on == off


@pytest.mark.parametrize("join_type", ("inner", "left_outer",
                                       "left_anti"))
def test_join_empty_build_side(join_type):
    left, _right = _join_tables(n_stream=96)
    empty_right = pa.table({
        "k": pa.array([], pa.int64()),
        "w": pa.array([], pa.int64()),
    })
    get_conf().set(ENABLED, True)
    on = _rows(_join_exec(join_type, left, empty_right))
    get_conf().set(ENABLED, False)
    off = _rows(_join_exec(join_type, left, empty_right))
    assert on == off
    if join_type == "inner":
        assert sum(on.values()) == 0
    else:
        assert sum(on.values()) == 96  # every stream row preserved


def test_join_warmup_batches_pay_the_sync():
    """warmupBatches=3 with lookahead 1: the first 4 retires happen
    before the predictor has 3 observations at dispatch time, so
    exactly 4 blocking sizing readbacks; everything after speculates."""
    get_conf().set(ENABLED, True)
    get_conf().set(WARMUP, 3)
    left, right = _join_tables(n_stream=320)
    with P.trace_events() as events:
        on = _rows(_join_exec("inner", left, right))
    ev = [kind for kind, tag in events if tag == "join.probe"]
    assert ev.count("readback") == 4
    assert ev.count("spec_hit") + ev.count("spec_overflow") \
        == ev.count("dispatch") - 4
    get_conf().set(ENABLED, False)
    off = _rows(_join_exec("inner", left, right))
    assert on == off


def test_join_steady_state_zero_blocking_sizing_readbacks(monkeypatch):
    """THE acceptance criterion: with speculation on (the default),
    the steady-state portion of an inner-join stream performs ZERO
    blocking sizing readbacks — only the warm-up prefix (warmupBatches
    + the lookahead window) pays the sync.

    The harvest grace window is widened FOR THIS TEST ONLY: under
    full-suite load the harvester thread can be preempted past the
    25ms production grace, degrading one speculative retire into an
    extra blocking readback — a CI scheduler stall, not a speculation
    regression.  The wide window keeps this test measuring the
    dispatch PROTOCOL (did the exec route sizing through a harvest
    future?) instead of thread-scheduling noise; a real regression —
    the exec syncing inline per batch — still fails, because the
    warm-up readbacks it would multiply are inline device_read calls
    that never touch the grace path."""
    monkeypatch.setattr(P, "_HARVEST_GRACE_S", 2.0)
    left, right = _join_tables(n_stream=480)
    assert get_conf().get(ENABLED) is True  # the default
    ex = _join_exec("inner", left, right)
    with P.trace_events() as events:
        got = _rows(ex)
    ev = [kind for kind, tag in events if tag == "join.probe"]
    n_batches = ev.count("dispatch")
    assert n_batches >= 10
    # warm-up prefix: warmupBatches(1) + lookahead(1) blocking syncs
    assert ev.count("readback") == 2, ev
    # ... and they are all BEFORE the first speculative retire: the
    # steady state is sync-free
    first_spec = next(i for i, k in enumerate(ev)
                      if k in ("spec_hit", "spec_overflow"))
    assert all(k != "readback" for k in ev[first_spec:]), ev
    # every steady-state batch resolved speculatively
    assert ev.count("spec_hit") + ev.count("spec_overflow") \
        == n_batches - 2
    assert ex.metrics["specHits"].value \
        + ex.metrics["specOverflows"].value == n_batches - 2
    assert sum(got.values()) > 0


def test_join_speculation_off_trace_is_the_pr2_pattern():
    """The kill switch restores today's readback pattern exactly: one
    blocking readback per stream batch, no async harvests, no
    speculation events."""
    get_conf().set(ENABLED, False)
    left, right = _join_tables(n_stream=160)
    with P.trace_events() as events:
        _rows(_join_exec("inner", left, right))
    ev = [kind for kind, tag in events if tag == "join.probe"]
    assert set(ev) <= {"dispatch", "readback"}
    assert ev.count("readback") == ev.count("dispatch")


# -- aggregate sizing --------------------------------------------------- #

def _agg_df(session, n=4096, keys=64):
    rng = np.random.default_rng(5)
    t = pa.table({
        "k": rng.integers(0, keys, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),  # int: exact
    })
    return (session.create_dataframe(t)
            .group_by(col("k")).agg((sum_(col("v")), "sv")))


def _table_rows(tbl) -> list:
    return sorted(zip(*tbl.to_pydict().values()))


def test_aggregate_speculative_sizing_parity(session, monkeypatch):
    """Force the per-batch sizing path (capacity cap 0) on a grouped
    aggregate: speculative registration + async harvest + drain
    reconciliation must match speculation off exactly (integer sums)."""
    from spark_rapids_tpu.execs import aggregate as agg_mod

    monkeypatch.setattr(agg_mod, "_DEFER_SYNC_CAP", 0)
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    get_conf().set("spark.rapids.tpu.sql.shuffle.partitions", 1)
    df = _agg_df(session)
    get_conf().set(ENABLED, True)
    with P.trace_events() as events:
        on = df.collect(engine="tpu")
    # the sizing path ran, and ran sync-free: async harvests happened,
    # zero blocking agg.size readbacks (warm-up estimates by capacity
    # upper bound instead of syncing)
    agg_ev = [kind for kind, tag in events if tag == "agg.size"]
    assert agg_ev.count("readback_async") > 0
    assert agg_ev.count("readback") == 0, agg_ev
    get_conf().set(ENABLED, False)
    off = df.collect(engine="tpu")
    assert _table_rows(on) == _table_rows(off)


def test_aggregate_speculation_off_sizing_path_unchanged(session,
                                                         monkeypatch):
    """Kill switch: the sizing path pays its one blocking readback per
    big partial, exactly the pre-speculation behavior."""
    from spark_rapids_tpu.execs import aggregate as agg_mod

    monkeypatch.setattr(agg_mod, "_DEFER_SYNC_CAP", 0)
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    get_conf().set("spark.rapids.tpu.sql.shuffle.partitions", 1)
    get_conf().set(ENABLED, False)
    df = _agg_df(session)
    with P.trace_events() as events:
        df.collect(engine="tpu")
    agg_ev = [kind for kind, tag in events if tag == "agg.size"]
    assert agg_ev.count("readback_async") == 0
    assert agg_ev.count("readback") > 0


# -- exchange split sizing ---------------------------------------------- #

def test_exchange_speculative_split_parity(session):
    """Hash-exchange map tasks harvest split counts asynchronously:
    zero blocking exchange.split readbacks, same shuffle routing."""
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    get_conf().set("spark.rapids.tpu.sql.shuffle.partitions", 4)
    df = _agg_df(session, n=2048, keys=32)
    get_conf().set(ENABLED, True)
    with P.trace_events() as events:
        on = df.collect(engine="tpu")
    ex_ev = [kind for kind, tag in events if tag == "exchange.split"]
    assert ex_ev.count("readback_async") > 0
    assert ex_ev.count("readback") == 0, ex_ev
    get_conf().set(ENABLED, False)
    off = df.collect(engine="tpu")
    assert _table_rows(on) == _table_rows(off)


# -- the CI smoke (scripts/bench_smoke.sh contract, in tier-1) ---------- #

def test_bench_smoke_queries_match():
    from spark_rapids_tpu.tools.bench_smoke import run_smoke

    out = run_smoke()
    assert set(out) == {"join", "aggregate", "exchange"}
    assert all(v > 0 for v in out.values())


# -- observability ------------------------------------------------------ #

def test_explain_analyze_shows_speculation_and_jit_cache(session):
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 64)
    rng = np.random.default_rng(3)
    left = session.create_dataframe(pa.table({
        "k": rng.integers(0, 16, 512).astype(np.int64),
        "v": rng.integers(0, 9, 512).astype(np.int64),
    }))
    right = session.create_dataframe(pa.table({
        "k": np.arange(16, dtype=np.int64),
        "w": np.arange(16, dtype=np.int64),
    }))
    df = left.join(right, left_on=[col("k")], right_on=[col("k")])
    df.collect(engine="tpu")  # warm the predictor + compile cache
    out = df.explain("analyze")
    assert "jit cache:" in out
    assert "specHits" in out, out  # the join ran sync-free batches


def test_speculation_stats_and_hit_rate():
    left, right = _join_tables(n_stream=320)
    _rows(_join_exec("inner", left, right))
    st = SP.stats()
    assert "join.probe" in st
    s = st["join.probe"]
    assert s["hits"] + s["overflows"] > 0
    assert 0.0 <= SP.hit_rate() <= 1.0
    assert SP.hit_rate(tags=("join.probe",)) == SP.hit_rate()
    SP.reset_stats()
    assert SP.stats() == {}


def test_adaptive_kill_switch_convicts_a_cold_tag():
    """The adaptive kill-switch (speculation.adaptive.minHitRate):
    a tag whose rolling hit rate over a FULL window falls below the
    threshold is auto-disabled — tag_enabled() goes False (the
    predictor-creation sites consult it, reverting the operator to
    honest synchronous sizing), the tag lands in disabled_tags(), and
    the monotonic disabled_total() feeds the `speculation.disabled`
    event-log counter.  A healthy tag is untouched, and reset_stats
    re-arms the windows WITHOUT rewinding the monotonic total."""
    conf = get_conf()
    conf.set("spark.rapids.tpu.sql.speculation.adaptive.minHitRate",
             0.5)
    conf.set("spark.rapids.tpu.sql.speculation.adaptive.window", 4)
    total0 = SP.disabled_total()
    # three misses do NOT convict: the window must be FULL first (one
    # unlucky warm-up batch cannot disable a tag)
    for _ in range(3):
        SP.record_overflow("kill.cold", 64, 100)
    assert SP.tag_enabled("kill.cold")
    SP.record_overflow("kill.cold", 64, 100)
    assert not SP.tag_enabled("kill.cold")
    assert "kill.cold" in SP.disabled_tags()
    assert SP.disabled_total() == total0 + 1
    # healthy tag: full window of hits stays enabled
    for _ in range(5):
        SP.record_hit("kill.warm", 128, 60)
    assert SP.tag_enabled("kill.warm")
    assert "kill.warm" not in SP.disabled_tags()
    # further outcomes on a convicted tag don't re-convict (the total
    # stays monotone and exact)
    SP.record_overflow("kill.cold", 64, 100)
    assert SP.disabled_total() == total0 + 1
    # the eventlog counter surface reads the same monotonic total
    from spark_rapids_tpu.eventlog import counters_snapshot

    assert counters_snapshot()["speculation.disabled"] == \
        SP.disabled_total()
    # reset re-arms (fresh window, tag enabled again) but never
    # rewinds the monotonic total (eventlog deltas clamp at >= 0)
    SP.reset_stats()
    assert SP.tag_enabled("kill.cold")
    assert SP.disabled_total() == total0 + 1


def test_adaptive_kill_switch_off_by_default():
    """With the default minHitRate=0.0 the kill-switch never engages:
    any number of overflows leaves the tag enabled (bit-for-bit the
    pre-adaptive engine)."""
    for _ in range(32):
        SP.record_overflow("kill.default", 8, 999)
    assert SP.tag_enabled("kill.default")
    assert SP.disabled_tags() == []


def test_jit_cache_stats_counters():
    from spark_rapids_tpu.execs import jit_cache as JC

    JC.reset_cache_stats()
    before = JC.cache_stats()
    assert before["hits"] == 0 and before["misses"] == 0
    key = ("teststats", "unique-key-1")
    JC.cached_jit(key, lambda: lambda x: x)
    JC.cached_jit(key, lambda: lambda x: x)
    after = JC.cache_stats()
    assert after["misses"] == 1
    assert after["hits"] == 1
    assert after["hit_rate"] == 0.5
