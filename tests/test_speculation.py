"""Output sizing: the predictor's contracts and the speculative
aggregate and exchange sizing (parallel/speculation.py), and the join,
which counts and then expands: its expansion's capacity is the bucket
of the counted pairs, one blocking readback a stream batch behind the
next batch's probe, on/off parity across every join type."""

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


def _host_join(join_type, left, right) -> Counter:
    """The plain reference: a dict of the build side's rows by key, a
    loop over the stream side's; NULL keys match nothing."""
    by_key: dict = {}
    for k, w in zip(right["k"].to_pylist(), right["w"].to_pylist()):
        by_key.setdefault(k, []).append(w)
    out: Counter = Counter()
    seen = set()
    for k, v in zip(left["k"].to_pylist(), left["v"].to_pylist()):
        ws = by_key.get(k, ()) if k is not None else ()
        for w in ws:
            out[(k, v, k, w)] += 1
        if ws:
            seen.add(k)
        elif join_type != "inner":
            out[(k, v, None, None)] += 1
    if join_type == "full_outer":
        for k, ws in by_key.items():
            if k not in seen:
                out.update((None, None, k, w) for w in ws)
    return out


def _expand_events(run) -> tuple:
    """`run()`'s result and the attributes of its `join.expand`
    events, in the order the chunks were dispatched."""
    from spark_rapids_tpu import trace

    trace.clear()
    trace.enable()
    try:
        out = run()
        return out, [s.attrs for s in trace.snapshot()
                     if s.name == "join.expand"]
    finally:
        trace.disable()
        trace.clear()


@pytest.mark.parametrize("count", [600, 700, 1024, 1025])
def test_join_expands_at_the_bucket_of_its_counted_pairs(count):
    """Every stream row matches one build row, so a batch of `count`
    rows counts `count` pairs: under two thirds of the 1,024 bucket
    (600), over two thirds of it (700: 1.5 x the count is 1,050, which
    a guess pads to 2,048), at its edge (1,024) and one past it.  The
    expansion runs at `pad_capacity` of the count on the first batch
    and on the later ones alike, and the tail batch at its own."""
    from spark_rapids_tpu.columnar.column import pad_capacity

    tail = 40
    n = 2 * count + tail
    left = pa.table({"k": np.arange(n, dtype=np.int64) % 50,
                     "v": np.arange(n, dtype=np.int64)})
    right = pa.table({"k": np.arange(50, dtype=np.int64),
                      "w": np.arange(50, dtype=np.int64)})
    ex = _join_exec("inner", left, right, batch_rows=count)
    got, events = _expand_events(lambda: _rows(ex))
    assert got == _host_join("inner", left, right)
    assert [e["rows"] for e in events] == [count, count, tail]
    assert [e["capacity"] for e in events] \
        == [pad_capacity(count)] * 2 + [pad_capacity(tail)]
    assert all(e["offset"] == 0 and e["join_type"] == "inner"
               for e in events)
    assert ex.metrics["expandRows"].value == n
    assert ex.metrics["expandCapacityRows"].value \
        == sum(e["capacity"] for e in events)
    # the fill: over a half for any count over MIN_CAPACITY
    assert 2 * n > ex.metrics["expandCapacityRows"].value


@pytest.mark.parametrize("join_type", ("inner", "left_outer",
                                       "full_outer"))
def test_join_continuation_chunks_equal_a_host_join(join_type):
    """`join.outputChunkRows` well under a batch's pair count (a 32-row
    batch matches some 200 pairs): the expansion goes on in chunks of
    64 from `offset`, the last at the bucket of what is left, and the
    rows are the host reference's."""
    get_conf().set("spark.rapids.tpu.sql.join.outputChunkRows", 64)
    left, right = _join_tables(n_stream=128, dup=8)
    ex = _join_exec(join_type, left, right)
    got, events = _expand_events(lambda: _rows(ex))
    assert got == _host_join(join_type, left, right)
    assert len(events) > 2 * ex.metrics["probeBatches"].value
    assert {e["capacity"] for e in events if e["rows"] == 64} == {64}
    assert all(e["capacity"] < 2 * max(e["rows"], 8) for e in events)
    assert any(e["offset"] >= 128 for e in events)
    assert sum(e["rows"] for e in events) \
        == ex.metrics["expandRows"].value


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


def test_join_one_blocking_readback_a_batch_at_the_default_conf():
    """The join counts, then expands: at the DEFAULT conf every stream
    batch pays exactly one blocking `join.probe` readback, each after
    the NEXT batch's probe was dispatched; nothing is harvested on the
    side and nothing is guessed under that tag."""
    assert get_conf().get(ENABLED) is True  # the default
    left, right = _join_tables(n_stream=320)
    ex = _join_exec("inner", left, right)
    with P.trace_events() as events:
        got = _rows(ex)
    ev = [kind for kind, tag in events if tag == "join.probe"]
    n_batches = ev.count("dispatch")
    assert n_batches == 10
    assert set(ev) == {"dispatch", "readback"}, ev
    assert ev.count("readback") == n_batches
    seen_d = seen_r = 0
    for kind in ev:
        if kind == "dispatch":
            seen_d += 1
        else:
            seen_r += 1
            assert seen_d >= min(seen_r + 1, n_batches), ev
    assert "join.probe" not in SP.stats()
    assert got == _host_join("inner", left, right)


def test_join_second_pass_over_the_same_batches_compiles_nothing():
    """A capacity is a function of the count, so the same batches ask
    for the same programs: the second pass misses the cache nowhere."""
    from spark_rapids_tpu.execs.jit_cache import cache_stats

    left, right = _join_tables(n_stream=320, dup=3)
    first = _rows(_join_exec("left_outer", left, right))
    before = cache_stats()["misses"]
    assert _rows(_join_exec("left_outer", left, right)) == first
    assert cache_stats()["misses"] == before


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


def _per_batch_agg_sizing(monkeypatch) -> None:
    """Put `_agg_df` on the aggregate's per-batch sizing path: no
    partial is small enough to defer its count, sixteen batches, one
    partition."""
    from spark_rapids_tpu.execs import aggregate as agg_mod

    monkeypatch.setattr(agg_mod, "_DEFER_SYNC_CAP", 0)
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    get_conf().set("spark.rapids.tpu.sql.shuffle.partitions", 1)


def _table_rows(tbl) -> list:
    return sorted(zip(*tbl.to_pydict().values()))


def test_aggregate_speculative_sizing_parity(session, monkeypatch):
    """Force the per-batch sizing path (capacity cap 0) on a grouped
    aggregate: speculative registration + async harvest + drain
    reconciliation must match speculation off exactly (integer sums)."""
    _per_batch_agg_sizing(monkeypatch)
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
    _per_batch_agg_sizing(monkeypatch)
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
    df.collect(engine="tpu")
    out = df.explain("analyze")
    assert "jit cache:" in out
    # the join's fill: pairs counted, and the capacities expanded at
    assert "expandRows" in out and "expandCapacityRows" in out, out


def test_speculation_stats_and_hit_rate(session, monkeypatch):
    """Driven by the aggregate's per-batch sizing path (the join sizes
    from its count and records nothing here)."""
    _per_batch_agg_sizing(monkeypatch)
    _agg_df(session).collect(engine="tpu")
    st = SP.stats()
    assert set(st) == {"agg.size"}
    s = st["agg.size"]
    assert s["hits"] + s["overflows"] > 0
    assert 0.0 <= SP.hit_rate() <= 1.0
    assert SP.hit_rate(tags=("agg.size",)) == SP.hit_rate()
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
