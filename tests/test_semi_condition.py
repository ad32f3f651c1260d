"""Semi and anti joins with a residual condition (EXISTS / NOT EXISTS
with a correlation beside the equalities, TPC-DS q16's shape) on the
device, against the CPU engine and a numpy reference, under every join
strategy; and `count(DISTINCT x)` beside aggregates whose partials
merge."""

import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import trace
from spark_rapids_tpu.config import BATCH_SIZE_ROWS, get_conf
from spark_rapids_tpu.execs import jit_cache
from spark_rapids_tpu.execs.adaptive import (
    ADAPTIVE_ENABLED,
    TpuAdaptiveJoinExec,
)
from spark_rapids_tpu.execs.join import (
    JOIN_OUTPUT_CHUNK_ROWS,
    TpuBroadcastHashJoinExec,
    TpuRuntimeFilterBuildExec,
    TpuShuffledHashJoinExec,
)
from spark_rapids_tpu.plan.planner import BROADCAST_THRESHOLD, plan_query
from spark_rapids_tpu.session import (
    TpuSession,
    avg,
    col,
    collect_list,
    count,
    count_distinct,
    count_star,
    first,
    max_,
    min_,
    sum_,
)

JOIN_TYPES = ["left_semi", "left_anti"]
STRATEGIES = ["broadcast", "shuffled", "partition_wise", "adaptive"]


def _nulled(rng, values, share: float) -> list:
    return [None if rng.random() < share else int(v) for v in values]


def _sides(seed: int, n_left: int = 300, n_right: int = 500) -> tuple:
    """Orders of a few lines each on both sides: a key of 40 values and
    a warehouse of 4, NULL in a tenth of every column, so a key has
    some tens of pairs and the condition is NULL, TRUE and FALSE over
    them."""
    rng = np.random.default_rng(seed)
    left = pa.table({
        "k": pa.array(_nulled(rng, rng.integers(0, 40, n_left), 0.1),
                      pa.int64()),
        "w": pa.array(_nulled(rng, rng.integers(1, 5, n_left), 0.1),
                      pa.int64()),
        "v": pa.array(rng.integers(0, 10_000, n_left) / 100.0),
    })
    right = pa.table({
        "k2": pa.array(_nulled(rng, rng.integers(0, 40, n_right), 0.1),
                       pa.int64()),
        "w2": pa.array(_nulled(rng, rng.integers(1, 5, n_right), 0.1),
                       pa.int64()),
    })
    return left, right


def _reference(left: pa.Table, right: pa.Table, join_type: str,
               cond) -> list:
    """The left rows, in order, that have (semi) or have not (anti) a
    right row with an equal, non-NULL key for which `cond(w, w2)` is
    TRUE; `cond` sees None for NULL and returns None for NULL."""
    lk, lw = left["k"].to_pylist(), left["w"].to_pylist()
    rk, rw = right["k2"].to_pylist(), right["w2"].to_pylist()
    rows = left.to_pylist()
    out = []
    for i, row in enumerate(rows):
        hit = lk[i] is not None and any(
            rk[j] == lk[i] and cond(lw[i], rw[j]) is True
            for j in range(len(rk)))
        if hit == (join_type == "left_semi"):
            out.append(tuple(row.values()))
    return out


def _ne(a, b):
    return None if a is None or b is None else a != b


def _tuples(table: pa.Table) -> list:
    return [tuple(row.values()) for row in table.to_pylist()]


def _sorted(rows) -> list:
    return sorted(rows, key=lambda t: tuple((x is None, x) for x in t))


def _set_strategy(strategy: str) -> None:
    """The conf a strategy takes (conftest restores it)."""
    conf = get_conf()
    if strategy != "broadcast":
        conf.set(BROADCAST_THRESHOLD.key, 0)
    if strategy in ("partition_wise", "adaptive"):
        conf.set(BATCH_SIZE_ROWS.key, 64)  # sources of several partitions
        conf.set(ADAPTIVE_ENABLED.key, strategy == "adaptive")


def _joins(df) -> list:
    exec_, _ = plan_query(df._plan, get_conf())
    return [e for e in exec_._walk()
            if isinstance(e, (TpuBroadcastHashJoinExec,
                              TpuShuffledHashJoinExec, TpuAdaptiveJoinExec))]


def _held_to_its_strategy(df, strategy: str) -> None:
    (join,) = _joins(df)
    if strategy == "broadcast":
        assert isinstance(join, TpuBroadcastHashJoinExec)
    elif strategy == "adaptive":
        assert isinstance(join, TpuAdaptiveJoinExec)
        assert join._template.condition is not None
    else:
        assert isinstance(join, TpuShuffledHashJoinExec)
        assert join.partition_wise == (strategy == "partition_wise")
    assert join.condition is not None


def _exists(session, left, right, join_type, condition=None):
    a, b = session.create_dataframe(left), session.create_dataframe(right)
    return a.join(b, how=join_type, left_on=[col("k")],
                  right_on=[col("k2")],
                  condition=col("w").ne(col("w2")) if condition is None
                  else condition)


@pytest.mark.parametrize("chunk", ["default", "small"])
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_a_conditional_semi_or_anti_join_is_the_references(
        join_type, strategy, chunk):
    """NULLs in both keys and both condition columns; with `small`
    chunks of 16 pairs a stream batch's mask is carried across
    several chunks."""
    left, right = _sides(16)
    _set_strategy(strategy)
    if chunk == "small":
        get_conf().set(JOIN_OUTPUT_CHUNK_ROWS.key, 16)
    session = TpuSession()
    df = _exists(session, left, right, join_type)
    _held_to_its_strategy(df, strategy)
    want = _reference(left, right, join_type, _ne)
    assert 30 < len(want) < left.num_rows - 30
    for engine in ("tpu", "cpu"):
        got = _tuples(df.collect(engine=engine))
        assert _sorted(got) == _sorted(want), engine
    assert df.schema.names == ["k", "w", "v"]
    assert "[degraded to CPU engine" not in session.history.events[-1] \
        .explain


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_the_condition_decides_beyond_the_keys(join_type):
    """The same keys under three conditions: TRUE for every pair (the
    join without a condition), FALSE for every pair, and an order
    between the two sides' columns."""
    from spark_rapids_tpu.exprs.base import Literal

    left, right = _sides(17)
    session = TpuSession()
    a, b = session.create_dataframe(left), session.create_dataframe(right)
    plain = _tuples(a.join(b, how=join_type, left_on=[col("k")],
                           right_on=[col("k2")]).collect())
    always = _exists(session, left, right, join_type,
                     condition=Literal.of(True))
    assert _sorted(_tuples(always.collect())) == _sorted(plain)
    never = _tuples(_exists(session, left, right, join_type,
                            condition=Literal.of(False)).collect())
    alive = [r for r in _tuples(left)] if join_type == "left_anti" else []
    assert _sorted(never) == _sorted(alive)
    late = _exists(session, left, right, join_type,
                   condition=col("w2") > col("w"))
    want = _reference(left, right, join_type, lambda x, y: None
                      if x is None or y is None else y > x)
    assert _sorted(_tuples(late.collect())) == _sorted(want)
    assert _sorted(_tuples(late.collect(engine="cpu"))) == _sorted(want)


@pytest.mark.parametrize("empty", ["build", "stream", "both"])
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_an_empty_side(join_type, empty):
    left, right = _sides(18)
    if empty in ("build", "both"):
        right = right.slice(0, 0)
    if empty in ("stream", "both"):
        left = left.slice(0, 0)
    session = TpuSession()
    df = _exists(session, left, right, join_type)
    want = _reference(left, right, join_type, _ne)
    if join_type == "left_anti":
        assert want == _tuples(left)  # NULL keys too: NOT EXISTS
    for engine in ("tpu", "cpu"):
        assert _sorted(_tuples(df.collect(engine=engine))) == _sorted(want)


def test_the_cpu_engine_keeps_the_left_order_and_its_nulls():
    """The repaired CPU join: by row identity, so duplicate left rows
    stay duplicates, in the left's order; an anti join keeps a NULL
    key and a NULL condition."""
    session = TpuSession()
    left = pa.table({"k": pa.array([3, 1, None, 1, 2, 1], pa.int64()),
                     "w": pa.array([1, 1, 1, 1, None, 2], pa.int64()),
                     "v": [0.5, 1.5, 2.5, 1.5, 3.5, 4.5]})
    right = pa.table({"k2": pa.array([1, 1, 2, None], pa.int64()),
                      "w2": pa.array([1, None, 3, 1], pa.int64())})
    semi = _exists(session, left, right, "left_semi")
    anti = _exists(session, left, right, "left_anti")
    assert _tuples(semi.collect(engine="cpu")) == [(1, 2, 4.5)]
    assert _tuples(anti.collect(engine="cpu")) == [
        (3, 1, 0.5), (1, 1, 1.5), (None, 1, 2.5), (1, 1, 1.5),
        (2, None, 3.5)]
    for df in (semi, anti):
        assert _sorted(_tuples(df.collect())) == _sorted(
            _tuples(df.collect(engine="cpu")))


def test_a_plain_semi_join_compiles_what_it_did(tracer):
    """Without a condition a semi join presents the cache keys it
    presented before and runs no `semi_cond` program; with one it
    shares the plain probe and adds `semi_cond` programs alone."""
    left, right = _sides(19)
    session = TpuSession()
    a, b = session.create_dataframe(left), session.create_dataframe(right)
    plain = a.join(b, how="left_semi", left_on=[col("k")],
                   right_on=[col("k2")])
    (join,) = _joins(plain)
    assert join.condition is None
    before = set(jit_cache._CACHE)
    plain.collect()
    keys = set(jit_cache._CACHE)
    assert ("left_semi_probe",) + join._cache_key() in keys
    assert ("semi_compact",) in keys
    assert not any(k[0] == "semi_cond" for k in keys - before)
    # a condition no other test uses, so its programs are new here
    _exists(session, left, right, "left_semi",
            condition=col("w").ne(col("w2") + 19)).collect()
    added = set(jit_cache._CACHE) - keys
    assert added and {k[0] for k in added} == {"semi_cond"}


@pytest.fixture
def tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _stamped(desc_part: str) -> list:
    for _ in range(100):  # the history worker stamps at the query's end
        found = [e for e in trace.snapshot() if e.name == "query.operator"
                 and desc_part in e.attrs.get("desc", "")]
        if found:
            return found
        time.sleep(0.05)
    return []


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_the_tracer_and_the_metrics_say_what_the_condition_did(
        tracer, join_type):
    conf = get_conf()
    conf.set("spark.rapids.tpu.trace.enabled", "true")
    conf.set(JOIN_OUTPUT_CHUNK_ROWS.key, 64)
    session = TpuSession(conf)
    left, right = _sides(20)
    df = _exists(session, left, right, join_type)
    df.collect()
    events = trace.snapshot()
    chunks = [e for e in events if e.name == "join.semi_cond"]
    assert len(chunks) > 1
    pairs = sum(e.attrs["pairs"] for e in chunks)
    for e in chunks:
        assert e.attrs["join_type"] == join_type
        assert e.attrs["capacity"] >= e.attrs["pairs"] > 0
        assert e.attrs["capacity"] <= 64
        assert e.attrs["stream_capacity"] >= left.num_rows
        # two int64 columns: 8 bytes of value and 1 of validity each
        assert e.attrs["cond_row_bytes"] == 18
    lk, rk = left["k"].to_pylist(), right["k2"].to_pylist()
    assert pairs == sum(1 for x in lk if x is not None for y in rk
                        if y == x)
    probes = [e for e in events if e.name.startswith("exec.Tpu")
              and e.attrs.get("join_type") == join_type]
    assert probes and all(e.attrs["condition"] is True for e in probes)
    builds = [e for e in events if e.name == "join.build"]
    assert [e.attrs["condition"] for e in builds] == [True]
    (op,) = _stamped(f" {join_type} ")
    assert op.attrs["condPairs"] == pairs
    assert op.attrs["condCapacityRows"] == sum(
        e.attrs["capacity"] for e in chunks)
    assert op.attrs["buildRows"] == right.num_rows
    assert " cond=" in op.attrs["desc"]


def test_explain_prints_the_condition_and_outer_joins_still_fall_back():
    left, right = _sides(21)
    session = TpuSession()
    df = _exists(session, left, right, "left_semi")
    df.collect()
    text = session.history.events[-1].explain
    line = next(ln for ln in text.splitlines() if "left_semi" in ln)
    assert line.lstrip().startswith("* ") and "cond=" in line
    a, b = session.create_dataframe(left), session.create_dataframe(right)
    outer = a.join(b, how="full_outer", left_on=[col("k")],
                   right_on=[col("k2")], condition=col("w").ne(col("w2")))
    assert "full_outer join with residual condition" in outer.explain()


def test_a_conditional_semi_join_keeps_its_runtime_filter(tmp_path):
    """A probe row with no key match has no pair for the condition to
    pass, so the key filter stays sound and is still built."""
    from spark_rapids_tpu.exprs.base import lit

    rng = np.random.default_rng(22)
    n = 4096
    stream = pa.table({"k": rng.integers(0, 512, n).astype(np.int64),
                       "w": rng.integers(1, 5, n).astype(np.int64)})
    build = pa.table({"k2": np.arange(512, dtype=np.int64),
                      "w2": rng.integers(1, 5, 512).astype(np.int64),
                      "d": rng.integers(0, 100, 512).astype(np.int32)})
    paths = []
    for name, table in (("stream", stream), ("build", build)):
        paths.append(os.path.join(str(tmp_path), f"{name}.parquet"))
        pq.write_table(table, paths[-1], row_group_size=1024)
    session = TpuSession()
    a = session.read_parquet(paths[0])
    b = session.read_parquet(paths[1]).where(col("d") < lit(20))
    df = a.join(b, how="left_semi", left_on=[col("k")],
                right_on=[col("k2")], condition=col("w").ne(col("w2")))
    root, _ = plan_query(df._plan, get_conf())
    assert any(isinstance(n, TpuRuntimeFilterBuildExec)
               for n in root._walk())
    got = _sorted(_tuples(df.collect()))
    assert got == _sorted(_tuples(df.collect(engine="cpu")))
    assert 100 < len(got) < n


# -- count(DISTINCT x) beside other aggregates ---------------------------- #

def _grouped_input(rng, n: int) -> pa.Table:
    return pa.table({
        "g": pa.array(_nulled(rng, rng.integers(0, 6, n), 0.05),
                      pa.int64()),
        "x": pa.array(_nulled(rng, rng.integers(0, 30, n), 0.15),
                      pa.int64()),
        "i": pa.array(_nulled(rng, rng.integers(-50, 50, n), 0.1),
                      pa.int32()),
        "d": pa.array([None if rng.random() < 0.1 else float(v)
                       for v in rng.integers(0, 10 ** 6, n) / 100.0]),
    })


MIXES = {
    "sum": lambda: [(sum_(col("d")), "sd"), (sum_(col("i")), "si")],
    "count": lambda: [(count(col("d")), "cd"), (count_star(), "n")],
    "min_max": lambda: [(min_(col("i")), "lo"), (max_(col("d")), "hi")],
    "avg": lambda: [(avg(col("i")), "ai")],
    "all": lambda: [(sum_(col("d")), "sd"), (count(col("i")), "ci"),
                    (min_(col("d")), "lo"), (max_(col("i")), "hi"),
                    (avg(col("d")), "ad"), (count_star(), "n")],
}


def _pandas_reference(table: pa.Table, keys: list, mix: str):
    frame = table.to_pandas()
    if not keys:
        frame["__all"] = 0
        keys = ["__all"]
    grouped = frame.groupby(keys, dropna=False)
    out = {"xd": grouped["x"].nunique()}
    if mix in ("sum", "all"):
        out["sd"] = grouped["d"].sum(min_count=1)
    if mix == "sum":
        out["si"] = grouped["i"].sum(min_count=1)
    if mix == "count":
        out["cd"] = grouped["d"].count()
        out["n"] = grouped.size()
    if mix == "all":
        out["ci"] = grouped["i"].count()
        out["lo"] = grouped["d"].min()
        out["hi"] = grouped["i"].max()
        out["ad"] = grouped["d"].mean()
        out["n"] = grouped.size()
    if mix == "min_max":
        out["lo"] = grouped["i"].min()
        out["hi"] = grouped["d"].max()
    if mix == "avg":
        out["ai"] = grouped["i"].mean()
    return out


def _close(a, b) -> bool:
    if a is None or b is None or (isinstance(b, float) and np.isnan(b)):
        return (a is None or (isinstance(a, float) and np.isnan(a))) and (
            b is None or (isinstance(b, float) and np.isnan(b)))
    return abs(float(a) - float(b)) <= 1e-9 * max(1.0, abs(float(b)))


@pytest.mark.parametrize("grouped", [True, False])
@pytest.mark.parametrize("mix", list(MIXES))
def test_count_distinct_beside_mergeable_aggregates(mix, grouped):
    """NULL distinct values, NULL groups, NULL inputs: both engines
    against pandas, the distinct count exactly."""
    table = _grouped_input(np.random.default_rng(40), 400)
    session = TpuSession()
    df = session.create_dataframe(table)
    keys = ["g"] if grouped else []
    aggs = [(count_distinct(col("x")), "xd")] + MIXES[mix]()
    q = (df.group_by(col("g")) if grouped else df).agg(*aggs)
    assert q.schema.names == keys + [name for _, name in aggs]
    want = _pandas_reference(table, keys, mix)
    for engine in ("tpu", "cpu"):
        got = q.collect(engine=engine).to_pylist()
        assert len(got) == (7 if grouped else 1), engine
        for row in got:
            at = (row["g"] if row["g"] is not None else np.nan) \
                if grouped else 0
            for name, series in want.items():
                exp = series.loc[at] if not (grouped and row["g"] is None) \
                    else series[series.index.isna()].iloc[0]
                exp = None if exp is None or (
                    isinstance(exp, float) and np.isnan(exp)) else exp
                if name == "xd":
                    assert row[name] == exp, (engine, row)
                else:
                    assert _close(row[name], exp), (engine, name, row, exp)


@pytest.mark.parametrize("grouped", [True, False])
def test_count_distinct_beside_others_over_an_empty_input(grouped):
    """A global aggregate of nothing is one row: counts 0, the rest
    NULL; a grouped one is no row."""
    table = _grouped_input(np.random.default_rng(41), 50)
    session = TpuSession()
    df = session.create_dataframe(table).where(col("g") > 100)
    aggs = [(count_distinct(col("x")), "xd")] + MIXES["all"]()
    q = (df.group_by(col("g")) if grouped else df).agg(*aggs)
    want = [] if grouped else [{"xd": 0, "sd": None, "ci": 0, "lo": None,
                                "hi": None, "ad": None, "n": 0}]
    for engine in ("tpu", "cpu"):
        assert q.collect(engine=engine).to_pylist() == want, engine


@pytest.mark.parametrize("other", [
    lambda: (first(col("i")), "f"), lambda: (collect_list(col("i")), "l")])
def test_other_mixes_keep_raising_by_name(other):
    session = TpuSession()
    df = session.create_dataframe(_grouped_input(
        np.random.default_rng(42), 20))
    fn, _ = other()
    with pytest.raises(ValueError, match=f"beside {fn.name}"):
        df.agg((count_distinct(col("x")), "xd"), other())


# -- a semi or anti join probes a dense stream ---------------------------- #

def _batch(values):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    schema = T.Schema([T.Field("k", T.LONG, True)])
    return ColumnarBatch.from_numpy(
        {"k": np.asarray(values, np.int64)}, schema)


def _coalesced(parts, target: int) -> tuple:
    """The semi join's stream coalesce over `parts`, at `target` rows:
    what it put out, and the programs it compiled."""
    from spark_rapids_tpu.execs.basic import TpuBatchSourceExec
    from spark_rapids_tpu.execs.coalesce import TpuCoalesceBatchesExec

    exec_ = TpuCoalesceBatchesExec(
        TpuBatchSourceExec(parts, parts[0].schema), target_rows=target,
        counts_vary=True)
    before = jit_cache.cache_stats()["misses"]
    out = list(exec_.execute())
    return out, jit_cache.cache_stats()["misses"] - before


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_a_semi_or_anti_join_streams_through_a_coalesce(join_type):
    """The stream child is the coalesce exec, at the join's chunk
    target, whose concat takes no shape from a count; the build child
    is left as it was."""
    from spark_rapids_tpu.execs.coalesce import TpuCoalesceBatchesExec

    get_conf().set(JOIN_OUTPUT_CHUNK_ROWS.key, 96)
    left, right = _sides(23, 10, 10)
    session = TpuSession()
    df = session.create_dataframe(left).join(
        session.create_dataframe(right), how=join_type,
        left_on=[col("k")], right_on=[col("k2")])
    (join,) = _joins(df)
    stream, build = join.children
    assert isinstance(stream, TpuCoalesceBatchesExec)
    assert stream._target_rows == 96 and stream._counts_vary
    assert not isinstance(build, TpuCoalesceBatchesExec)


def test_the_stream_is_concatenated_in_order_with_its_seams():
    parts = [_batch(range(i * 10, i * 10 + 10)) for i in range(5)]
    parts.insert(2, _batch([]))
    out, _ = _coalesced(parts, 25)
    assert [b.num_rows for b in out] == [30, 20]
    assert [b.coalesce_seams for b in out] == [(10, 10, 0, 10), (10, 10)]
    got = np.concatenate([np.asarray(b.columns[0].data)[:b.num_rows]
                          for b in out])
    assert got.tolist() == list(range(50))
    # a lone batch passes as it is
    lone = _batch([7, 8])
    out, _ = _coalesced([lone], 25)
    assert out == [lone]


def test_new_block_counts_compile_nothing():
    """An exchange's blocks have other counts in every partition and
    under every seed: once warm, the concat compiles no program for a
    count it has not seen."""
    def blocks(sizes):
        return [_batch(range(n)) for n in sizes]

    _coalesced(blocks((3, 5, 2)), 1 << 10)
    _, compiled = _coalesced(blocks((4, 1, 6)), 1 << 10)
    assert compiled == 0


def test_a_count_still_on_the_device_passes_through():
    """Sizing it would sync: such a batch is handed on as it is."""
    parts = [_batch(range(4)).with_device_num_rows() for _ in range(3)]
    out, _ = _coalesced(parts, 1 << 10)
    assert len(out) == 3 and all(o is p for o, p in zip(out, parts))


@pytest.mark.parametrize("condition", [False, True])
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_a_partition_of_many_blocks_is_probed_once(join_type, condition):
    """Under the partition-wise strategy each partition's stream arrives
    in a block per source batch; the join probes it once a partition,
    and answers as the CPU engine does."""
    left, right = _sides(24, 600, 500)
    _set_strategy("partition_wise")
    get_conf().set(BATCH_SIZE_ROWS.key, 16)
    session = TpuSession()
    a, b = session.create_dataframe(left), session.create_dataframe(right)
    df = a.join(b, how=join_type, left_on=[col("k")], right_on=[col("k2")],
                condition=col("w").ne(col("w2")) if condition else None)
    got = _sorted(_tuples(df.collect()))
    assert got == _sorted(_tuples(df.collect(engine="cpu")))
    root = session.history.events[-1].root
    todo, joins = [root], []
    while todo:
        node = todo.pop()
        todo += node.children
        if f" {join_type} " in node.desc:
            joins.append(node)
    (join,) = joins
    stream = join.children[0]
    assert stream.desc.startswith("TpuCoalesceBatchesExec")
    (exchange,) = stream.children  # of the left side
    assert exchange.metrics["numOutputBatches"] > 2 * join.metrics[
        "probeBatches"] > 0
