"""INTERSECT, EXCEPT and DISTINCT through the DataFrame API and the SQL
grammar, and the null-safe (`<=>`) join keys they lower to: against
Python sets of tuples, where None equals None, under every join
strategy, on both engines."""

import datetime

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import BATCH_SIZE_ROWS, get_conf
from spark_rapids_tpu.execs import jit_cache
from spark_rapids_tpu.execs.adaptive import (
    ADAPTIVE_ENABLED,
    TpuAdaptiveJoinExec,
)
from spark_rapids_tpu.execs.basic import TpuBatchSourceExec
from spark_rapids_tpu.execs.join import (
    TpuBroadcastHashJoinExec,
    TpuShuffledHashJoinExec,
)
from spark_rapids_tpu.exprs.base import ColumnReference as C
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.planner import BROADCAST_THRESHOLD, plan_query
from spark_rapids_tpu.session import AnalysisException, TpuSession, col

DAY0 = datetime.date(2000, 1, 1)


def _side(rng, n: int, names) -> pa.Table:
    """`n` rows over a dense domain (4 x 4 x 3 values and NULL in every
    column), so rows repeat within a side and across the sides, and
    rows differ only in which column is NULL."""
    def nulled(values, share):
        return [None if rng.random() < share else v for v in values]

    words = np.array(["a", "bb", "ccc", "a much longer name than those"])
    return pa.table({
        names[0]: pa.array(nulled(rng.integers(1, 5, n).tolist(), 0.2),
                           pa.int64()),
        names[1]: pa.array(nulled(words[rng.integers(0, 4, n)].tolist(),
                                  0.2), pa.string()),
        names[2]: pa.array(nulled(
            [DAY0 + datetime.timedelta(int(d))
             for d in rng.integers(0, 3, n)], 0.2), pa.date32()),
    })


def _tuples(table: pa.Table) -> list:
    return [tuple(row.values()) for row in table.to_pylist()]


def _sorted(rows) -> list:
    return sorted(rows, key=lambda t: tuple((x is None, x) for x in t))


STRATEGIES = ["broadcast", "shuffled", "partition_wise", "adaptive"]


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
    else:
        assert isinstance(join, TpuShuffledHashJoinExec)
        assert join.partition_wise == (strategy == "partition_wise")
    assert all(join.null_safe) and len(join.null_safe) == 3


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("op", ["intersect", "subtract"])
def test_a_set_operation_is_pythons(op, strategy):
    """Duplicates on both sides, NULLs in every key column of both
    sides: the result is distinct, and None equals None."""
    rng = np.random.default_rng(38)
    left = _side(rng, 300, ("k", "name", "day"))
    right = _side(rng, 150, ("rk", "rname", "rday"))
    _set_strategy(strategy)
    session = TpuSession()
    df = getattr(session.create_dataframe(left), op)(
        session.create_dataframe(right))
    _held_to_its_strategy(df, strategy)
    ours, theirs = set(_tuples(left)), set(_tuples(right))
    want = ours & theirs if op == "intersect" else ours - theirs
    assert len(want) > 10 and any(None in t for t in want)
    for engine in ("tpu", "cpu"):
        got = _tuples(df.collect(engine=engine))
        assert len(got) == len(set(got)), engine
        assert set(got) == want, engine
    assert df.schema.names == ["k", "name", "day"]


def test_rows_that_differ_only_in_which_column_is_null():
    session = TpuSession()
    a = session.create_dataframe(pa.table({
        "x": pa.array([1, None, 1, None, 1], pa.int64()),
        "y": pa.array([None, 1, 1, None, None], pa.int64())}))
    b = session.create_dataframe(pa.table({
        "x": pa.array([None, None, 2], pa.int64()),
        "y": pa.array([1, None, None], pa.int64())}))
    for engine in ("tpu", "cpu"):
        got = _sorted(_tuples(a.intersect(b).collect(engine=engine)))
        assert got == [(None, 1), (None, None)], engine
        got = _sorted(_tuples(a.subtract(b).collect(engine=engine)))
        assert got == [(1, 1), (1, None)], engine


@pytest.mark.parametrize("empty", ["left", "right"])
@pytest.mark.parametrize("op", ["intersect", "subtract"])
def test_an_empty_side(op, empty):
    """`left_anti` against an empty build side keeps every row,
    `left_semi` none; an empty stream side gives nothing."""
    full = pa.table({"x": pa.array([1, None, 1], pa.int64()),
                     "s": pa.array(["a", None, "a"])})
    none = full.slice(0, 0)
    session = TpuSession()
    left, right = (none, full) if empty == "left" else (full, none)
    df = getattr(session.create_dataframe(left), op)(
        session.create_dataframe(right))
    want = {(1, "a"), (None, None)} \
        if (op, empty) == ("subtract", "right") else set()
    for engine in ("tpu", "cpu"):
        assert set(_tuples(df.collect(engine=engine))) == want, engine


def test_string_keys_of_unlike_widths():
    """The sides' strings sit in device columns of unlike widths (a
    width is the bucket of the side's longest string) and are padded
    to each other's before they are ranked."""
    short = ["ab", "abc", None, "ab", ""]
    long_ = ["abc", "ab" + "x" * 40, None, "abc" + "y" * 70, ""]
    session = TpuSession()
    a = session.create_dataframe(pa.table({"s": pa.array(short)}))
    b = session.create_dataframe(pa.table({"s": pa.array(long_)}))
    for left, right, ours, theirs in ((a, b, short, long_),
                                      (b, a, long_, short)):
        got = {t[0] for t in _tuples(left.intersect(right).collect())}
        assert got == set(ours) & set(theirs) == {"abc", None, ""}
        got = {t[0] for t in _tuples(left.subtract(right).collect())}
        assert got == set(ours) - set(theirs)


@pytest.mark.parametrize("op", ["intersect", "subtract"])
def test_the_chained_form(op):
    """`a.intersect(b).intersect(c)` and `a.subtract(b).subtract(c)`,
    left to right, as q38 and q87 chain them."""
    rng = np.random.default_rng(87)
    sizes = (300, 250, 200) if op == "intersect" else (300, 60, 40)
    tables = [_side(rng, n, ("k", "name", "day")) for n in sizes]
    session = TpuSession()
    a, b, c = (session.create_dataframe(t) for t in tables)
    df = getattr(getattr(a, op)(b), op)(c)
    sets = [set(_tuples(t)) for t in tables]
    want = sets[0] & sets[1] & sets[2] if op == "intersect" \
        else sets[0] - sets[1] - sets[2]
    assert len(want) > 3
    for engine in ("tpu", "cpu"):
        got = _tuples(df.collect(engine=engine))
        assert len(got) == len(want) and set(got) == want, engine


@pytest.mark.parametrize("op", ["intersect", "subtract"])
def test_a_set_operation_under_the_mesh(op):
    """The collective join takes the null-safe flag: a NULL key hashes
    to one destination on both sides, so a `<=>` match stays on its
    shard."""
    rng = np.random.default_rng(8)
    left = _side(rng, 300, ("k", "name", "day"))
    right = _side(rng, 150, ("k", "name", "day"))
    get_conf().set(BROADCAST_THRESHOLD.key, -1)
    session = TpuSession()
    session.enable_collective_shuffle(8)
    try:
        df = getattr(session.create_dataframe(left), op)(
            session.create_dataframe(right))
        exec_, _ = plan_query(df._plan, session.conf)
        tree = exec_.tree_string()
        join_type = "left_semi" if op == "intersect" else "left_anti"
        assert (f"TpuCollectiveHashJoinExec {join_type} "
                "[k<=>k, name<=>name, day<=>day]") in tree, tree
        got = _tuples(df.collect())
    finally:
        session.disable_collective_shuffle()
    ours, theirs = set(_tuples(left)), set(_tuples(right))
    want = ours & theirs if op == "intersect" else ours - theirs
    assert len(got) == len(want) and set(got) == want
    assert any(None in t for t in got)


def _aggregates(plan) -> int:
    return isinstance(plan, L.Aggregate) + sum(
        _aggregates(c) for c in plan.children)


def test_the_outer_distinct_is_left_out_only_over_a_distinct_left_side():
    """Spark lowers to Distinct(Join(...)).  The engine leaves the
    Distinct out where the left side is an aggregate grouped by exactly
    its output columns (q38's and q87's are), or a semi or anti join
    over one: then no two kept rows are equal."""
    session = TpuSession()
    t = pa.table({"x": pa.array([1, 1, None, None, 2], pa.int64())})
    a, b = session.create_dataframe(t), session.create_dataframe(t)
    assert _aggregates(a.intersect(b)._plan) == 1
    assert _aggregates(a.distinct().intersect(b)._plan) == 1
    chained = a.group_by(col("x")).agg().subtract(b).intersect(b)
    assert _aggregates(chained._plan) == 1
    assert isinstance(chained._plan, L.Join)
    # a distinct of a distinct is the one aggregate
    assert _aggregates(a.distinct().distinct()._plan) == 1
    got = _sorted(_tuples(a.intersect(b).collect()))
    assert got == [(1,), (2,), (None,)]
    assert _sorted(_tuples(a.distinct().collect())) == got
    assert chained.collect().num_rows == 0


def test_members_are_matched_by_position_and_widened_as_union_widens():
    session = TpuSession()
    a = session.create_dataframe(pa.table(
        {"i": pa.array([1, 2, None], pa.int32()), "s": ["a", "b", "c"]}))
    b = session.create_dataframe(pa.table(
        {"other": pa.array([2, None, 7], pa.int64()),
         "name": ["b", "c", "a"]}))
    df = a.intersect(b)
    assert df.schema.names == ["i", "s"]
    assert df.schema.fields[0].dtype == T.LONG
    for engine in ("tpu", "cpu"):
        assert set(_tuples(df.collect(engine=engine))) == {(2, "b"),
                                                           (None, "c")}
    with pytest.raises(AnalysisException, match="INTERSECT members"):
        a.intersect(a.select(col("i")))
    with pytest.raises(AnalysisException, match="EXCEPT member column 1"):
        a.subtract(b.select(col("name"), col("other")))


# -- the join under them -------------------------------------------------- #

L_SCHEMA = T.Schema([T.Field("la", T.LONG), T.Field("lb", T.STRING)])
R_SCHEMA = T.Schema([T.Field("ra", T.LONG), T.Field("rb", T.STRING)])


def _source(schema, rows, n_batches: int = 1):
    batches = []
    for chunk in np.array_split(np.arange(len(rows)), n_batches):
        data, valid = {}, {}
        for at, f in enumerate(schema.fields):
            vals = [rows[i][at] for i in chunk]
            valid[f.name] = np.array([v is not None for v in vals], bool)
            data[f.name] = np.array(
                ["" if v is None else v for v in vals], object) \
                if isinstance(f.dtype, T.StringType) else np.array(
                [0 if v is None else v for v in vals], np.int64)
        batches.append(ColumnarBatch.from_numpy(data, schema, valid))
    return TpuBatchSourceExec(batches, schema)


def _rows_of(exec_) -> list:
    out = []
    for b in exec_.execute():
        d = b.to_pydict()
        out += list(zip(*(d[n] for n in d)))
    return _sorted(out)


def _oracle(left, right, join_type, null_safe) -> list:
    """Nested loops: key pair `at` matches when both are equal and not
    None, or, where it is null-safe, when both are None."""
    def match(l, r):
        return all(
            (a == b and a is not None) or (safe and a is None and b is None)
            for a, b, safe in zip(l, r, null_safe))

    out = []
    for l in left:
        hits = [r for r in right if match(l, r)]
        if join_type == "inner":
            out += [l + r for r in hits]
        elif join_type == "left_outer":
            out += [l + r for r in hits] or [l + (None, None)]
        elif (join_type == "left_semi") == bool(hits):
            out.append(l)
    return _sorted(out)


def _mixed_sides(rng, n_left: int, n_right: int) -> tuple:
    def side(n):
        return [(None if rng.random() < 0.25 else int(rng.integers(1, 4)),
                 None if rng.random() < 0.25
                 else str(rng.choice(["p", "q", "a longer one"])))
                for _ in range(n)]

    return side(n_left), side(n_right)


@pytest.mark.parametrize("null_safe", [(True, True), (True, False),
                                       (False, True)])
@pytest.mark.parametrize("join_type", ["inner", "left_outer", "left_semi",
                                       "left_anti"])
def test_a_join_with_null_safe_and_plain_keys(join_type, null_safe):
    """Each key pair by its own rule, and a stream side of several
    batches against one build side."""
    left, right = _mixed_sides(np.random.default_rng(5), 120, 40)
    join = TpuShuffledHashJoinExec(
        [C("la"), C("lb")], [C("ra"), C("rb")], join_type,
        _source(L_SCHEMA, left, 4), _source(R_SCHEMA, right, 2),
        null_safe=null_safe)
    want = _oracle(left, right, join_type, null_safe)
    assert _rows_of(join) == want
    assert want != _oracle(left, right, join_type, (False, False))
    assert join.metrics["probeBatches"].value == 4
    assert join.metrics["streamRows"].value == len(left)


def test_one_null_safe_and_one_plain_key_through_the_dataframe():
    left, right = _mixed_sides(np.random.default_rng(6), 80, 30)
    session = TpuSession()
    a = session.create_dataframe(pa.table({
        "la": pa.array([r[0] for r in left], pa.int64()),
        "lb": pa.array([r[1] for r in left])}))
    b = session.create_dataframe(pa.table({
        "ra": pa.array([r[0] for r in right], pa.int64()),
        "rb": pa.array([r[1] for r in right])}))
    for how in ("inner", "left_semi", "left_anti", "left_outer"):
        df = a.join(b, left_on=["la", "lb"], right_on=["ra", "rb"],
                    how=how, null_safe=[False, True])
        assert "[la=ra, lb<=>rb]" in df.explain()
        want = _oracle(left, right, how, (False, True))
        for engine in ("tpu", "cpu"):
            assert _sorted(_tuples(df.collect(engine=engine))) == want, \
                (how, engine)
    assert "[la<=>ra, lb<=>rb]" in a.join(
        b, left_on=["la", "lb"], right_on=["ra", "rb"],
        null_safe=True).explain()
    with pytest.raises(ValueError, match="null-safe flags"):
        a.join(b, left_on=["la", "lb"], right_on=["ra", "rb"],
               null_safe=[True])


def _plain_join(**kw):
    return TpuShuffledHashJoinExec(
        [C("la"), C("lb")], [C("ra"), C("rb")], "inner",
        _source(L_SCHEMA, [(1, "a")]), _source(R_SCHEMA, [(1, "a")]), **kw)


def test_a_plain_joins_cache_key_and_program_are_unchanged():
    """The flag is part of a program's cache key only where a key has
    it: a join without null-safe keys presents the key it presented
    before PR 38 and traces the program it traced."""
    import jax

    from spark_rapids_tpu.execs.jit_cache import exprs_key

    plain = _plain_join()
    assert plain.null_safe == ()
    assert plain._cache_key() == (
        "join", "inner", True, exprs_key(plain.left_keys),
        exprs_key(plain.right_keys), repr(plain.children[0].schema),
        repr(plain.children[1].schema), repr(plain.schema))
    assert _plain_join(null_safe=(False, False))._cache_key() \
        == plain._cache_key()
    assert plain.node_desc() == "TpuShuffledHashJoinExec inner [la=ra, lb=rb]"
    safe = _plain_join(null_safe=(False, True))
    assert safe._cache_key() == plain._cache_key() + (
        ("null_safe", (False, True)),)
    assert safe.node_desc().endswith("[la=ra, lb<=>rb]")

    def program(join) -> str:
        (build,), (stream,) = (list(c.execute()) for c in (
            join.children[1], join.children[0]))
        return jax.jit(join._probe).lower(build, stream).as_text()

    assert program(plain) == program(_plain_join(null_safe=False))
    assert program(plain) != program(safe)
    # and so for the logical node, whose attributes key the plan cache
    session = TpuSession()
    a = session.create_dataframe(pa.table({"x": [1]}))
    assert a.join(a, on="x")._plan.null_safe == ()
    assert "x=x" in a.join(a, on="x")._plan.node_desc()


def test_no_runtime_filter_is_built_for_a_null_safe_key():
    """A filter drops the probe side's NULL keys, which `<=>` matches."""
    from spark_rapids_tpu.exprs.base import BoundReference
    from spark_rapids_tpu.plan.runtime_filter import _eligible_key_pairs

    keys = [BoundReference(0, T.LONG, True, "a"),
            BoundReference(1, T.LONG, True, "b")]
    both = _eligible_key_pairs(keys, keys, True)
    assert [p[0] for p in both] == [0, 1]
    assert [p[0] for p in _eligible_key_pairs(keys, keys, True,
                                              (True, False))] == [1]


# -- spans, counters and program names ------------------------------------ #

@pytest.fixture
def tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def test_the_tracer_tells_the_set_operations_joins_apart(tracer):
    conf = get_conf()
    conf.set("spark.rapids.tpu.trace.enabled", "true")
    session = TpuSession(conf)
    rng = np.random.default_rng(3)
    a = session.create_dataframe(_side(rng, 90, ("k", "name", "day")))
    b = session.create_dataframe(_side(rng, 60, ("k", "name", "day")))
    a.intersect(b).collect()
    a.subtract(b).collect()
    a.join(b, on="k").collect()
    tags = {key[0] for key in jit_cache._CACHE if isinstance(key[0], str)}
    assert {"left_semi_probe", "left_anti_probe", "semi_compact",
            "join"} <= tags
    events = trace.snapshot()
    probes = [e for e in events if e.name.startswith("exec.Tpu")
              and e.attrs.get("join_type") in ("left_semi", "left_anti")]
    assert probes and all(e.attrs["null_safe"] == 3 for e in probes)
    plain = [e for e in events if e.name.startswith("exec.Tpu")
             and e.attrs.get("join_type") == "inner"]
    assert plain and all(e.attrs["null_safe"] == 0 for e in plain)
    builds = {e.attrs["join_type"]: e.attrs["null_safe"]
              for e in events if e.name == "join.build"}
    assert builds == {"left_semi": 3, "left_anti": 3, "inner": 0}


def test_a_semi_joins_operator_instant_counts_what_it_probed_and_kept(
        tracer):
    import time

    conf = get_conf()
    conf.set("spark.rapids.tpu.trace.enabled", "true")
    session = TpuSession(conf)
    rng = np.random.default_rng(4)
    left = _side(rng, 90, ("k", "name", "day"))
    right = _side(rng, 60, ("k", "name", "day"))
    a = session.create_dataframe(left).distinct()
    kept = a.intersect(session.create_dataframe(right)).collect().num_rows
    for _ in range(100):  # the history worker stamps at the query's end
        stamped = [e for e in trace.snapshot()
                   if e.name == "query.operator"
                   and " left_semi " in e.attrs.get("desc", "")]
        if stamped:
            break
        time.sleep(0.05)
    (semi,) = stamped
    assert semi.attrs["streamRows"] == len(set(_tuples(left)))
    assert semi.attrs["numOutputRows"] == kept > 0
    assert semi.attrs["buildRows"] == right.num_rows


# -- the SQL grammar ------------------------------------------------------ #

@pytest.fixture
def sql():
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("a", pa.table(
        {"x": pa.array([1, 2, 2, None, 3], pa.int64())}))
    fe.register_table("b", pa.table(
        {"x": pa.array([2, None, 4, 4], pa.int64())}))
    fe.register_table("c", pa.table(
        {"x": pa.array([None, 3, 4], pa.int64())}))
    return fe


@pytest.mark.parametrize("text,want", [
    ("select x from a intersect select x from b", {2, None}),
    ("select x from a except select x from b", {1, 3}),
    ("select x from a minus select x from b", {1, 3}),
    ("(select x from a) intersect (select x from b) intersect "
     "(select x from c)", {None}),
    ("(select x from a) except (select x from b) except (select x from c)",
     {1}),
    # INTERSECT binds tighter than UNION and EXCEPT; the rest left to right
    ("select x from a union select x from b intersect select x from c",
     {1, 2, 3, None, 4}),
    ("select x from a except select x from b intersect select x from c",
     {1, 2, 3}),
    ("select x from a except select x from b union all select x from c",
     [1, 3, None, 3, 4]),
    ("select count(*) from ((select distinct x from a) intersect "
     "(select distinct x from c)) both_have limit 100", {2}),
    ("select x from a intersect distinct select x from b", {2, None}),
], ids=["intersect", "except", "minus", "intersect-chain", "except-chain",
        "precedence-union", "precedence-except", "left-to-right",
        "q38-shape", "distinct-keyword"])
def test_sql_set_operations(sql, text, want):
    for engine in ("tpu", "cpu"):
        got = _tuples(sql.sql(text).collect(engine=engine))
        assert _sorted(got) == _sorted((v,) for v in want), engine


def test_sql_refuses_the_all_forms(sql):
    from spark_rapids_tpu.frontends.sql import SqlError

    with pytest.raises(SqlError, match="INTERSECT ALL is not supported"):
        sql.sql("select x from a intersect all select x from b")
    with pytest.raises(SqlError, match="same column count"):
        sql.sql("select x from a except select x, x from b")
