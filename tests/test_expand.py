"""Expand exec + grouping sets (rollup/cube) + count-distinct rewrite.

Coverage analog of the reference's Expand/distinct tests
(ref: GpuExpandExec.scala:67, hash_aggregate_test.py distinct cases)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.session import (
    TpuSession,
    col,
    count,
    count_distinct,
    sum_,
)
from tests.differential import assert_tpu_cpu_equal


@pytest.fixture
def session():
    return TpuSession()


@pytest.fixture
def sales(session):
    t = pa.table({
        "region": pa.array(["e", "e", "w", "w", "w", None], pa.string()),
        "product": pa.array(["a", "b", "a", "a", "b", "a"], pa.string()),
        "amount": pa.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0], pa.float64()),
    })
    return session.create_dataframe(t)


def test_rollup_hand_checked(sales):
    out = sales.rollup("region", "product").agg(
        (sum_(col("amount")), "s")).collect().to_pydict()
    rows = {(r, p): s for r, p, s in zip(out["region"], out["product"],
                                         out["s"])}
    # full detail
    assert rows[("e", "a")] == 1.0 and rows[("e", "b")] == 2.0
    assert rows[("w", "a")] == 12.0 and rows[("w", "b")] == 16.0
    assert rows[(None, "a")] == 32.0  # real NULL region, product level
    # region subtotals (product rolled up)
    assert rows[("e", None)] == 3.0
    assert rows[("w", None)] == 28.0
    # grand total
    assert rows[(None, None)] == 63.0 or (None, None) in rows
    # 5 detail groups + 3 region subtotals (e, w, NULL) + 1 grand = 9
    assert len(out["s"]) == 9


def test_rollup_matches_cpu(sales):
    assert_tpu_cpu_equal(sales.rollup("region", "product").agg(
        (sum_(col("amount")), "s"), (count(col("amount")), "c")))


def test_cube_matches_cpu(sales):
    df = sales.cube("region", "product").agg((sum_(col("amount")), "s"))
    assert_tpu_cpu_equal(df)
    out = df.collect().to_pydict()
    rows = list(zip(out["region"], out["product"], out["s"]))
    # cube adds product-only subtotals
    assert (None, "a", 45.0) in rows
    assert (None, "b", 18.0) in rows


def test_grouping_sets_explicit(session):
    t = pa.table({"a": pa.array([1, 1, 2], pa.int64()),
                  "b": pa.array([10, 20, 10], pa.int64()),
                  "v": pa.array([1.0, 2.0, 3.0], pa.float64())})
    df = session.create_dataframe(t).grouping_sets(
        [["a"], ["b"]], keys=["a", "b"]).agg((sum_(col("v")), "s"))
    out = df.collect().to_pydict()
    rows = set(zip(out["a"], out["b"], out["s"]))
    assert (1, None, 3.0) in rows and (2, None, 3.0) in rows
    assert (None, 10, 4.0) in rows and (None, 20, 2.0) in rows
    assert_tpu_cpu_equal(df)


def test_count_distinct_grouped(session):
    t = pa.table({
        "g": pa.array([1, 1, 1, 2, 2, 2, 2], pa.int64()),
        "x": pa.array([5, 5, 7, 1, None, 1, 2], pa.int64()),
    })
    df = session.create_dataframe(t).group_by(col("g")).agg(
        (count_distinct(col("x")), "d"))
    out = df.collect().to_pydict()
    assert dict(zip(out["g"], out["d"])) == {1: 2, 2: 2}
    assert_tpu_cpu_equal(df)


def test_count_distinct_grand(session):
    t = pa.table({"x": pa.array([1, 1, 2, None, 3, 3], pa.int64())})
    df = session.create_dataframe(t).agg((count_distinct(col("x")), "d"))
    assert df.collect().to_pydict() == {"d": [3]}
    assert_tpu_cpu_equal(df)


def test_count_distinct_mixed_rejected(session):
    """Beside a sum the distinct count lowers as Spark's single-distinct
    rewrite; beside an aggregate whose partials do not merge it is
    refused by name."""
    from spark_rapids_tpu.session import first

    t = pa.table({"x": pa.array([1, 1, 2], pa.int64())})
    df = session.create_dataframe(t)
    mixed = df.agg((count_distinct(col("x")), "d"), (sum_(col("x")), "s"))
    assert mixed.collect().to_pylist() == [{"d": 2, "s": 4}]
    with pytest.raises(ValueError, match="count_distinct beside first"):
        df.agg((count_distinct(col("x")), "d"), (first(col("x")), "f"))


def test_rollup_multi_partition(session, tmp_path):
    """Grouping sets compose with the partial/exchange/final aggregate
    shape over a multi-file scan."""
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    for i in range(3):
        t = pa.table({
            "k": pa.array(rng.integers(0, 4, 500), pa.int64()),
            "v": pa.array(rng.random(500), pa.float64()),
        })
        pq.write_table(t, str(tmp_path / f"f{i}.parquet"))
    df = session.read_parquet(str(tmp_path)).rollup("k").agg(
        (sum_(col("v")), "s"), (count(col("v")), "c"))
    assert_tpu_cpu_equal(df, approx_float=True)


# ------------------------------------------------------------------ #
# Nested grouping sets taken from ONE sort of the rows that enter the
# Expand (ops.groupby's rollup path; execs/aggregate.py:_rollup_of)
# ------------------------------------------------------------------ #

#: eight keys of mixed kinds, the order a rollup drops them from the
#: right; `c` is NULL in a fifth of the rows, `h` in a tenth
ROLLUP_KEYS = ["a", "b", "c", "d", "e", "f", "g", "h"]


def _levels_table(n=600, seed=7):
    rng = np.random.default_rng(seed)

    def nulls(p):
        return rng.random(n) < p

    words = ["", "ab", "ünï", "日本", "x" * 20]
    return pa.table({
        "a": pa.array([words[i] for i in rng.integers(0, 3, n)],
                      pa.string(), mask=nulls(0.1)),
        "b": pa.array(rng.integers(-2, 2, n), pa.int64(), mask=nulls(0.1)),
        "c": pa.array(rng.integers(10000, 10003, n).astype(np.int32),
                      pa.int32(), mask=nulls(0.2)).cast(pa.date32()),
        "d": pa.array([words[i] for i in rng.integers(0, 5, n)],
                      pa.string(), mask=nulls(0.1)),
        "e": pa.array(rng.integers(0, 2, n).astype(np.int32), pa.int32()),
        "f": pa.array(rng.integers(0, 2, n), pa.int64(), mask=nulls(0.3)),
        "g": pa.array([words[i] for i in rng.integers(3, 5, n)],
                      pa.string()),
        "h": pa.array(rng.integers(0, 2, n), pa.int64(), mask=nulls(0.1)),
        "v": pa.array(rng.normal(0, 1e3, n), pa.float64(),
                      mask=nulls(0.15)),
        "q": pa.array(rng.integers(-50, 50, n), pa.int64(),
                      mask=nulls(0.15)),
    })


def _all_specs():
    from spark_rapids_tpu.session import avg, count_star, max_, min_

    return [(sum_(col("v")), "sv"), (count(col("v")), "cv"),
            (count_star(), "n"), (min_(col("v")), "lo"),
            (max_(col("v")), "hi"), (avg(col("v")), "av"),
            (sum_(col("q")), "sq"), (min_(col("q")), "loq")]


def _with_gid(df):
    """The aggregate under `df`'s last projection: its `__gid` tells a
    NULL in a key's DATA from the NULL a level writes."""
    from spark_rapids_tpu.session import DataFrame

    return DataFrame(df._plan.children[0], df._session)


def _sorted_rows(table):
    names = table.schema.names
    rows = list(zip(*(table.column(c).to_pylist() for c in names)))
    exact = [i for i, f in enumerate(table.schema)
             if not pa.types.is_floating(f.type)]
    return names, exact, sorted(
        rows, key=lambda r: tuple((r[i] is not None, r[i]) for i in exact))


def _assert_same_groups(got, want):
    """Keys, `__gid`, counts and integer sums exactly; DOUBLE aggregates
    to 1e-12 relative."""
    names, exact, g = _sorted_rows(got)
    names_w, _, w = _sorted_rows(want)
    assert names == names_w and len(g) == len(w), (names, len(g), len(w))
    for rg, rw in zip(g, w):
        for i, (x, y) in enumerate(zip(rg, rw)):
            if i in exact or x is None or y is None:
                assert x == y, (names[i], rg, rw)
            else:
                assert abs(x - y) <= 1e-12 * max(abs(x), abs(y)), \
                    (names[i], rg, rw)


def _update_spans(df):
    """(`agg.update` spans of one traced collect, its table).  The
    path a span carries was noted when its program was traced, so a
    first collect warms the program."""
    from spark_rapids_tpu import trace

    df.collect(engine="tpu")
    trace.clear()
    trace.enable()
    try:
        out = df.collect(engine="tpu")
        spans = [e for e in trace.snapshot() if e.name == "agg.update"]
    finally:
        trace.disable()
        trace.clear()
    return spans, out


def _expand_rows(session):
    """(`numOutputRows`, description) of the last collect's Expand."""
    def walk(node):
        yield node
        for c in node.children:
            yield from walk(c)

    ops = [n for n in walk(session.history.events[-1].root)
           if n.desc.startswith("TpuExpandExec")]
    assert len(ops) == 1, [n.desc for n in ops]
    return ops[0].metrics["numOutputRows"], ops[0].desc


@pytest.mark.parametrize("n_keys", [1, 3, 8])
def test_rollup_levels_from_one_sort(session, n_keys):
    """Every level of a rollup over string, int and date keys with NULLs
    in their data, every kind of spec: the CPU engine's groups (which
    expands the rows), the rollup path, no row expanded."""
    t = _levels_table()
    keys = ROLLUP_KEYS[:n_keys]
    df = _with_gid(session.create_dataframe(t).rollup(*keys)
                   .agg(*_all_specs()))
    spans, got = _update_spans(df)
    _assert_same_groups(got, df.collect(engine="cpu"))
    assert [s.attrs["path"] for s in spans] == ["rollup"]
    assert spans[0].attrs["levels"] == n_keys + 1
    assert spans[0].attrs["capacity"] == 1024  # the un-expanded batch's
    assert sorted(set(got.column("__gid").to_pylist())) \
        == list(range(n_keys + 1))
    if n_keys >= 3:
        # NULL dates in the data at the level that keeps `c`, the
        # level's own NULL one gid on: two groups, the same keys
        a_b = {(a, b) for a, b, c in zip(*(t.column(k).to_pylist()
                                           for k in "abc")) if c is None}
        rows = list(zip(*(got.column(k).to_pylist()
                          for k in ("a", "b", "c", "__gid"))))
        for a, b in a_b:
            assert (a, b, None, n_keys - 3) in rows
            assert (a, b, None, n_keys - 2) in rows


@pytest.mark.parametrize("batches", [2, 3])
def test_rollup_merges_the_levels_of_several_batches(session, batches):
    from spark_rapids_tpu.config import get_conf

    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    t = _levels_table(n=256 * batches - 40)
    df = _with_gid(session.create_dataframe(t).rollup("a", "b", "c", "d")
                   .agg(*_all_specs()))
    spans, got = _update_spans(df)
    assert [s.attrs["path"] for s in spans] == ["rollup"] * batches
    _assert_same_groups(got, df.collect(engine="cpu"))


def test_rollup_of_no_rows(session):
    t = _levels_table(n=40).slice(0, 0)
    df = session.create_dataframe(t).rollup("a", "b").agg(*_all_specs())
    assert df.collect(engine="tpu").num_rows == 0
    assert df.collect(engine="cpu").num_rows == 0


def test_rollup_under_an_absorbed_filter(session):
    """The filter under the Expand folds into the same program, as a
    mask now that no exec of the chain multiplies rows."""
    df = _with_gid(session.create_dataframe(_levels_table())
                   .where(col("q") > col("b"))
                   .rollup("a", "b", "g").agg(*_all_specs()))
    spans, got = _update_spans(df)
    assert [s.attrs["path"] for s in spans] == ["rollup"]
    _assert_same_groups(got, df.collect(engine="cpu"))


def test_grouping_sets_given_as_a_chain(session):
    """Nested sets in any order, a key every set keeps, the keys listed
    in another order than the sets drop them: still one sort."""
    df = _with_gid(session.create_dataframe(_levels_table()).grouping_sets(
        [["e"], ["b", "e", "a"], ["e", "a"]], keys=["a", "b", "e"])
        .agg(*_all_specs()))
    spans, got = _update_spans(df)
    assert [s.attrs["path"] for s in spans] == ["rollup"]
    assert spans[0].attrs["levels"] == 3
    _assert_same_groups(got, df.collect(engine="cpu"))


@pytest.mark.parametrize("shape", ["cube", "unrelated-sets"])
def test_sets_that_are_no_chain_keep_the_expand(session, shape):
    base = session.create_dataframe(_levels_table())
    grouped = base.cube("a", "b", "e") if shape == "cube" \
        else base.grouping_sets([["a", "b"], ["b", "e"]],
                                keys=["a", "b", "e"])
    df = _with_gid(grouped.agg(*_all_specs()))
    spans, got = _update_spans(df)
    assert [s.attrs["path"] for s in spans] == ["sort"]
    assert "levels" not in spans[0].attrs
    assert spans[0].attrs["capacity"] == 1024
    rows, desc = _expand_rows(session)
    fanout = 8 if shape == "cube" else 2
    assert rows == 600 * fanout and "rollup" not in desc
    _assert_same_groups(got, df.collect(engine="cpu"))


def test_absorbed_expand_counts_the_rows_it_was_handed(session):
    """On the rollup path the Expand hands the aggregate each input row
    once; `plan_has` still finds it by its first word."""
    df = session.create_dataframe(_levels_table()).rollup(
        "a", "b", "c").agg((sum_(col("v")), "s"))
    df.collect(engine="tpu")
    rows, desc = _expand_rows(session)
    assert rows == 600
    assert desc.split(" ", 1)[0] == "TpuExpandExec" and "rollup" in desc


def test_equal_row_sets_at_two_levels_sum_to_the_same_bits(session):
    """The tie rule: `b` is constant within its `a`, so the levels
    (g, a, b) and (g, a) hold the same rows; their DOUBLE sums add the
    same terms in the same order, and a rank over the sums gives each
    such pair ONE rank (q67's `rk` under DMS = 1200)."""
    from spark_rapids_tpu.exprs.window import Window, rank

    rng = np.random.default_rng(11)
    n = 4000
    a = rng.integers(0, 40, n)
    t = pa.table({"g": a % 3, "a": a, "b": a * 7,
                  "v": rng.normal(0, 1e6, n) * rng.random(n)})
    sums = session.create_dataframe(t).rollup("g", "a", "b").agg(
        (sum_(col("v")), "s"))
    ranked = sums.select(
        col("g"), col("a"), col("b"), col("s"),
        rank().over(Window.partition_by("g").order_by(
            "s", desc=True)).alias("rk"))
    out = ranked.collect(engine="tpu").to_pydict()
    level = {}
    for g, a_, b, s, rk in zip(*(out[k] for k in ("g", "a", "b", "s",
                                                  "rk"))):
        if a_ is not None:
            level.setdefault((g, a_), {})[b is None] = (s, rk)
    assert len(level) == 40
    for pair in level.values():
        (s_leaf, rk_leaf), (s_up, rk_up) = pair[False], pair[True]
        assert s_leaf.hex() == s_up.hex() and rk_leaf == rk_up
    # the two rows of a pair share their rank with nobody else
    ranks = list(zip(out["g"], out["rk"]))
    for (g, _), pair in level.items():
        assert ranks.count((g, pair[False][1])) == 2


def _update_exec(session, df):
    """The planned aggregate whose update absorbs its child chain."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.planner import plan_query

    root, _ = plan_query(df._plan, session.conf)

    def find(e):
        if isinstance(e, TpuHashAggregateExec) and e.mode != "final":
            return e
        for c in e.children:
            hit = find(c)
            if hit is not None:
                return hit

    agg = find(root)
    list(agg.execute())
    return agg


@pytest.mark.parametrize("shape", ["q1", "q3"])
def test_an_aggregate_without_expand_keeps_its_programs(session, tmp_path,
                                                        shape):
    """No Expand under the update: the update program's cache key is
    the one it has always had (`... "absorb", chain keys, "update"`),
    no rollup is taken, and the traced path is the one the keys choose:
    q1's dictionary-coded string keys the masked sum, q3's plain keys
    the sort."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.exprs.base import lit

    rng = np.random.default_rng(5)
    n = 2000
    if shape == "q1":
        t = pa.table({
            "flag": pa.array([["A", "N", "R"][i]
                              for i in rng.integers(0, 3, n)]),
            "status": pa.array([["F", "O"][i]
                                for i in rng.integers(0, 2, n)]),
            "qty": rng.random(n), "day": rng.integers(0, 100, n)})
        pq.write_table(t, str(tmp_path / "l.parquet"),
                       use_dictionary=True)
        df = (session.read_parquet(str(tmp_path / "l.parquet"))
              .where(col("day") <= lit(90))
              .group_by(col("flag"), col("status"))
              .agg((sum_(col("qty")), "s"), (count(col("qty")), "c")))
        want = "masked"
    else:
        t = pa.table({"okey": rng.integers(0, 300, n),
                      "date": rng.integers(0, 9, n),
                      "prio": rng.integers(0, 2, n),
                      "rev": rng.random(n)})
        df = (session.create_dataframe(t)
              .group_by(col("okey"), col("date"), col("prio"))
              .agg((sum_(col("rev")), "revenue")))
        want = "sort"
    agg = _update_exec(session, df)
    chain = agg._absorbed_chain()
    assert agg._rollup is None
    assert agg._path_keys == (
        agg._cache_key() + ("absorb", chain[2] if chain else (), "update"),
        agg._cache_key() + ("merge",))
    assert agg._cache_key()[:3] == ("agg", agg.mode, agg.n_keys)
    assert A._PATHS[agg._path_keys[0]] == want
    spans, _ = _update_spans(df)
    assert {s.attrs["path"] for s in spans} == {want}
    assert all("levels" not in s.attrs for s in spans)
