"""Regression tests for round-1 advisor findings (ADVICE.md)."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.session import TpuSession, col, first, last, sum_
from tests.differential import assert_tpu_cpu_equal


@pytest.fixture
def session():
    return TpuSession()


# -- Ceil/Floor on non-finite doubles (medium) -------------------------- #

def test_ceil_floor_nan_inf_saturate(session):
    from spark_rapids_tpu.exprs.math import Ceil, Floor

    data = {"x": [float("nan"), float("inf"), float("-inf"),
                  1.5, -1.5, 2.0 ** 70, -(2.0 ** 70), 0.0]}
    df = session.create_dataframe(pa.table(data)).select(
        Ceil(col("x")).alias("c"), Floor(col("x")).alias("f"))
    out = df.collect(engine="tpu").to_pydict()
    i64 = np.iinfo(np.int64)
    assert out["c"] == [0, i64.max, i64.min, 2, -1, i64.max, i64.min, 0]
    assert out["f"] == [0, i64.max, i64.min, 1, -2, i64.max, i64.min, 0]
    # CPU oracle must agree (it previously raised on these inputs)
    assert_tpu_cpu_equal(df)


# -- First/Last default ignoreNulls=false (low) ------------------------- #

def test_first_last_default_keeps_nulls(session):
    t = pa.table({"k": [1, 1, 2, 2], "v": [None, 10, 20, None]})
    df = session.create_dataframe(t).group_by("k").agg(
        (first("v"), "f"), (last("v"), "l"))
    out = {r["k"]: (r["f"], r["l"])
           for r in df.collect(engine="tpu").to_pylist()}
    # group 1 first value is NULL -> NULL; group 2 last value NULL -> NULL
    assert out[1] == (None, 10)
    assert out[2] == (20, None)
    assert_tpu_cpu_equal(df)


def test_first_last_ignore_nulls(session):
    t = pa.table({"k": [1, 1, 2, 2], "v": [None, 10, 20, None]})
    df = session.create_dataframe(t).group_by("k").agg(
        (first("v", ignore_nulls=True), "f"),
        (last("v", ignore_nulls=True), "l"))
    out = {r["k"]: (r["f"], r["l"])
           for r in df.collect(engine="tpu").to_pylist()}
    assert out[1] == (10, 10)
    assert out[2] == (20, 20)
    assert_tpu_cpu_equal(df)


def test_grand_first_last_null(session):
    t = pa.table({"v": [None, 7, None]}, schema=pa.schema(
        [pa.field("v", pa.int64())]))
    df = session.create_dataframe(t).agg((first("v"), "f"),
                                         (last("v"), "l"),
                                         (first("v", True), "fi"),
                                         (last("v", True), "li"))
    row = df.collect(engine="tpu").to_pylist()[0]
    assert (row["f"], row["l"], row["fi"], row["li"]) == (None, None, 7, 7)
    assert_tpu_cpu_equal(df)


# -- shuffle blocks released when a limit abandons partitions (low) ----- #

def test_shuffle_blocks_released_on_early_stop(session):
    from spark_rapids_tpu.memory import get_store, reset_store
    from spark_rapids_tpu.shuffle import reset_shuffle_manager

    reset_store()
    reset_shuffle_manager()
    t = pa.table({"k": list(range(100)), "v": list(range(100))})
    # multi-partition aggregate forces a shuffle; limit(3) stops early
    df = (session.create_dataframe(t).union(session.create_dataframe(t))
          .group_by("k").agg((sum_("v"), "s")).limit(3))
    out = df.collect(engine="tpu")
    assert out.num_rows == 3
    store = get_store()
    assert store._entries == {}, (
        f"leaked {len(store._entries)} spillable buffers after collect")


# -- semaphore: same task_id from two racing threads leaks no permit ---- #

def test_semaphore_same_task_race():
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore

    sem = TpuSemaphore(1)
    n_threads = 8
    barrier = threading.Barrier(n_threads)

    def run():
        barrier.wait()
        sem.acquire_if_necessary(42)

    threads = [threading.Thread(target=run) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=10)
    assert not any(th.is_alive() for th in threads)
    sem.release_if_necessary(42)
    assert sem._available == sem.permits, "permit leaked"


# -- disk-tier acquire keeps the spill file until upload succeeds ------- #

def test_disk_acquire_survives_reserve_failure(monkeypatch):
    import spark_rapids_tpu.memory.store as store_mod
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.memory import reset_store
    from spark_rapids_tpu.memory.store import BufferStore, StorageTier

    reset_store()
    store = BufferStore(device_budget=10 ** 9, host_budget=0)
    schema = T.Schema([T.Field("x", T.LONG)])
    b = ColumnarBatch.from_numpy(
        {"x": np.arange(16, dtype=np.int64)}, schema)
    h = store.register(b)
    h.unpin()
    e = store._entries[h.buffer_id]
    store._spill_to_host_locked(e)  # host_budget=0 cascades straight to disk
    assert e.tier == StorageTier.DISK

    # first acquire attempt dies mid-upload; the file must survive
    real = store_mod._host_to_batch
    calls = {"n": 0}

    def boom(arrays, schema):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("injected H2D failure")
        return real(arrays, schema)

    monkeypatch.setattr(store_mod, "_host_to_batch", boom)
    with pytest.raises(RuntimeError, match="injected"):
        store.acquire(h.buffer_id)
    assert e.pins == 0  # a failed acquire rolls its pin back
    got = store.acquire(h.buffer_id)  # retry succeeds from the same file
    assert np.asarray(got.columns[0].data)[:16].tolist() == list(range(16))
    h.close()


# -- Round-2 advisor findings ------------------------------------------- #

def test_window_orderby_grouping_is_structural(session):
    """Two window exprs whose order-by exprs differ structurally but share
    a display name must land in separate Window nodes (no crash, correct
    results); structurally identical specs must share one node."""
    from spark_rapids_tpu.exprs.window import Window, row_number

    t = pa.table({
        "g": pa.array([1, 1, 2, 2], pa.int64()),
        "a": pa.array([3.0, 1.0, 4.0, 2.0], pa.float64()),
    })
    df = session.create_dataframe(t)
    # order by a ascending vs a descending: same display name "a"
    asc = Window.partition_by("g").order_by("a")
    desc = Window.partition_by("g").order_by("a", desc=True)
    out = df.select(
        col("g"), col("a"),
        row_number().over(asc).alias("rn_asc"),
        row_number().over(desc).alias("rn_desc"),
    ).collect().to_pydict()
    by_pair = {(g, a): (x, y) for g, a, x, y in zip(
        out["g"], out["a"], out["rn_asc"], out["rn_desc"])}
    assert by_pair[(1, 1.0)] == (1, 2)
    assert by_pair[(1, 3.0)] == (2, 1)
    assert by_pair[(2, 2.0)] == (1, 2)
    assert by_pair[(2, 4.0)] == (2, 1)


def test_join_cache_key_covers_child_split():
    """Joins with identical output schema but different left/right child
    splits must not share compiled closures."""
    from spark_rapids_tpu.execs.join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.io.scan import ArrowSourceExec
    from spark_rapids_tpu.exprs.base import ColumnReference

    l1 = ArrowSourceExec(pa.table({"k": pa.array([1], pa.int64()),
                                   "x": pa.array([1.0], pa.float64())}))
    r1 = ArrowSourceExec(pa.table({"k": pa.array([1], pa.int64())}))
    l2 = ArrowSourceExec(pa.table({"k": pa.array([1], pa.int64())}))
    r2 = ArrowSourceExec(pa.table({"k": pa.array([1], pa.int64()),
                                   "x": pa.array([1.0], pa.float64())}))
    j1 = TpuShuffledHashJoinExec([ColumnReference("k")],
                                 [ColumnReference("k")], "inner", l1, r1)
    j2 = TpuShuffledHashJoinExec([ColumnReference("k")],
                                 [ColumnReference("k")], "inner", l2, r2)
    assert j1._cache_key() != j2._cache_key()


def test_expr_key_rejects_non_dataclass_expression():
    from spark_rapids_tpu.execs.jit_cache import expr_key
    from spark_rapids_tpu.exprs.base import Expression

    class Sneaky(Expression):
        def __init__(self):
            self.state = 42

    with pytest.raises(TypeError, match="dataclass"):
        expr_key(Sneaky())


# ===================================================================== #
# Round-5 advisor findings
# ===================================================================== #

# -- SQL UNION dtype widening (medium) ---------------------------------- #

def _sql_session_ab():
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("ta", pa.table(
        {"x": pa.array([1, 2], pa.int32())}))
    fe.register_table("tb", pa.table(
        {"x": pa.array([1.5, 2.5], pa.float64())}))
    fe.register_table("tc", pa.table({"x": ["a", "b"]}))
    return fe


def test_sql_union_widens_member_types():
    """Pre-fix, TpuUnionExec re-tagged the DOUBLE member's batches with
    the INT first-member schema, silently truncating 1.5 -> 1.  Now the
    lowering inserts widening casts (WidenSetOperationTypes)."""
    fe = _sql_session_ab()
    df = fe.sql("select x from ta union all select x from tb")
    import spark_rapids_tpu.types as T

    assert isinstance(df.schema.fields[0].dtype, T.DoubleType)
    out = sorted(df.collect(engine="tpu")["x"].to_pylist())
    assert out == [1.0, 1.5, 2.0, 2.5]
    assert_tpu_cpu_equal(df)


def test_sql_union_widens_first_member_too():
    """Widening must coerce the FIRST member as well (double comes
    second)."""
    fe = _sql_session_ab()
    df = fe.sql("select x from tb union all select x from ta")
    out = sorted(df.collect(engine="tpu")["x"].to_pylist())
    assert out == [1.0, 1.5, 2.0, 2.5]
    assert_tpu_cpu_equal(df)


def test_sql_union_widening_with_duplicate_output_names():
    """Coercion must be positional: name-based references would
    resolve both 'a' columns to the first one."""
    fe = _sql_session_ab()
    fe.register_table("td", pa.table({"p": [10, 20], "q": [30, 40]}))
    fe.register_table("te", pa.table({"r": [1.5], "s": [2.5]}))
    df = fe.sql("select p as a, q as a from td "
                "union all select r, s from te")
    out = df.collect(engine="tpu")
    # positional read: to_pylist() dicts would collapse the dup names
    rows = sorted(zip(*(c.to_pylist() for c in out.columns)))
    assert rows == [(1.5, 2.5), (10.0, 30.0), (20.0, 40.0)]


def test_sql_union_incompatible_types_fail_analysis():
    from spark_rapids_tpu.frontends.sql import SqlError

    fe = _sql_session_ab()
    with pytest.raises(SqlError, match="incompatible types"):
        fe.sql("select x from ta union all select x from tc")


def test_dtype_flow_checker_catches_prefix_union():
    """The lint regression demanded by the fix: a hand-built L.Union
    (bypassing DataFrame.union's widening) still produces the pre-fix
    plan shape, and the static dtype-flow checker flags it without
    execution."""
    from spark_rapids_tpu.lint import lint_exec_tree
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.plan.planner import plan_query
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession()
    a = s.create_dataframe(pa.table({"x": pa.array([1], pa.int32())}))
    b = s.create_dataframe(pa.table({"x": pa.array([1.5], pa.float64())}))
    root, _ = plan_query(L.Union([a._plan, b._plan]), s.conf)
    assert any(d.rule == "DT001" and d.severity == "error"
               for d in lint_exec_tree(root))


def test_dataframe_union_widens_at_engine_layer(session):
    """DataFrame.union (the single producer of L.Union) must widen, so
    every frontend is protected — a SQL-only fix would leave the
    DataFrame surface collecting truncated values."""
    import spark_rapids_tpu.types as T

    a = session.create_dataframe(
        pa.table({"x": pa.array([1, 2], pa.int32())}))
    b = session.create_dataframe(pa.table({"x": [1.5, 2.5]}))
    df = a.union(b)
    assert isinstance(df.schema.fields[0].dtype, T.DoubleType)
    out = sorted(df.collect(engine="tpu")["x"].to_pylist())
    assert out == [1.0, 1.5, 2.0, 2.5]
    assert_tpu_cpu_equal(df)


def test_dataframe_union_incompatible_types_raise(session):
    from spark_rapids_tpu.session import AnalysisException

    a = session.create_dataframe(pa.table({"x": [1, 2]}))
    b = session.create_dataframe(pa.table({"x": ["a", "b"]}))
    with pytest.raises(AnalysisException, match="incompatible types"):
        a.union(b)


def test_dataframe_union_column_count_mismatch(session):
    from spark_rapids_tpu.session import AnalysisException

    a = session.create_dataframe(pa.table({"x": [1]}))
    b = session.create_dataframe(pa.table({"x": [1], "y": [2]}))
    with pytest.raises(AnalysisException, match="column count"):
        a.union(b)


def test_sql_union_decimal_members_widen():
    """decimal(10,2) union decimal(8,4) -> decimal(12,4): Spark's
    DecimalPrecision keeps the integral and fractional digits of both
    sides; the cast rescales the int64 unscaled values.  The pre-review
    widening rejected ALL decimal pairs, regressing same-scale unions
    that previously worked by benign re-tagging."""
    from decimal import Decimal

    import spark_rapids_tpu.types as T
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("t1", pa.table(
        {"d": pa.array([Decimal("1.50"), Decimal("2.25")],
                       pa.decimal128(10, 2))}))
    fe.register_table("t2", pa.table(
        {"d": pa.array([Decimal("3.1234")], pa.decimal128(8, 4))}))
    df = fe.sql("select d from t1 union all select d from t2")
    assert df.schema.fields[0].dtype == T.DecimalType(12, 4)
    out = sorted(df.collect(engine="tpu")["d"].to_pylist())
    assert out == [Decimal("1.5000"), Decimal("2.2500"),
                   Decimal("3.1234")]


def test_sql_union_same_scale_decimals_widen():
    """Same scale, different precision — the exact pair the first
    widening cut regressed (it worked pre-widening because the int64
    unscaled payloads are identical)."""
    from decimal import Decimal

    import spark_rapids_tpu.types as T
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("t1", pa.table(
        {"d": pa.array([Decimal("1.00")], pa.decimal128(10, 2))}))
    fe.register_table("t2", pa.table(
        {"d": pa.array([Decimal("2.00"), Decimal("3.00")],
                       pa.decimal128(12, 2))}))
    df = fe.sql("select d from t1 union all select d from t2")
    assert df.schema.fields[0].dtype == T.DecimalType(12, 2)
    out = sorted(df.collect(engine="tpu")["d"].to_pylist())
    assert out == [Decimal("1.00"), Decimal("2.00"), Decimal("3.00")]


def test_sql_union_int_decimal_promotes():
    """int union decimal(10,2) -> decimal(12,2) (Spark's
    DecimalPrecision via DecimalType.forType(int) = decimal(10,0));
    the int side rescales to unscaled*100."""
    from decimal import Decimal

    import spark_rapids_tpu.types as T
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("ti", pa.table(
        {"v": pa.array([1, 2], pa.int32())}))
    fe.register_table("td", pa.table(
        {"v": pa.array([Decimal("3.25")], pa.decimal128(10, 2))}))
    df = fe.sql("select v from ti union all select v from td")
    assert df.schema.fields[0].dtype == T.DecimalType(12, 2)
    out = sorted(df.collect(engine="tpu")["v"].to_pylist())
    assert out == [Decimal("1.00"), Decimal("2.00"), Decimal("3.25")]


def test_dataframe_union_decimal_double_promotes(session):
    """decimal + fractional -> double (Spark's DecimalPrecision)."""
    from decimal import Decimal

    import spark_rapids_tpu.types as T

    a = session.create_dataframe(pa.table(
        {"v": pa.array([Decimal("1.25")], pa.decimal128(10, 2))}))
    b = session.create_dataframe(pa.table({"v": [2.5]}))
    df = a.union(b)
    assert isinstance(df.schema.fields[0].dtype, T.DoubleType)
    out = sorted(df.collect(engine="tpu")["v"].to_pylist())
    assert out == [1.25, 2.5]


def test_dataframe_union_long_decimal_has_no_common_type(session):
    """LONG needs 19 integral digits — past the int64-backed
    MAX_PRECISION — so decimal+long fails analysis instead of losing
    digits (Spark would widen to decimal(20,s) on 128-bit storage)."""
    from decimal import Decimal

    from spark_rapids_tpu.session import AnalysisException

    a = session.create_dataframe(pa.table({"v": pa.array([1], pa.int64())}))
    b = session.create_dataframe(pa.table(
        {"v": pa.array([Decimal("1.00")], pa.decimal128(10, 2))}))
    with pytest.raises(AnalysisException, match="incompatible types"):
        a.union(b)


def test_dataframe_union_date_timestamp_promotes(session):
    """date + timestamp members promote to timestamp (Spark's
    findWiderTypeForTwo); the date side casts to midnight UTC."""
    import datetime as dt

    import spark_rapids_tpu.types as T

    a = session.create_dataframe(
        pa.table({"t": pa.array([0, 1], pa.int32()).cast(pa.date32())}))
    b = session.create_dataframe(
        pa.table({"t": pa.array([1_000_000], pa.timestamp("us"))}))
    df = a.union(b)
    assert isinstance(df.schema.fields[0].dtype, T.TimestampType)
    out = sorted(t.replace(tzinfo=None)
                 for t in df.collect(engine="tpu")["t"].to_pylist())
    assert out == [dt.datetime(1970, 1, 1),
                   dt.datetime(1970, 1, 1, 0, 0, 1),
                   dt.datetime(1970, 1, 2)]


# -- EXISTS derived tables lowered once (low) --------------------------- #

def test_exists_over_derived_table_reuses_lowering(monkeypatch):
    """_lower_exists pre-lowers derived tables into ("__df__", df) refs;
    q2 must consume them (no double lowering, and _lower must accept
    the __df__ tag)."""
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("t1", pa.table({"ok": [1, 2, 3, 4]}))
    fe.register_table("t2", pa.table({"k": [2, 4, 4]}))

    calls: list[int] = []
    orig = SqlSession._lower

    def spy(self, q, ctes=None):
        calls.append(id(q))
        return orig(self, q, ctes)

    monkeypatch.setattr(SqlSession, "_lower", spy)
    df = fe.sql("select ok from t1 where exists "
                "(select k from (select k from t2) d where k = ok)")
    # each parsed query dict is lowered at most once — pre-fix the
    # derived table's dict went through _lower twice
    assert len(calls) == len(set(calls))
    out = sorted(df.collect(engine="tpu")["ok"].to_pylist())
    assert out == [2, 4]
    assert_tpu_cpu_equal(df)


def test_not_exists_over_derived_table():
    from spark_rapids_tpu.frontends.sql import SqlSession

    fe = SqlSession()
    fe.register_table("t1", pa.table({"ok": [1, 2, 3, 4]}))
    fe.register_table("t2", pa.table({"k": [2, 4, 4]}))
    df = fe.sql("select ok from t1 where not exists "
                "(select k from (select k from t2) d where k = ok)")
    assert sorted(df.collect(engine="tpu")["ok"].to_pylist()) == [1, 3]


# -- groupby coded-key domains use the TRUE dictionary length (low) ----- #

def test_coded_key_domains_use_dict_len():
    import jax.numpy as jnp

    import spark_rapids_tpu.types as T
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.ops.groupby import _coded_key_domains

    def make(dict_len):
        return Column(jnp.zeros(16, jnp.int64), jnp.ones(16, bool),
                      T.LONG, codes=jnp.zeros(16, jnp.int32),
                      dict_values=jnp.zeros(8, jnp.int64),
                      dict_len=dict_len)

    # wire-padded capacity 8, true entry count 2: the domain product
    # must use 2 (pre-fix it used 8, compounding per key)
    assert _coded_key_domains([make(2)]) == [2]
    # decode paths that predate the sidecar still fall back to capacity
    assert _coded_key_domains([make(None)]) == [8]


def test_transfer_decode_carries_dict_len():
    """Parquet-style dictionary columns decode with a tight bucketed
    bound on the true entry count riding alongside the pow2-padded
    device dictionary.  130 entries: bound = 144 (multiple of 16),
    padded capacity = 256 — the domain product must use 144, while the
    bucketing keeps jit treedefs from fragmenting per exact
    cardinality."""
    import numpy as np

    n_dict = 130
    codes = pa.array(np.arange(400, dtype=np.int32) % n_dict)
    ints = pa.DictionaryArray.from_arrays(
        codes, pa.array((np.arange(n_dict) * 10**9).tolist()))
    strs = pa.DictionaryArray.from_arrays(
        codes, pa.array([f"v{i:03d}" for i in range(n_dict)]))
    t = pa.table({"i": ints, "s": strs})

    from spark_rapids_tpu.columnar import transfer
    from spark_rapids_tpu.columnar.arrow import schema_from_arrow

    schema = schema_from_arrow(t.schema)
    arrays = [c.combine_chunks() for c in t.columns]
    enc = transfer.encode_for_device(arrays, schema, t.num_rows)
    assert enc is not None
    cols = transfer.decode_on_device(*enc, schema)
    icol, scol = cols
    assert icol.dict_len == 144
    assert int(icol.dict_values.shape[0]) == 256  # pow2 pad
    assert scol.dict_len == 144
    assert int(scol.dict_chars.shape[0]) == 256


def test_groupby_on_dict_column_differential():
    """End-to-end: grouping on a dictionary-encoded key column stays
    correct with the dict_len-sized domains."""
    import numpy as np

    from spark_rapids_tpu.session import TpuSession, sum_

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 3, 64)
    t = pa.table({
        "k": pa.DictionaryArray.from_arrays(
            pa.array(keys, pa.int32()),
            pa.array([10**9, 2 * 10**9, 3 * 10**9])),
        "v": rng.normal(size=64),
    })
    s = TpuSession()
    df = s.create_dataframe(t).group_by("k").agg((sum_("v"), "sv"))
    # the masked reduction adds the terms in another order than the CPU
    # engine: equal to nine decimals, not to the last bit
    assert_tpu_cpu_equal(df, approx_float=True)
