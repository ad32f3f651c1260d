"""Session/DataFrame + planner tests: TPU-vs-CPU differential runs,
fallback behavior, explain output (mirrors the reference's pytest
integration tier + StringFallbackSuite-style fallback assertions)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import TpuConf, set_conf
from spark_rapids_tpu.session import (
    DataFrame,
    TpuSession,
    avg,
    col,
    count,
    count_star,
    max_,
    min_,
    sum_,
)
from spark_rapids_tpu.exprs.base import lit

from differential import assert_tpu_cpu_equal, gen_table


@pytest.fixture
def spark():
    return TpuSession()


def test_select_where_differential(spark):
    t = gen_table({"a": "int64", "b": "int64", "x": "float64"}, 500, seed=1)
    df = spark.create_dataframe(t)
    q = df.where((col("a") > lit(0)) & col("x").is_not_null()) \
          .select(col("a"), (col("a") + col("b")).alias("ab"),
                  (col("x") / lit(2.0)).alias("half"))
    assert_tpu_cpu_equal(q)


def test_groupby_differential(spark):
    t = gen_table({"k": "smallint64", "v": "int64", "x": "float64"},
                  800, seed=2)
    df = spark.create_dataframe(t)
    q = df.group_by("k").agg((sum_("v"), "s"), (count("v"), "c"),
                             (min_("v"), "mn"), (max_("v"), "mx"),
                             (count_star(), "n"))
    assert_tpu_cpu_equal(q)


def test_avg_differential_approx(spark):
    t = gen_table({"k": "smallint64", "v": "int64"}, 400, seed=3)
    q = spark.create_dataframe(t).group_by("k").agg((avg("v"), "a"))
    assert_tpu_cpu_equal(q, approx_float=True)


@pytest.mark.slow
def test_join_differential(spark):
    lt = gen_table({"k": "smallint64", "lv": "int64"}, 300, seed=4)
    rt = gen_table({"k": "smallint64", "rv": "string"}, 60, seed=5)
    left = spark.create_dataframe(lt)
    right = spark.create_dataframe(
        rt.rename_columns(["rk", "rv"]))
    for how in ("inner", "left_outer", "right_outer", "full_outer",
                "left_semi", "left_anti"):
        q = left.join(right, left_on=["k"], right_on=["rk"], how=how)
        assert_tpu_cpu_equal(q)


def test_sort_limit_differential(spark):
    t = gen_table({"a": "int64", "x": "float64"}, 300, seed=6)
    df = spark.create_dataframe(t)
    # total order (tie-break on both columns) so limit is deterministic
    q = df.order_by("a", "x").limit(17)
    assert_tpu_cpu_equal(q, ignore_order=False)


def test_union_differential(spark):
    t1 = gen_table({"a": "int64", "s": "string"}, 100, seed=7)
    t2 = gen_table({"a": "int64", "s": "string"}, 80, seed=8)
    q = spark.create_dataframe(t1).union(spark.create_dataframe(t2))
    assert_tpu_cpu_equal(q)


def test_range(spark):
    q = spark.range(0, 1000, 7).select(
        col("id"), (col("id") * lit(2)).alias("dbl"))
    assert_tpu_cpu_equal(q)


def test_parquet_scan(spark, tmp_path):
    import pyarrow.parquet as pq

    t = gen_table({"a": "int64", "s": "string", "x": "float64"}, 400,
                  seed=9)
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, row_group_size=100)
    q = spark.read_parquet(path).where(col("a").is_not_null())
    assert_tpu_cpu_equal(q)


def test_csv_scan(spark, tmp_path):
    import pyarrow.csv as pacsv

    t = pa.table({"a": pa.array([1, 2, 3], pa.int64()),
                  "b": pa.array([1.5, 2.5, 3.5])})
    path = str(tmp_path / "t.csv")
    pacsv.write_csv(t, path)
    q = spark.read_csv(path).select(
        (col("a") + lit(1)).alias("a1"), col("b"))
    assert_tpu_cpu_equal(q)


def test_explain_marks_everything_on_tpu(spark):
    df = spark.create_dataframe({"a": [1, 2, 3]})
    q = df.where(col("a") > lit(1)).select((col("a") * lit(2)).alias("d"))
    ex = q.explain()
    assert "!" not in ex
    assert ex.count("*") == 3  # project, filter, relation


def test_fallback_on_disabled_exec():
    conf = TpuConf()
    conf.set("spark.rapids.tpu.sql.exec.Filter", False)
    spark = TpuSession(conf)
    t = gen_table({"a": "int64"}, 100, seed=10)
    q = spark.create_dataframe(t).where(col("a") > lit(0)) \
             .select((col("a") + lit(1)).alias("a1"))
    ex = q.explain()
    assert "! Filter" in ex
    assert "disabled by spark.rapids.tpu.sql.exec.Filter" in ex
    assert "* Project" in ex
    # and the fallback still computes the right answer
    assert_tpu_cpu_equal(q)


def test_fallback_on_disabled_expression():
    conf = TpuConf()
    conf.set("spark.rapids.tpu.sql.expression.Divide", False)
    spark = TpuSession(conf)
    t = gen_table({"a": "int64", "b": "int64"}, 60, seed=11)
    q = spark.create_dataframe(t).select(
        (col("a") / col("b")).alias("q"))
    ex = q.explain()
    assert "expression Divide disabled" in ex
    assert_tpu_cpu_equal(q, approx_float=True)


def test_tpch_q6_shape(spark):
    """The BASELINE.md config-1 slice: scan+filter+project+sum."""
    n = 2000
    rng = np.random.default_rng(42)
    t = pa.table({
        "l_quantity": pa.array(
            rng.integers(1, 51, n).astype(np.float64)),
        "l_extendedprice": pa.array(rng.uniform(900, 105000, n)),
        "l_discount": pa.array(
            rng.integers(0, 11, n).astype(np.float64) / 100.0),
        "l_shipdate": pa.array(
            rng.integers(8000, 11000, n).astype(np.int32)),
    })
    df = spark.create_dataframe(t)
    q = df.where((col("l_shipdate") >= lit(8766))
                 & (col("l_shipdate") < lit(9131))
                 & (col("l_discount") >= lit(0.05))
                 & (col("l_discount") <= lit(0.07))
                 & (col("l_quantity") < lit(24.0))) \
          .select((col("l_extendedprice") * col("l_discount"))
                  .alias("rev")) \
          .agg((sum_("rev"), "revenue"))
    assert_tpu_cpu_equal(q, approx_float=True)


def test_tpch_q1_shape(spark):
    """BASELINE.md config-2 slice: multi-aggregate group-by."""
    n = 3000
    rng = np.random.default_rng(43)
    t = pa.table({
        "l_returnflag": pa.array(
            [["A", "N", "R"][i] for i in rng.integers(0, 3, n)]),
        "l_linestatus": pa.array(
            [["F", "O"][i] for i in rng.integers(0, 2, n)]),
        "l_quantity": pa.array(rng.integers(1, 51, n).astype(np.float64)),
        "l_extendedprice": pa.array(rng.uniform(900, 105000, n)),
        "l_discount": pa.array(
            rng.integers(0, 11, n).astype(np.float64) / 100.0),
        "l_tax": pa.array(rng.integers(0, 9, n).astype(np.float64) / 100.0),
    })
    df = spark.create_dataframe(t)
    disc_price = (col("l_extendedprice")
                  * (lit(1.0) - col("l_discount"))).alias("disc_price")
    charge = (col("l_extendedprice") * (lit(1.0) - col("l_discount"))
              * (lit(1.0) + col("l_tax"))).alias("charge")
    q = (df.select(col("l_returnflag"), col("l_linestatus"),
                   col("l_quantity"), col("l_extendedprice"),
                   col("l_discount"), disc_price, charge)
           .group_by("l_returnflag", "l_linestatus")
           .agg((sum_("l_quantity"), "sum_qty"),
                (sum_("l_extendedprice"), "sum_base_price"),
                (sum_("disc_price"), "sum_disc_price"),
                (sum_("charge"), "sum_charge"),
                (avg("l_quantity"), "avg_qty"),
                (avg("l_extendedprice"), "avg_price"),
                (avg("l_discount"), "avg_disc"),
                (count_star(), "count_order"))
           .order_by("l_returnflag", "l_linestatus"))
    # the coded group-by adds each sum's terms in another order than the
    # CPU engine; sums near 3e7 then differ past the harness's nine
    # decimals, so hold the doubles to a relative bound instead
    tpu, cpu = q.collect(engine="tpu"), q.collect(engine="cpu")
    assert tpu.schema.names == cpu.schema.names
    for name in tpu.schema.names:
        got, want = tpu[name].to_pylist(), cpu[name].to_pylist()
        if pa.types.is_floating(tpu[name].type):
            np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
        else:
            assert got == want, name


def test_to_device_arrays_zero_copy_into_jax():
    """ColumnarRdd-analog export (ref: ColumnarRdd.scala): SQL results
    stay on device and feed jax code directly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.exprs.base import lit

    session = TpuSession()
    rng = np.random.default_rng(4)
    t = pa.table({"x": rng.random(500), "y": rng.random(500)})
    df = (session.create_dataframe(t)
          .where(col("x") > lit(0.5))
          .select(col("x"), (col("x") * col("y")).alias("xy")))
    batches = df.to_device_arrays()
    assert batches and all(isinstance(b["x"], jax.Array)
                           for b in batches)
    # consume straight from HBM: a jitted reduction over the batches
    total = sum(float(jnp.sum(jnp.where(b["xy__valid"], b["xy"], 0.0)))
                for b in batches)
    x, y = np.asarray(t["x"]), np.asarray(t["y"])
    want = float((x[x > 0.5] * y[x > 0.5]).sum())
    assert abs(total - want) < 1e-6 * max(1.0, abs(want))
