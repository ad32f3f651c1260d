"""Declarative type-signature tagging tests (ref: TypeChecks.scala —
unsupported input types fall back with reasons, never wrong results)."""

import decimal

import pyarrow as pa
import pytest

from spark_rapids_tpu.session import TpuSession, col, count, min_, sum_
from tests.differential import assert_tpu_cpu_equal


@pytest.fixture
def session():
    return TpuSession()


def test_decimal_add_on_tpu_multiply_falls_back(session):
    """Decimal +/- and * run on TPU with Spark's result types (the
    product exact in 128 bits); an arithmetic rule that still refuses
    decimals (remainder) falls back with a reason."""
    t = pa.table({"d": pa.array(
        [decimal.Decimal("1.25"), decimal.Decimal("-2.50"), None],
        pa.decimal128(10, 2))})
    df = session.create_dataframe(t).select(
        (col("d") + col("d")).alias("dbl"))
    assert "does not support" not in df.explain()
    out = df.collect().to_pydict()
    assert out["dbl"][0] == decimal.Decimal("2.50")
    assert out["dbl"][2] is None

    dfm = session.create_dataframe(t).select(
        (col("d") * col("d")).alias("sq"))
    assert "does not support" not in dfm.explain()
    assert dfm.collect().to_pydict()["sq"] == [
        decimal.Decimal("1.5625"), decimal.Decimal("6.2500"), None]

    dfr = session.create_dataframe(t).select(
        (col("d") % col("d")).alias("r"))
    why = dfr.explain()
    assert "does not support input type decimal(10,2)" in why, why


def test_decimal_sum_stays_on_tpu(session):
    t = pa.table({"d": pa.array(
        [decimal.Decimal("1.25"), decimal.Decimal("2.50")],
        pa.decimal128(10, 2))})
    df = session.create_dataframe(t).agg((sum_(col("d")), "s"))
    assert "does not support" not in df.explain()
    assert df.collect().to_pydict()["s"] == [decimal.Decimal("3.75")]


def test_array_comparison_falls_back(session):
    from spark_rapids_tpu.exprs.predicates import EqualTo

    t = pa.table({"xs": pa.array([[1], [2]], pa.list_(pa.int64()))})
    df = session.create_dataframe(t).where(
        EqualTo(col("xs"), col("xs")))
    assert "does not support input type array<bigint>" in df.explain()


def test_string_min_falls_back_count_stays(session):
    t = pa.table({"g": pa.array([1, 1, 2], pa.int64()),
                  "s": pa.array(["b", "a", None], pa.string())})
    df_min = session.create_dataframe(t).group_by(col("g")).agg(
        (min_(col("s")), "m"))
    assert "aggregate min does not support input type string" \
        in df_min.explain()
    out = df_min.collect().to_pydict()  # via fallback
    assert dict(zip(out["g"], out["m"])) == {1: "a", 2: None}
    # count over strings runs on TPU (validity-only)
    df_cnt = session.create_dataframe(t).group_by(col("g")).agg(
        (count(col("s")), "c"))
    assert "does not support" not in df_cnt.explain()
    out = df_cnt.collect().to_pydict()
    assert dict(zip(out["g"], out["c"])) == {1: 2, 2: 0}
    assert_tpu_cpu_equal(df_cnt)


def test_generated_docs_cover_registries():
    from spark_rapids_tpu.plan import planner as PL
    from spark_rapids_tpu.tools.gen_docs import configs_md, supported_ops_md

    md = supported_ops_md()
    for cls in PL.SUPPORTED_EXPRS:
        assert f"| {cls.__name__} |" in md
    assert "decimal arithmetic falls back" in md
    cfg = configs_md()
    assert "spark.rapids.tpu.sql.batchSizeRows" in cfg
