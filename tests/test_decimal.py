"""Decimal precision management (CheckOverflow / PromotePrecision) —
the analyzer-wrapped decimal arithmetic shape, differential vs the CPU
oracle on unscaled int64 device math."""

import decimal

import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.decimal import CheckOverflow, PromotePrecision
from spark_rapids_tpu.session import TpuSession, col


def D(s):
    return decimal.Decimal(s)


@pytest.fixture
def session():
    return TpuSession()


def _tbl(vals, prec=10, scale=2, name="d"):
    return pa.table({name: pa.array(vals, pa.decimal128(prec, scale))})


def test_promote_then_add_then_check(session):
    """CheckOverflow(Add(PromotePrecision(l), PromotePrecision(r))) —
    the exact shape Spark's analyzer emits for decimal addition."""
    l = [D("1.10"), D("99999999.99"), None, D("-5.25")]
    r = [D("2.05"), D("0.01"), D("3.00"), D("-0.75")]
    t = pa.table({
        "l": pa.array(l, pa.decimal128(10, 2)),
        "r": pa.array(r, pa.decimal128(10, 2)),
    })
    wide = T.DecimalType(11, 2)
    expr = CheckOverflow(
        PromotePrecision(col("l"), wide) + PromotePrecision(col("r"),
                                                            wide),
        wide)
    df = session.create_dataframe(t).select(expr.alias("s"))
    got = df.collect(engine="tpu").to_pydict()["s"]
    want = df.collect(engine="cpu").to_pydict()["s"]
    assert got == want
    assert got[0] == D("3.15")
    assert got[2] is None  # null operand


def test_check_overflow_nulls_out_of_range(session):
    vals = [D("99999999.99"), D("-99999999.99"), D("1.00"), None]
    t = _tbl(vals)
    # narrow target: 4 integral digits only
    narrow = T.DecimalType(6, 2)
    df = (session.create_dataframe(t)
          .select(CheckOverflow(col("d"), narrow).alias("o")))
    got = df.collect(engine="tpu").to_pydict()["o"]
    want = df.collect(engine="cpu").to_pydict()["o"]
    assert got == want
    assert got[0] is None and got[1] is None
    assert got[2] == D("1.00")


def test_check_overflow_rescale_half_up(session):
    vals = [D("1.25"), D("1.24"), D("-1.25"), D("-1.24"), D("0.05")]
    t = _tbl(vals)
    one_dp = T.DecimalType(6, 1)
    df = (session.create_dataframe(t)
          .select(CheckOverflow(col("d"), one_dp).alias("o")))
    got = df.collect(engine="tpu").to_pydict()["o"]
    want = df.collect(engine="cpu").to_pydict()["o"]
    assert got == want
    assert got == [D("1.3"), D("1.2"), D("-1.3"), D("-1.2"), D("0.1")]


def test_mismatched_decimal_add_widens(session):
    """Spark's analyzer result type: operands rescale to the max scale
    and precision widens by one — computed on device as exact unscaled
    int64 math."""
    t = pa.table({
        "a": pa.array([D("1.10"), D("-2.55"), None],
                      pa.decimal128(10, 2)),
        "b": pa.array([D("1.1"), D("0.5"), D("3.0")],
                      pa.decimal128(10, 1)),
    })
    df = session.create_dataframe(t).select((col("a") + col("b"))
                                            .alias("s"))
    got = df.collect(engine="tpu").to_pydict()["s"]
    want = df.collect(engine="cpu").to_pydict()["s"]
    assert got == want
    assert got[0] == D("2.20") and got[1] == D("-2.05")
    assert got[2] is None


def test_decimal_add_beyond_precision_falls_back(session):
    """decimal(18,2) + decimal(18,2) is decimal(19,2), now a 128-bit
    add on the device; an add whose operands do not rescale within
    precision 38 (decimal(38,0) + decimal(38,2) would need 40 digits)
    still falls back to the CPU engine, by name."""
    t = pa.table({
        "a": pa.array([D("1.10")], pa.decimal128(18, 2)),
        "b": pa.array([D("1.10")], pa.decimal128(18, 2)),
    })
    df = session.create_dataframe(t).select((col("a") + col("b"))
                                            .alias("s"))
    from spark_rapids_tpu.plan.planner import plan_query, CpuFallbackExec

    exec_, meta = plan_query(df._plan)
    assert not isinstance(exec_, CpuFallbackExec), meta.explain()
    assert df.schema.fields[0].dtype == T.DecimalType(19, 2)
    assert df.collect(engine="tpu").to_pydict()["s"] == [D("2.20")]

    wide = pa.table({
        "a": pa.array([D("1")], pa.decimal128(38, 0)),
        "b": pa.array([D("1.10")], pa.decimal128(38, 2)),
    })
    df = session.create_dataframe(wide).select((col("a") + col("b"))
                                               .alias("s"))
    exec_, meta = plan_query(df._plan)
    assert isinstance(exec_, CpuFallbackExec), meta.explain()
    assert "within precision 38 falls back" in meta.explain()
    assert df.collect(engine="tpu").to_pydict()["s"] == [D("2.10")]


def test_check_overflow_scale_up_wraparound(session):
    """Scaling UP near int64 limits must NULL, not wrap back inside
    the bound (the int64 wraparound trap)."""
    v = D("184467440737095517")  # *100 wraps modulo 2**64 to ~84
    t = pa.table({"d": pa.array([v, D("1")], pa.decimal128(18, 0))})
    tgt = T.DecimalType(18, 2)
    df = (session.create_dataframe(t)
          .select(CheckOverflow(col("d"), tgt).alias("o")))
    got = df.collect(engine="tpu").to_pydict()["o"]
    assert got[0] is None  # overflow -> NULL, never a wrong value
    assert got[1] == D("1.00")
    assert got == df.collect(engine="cpu").to_pydict()["o"]


def test_wide_decimal_fallback_nulls_not_crashes(session):
    """A decimal product past 38 digits is NULL (Spark's non-ANSI
    CheckOverflow), computed on the device: decimal(18,0)^2 is
    decimal(37,0) and holds 10^32; decimal(38,0)^2 is cut to
    decimal(38,0), which 10^40 overflows."""
    t = pa.table({"d": pa.array([D("10000000000000000"), D("2")],
                                pa.decimal128(18, 0))})
    df = session.create_dataframe(t).select((col("d") * col("d"))
                                            .alias("sq"))
    assert "does not support" not in df.explain()
    out = df.collect(engine="tpu").to_pydict()["sq"]
    assert out == [D("100000000000000000000000000000000"), D("4")]
    assert out == df.collect(engine="cpu").to_pydict()["sq"]

    t = pa.table({"d": pa.array([D("10") ** 20, D("2")],
                                pa.decimal128(38, 0))})
    df = session.create_dataframe(t).select((col("d") * col("d"))
                                            .alias("sq"))
    assert df.schema.fields[0].dtype == T.DecimalType(38, 0)
    out = df.collect(engine="tpu").to_pydict()["sq"]
    assert out[0] is None  # 10^40 cannot fit 38 digits
    assert out[1] == D("4")
    assert out == df.collect(engine="cpu").to_pydict()["sq"]
