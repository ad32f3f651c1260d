"""TPC-H q1 and q6 over money as DECIMAL(15,2), as the benchmark's cell
`tpch-sf10-decimal.resident` runs them: `benchmarks/queries/
q1_decimal.py` / `q6_decimal.py` through `TpuSession` over a few
thousand rows of `lineitem_decimal`, compared exactly with the queries'
plain reference; Spark's result types and a plan that runs wholly on
the device.  A crafted table whose sums pass 2^63 holds the sums to
128 bits, and the same queries over DOUBLE money read unequal."""

import decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import lineitem, lineitem_decimal
from benchmarks.harness import check, engine
from benchmarks.queries import q1, q1_decimal, q6, q6_decimal

D = decimal.Decimal
ROWS, FILES = 4096, 2


@pytest.fixture
def session():
    from spark_rapids_tpu.session import TpuSession

    return TpuSession()


def _files(tmp_path, seed: int, gen):
    """The generator's files and its per-file arrays."""
    paths, cols = [], []
    for i in range(FILES):
        c = gen.generate(seed, i, ROWS)
        path = str(tmp_path / f"lineitem-{i}.parquet")
        pq.write_table(gen.to_arrow(c, seed, i), path)
        paths.append(path)
        cols.append(c)
    return paths, cols


def _on_device(session, df) -> None:
    text = df.explain()
    assert engine.off_device(text) == [], text
    assert "[degraded to CPU engine" not in text


@pytest.mark.parametrize("query", [q6_decimal, q1_decimal])
@pytest.mark.parametrize("seed", [45, 3000000045])
def test_query_equals_reference_exactly(session, tmp_path, query, seed):
    from spark_rapids_tpu.execs.retry import retry_stats

    paths, cols = _files(tmp_path, seed, lineitem_decimal)
    frame = session.read_parquet(*paths, columns=query.COLUMNS["lineitem"])
    want = query.combine([query.partial(c, {}) for c in cols])
    fallbacks = retry_stats()["cpu_fallbacks"]
    for frames in ({"lineitem": frame}, {"lineitem": frame.cache()}):
        df = query.build(session, frames)
        _on_device(session, df)
        got = df.collect()
        assert {f.name: f.type for f in got.schema} == query.TYPES
        reason, _ = check.compare(got, want, query.ORDERED)
        assert reason is None, reason
    assert retry_stats()["cpu_fallbacks"] == fallbacks


def test_reference_types_are_sparks():
    assert q6_decimal.TYPES == {"revenue": pa.decimal128(38, 4)}
    t = q1_decimal.TYPES
    assert (t["sum_qty"], t["sum_base_price"]) == (pa.decimal128(25, 2),) * 2
    assert t["sum_disc_price"] == pa.decimal128(38, 4)
    assert t["sum_charge"] == pa.decimal128(38, 6)
    assert t["avg_qty"] == t["avg_price"] == t["avg_disc"] \
        == pa.decimal128(19, 6)


def test_build_refuses_other_types(session):
    """A plan typed otherwise is refused before a round: here q6's sum
    as Spark's decimal(38,4) expected, and decimal(38,2) asked for."""
    from spark_rapids_tpu.session import col, sum_

    t = pa.table({"v": pa.array([D("1.00")], pa.decimal128(15, 2))})
    df = session.create_dataframe(t).agg((sum_(col("v")), "revenue"))
    with pytest.raises(SystemExit, match="REFUSED"):
        q6_decimal.refuse_other_types(df, q6_decimal.TYPES)


def _crafted(n: int, money) -> pa.Table:
    """`n` lines at the largest DECIMAL(15,2) price, shipped in 1994 at a
    discount of 0.06, 50 and then 20 of a part: they pass q6's filter
    and fall in one q1 group.  The money columns are DECIMAL(15,2)
    (`money` "decimal") or DOUBLE."""
    cents = {"l_quantity": np.where(np.arange(n) % 2, 2000, 5000),
             "l_extendedprice": np.full(n, PRICE, np.int64),
             "l_discount": np.full(n, 6, np.int64),
             "l_tax": np.full(n, 8, np.int64)}
    as_money = (lineitem_decimal.decimal_cents if money == "decimal"
                else (lambda c: pa.array(c / 100.0)))
    return pa.table({
        **{k: as_money(np.asarray(v, np.int64)) for k, v in cents.items()},
        "l_shipdate": pa.array(np.full(n, 9000, np.int32), pa.date32()),
        "l_returnflag": pa.array(["A"] * n),
        "l_linestatus": pa.array(["F"] * n)})


PRICE = 10 ** 15 - 1  # 9,999,999,999,999.99 in cents
CRAFTED = 10_000


def test_sums_past_two_to_the_63(session):
    """10,000 lines at the largest DECIMAL(15,2) price: the cents of
    sum_base_price and of each line's charge pass 2^63, where an int64
    accumulator wraps; q1 and q6 answer every sum exactly and each
    average by Spark's two roundings."""
    n = CRAFTED
    assert int(np.sum(np.full(n, PRICE, np.int64))) != n * PRICE  # wraps
    frame = session.create_dataframe(_crafted(n, "decimal"))
    df = q1_decimal.build(session, {"lineitem": frame})
    _on_device(session, df)
    got = df.collect().to_pylist()[0]
    p = D(PRICE).scaleb(-2)
    charge = p * D("0.94") * D("1.08")
    assert got["sum_qty"] == D(35 * n)
    assert got["sum_base_price"] == p * n
    assert got["sum_disc_price"] == p * D("0.94") * n
    assert got["sum_charge"] == charge * n
    assert got["avg_qty"] == D("35.000000")
    assert got["avg_price"] == p.quantize(D("0.000001"))
    assert got["avg_disc"] == D("0.060000")
    assert got["count_order"] == n
    assert df.collect(engine="cpu").to_pylist()[0] == got
    df = q6_decimal.build(session, {"lineitem": frame})
    _on_device(session, df)
    assert df.collect().to_pylist() == [
        {"revenue": p * D("0.06") * (n // 2)}]


@pytest.mark.parametrize("pair", [(q6, q6_decimal), (q1, q1_decimal)])
def test_double_money_fails_the_exact_comparison(session, pair):
    """The control: the twin's queries over the crafted lines with
    DOUBLE money do not answer as the DECIMAL(15,2) queries do (checked
    exact above): their sums have more digits than a double holds."""
    double_q, decimal_q = pair
    want = decimal_q.build(session, {"lineitem": session.create_dataframe(
        _crafted(CRAFTED, "decimal"))}).collect()
    got = double_q.build(session, {"lineitem": session.create_dataframe(
        _crafted(CRAFTED, "double"))}).collect()
    reason, _ = check.compare(got, want, decimal_q.ORDERED)
    assert reason is not None
