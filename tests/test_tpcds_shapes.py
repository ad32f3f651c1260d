"""TPC-DS q67/q93-shaped differential tests (BASELINE.md config #4:
sort + window workloads; ref: the reference validates these shapes via
its NDS runs).  Small-scale data, full plan shapes: rollup aggregate ->
ranking window -> rank filter -> order by (q67), and join + window +
conditional arithmetic -> grouped sum -> top-N (q93)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.exprs.base import lit
from spark_rapids_tpu.exprs.window import Window, rank
from spark_rapids_tpu.session import TpuSession, col, sum_
from tests.differential import assert_tpu_cpu_equal

pytestmark = pytest.mark.slow  # TPC tier


@pytest.fixture
def session():
    return TpuSession()


def _store_sales(tmp_path, n=20_000, seed=67):
    rng = np.random.default_rng(seed)
    t = pa.table({
        "ss_item_sk": rng.integers(1, 40, n),
        "ss_store_sk": rng.integers(1, 6, n),
        "ss_quantity": rng.integers(1, 20, n),
        "ss_sales_price": np.round(rng.uniform(1, 300, n), 2),
        "ss_ticket_number": rng.integers(1, n // 2, n),
        "ss_customer_sk": pa.array(
            [None if rng.random() < 0.08 else int(x)
             for x in rng.integers(1, 500, n)], pa.int64()),
    })
    paths = []
    for i in range(4):
        p = str(tmp_path / f"ss{i}.parquet")
        pq.write_table(t.slice(i * (n // 4), n // 4), p)
        paths.append(p)
    return paths


def test_q67_shape_rollup_window_rank(session, tmp_path):
    """q67: aggregate sales, rank items within each store by revenue,
    keep the top ranks, order the output — grouped aggregate under a
    ranking window under a filter under a global sort."""
    paths = _store_sales(tmp_path)
    agg = (session.read_parquet(*paths)
           .group_by(col("ss_store_sk"), col("ss_item_sk"))
           .agg((sum_(col("ss_sales_price") * col("ss_quantity")),
                 "sumsales")))
    spec = Window.partition_by("ss_store_sk").order_by(
        "sumsales", desc=True)
    ranked = agg.select(col("ss_store_sk"), col("ss_item_sk"),
                        col("sumsales"),
                        rank().over(spec).alias("rk"))
    out = (ranked.where(col("rk") <= lit(5))
           .order_by(col("ss_store_sk"), col("rk"),
                     col("ss_item_sk")))
    assert_tpu_cpu_equal(out, ignore_order=False, approx_float=True)
    got = out.collect(engine="tpu").to_pydict()
    assert got["rk"] and max(got["rk"]) <= 5


def test_q93_shape_join_conditional_topn(session, tmp_path):
    """q93: sales joined to returns on (item, ticket), refunded
    quantity subtracted conditionally, summed per customer, top-N by
    total — shuffled join + conditional arithmetic + grouped sum +
    TakeOrdered."""
    from spark_rapids_tpu.exprs.predicates import If, IsNotNull

    rng = np.random.default_rng(93)
    paths = _store_sales(tmp_path, seed=93)
    nr = 3_000
    returns = pa.table({
        "sr_item_sk": rng.integers(1, 40, nr),
        "sr_ticket_number": rng.integers(1, 10_000, nr),
        "sr_return_quantity": rng.integers(1, 10, nr),
        "sr_reason_sk": rng.integers(1, 5, nr),
    })
    sales = session.read_parquet(*paths)
    rdf = session.create_dataframe(returns).where(
        col("sr_reason_sk").eq(lit(3)))
    joined = sales.join(
        rdf, how="left_outer",
        left_on=[col("ss_item_sk"), col("ss_ticket_number")],
        right_on=[col("sr_item_sk"), col("sr_ticket_number")])
    act_qty = If(IsNotNull(col("sr_ticket_number")),
                 col("ss_quantity") - col("sr_return_quantity"),
                 col("ss_quantity"))
    out = (joined.select(col("ss_customer_sk"),
                         (act_qty * col("ss_sales_price")).alias("act"))
           .group_by(col("ss_customer_sk"))
           .agg((sum_(col("act")), "sumsales"))
           .order_by(col("sumsales"), col("ss_customer_sk"))
           .limit(50))
    assert_tpu_cpu_equal(out, ignore_order=False, approx_float=True)


def test_q67_shape_on_collective_mesh(tmp_path):
    """The q67 shape through the collective tier: rollup aggregate +
    window + sort all lower onto the 8-device mesh programs."""
    session = TpuSession()
    session.enable_collective_shuffle(8)
    try:
        paths = _store_sales(tmp_path, n=8_000, seed=68)
        agg = (session.read_parquet(*paths)
               .group_by(col("ss_store_sk"), col("ss_item_sk"))
               .agg((sum_(col("ss_sales_price")), "s")))
        spec = Window.partition_by("ss_store_sk").order_by(
            "s", desc=True)
        out = (agg.select(col("ss_store_sk"), col("ss_item_sk"),
                          col("s"), rank().over(spec).alias("rk"))
               .where(col("rk") <= lit(3))
               .order_by(col("ss_store_sk"), col("rk"),
                         col("ss_item_sk")))
        assert_tpu_cpu_equal(out, ignore_order=False,
                             approx_float=True)
        # the window crosses the mesh in a stage of its own: no local
        # exchange hands it batches parked on different chips
        held, todo = set(), [session.history.events[-1].root]
        while todo:
            node = todo.pop()
            held.add(node.desc.split(" ", 1)[0])
            todo += node.children
        assert "TpuCollectiveWindowExec" in held, held
        assert "TpuShuffleExchangeExec" not in held, held
    finally:
        session.disable_collective_shuffle()


def test_q93_shape_sql_text(tmp_path):
    """The q93 moving parts driven from SQL TEXT through
    frontend("sql"): join on (item, ticket), CASE'd refund arithmetic,
    grouped sum, top-N — the user's query string, unmodified."""
    from spark_rapids_tpu.frontends.sql import SqlSession

    rng = np.random.default_rng(93)
    n = 8_000
    fe = SqlSession()
    fe.register_table("store_sales", pa.table({
        "ss_item_sk": rng.integers(1, 40, n),
        "ss_ticket_number": rng.integers(1, n // 2, n),
        "ss_customer_sk": rng.integers(1, 300, n),
        "ss_quantity": rng.integers(1, 20, n).astype(np.int64),
        "ss_sales_price": np.round(rng.uniform(1, 300, n), 2),
    }))
    m = 2_000
    fe.register_table("store_returns", pa.table({
        "sr_item_sk": rng.integers(1, 40, m),
        "sr_ticket_number": rng.integers(1, n // 2, m),
        "sr_return_quantity": rng.integers(1, 10, m).astype(np.int64),
    }))
    df = fe.sql("""
        select ss_customer_sk,
               sum(case when sr_return_quantity is not null
                        then (ss_quantity - sr_return_quantity)
                             * ss_sales_price
                        else ss_quantity * ss_sales_price end) as sumsales
        from store_sales
             left join store_returns
               on ss_item_sk = sr_item_sk
              and ss_ticket_number = sr_ticket_number
        group by ss_customer_sk
        order by sumsales, ss_customer_sk
        limit 25
    """)
    t_tpu = df.collect(engine="tpu")
    t_cpu = df.collect(engine="cpu")
    a = list(zip(*t_tpu.to_pydict().values()))
    b = list(zip(*t_cpu.to_pydict().values()))
    assert len(a) == len(b) == 25
    # revenue ordering is the contract; customer tiebreak may differ on
    # equal sums, so compare the sorted value columns
    for (ac, av), (bc, bv) in zip(a, b):
        assert abs(av - bv) <= 1e-6 * max(1.0, abs(bv)), ((ac, av),
                                                          (bc, bv))
