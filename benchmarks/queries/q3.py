"""TPC-H Q3 (specification clause 2.4.3), DATE = 1995-03-15, on two
tables: lineitem joined to orders, date filters on both sides, revenue
per order, the ten largest.  Without `customer` and its segment filter
(ROADMAP R2), so five times the orders of the query as written reach
the join.  Copied from `bench.q3_dataframe`.
"""

import numpy as np
import pyarrow as pa

ORDERED = True
COLUMNS = {"lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"],
           "orders": ["o_orderkey", "o_orderdate", "o_shippriority"]}
DRIVER = "lineitem"

_DAY = 9204  # 1995-03-15, days since 1970-01-01


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.session import col, sum_

    day = Literal.of(_DAY, T.DATE)
    li = frames["lineitem"].where(col("l_shipdate") > day)
    orders = frames["orders"].where(col("o_orderdate") < day)
    joined = li.join(orders, left_on=[col("l_orderkey")],
                     right_on=[col("o_orderkey")])
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (joined
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg((sum_(rev), "revenue"))
            .order_by(col("revenue"), desc=True)
            .limit(10))


def partial(cols: dict, side: dict) -> tuple:
    """Revenue per order among this file's rows that pass both filters,
    with the order's own columns.  `o_orderkey` ascends, so the join
    is a binary search."""
    orders = side["orders"]
    at = np.searchsorted(orders["o_orderkey"], cols["l_orderkey"])
    assert np.array_equal(orders["o_orderkey"][at], cols["l_orderkey"])
    keep = (cols["l_shipdate"] > _DAY) & (orders["o_orderdate"][at] < _DAY)
    at, group = np.unique(at[keep], return_inverse=True)
    rev = cols["l_extendedprice"][keep] * (1.0 - cols["l_discount"][keep])
    return (orders["o_orderkey"][at],
            np.bincount(group, rev, minlength=len(at)),
            orders["o_orderdate"][at], orders["o_shippriority"][at])


def combine(partials: list) -> pa.Table:
    keys, revenue, date, priority = (
        np.concatenate([p[i] for p in partials]) for i in range(4))
    keys, first, at = np.unique(keys, return_index=True,
                                return_inverse=True)
    revenue = np.bincount(at, revenue, minlength=len(keys))
    top = np.argsort(-revenue, kind="stable")[:10]
    return pa.table({
        "l_orderkey": keys[top],
        "o_orderdate": pa.array(date[first[top]], pa.date32()),
        "o_shippriority": priority[first[top]],
        "revenue": revenue[top],
    })
