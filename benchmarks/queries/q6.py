"""TPC-H Q6 (specification clause 2.4.6), DATE = 1994-01-01, DISCOUNT =
0.06, QUANTITY = 24: forecast revenue change.

One filtered sum over lineitem; the filter keeps 1.9% of the rows.
Copied from `bench.q6_over`.
"""

import numpy as np
import pyarrow as pa

ORDERED = False
COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate"]}
DRIVER = "lineitem"

#: 1994-01-01 and 1995-01-01, days since 1970-01-01
_FROM, _TO = 8766, 9131


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.session import col, sum_

    ship, disc, qty = col("l_shipdate"), col("l_discount"), col("l_quantity")
    cond = ((ship >= Literal.of(_FROM, T.DATE))
            & (ship < Literal.of(_TO, T.DATE))
            & (disc >= lit(0.05)) & (disc <= lit(0.07))
            & (qty < lit(24.0)))
    return frames["lineitem"].where(cond).agg(
        (sum_(col("l_extendedprice") * disc), "revenue"))


def partial(cols: dict, side: dict) -> float:
    ship, disc = cols["l_shipdate"], cols["l_discount"]
    keep = ((ship >= _FROM) & (ship < _TO) & (disc >= 0.05)
            & (disc <= 0.07) & (cols["l_quantity"] < 24.0))
    return float(np.sum(cols["l_extendedprice"][keep] * disc[keep]))


def combine(partials: list) -> pa.Table:
    return pa.table({"revenue": pa.array([sum(partials)], pa.float64())})
