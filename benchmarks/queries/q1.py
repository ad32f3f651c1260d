"""TPC-H Q1 (specification clause 2.4.1), DELTA = 90: pricing summary
report over the lines shipped by 1998-09-02, 98.6% of them.

Eight aggregates over the four groups of (l_returnflag, l_linestatus)
the data holds.  Copied from `bench.q1_over`, which leaves out the
query's ORDER BY of those four rows; rows are compared as a set.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators._tpch import LINESTATUSES, RETURNFLAGS

ORDERED = False
COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_shipdate", "l_returnflag",
                        "l_linestatus"]}
DRIVER = "lineitem"

_GROUPS = len(RETURNFLAGS) * len(LINESTATUSES)
#: 1998-12-01 less 90 days, 1998-09-02, in days since 1970-01-01
_LAST_DAY = 10471


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.session import avg, col, count_star, sum_

    qty, price = col("l_quantity"), col("l_extendedprice")
    disc, tax = col("l_discount"), col("l_tax")
    return (frames["lineitem"]
            .where(col("l_shipdate") <= Literal.of(_LAST_DAY, T.DATE))
            .group_by(col("l_returnflag"), col("l_linestatus"))
            .agg((sum_(qty), "sum_qty"),
                 (sum_(price), "sum_base_price"),
                 (sum_(price * (lit(1.0) - disc)), "sum_disc_price"),
                 (sum_(price * (lit(1.0) - disc) * (lit(1.0) + tax)),
                  "sum_charge"),
                 (avg(qty), "avg_qty"),
                 (avg(price), "avg_price"),
                 (avg(disc), "avg_disc"),
                 (count_star(), "count_order")))


def partial(cols: dict, side: dict) -> np.ndarray:
    """Per group: count and the five sums the eight aggregates need."""
    keep = cols["l_shipdate"] <= _LAST_DAY
    group = (cols["l_returnflag"][keep].astype(np.int64) * len(LINESTATUSES)
             + cols["l_linestatus"][keep])
    qty, price = cols["l_quantity"][keep], cols["l_extendedprice"][keep]
    disc, tax = cols["l_discount"][keep], cols["l_tax"][keep]
    disc_price = price * (1.0 - disc)
    weights = [None, qty, price, disc_price, disc_price * (1.0 + tax), disc]
    return np.stack([np.bincount(group, w, minlength=_GROUPS)
                     for w in weights])


def combine(partials: list) -> pa.Table:
    n, qty, price, disc_price, charge, disc = np.sum(partials, axis=0)
    seen = n > 0
    group = np.arange(_GROUPS)[seen]
    n, qty, price, disc_price, charge, disc = (
        x[seen] for x in (n, qty, price, disc_price, charge, disc))
    return pa.table({
        "l_returnflag": RETURNFLAGS[group // len(LINESTATUSES)],
        "l_linestatus": LINESTATUSES[group % len(LINESTATUSES)],
        "sum_qty": qty, "sum_base_price": price,
        "sum_disc_price": disc_price, "sum_charge": charge,
        "avg_qty": qty / n, "avg_price": price / n, "avg_disc": disc / n,
        "count_order": n.astype(np.int64),
    })
