"""TPC-H Q1 (specification clause 2.4.1), DELTA = 90, over money as
DECIMAL(15,2): the pricing summary report over the lines shipped by
1998-09-02, written as the specification writes it, `1` an integer
literal in `l_extendedprice * (1 - l_discount) * (1 + l_tax)`.
Without its ORDER BY of four rows, as `q1.py`; rows are compared as a
set.

Under Spark 3.x's `DecimalPrecision` (allowPrecisionLoss, non-ANSI,
pickMinimumPrecision: the literal 1 is decimal(1,0)):
`sum_qty` and `sum_base_price` decimal(25,2); `1 - l_discount`
decimal(16,2), the product with `l_extendedprice` decimal(32,4) and its
sum decimal(38,4); times `1 + l_tax` decimal(49,6) cut to decimal(38,6),
and its sum decimal(38,6); each average decimal(19,6), Spark's `Average`
of a decimal(15,2): the decimal(25,2) sum over the count as
decimal(20,0), at decimal(38,15), rounded HALF_UP, then cast to
decimal(19,6), rounded HALF_UP again.  `build` refuses, before a round,
a plan that types it otherwise.

The plain reference works in integer cents: per file and group, the
products and sums in int64 (each product under 1.1e11, 2^20 rows a
file); the files' sums in Python integers; the averages in Python
decimals with Spark's two roundings.
"""

import decimal

import numpy as np
import pyarrow as pa

from benchmarks.generators._tpch import LINESTATUSES, RETURNFLAGS
from benchmarks.queries.q6_decimal import refuse_other_types

ORDERED = False
COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_shipdate", "l_returnflag",
                        "l_linestatus"]}
DRIVER = "lineitem"

_GROUPS = len(RETURNFLAGS) * len(LINESTATUSES)
#: 1998-12-01 less 90 days, 1998-09-02, in days since 1970-01-01
_LAST_DAY = 10471

_AVG = pa.decimal128(19, 6)
TYPES = {
    "l_returnflag": pa.string(), "l_linestatus": pa.string(),
    "sum_qty": pa.decimal128(25, 2), "sum_base_price": pa.decimal128(25, 2),
    "sum_disc_price": pa.decimal128(38, 4),
    "sum_charge": pa.decimal128(38, 6),
    "avg_qty": _AVG, "avg_price": _AVG, "avg_disc": _AVG,
    "count_order": pa.int64(),
}


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.session import avg, col, count_star, sum_

    qty, price = col("l_quantity"), col("l_extendedprice")
    disc, tax = col("l_discount"), col("l_tax")
    df = (frames["lineitem"]
          .where(col("l_shipdate") <= Literal.of(_LAST_DAY, T.DATE))
          .group_by(col("l_returnflag"), col("l_linestatus"))
          .agg((sum_(qty), "sum_qty"),
               (sum_(price), "sum_base_price"),
               (sum_(price * (lit(1) - disc)), "sum_disc_price"),
               (sum_(price * (lit(1) - disc) * (lit(1) + tax)),
                "sum_charge"),
               (avg(qty), "avg_qty"),
               (avg(price), "avg_price"),
               (avg(disc), "avg_disc"),
               (count_star(), "count_order")))
    refuse_other_types(df, TYPES)
    return df


def partial(cols: dict, side: dict) -> list:
    """Per group: the count and the five sums the eight aggregates need,
    as Python integers (cents, or the products' own scales)."""
    keep = cols["l_shipdate"] <= _LAST_DAY
    group = (cols["l_returnflag"][keep].astype(np.int64) * len(LINESTATUSES)
             + cols["l_linestatus"][keep])
    qty, price = cols["l_quantity"][keep], cols["l_extendedprice"][keep]
    disc, tax = cols["l_discount"][keep], cols["l_tax"][keep]
    disc_price = price * (100 - disc)  # scale 4
    charge = disc_price * (100 + tax)  # scale 6
    out = []
    for g in range(_GROUPS):
        at = group == g
        out.append([int(np.count_nonzero(at))]
                   + [int(np.sum(x[at], dtype=np.int64))
                      for x in (qty, price, disc_price, charge, disc)])
    return out


def _average(total: int, n: int):
    """Spark's decimal average of a decimal(15,2) column from its sum
    in cents: at decimal(38,15) HALF_UP, then decimal(19,6) HALF_UP."""
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        q = (decimal.Decimal(total).scaleb(-2) / n).quantize(
            decimal.Decimal(1).scaleb(-15), rounding=decimal.ROUND_HALF_UP)
        return q.quantize(decimal.Decimal(1).scaleb(-6),
                          rounding=decimal.ROUND_HALF_UP)


def combine(partials: list) -> pa.Table:
    sums = [[sum(p[g][k] for p in partials) for k in range(6)]
            for g in range(_GROUPS)]
    seen = [g for g in range(_GROUPS) if sums[g][0]]
    d = decimal.Decimal
    cols = {
        "l_returnflag": [str(RETURNFLAGS[g // len(LINESTATUSES)])
                         for g in seen],
        "l_linestatus": [str(LINESTATUSES[g % len(LINESTATUSES)])
                         for g in seen],
        "sum_qty": [d(sums[g][1]).scaleb(-2) for g in seen],
        "sum_base_price": [d(sums[g][2]).scaleb(-2) for g in seen],
        "sum_disc_price": [d(sums[g][3]).scaleb(-4) for g in seen],
        "sum_charge": [d(sums[g][4]).scaleb(-6) for g in seen],
        "avg_qty": [_average(sums[g][1], sums[g][0]) for g in seen],
        "avg_price": [_average(sums[g][2], sums[g][0]) for g in seen],
        "avg_disc": [_average(sums[g][5], sums[g][0]) for g in seen],
        "count_order": [sums[g][0] for g in seen],
    }
    return pa.table({k: pa.array(v, TYPES[k]) for k, v in cols.items()})
