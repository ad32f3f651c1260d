"""TPC-H Q6 (specification clause 2.4.6) over money as DECIMAL(15,2):
DATE = 1994-01-01, DISCOUNT = 0.06, QUANTITY = 24, written as the
specification writes it, in Spark's literals: `l_discount between 0.06
- 0.01 and 0.06 + 0.01` with decimal(2,2) literals, `l_quantity < 24`
with an integer literal.

Under Spark 3.x's `DecimalPrecision` (default conf) the answer,
`sum(l_extendedprice * l_discount)`, is decimal(38,4): the product of
two decimal(15,2) is decimal(31,4), its sum decimal(min(31 + 10, 38),
4).  `build` refuses, before a round, a plan that types it otherwise.

The plain reference works in integer cents: per file, the products in
int64 (under 2^20 rows x 1.1e11) and their sum; the files' sums in
Python integers.
"""

import decimal

import numpy as np
import pyarrow as pa

ORDERED = False
COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate"]}
DRIVER = "lineitem"

#: 1994-01-01 and 1995-01-01, days since 1970-01-01
_FROM, _TO = 8766, 9131

#: Spark's types of the answer, name by name
TYPES = {"revenue": pa.decimal128(38, 4)}


def refuse_other_types(df, types: dict) -> None:
    """The plan's output is typed as Spark types it, or no round runs:
    the comparison casts a narrower decimal to the expected type, so an
    answer typed otherwise could still read as equal."""
    from spark_rapids_tpu import types as T

    got = {f.name: T.to_arrow_type(f.dtype) for f in df.schema.fields}
    if got != types:
        raise SystemExit(
            "benchmarks.run REFUSED: the plan types its answer "
            f"{ {k: str(v) for k, v in got.items()} }, Spark "
            f"{ {k: str(v) for k, v in types.items()} } (PERF.md "
            "section 4)")


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.session import col, sum_

    ship, disc, qty = col("l_shipdate"), col("l_discount"), col("l_quantity")
    d = decimal.Decimal
    cond = ((ship >= Literal.of(_FROM, T.DATE))
            & (ship < Literal.of(_TO, T.DATE))
            & (disc >= lit(d("0.06")) - lit(d("0.01")))
            & (disc <= lit(d("0.06")) + lit(d("0.01")))
            & (qty < lit(24)))
    df = frames["lineitem"].where(cond).agg(
        (sum_(col("l_extendedprice") * disc), "revenue"))
    refuse_other_types(df, TYPES)
    return df


def partial(cols: dict, side: dict) -> int:
    """The file's revenue, in ten-thousandths."""
    ship, disc = cols["l_shipdate"], cols["l_discount"]
    keep = ((ship >= _FROM) & (ship < _TO) & (disc >= 5) & (disc <= 7)
            & (cols["l_quantity"] < 2400))
    return int(np.sum(cols["l_extendedprice"][keep] * disc[keep],
                      dtype=np.int64))


def combine(partials: list) -> pa.Table:
    total = decimal.Decimal(sum(partials)).scaleb(-4)
    return pa.table({"revenue": pa.array([total], TYPES["revenue"])})
