"""TPC-DS query 97 (`query97.tpl`), DMS = 1200, as the specification
writes it: the distinct (customer, item) pairs that `store_sales` sold
in the twelve months from `d_month_seq` 1200, the distinct pairs that
`catalog_sales` billed in the same months, the two met in a FULL OUTER
JOIN on both columns, and three counts of the joined rows: the pairs
of the store channel alone, of the catalog channel alone, of both.

`build` is the query through the DataFrame API.  The month filter
stands on `date_dim` under each join, where Spark's optimizer puts it
(the engine's planner moves no filter through a join).  The two sides
keep their tables' column names (`ss_customer_sk`, `ss_item_sk`;
`cs_bill_customer_sk`, `cs_item_sk`) where the SQL renames both to
`customer_sk`, `item_sk`: a joined frame cannot hold a name twice.

**NULLs, in the program and in the plain reference alike.**  A sale
with a NULL date finds no `date_dim` row and is dropped.  (NULL, item)
is a group of either DISTINCT, and a row of the join: its key equals
nothing, so it leaves the join with the other side NULL, and then
BOTH `customer_sk` are NULL: it counts in none of the three sums.

The plain reference walks `store_sales` a file at a time: a file's
distinct in-year pairs, packed into one int64 a pair, and which of
them the catalog side's pair set holds; that set is made from the
whole `catalog_sales` side table in every worker.  `combine` unites
the files' pairs and counts.  numpy only: nothing of `plan/`, `execs/`
or `cpu/engine.py`.
"""

import numpy as np
import pyarrow as pa

ORDERED = True
COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_customer_sk", "ss_item_sk"],
    "catalog_sales": ["cs_sold_date_sk", "cs_bill_customer_sk",
                      "cs_item_sk"],
    "date_dim": ["d_date_sk", "d_month_seq"],
}
DRIVER = "store_sales"

DMS = 1200
ANSWER = ["store_only", "catalog_only", "store_and_catalog"]


def build(session, frames):
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.exprs.predicates import CaseWhen, IsNotNull, IsNull
    from spark_rapids_tpu.session import col, sum_

    months = frames["date_dim"].where(
        (col("d_month_seq") >= lit(DMS)) & (col("d_month_seq")
                                            <= lit(DMS + 11)))
    ssci = (frames["store_sales"]
            .join(months, left_on=[col("ss_sold_date_sk")],
                  right_on=[col("d_date_sk")])
            .group_by(col("ss_customer_sk"), col("ss_item_sk")).agg())
    csci = (frames["catalog_sales"]
            .join(months, left_on=[col("cs_sold_date_sk")],
                  right_on=[col("d_date_sk")])
            .group_by(col("cs_bill_customer_sk"), col("cs_item_sk")).agg())
    joined = ssci.join(
        csci, how="full_outer",
        left_on=[col("ss_customer_sk"), col("ss_item_sk")],
        right_on=[col("cs_bill_customer_sk"), col("cs_item_sk")])
    store, catalog = col("ss_customer_sk"), col("cs_bill_customer_sk")

    def count_where(cond):
        return sum_(CaseWhen(((cond, lit(1)),), lit(0)))

    return joined.agg(
        (count_where(IsNotNull(store) & IsNull(catalog)), ANSWER[0]),
        (count_where(IsNull(store) & IsNotNull(catalog)), ANSWER[1]),
        (count_where(IsNotNull(store) & IsNotNull(catalog)), ANSWER[2]),
    ).limit(100)


# -- the plain reference ------------------------------------------------ #

def _pairs(date, customer, item, dates: dict) -> np.ndarray:
    """The distinct (customer, item) pairs of the sales whose date is a
    day of the twelve months, one int64 a pair, ascending: the customer
    above bit 32, and 0 there for a NULL one (a key is 1 or more; NULL
    is -1, and no `date_dim` row holds it)."""
    days = dates["d_date_sk"][(dates["d_month_seq"] >= DMS)
                              & (dates["d_month_seq"] <= DMS + 11)]
    keep = np.isin(date, days)
    return np.unique(((customer[keep] + 1) << 32) | item[keep])


def _known(pairs: np.ndarray) -> np.ndarray:
    """Which pairs have a customer."""
    return pairs >> 32 != 0


def partial(cols: dict, side: dict) -> tuple:
    """Over one file of `store_sales`: its distinct in-year pairs,
    whether the catalog side's pair set holds each (a pair without a
    customer equals nothing), and how many pairs with a customer that
    set has."""
    catalog = side["catalog_sales"]
    billed = _pairs(catalog["cs_sold_date_sk"],
                    catalog["cs_bill_customer_sk"], catalog["cs_item_sk"],
                    side["date_dim"])
    sold = _pairs(cols["ss_sold_date_sk"], cols["ss_customer_sk"],
                  cols["ss_item_sk"], side["date_dim"])
    in_catalog = _known(sold) & np.isin(sold, billed, assume_unique=True)
    return sold, in_catalog, int(_known(billed).sum())


def combine(partials: list) -> pa.Table:
    sold, first = np.unique(np.concatenate([p[0] for p in partials]),
                            return_index=True)
    in_catalog = np.concatenate([p[1] for p in partials])[first]
    billed = {p[2] for p in partials}
    assert len(billed) == 1, billed  # every worker made the same side
    both = int(in_catalog.sum())
    counts = [int(_known(sold).sum()) - both, billed.pop() - both, both]
    return pa.table({name: pa.array([n], pa.int64())
                     for name, n in zip(ANSWER, counts)})
