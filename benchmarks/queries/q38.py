"""TPC-DS query 38 (`query38.tpl`), DMS = 1200, as the specification
writes it: the distinct (last name, first name, date) triples of the
customers `store_sales` sold to in the twelve months from
`d_month_seq` 1200, INTERSECT the same of `catalog_sales`' bill-to
customers, INTERSECT the same of `web_sales`', and how many triples
are left.  Query 87 (`q87.py`) is the same text with EXCEPT.

`build` is the query through the DataFrame API.  The month filter
stands on `date_dim` under each date join, where Spark's optimizer
puts it (the engine's planner moves no filter through a join), and
the date join under the customer join.  `DataFrame.intersect` is
INTERSECT DISTINCT, lowered as Spark lowers it: a `left_semi` join of
the three columns by position, every key `<=>`.

**What a NULL is, in the program and in the plain reference alike.**
A sale with a NULL date finds no `date_dim` row and one with a NULL
customer key no `customer` row: both are dropped by the inner joins.
A customer's NULL first or last name is a VALUE of the triple: the
DISTINCT groups it with the other NULLs of its column, and INTERSECT
and EXCEPT hold (NULL, 'Smith', 2000-03-01) equal to itself.  3.5% of
either name is NULL, as common as the third name of its list, so a set
operation that compared with `=` would lose those triples from q38 and
keep them in q87.

The plain reference walks `store_sales` a file at a time: a file's
distinct in-year triples, packed into one int64 a triple (a name is
its rank in the generator's list and 0 for NULL, the date its row in
`date_dim`), and which of them the catalog side's and the web side's
triple sets hold; those sets are made from the whole side tables in
every worker.  `combine` unites the files' triples and counts those
in both sets.  numpy only: nothing of `plan/`, `execs/` or
`cpu/engine.py`.
"""

import numpy as np
import pyarrow as pa

ORDERED = True
COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_customer_sk"],
    "catalog_sales": ["cs_sold_date_sk", "cs_bill_customer_sk"],
    "web_sales": ["ws_sold_date_sk", "ws_bill_customer_sk"],
    "customer": ["c_customer_sk", "c_first_name", "c_last_name"],
    "date_dim": ["d_date_sk", "d_date", "d_month_seq"],
}
DRIVER = "store_sales"

DMS = 1200
ANSWER = "count"
TRIPLE = ("c_last_name", "c_first_name", "d_date")
#: fact table -> its date key and the key of the customer it bills
CHANNELS = {"store_sales": ("ss_sold_date_sk", "ss_customer_sk"),
            "catalog_sales": ("cs_sold_date_sk", "cs_bill_customer_sk"),
            "web_sales": ("ws_sold_date_sk", "ws_bill_customer_sk")}


def channels(session, frames) -> list:
    """Each channel's DISTINCT triples, in the order the SQL names the
    channels: a program that cannot say INTERSECT or EXCEPT is told so
    at once, before a round, and exits non-zero."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import DataFrame, col

    if not hasattr(DataFrame, "intersect"):
        raise SystemExit(
            "benchmarks.run REFUSED: this program has no DataFrame.intersect "
            "/ subtract (session.py), so it cannot run queries 38 and 87 "
            "(PERF.md section 6, PR 38)")
    months = frames["date_dim"].where(
        (col("d_month_seq") >= lit(DMS)) & (col("d_month_seq")
                                            <= lit(DMS + 11)))
    return [
        frames[fact]
        .join(months, left_on=[col(date)], right_on=[col("d_date_sk")])
        .join(frames["customer"], left_on=[col(customer)],
              right_on=[col("c_customer_sk")])
        .group_by(*[col(name) for name in TRIPLE]).agg()
        for fact, (date, customer) in CHANNELS.items()]


def build(session, frames):
    from spark_rapids_tpu.session import count_star

    store, catalog, web = channels(session, frames)
    return store.intersect(catalog).intersect(web).agg(
        (count_star(), ANSWER)).limit(100)


# -- the plain reference ------------------------------------------------ #

def _triples(date, customer, dates: dict, customers: dict) -> np.ndarray:
    """The distinct triples of the sales whose date is a day of the
    twelve months and whose customer is a row of `customer`, one int64
    a triple, ascending: the last name above bit 40 and the first name
    above bit 20, each its rank in the generator's list plus one, 0 for
    NULL, and below them the date's row in `date_dim`.  A NULL date or
    customer key is -1, and no row of either table holds it."""
    in_year = (dates["d_month_seq"] >= DMS) & (dates["d_month_seq"]
                                              <= DMS + 11)
    by_sk = np.argsort(dates["d_date_sk"])
    day = np.clip(np.searchsorted(dates["d_date_sk"], date, sorter=by_sk),
                  0, len(by_sk) - 1)
    day = by_sk[day]
    keep = (dates["d_date_sk"][day] == date) & in_year[day]
    by_sk = np.argsort(customers["c_customer_sk"])
    who = np.clip(np.searchsorted(customers["c_customer_sk"], customer,
                                  sorter=by_sk), 0, len(by_sk) - 1)
    who = by_sk[who]
    keep &= customers["c_customer_sk"][who] == customer
    day, who = day[keep], who[keep]
    last = customers["c_last_name"][who].astype(np.int64) + 1
    first = customers["c_first_name"][who].astype(np.int64) + 1
    return np.unique((last << 40) | (first << 20) | day)


def partial(cols: dict, side: dict) -> tuple:
    """Over one file of `store_sales`: its distinct in-year triples,
    whether the catalog side's and the web side's triple sets hold
    each, and how many triples those sets have."""
    dates, customers = side["date_dim"], side["customer"]
    others = [_triples(*(side[fact][key] for key in CHANNELS[fact]),
                       dates, customers)
              for fact in ("catalog_sales", "web_sales")]
    sold = _triples(*(cols[key] for key in CHANNELS[DRIVER]), dates,
                    customers)
    held = [np.isin(sold, other, assume_unique=True) for other in others]
    return sold, held[0], held[1], tuple(len(other) for other in others)


def united(partials: list) -> tuple:
    """The store channel's distinct triples over every file, whether
    the catalog side holds each, whether the web side does, and the
    sizes of those two sides."""
    sold, first = np.unique(np.concatenate([p[0] for p in partials]),
                            return_index=True)
    in_catalog = np.concatenate([p[1] for p in partials])[first]
    in_web = np.concatenate([p[2] for p in partials])[first]
    sides = {p[3] for p in partials}
    assert len(sides) == 1, sides  # every worker made the same sides
    return sold, in_catalog, in_web, sides.pop()


def answer(count: int) -> pa.Table:
    return pa.table({ANSWER: pa.array([count], pa.int64())})


def combine(partials: list) -> pa.Table:
    _, in_catalog, in_web, _ = united(partials)
    return answer(int((in_catalog & in_web).sum()))
