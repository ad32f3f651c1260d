"""TPC-DS query 16 (`query16.tpl`) with the qualification substitution,
as the specification writes it: the catalog orders shipped in the 60
days from 2002-02-01 to an address in Georgia through a call center in
Williamson County, that ship from more than one warehouse (EXISTS a
line of the same order from another warehouse) and were never returned
(NOT EXISTS a return of the order); how many orders, and their lines'
shipping cost and net profit.

    select count(distinct cs_order_number) as "order count",
           sum(cs_ext_ship_cost) as "total shipping cost",
           sum(cs_net_profit) as "total net profit"
    from catalog_sales cs1, date_dim, customer_address, call_center
    where d_date between '2002-2-01' and
            (cast('2002-2-01' as date) + 60 days)
      and cs1.cs_ship_date_sk = d_date_sk
      and cs1.cs_ship_addr_sk = ca_address_sk and ca_state = 'GA'
      and cs1.cs_call_center_sk = cc_call_center_sk
      and cc_county in ('Williamson County', 'Williamson County',
                        'Williamson County', 'Williamson County',
                        'Williamson County')
      and exists (select * from catalog_sales cs2
                  where cs1.cs_order_number = cs2.cs_order_number
                    and cs1.cs_warehouse_sk <> cs2.cs_warehouse_sk)
      and not exists (select * from catalog_returns cr1
                      where cs1.cs_order_number = cr1.cr_order_number)
    order by count(distinct cs_order_number)
    limit 100

`build` is the query through the DataFrame API.  The filters stand on
the dimensions under their joins and the three inner joins under the
subquery joins, where Spark's optimizer puts them (RewritePredicateSub
query makes the EXISTS a `left_semi` join and the NOT EXISTS a
`left_anti` join of what the inner joins leave).  The EXISTS is a
`left_semi` join on the order number whose residual condition is
`cs_warehouse_sk <> cs2_warehouse_sk`: `cs2`'s two columns are renamed
apart, a joined frame holding a name once, and its scan is handed its
own read schema, the 2 of 34 columns it reads, as Spark's column
pruning hands one to each scan.  `count(DISTINCT)` beside the two sums
lowers as Spark's single-distinct rewrite: a group by the order number
with the sums' partials, then a count and the sums' merge.

**What a NULL is, in the program and in the plain reference alike.**
A line with a NULL ship date, address or call center finds no
dimension row; an address with a NULL state or a call center with a
NULL county passes no filter.  The condition `<>` is NULL where either
warehouse is NULL, and NULL is not TRUE: a line with a NULL warehouse
is in no EXISTS.  The NOT EXISTS keys are never NULL (a primary key of
both tables).  A NULL money value is left out of its sum.

**Before a collect** the module looks at the program (`explain()`,
some milliseconds): a program that cannot say `count(DISTINCT)` beside
a sum, whose plan has an operator without the `*` mark (a
conditional semi join that would run on the CPU engine for hours), or
whose plan lacks a `left_semi` join with a `cond=` or a `left_anti`
join, is told so at once and exits non-zero.

The plain reference walks `catalog_sales` a file at a time: the lines
that pass the three filters; the EXISTS by the order's non-NULL
warehouses, each order being wholly in one file (`cs_order_number` is
`index * orders + 1 ..`): a line with warehouse `w` passes iff `w` is
not NULL and the order holds a non-NULL warehouse other than `w`, that
is not (min == max == w); the NOT EXISTS against every
`cr_order_number` of the side table.  `combine` adds the files'
distinct-order counts (no order crosses files) and their sums.  numpy
only: nothing of `plan/`, `execs/` or `cpu/engine.py`.
"""

import re

import numpy as np
import pyarrow as pa

ORDERED = True
COLUMNS = {
    "catalog_sales": ["cs_ship_date_sk", "cs_ship_addr_sk",
                      "cs_call_center_sk", "cs_warehouse_sk",
                      "cs_order_number", "cs_ext_ship_cost",
                      "cs_net_profit"],
    "catalog_returns": ["cr_order_number"],
    "date_dim": ["d_date_sk", "d_date"],
    "customer_address": ["ca_address_sk", "ca_state"],
    "call_center": ["cc_call_center_sk", "cc_county"],
}
DRIVER = "catalog_sales"

#: 2002-02-01 and 60 days on, days since 1970-01-01
FIRST_DAY, LAST_DAY = 11_719, 11_779
STATE = "GA"
COUNTIES = ("Williamson County",) * 5
ANSWER = ("order count", "total shipping cost", "total net profit")
#: what cs2 reads, and the names it is renamed to
CS2 = {"cs_order_number": "cs2_order_number",
       "cs_warehouse_sk": "cs2_warehouse_sk"}


def _refuse(why: str):
    raise SystemExit(f"benchmarks.run REFUSED: {why} (PERF.md section 6)")


def _refuse_a_plan_without_its_subquery_joins(plan: str) -> None:
    """`plan_has` matches an operator's name alone, and the inner joins
    hold the names the subquery joins run under: these two are matched
    by what the plan says of them."""
    for what, says in (("the EXISTS as a left_semi join with its "
                        "condition", r"\bleft_semi\b.* cond="),
                       ("the NOT EXISTS as a left_anti join",
                        r"\bleft_anti\b")):
        if not re.search(says, plan):
            _refuse(f"this program's plan does not run {what}")


def build(session, frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import Literal, lit
    from spark_rapids_tpu.exprs.predicates import In
    from spark_rapids_tpu.session import col, count_distinct, sum_

    sales = frames["catalog_sales"]
    days = frames["date_dim"].where(
        (col("d_date") >= Literal(FIRST_DAY, T.DATE))
        & (col("d_date") <= Literal(LAST_DAY, T.DATE)))
    georgia = frames["customer_address"].where(col("ca_state").eq(
        lit(STATE)))
    centers = frames["call_center"].where(
        In(col("cc_county"), tuple(COUNTIES)))
    cs1 = (sales
           .join(days, left_on=[col("cs_ship_date_sk")],
                 right_on=[col("d_date_sk")])
           .join(georgia, left_on=[col("cs_ship_addr_sk")],
                 right_on=[col("ca_address_sk")])
           .join(centers, left_on=[col("cs_call_center_sk")],
                 right_on=[col("cc_call_center_sk")]))
    cs2 = session.read_parquet(*sales._plan.paths, columns=list(CS2)) \
        .select(*[col(name).alias(renamed) for name, renamed in CS2.items()])
    shipped = (cs1
               .join(cs2, how="left_semi",
                     left_on=[col("cs_order_number")],
                     right_on=[col("cs2_order_number")],
                     condition=col("cs_warehouse_sk").ne(
                         col("cs2_warehouse_sk")))
               .join(frames["catalog_returns"], how="left_anti",
                     left_on=[col("cs_order_number")],
                     right_on=[col("cr_order_number")]))
    try:
        answer = shipped.agg(
            (count_distinct(col("cs_order_number")), ANSWER[0]),
            (sum_(col("cs_ext_ship_cost")), ANSWER[1]),
            (sum_(col("cs_net_profit")), ANSWER[2]))
    except ValueError as e:
        _refuse(f"this program cannot run query 16's aggregate: {e}")
    from benchmarks.harness.engine import off_device

    plan = shipped.explain()
    off = off_device(plan)
    if off:
        _refuse("this program would run part of query 16 off the device: "
                f"{off[0].strip()}")
    _refuse_a_plan_without_its_subquery_joins(plan)
    return answer.order_by(col(ANSWER[0])).limit(100)


# -- the plain reference ------------------------------------------------ #

def _keys_where(keys: np.ndarray, keep: np.ndarray) -> np.ndarray:
    return np.unique(keys[keep & (keys >= 0)])


def partial(cols: dict, side: dict) -> tuple:
    """Over one file of `catalog_sales`: the distinct orders of the
    lines that pass, and each sum with the count of values it added."""
    from benchmarks.generators import call_center, customer_address

    dates = side["date_dim"]
    in_window = _keys_where(dates["d_date_sk"],
                            (dates["d_date"] >= FIRST_DAY)
                            & (dates["d_date"] <= LAST_DAY))
    address = side["customer_address"]
    georgia = _keys_where(address["ca_address_sk"], address["ca_state"]
                          == list(customer_address.STATES).index(STATE))
    centers = side["call_center"]
    named = np.isin(call_center.COUNTIES[np.maximum(centers["cc_county"],
                                                    0)], COUNTIES) \
        & (centers["cc_county"] >= 0)
    williamson = _keys_where(centers["cc_call_center_sk"], named)
    keep = (np.isin(cols["cs_ship_date_sk"], in_window)
            & np.isin(cols["cs_ship_addr_sk"], georgia)
            & np.isin(cols["cs_call_center_sk"], williamson))
    # EXISTS: another non-NULL warehouse in the line's order
    order, house = cols["cs_order_number"], cols["cs_warehouse_sk"]
    at = order - order.min()
    known = house >= 0
    low = np.full(at.max() + 1, np.iinfo(np.int64).max)
    high = np.full(at.max() + 1, -1)
    np.minimum.at(low, at[known], house[known])
    np.maximum.at(high, at[known], house[known])
    alone = (low[at] == high[at]) & (high[at] == house)
    keep &= known & ~alone
    # NOT EXISTS: no return of the order
    keep &= ~np.isin(order, side["catalog_returns"]["cr_order_number"])
    sums = []
    for name in ("cs_ext_ship_cost", "cs_net_profit"):
        values = cols[name][keep]
        values = values[~np.isnan(values)]
        sums.append((values.sum(), len(values)))
    return np.unique(order[keep]), sums


def answer(orders: int, sums: list) -> pa.Table:
    return pa.table({
        ANSWER[0]: pa.array([orders], pa.int64()),
        **{name: pa.array([float(total) if n else None], pa.float64())
           for name, (total, n) in zip(ANSWER[1:], sums)}})


def combine(partials: list) -> pa.Table:
    orders = sum(len(p[0]) for p in partials)
    sums = [(sum(float(p[1][at][0]) for p in partials),
             sum(p[1][at][1] for p in partials)) for at in range(2)]
    return answer(orders, sums)
