"""TPC-DS `store_sales`: the 23 columns of the specification, drawn by
dsdgen's rules as `_tpcds.py` recalls them.

File `i` holds `rows // 12` tickets of 8 to 16 lines; the lines of a
ticket share date, time, customer and store.  Surrogate keys int64,
`ss_quantity` int32, and one stated engine limit (ROADMAP R1): the
twelve DECIMAL(7,2) columns are written as DOUBLE, each value a whole
number of cents over 100.  NULLs as dsdgen leaves them: 4.5% of every
column but `ss_item_sk` and `ss_ticket_number`, the primary key.
"""

import pyarrow as pa

from benchmarks.generators import _tpcds

#: bytes one row of each column takes on the device: a surrogate key or
#: a DOUBLE 8, `ss_quantity` 4
COLUMN_BYTES = {
    "ss_sold_date_sk": 8, "ss_sold_time_sk": 8, "ss_item_sk": 8,
    "ss_customer_sk": 8, "ss_cdemo_sk": 8, "ss_hdemo_sk": 8,
    "ss_addr_sk": 8, "ss_store_sk": 8, "ss_promo_sk": 8,
    "ss_ticket_number": 8, "ss_quantity": 4, "ss_wholesale_cost": 8,
    "ss_list_price": 8, "ss_sales_price": 8, "ss_ext_discount_amt": 8,
    "ss_ext_sales_price": 8, "ss_ext_wholesale_cost": 8,
    "ss_ext_list_price": 8, "ss_ext_tax": 8, "ss_coupon_amt": 8,
    "ss_net_paid": 8, "ss_net_paid_inc_tax": 8, "ss_net_profit": 8,
}


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays, for the plain reference: every
    column whatever is named; NULL is -1 in an integer column and NaN
    in a DOUBLE one."""
    return _tpcds.sales_draws(seed, index, rows)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    return pa.table({name: _tpcds.arrow(cols[name])
                     for name in COLUMN_BYTES})
