"""TPC-DS `catalog_returns`: the 27 columns of the specification, drawn
by dsdgen's rules as recalled (no network here; the configuration's
file lists each under `assumed`), with numpy's random streams.

**A return is a line of `catalog_sales`.**  File `i` holds returns of
lines of `catalog_sales` file `i` under the same seed: the
`LINES_PER_RETURN` (10) times its rows that the sales file holds are
made again from `catalog_sales`' order structure and the line's own
streams (never its pricing, so a returns file is cheap to make), and
`rows` of them, distinct, are drawn as returned.  So
`(cr_item_sk, cr_order_number)` is a real line's primary key, an order
is returned only in the file that sold it, and a cut of the file count
keeps orders and their returns aligned.  From the line a return takes
its order number and item, the order's call center and its bill-to
(refunded) and ship-to (returning) customer with demographics and
address, and the line's catalog page, ship mode and warehouse; it is
returned 1 to 90 days after the line shipped, at a time of day, for
one of `REASONS` reasons.

The quantity and the nine money columns are the return's own draws
(the line's pricing is not made): a quantity of 1-100, a price of
1.00-300.00, the return amount their product, a tax of 0-9% of it, a
fee of 0.50-100.00, a shipping cost of 0-50% of the amount, the amount
refunded as cash, reversed charge and store credit in shares drawn per
return, and the net loss the fee, the shipping cost and the tax.
Surrogate keys int64, `cr_return_quantity` int32, DOUBLE for the
DECIMAL(7,2) columns, each a whole number of cents over 100 (ROADMAP
R1).  NULLs by dsdgen's rule (`_tpcds.py`): a row is picked with the
table's `nNullPct`, 1% here, and each nullable column of a picked row
is blanked with probability one half; the primary key
(`cr_item_sk`, `cr_order_number`) never.

**Every column group has a random stream of its own**
(`default_rng([seed, id, index, stream])`), so `generate` draws only
what the named columns need: the plain reference's workers make
`cr_order_number` of every file and nothing else.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds, catalog_sales

CATALOG_RETURNS_ID = 17

#: a returns file holds one return for this many lines of its sales file
LINES_PER_RETURN = 10
REASONS = 45  # dsdgen -scale 10, recalled
SECONDS = 86_400
#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = 100

_MONEY = ("cr_return_amount", "cr_return_tax", "cr_return_amt_inc_tax",
          "cr_fee", "cr_return_ship_cost", "cr_refunded_cash",
          "cr_reversed_charge", "cr_store_credit", "cr_net_loss")
#: the return's column -> the sales line's column it copies
_FROM_LINE = {
    "cr_item_sk": "cs_item_sk",
    "cr_refunded_customer_sk": "cs_bill_customer_sk",
    "cr_refunded_cdemo_sk": "cs_bill_cdemo_sk",
    "cr_refunded_hdemo_sk": "cs_bill_hdemo_sk",
    "cr_refunded_addr_sk": "cs_bill_addr_sk",
    "cr_returning_customer_sk": "cs_ship_customer_sk",
    "cr_returning_cdemo_sk": "cs_ship_cdemo_sk",
    "cr_returning_hdemo_sk": "cs_ship_hdemo_sk",
    "cr_returning_addr_sk": "cs_ship_addr_sk",
    "cr_call_center_sk": "cs_call_center_sk",
    "cr_catalog_page_sk": "cs_catalog_page_sk",
    "cr_ship_mode_sk": "cs_ship_mode_sk",
    "cr_warehouse_sk": "cs_warehouse_sk",
    "cr_order_number": "cs_order_number",
}

#: bytes one row of each column takes on the device, in the
#: specification's order: a surrogate key or a DOUBLE 8, the quantity 4
COLUMN_BYTES = {
    "cr_returned_date_sk": 8, "cr_returned_time_sk": 8, "cr_item_sk": 8,
    "cr_refunded_customer_sk": 8, "cr_refunded_cdemo_sk": 8,
    "cr_refunded_hdemo_sk": 8, "cr_refunded_addr_sk": 8,
    "cr_returning_customer_sk": 8, "cr_returning_cdemo_sk": 8,
    "cr_returning_hdemo_sk": 8, "cr_returning_addr_sk": 8,
    "cr_call_center_sk": 8, "cr_catalog_page_sk": 8, "cr_ship_mode_sk": 8,
    "cr_warehouse_sk": 8, "cr_reason_sk": 8, "cr_order_number": 8,
    "cr_return_quantity": 4, **{name: 8 for name in _MONEY},
}
_STREAM = {name: at for at, name in enumerate(COLUMN_BYTES)}
_WHICH, _PICKED, _PRICING = 100, 101, 102
_NEVER_NULL = ("cr_item_sk", "cr_order_number")


def _rng(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, CATALOG_RETURNS_ID, index, stream])


def _line_values(name: str, seed: int, index: int, lines: int,
                 of: np.ndarray, shared: dict) -> np.ndarray:
    """A `catalog_sales` column of every line of file `index`, before
    its NULLs: what `catalog_sales.generate` draws from the same
    streams."""
    if name in shared:
        return shared[name][of]
    rng = catalog_sales._rng(seed, index, catalog_sales._STREAM[name])
    return catalog_sales._line(name, rng, lines,
                               shared["cs_sold_date_sk"][of])


def _pricing(seed: int, index: int, rows: int) -> dict:
    """The quantity and the nine money columns, in whole cents until
    the end."""
    rng = _rng(seed, index, _PRICING)
    quantity = rng.integers(1, 101, rows)
    amount = rng.integers(100, 30_001, rows) * quantity
    tax = amount * rng.integers(0, 10, rows) // 100
    fee = rng.integers(50, 10_001, rows)
    ship = amount * rng.integers(0, 51, rows) // 100
    cash = amount * rng.integers(0, 101, rows) // 100
    charge = (amount - cash) * rng.integers(0, 101, rows) // 100
    cents = {
        "cr_return_amount": amount, "cr_return_tax": tax,
        "cr_return_amt_inc_tax": amount + tax, "cr_fee": fee,
        "cr_return_ship_cost": ship, "cr_refunded_cash": cash,
        "cr_reversed_charge": charge,
        "cr_store_credit": amount - cash - charge,
        "cr_net_loss": fee + ship + tax,
    }
    out = {name: values / 100.0 for name, values in cents.items()}
    out["cr_return_quantity"] = quantity.astype(np.int32)
    return out


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays, the named columns only (all 27
    where none is named), for the plain reference; NULL is -1 in an
    integer column and NaN in a DOUBLE one."""
    wanted = list(COLUMN_BYTES) if columns is None else list(columns)
    lines = rows * LINES_PER_RETURN
    of, shared = catalog_sales._orders(seed, index, lines)
    returned = np.sort(_rng(seed, index, _WHICH).choice(
        lines, rows, replace=False))
    priced = _pricing(seed, index, rows) \
        if any(n in _MONEY or n == "cr_return_quantity"
               for n in wanted) else {}
    picked = _rng(seed, index, _PICKED).integers(0, 10_000, rows) < NULL_PCT
    out = {}
    for name in wanted:
        rng = _rng(seed, index, _STREAM[name])
        if name in _FROM_LINE:
            values = _line_values(_FROM_LINE[name], seed, index, lines, of,
                                  shared)[returned]
        elif name in priced:
            values = priced[name]
        elif name == "cr_returned_date_sk":
            shipped = _line_values("cs_ship_date_sk", seed, index, lines,
                                   of, shared)[returned]
            values = shipped + rng.integers(1, 91, rows)
        elif name == "cr_returned_time_sk":
            values = rng.integers(0, SECONDS, rows)
        else:  # cr_reason_sk
            values = rng.integers(1, REASONS + 1, rows)
        if name not in _NEVER_NULL:
            values = _tpcds.blanked(values, picked & (
                rng.integers(0, 2, rows, dtype=np.uint8) == 1))
        out[name] = values
    return out


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    return pa.table({name: _tpcds.arrow(cols[name])
                     for name in COLUMN_BYTES})
