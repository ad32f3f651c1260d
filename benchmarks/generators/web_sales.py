"""TPC-DS `web_sales`: the 34 columns of the specification, drawn by
dsdgen's rules as recalled (no network here; the configuration's file
lists each under `assumed`), with numpy's random streams.

File `i` holds `rows // 12` orders of 8 to 16 lines; the lines of an
order share the sold date and time, the bill-to and ship-to customer
with their demographics and addresses, the web page and the web site;
the ship date, ship mode, warehouse, item, promotion and pricing are a
line's own.  An order ships to its bill-to customer, except one in
seven that is a gift and ships to another.  Surrogate keys int64,
`ws_quantity` int32, and the one stated engine limit (ROADMAP R1): the
fifteen DECIMAL(7,2) columns are DOUBLE, each a whole number of cents
over 100.  NULLs by dsdgen's rule (`_tpcds.py`): a row is picked with
the table's `nNullPct`, 0.05% here, and each nullable column of a
picked row is blanked with probability one half; `ws_item_sk` and
`ws_order_number`, the primary key, never.

**Every column group has a random stream of its own**
(`default_rng([seed, id, index, stream])`), so `generate` draws only
what the named columns need and the values do not depend on which
columns were asked for: the plain reference's workers make the two
columns queries 38 and 87 read of every file, again and again.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

WEB_SALES_ID = 17

# dsdgen -scale 10, recalled
WEB_PAGES = 200
WEB_SITES = 42
SHIP_MODES = 20
WAREHOUSES = 10
SECONDS = 86_400  # time_dim

#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = 5
ORDER_LINES = 12  # an order has 8..16 lines, uniform
GIFT_ONE_IN = 7

_MONEY = ("ws_wholesale_cost", "ws_list_price", "ws_sales_price",
          "ws_ext_discount_amt", "ws_ext_sales_price",
          "ws_ext_wholesale_cost", "ws_ext_list_price", "ws_ext_tax",
          "ws_coupon_amt", "ws_ext_ship_cost", "ws_net_paid",
          "ws_net_paid_inc_tax", "ws_net_paid_inc_ship",
          "ws_net_paid_inc_ship_tax", "ws_net_profit")
_ORDER = ("ws_sold_date_sk", "ws_sold_time_sk", "ws_bill_customer_sk",
          "ws_bill_cdemo_sk", "ws_bill_hdemo_sk", "ws_bill_addr_sk",
          "ws_ship_customer_sk", "ws_ship_cdemo_sk", "ws_ship_hdemo_sk",
          "ws_ship_addr_sk", "ws_web_page_sk", "ws_web_site_sk",
          "ws_order_number")

#: bytes one row of each column takes on the device, in the
#: specification's order: a surrogate key or a DOUBLE 8, `ws_quantity` 4
COLUMN_BYTES = {
    "ws_sold_date_sk": 8, "ws_sold_time_sk": 8, "ws_ship_date_sk": 8,
    "ws_item_sk": 8, "ws_bill_customer_sk": 8, "ws_bill_cdemo_sk": 8,
    "ws_bill_hdemo_sk": 8, "ws_bill_addr_sk": 8, "ws_ship_customer_sk": 8,
    "ws_ship_cdemo_sk": 8, "ws_ship_hdemo_sk": 8, "ws_ship_addr_sk": 8,
    "ws_web_page_sk": 8, "ws_web_site_sk": 8, "ws_ship_mode_sk": 8,
    "ws_warehouse_sk": 8, "ws_promo_sk": 8, "ws_order_number": 8,
    "ws_quantity": 4, **{name: 8 for name in _MONEY},
}
_STREAM = {name: at for at, name in enumerate(COLUMN_BYTES)}
_STRUCTURE, _PICKED, _PRICING = 100, 101, 102


def _rng(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, WEB_SALES_ID, index, stream])


def _orders(seed: int, index: int, rows: int) -> tuple:
    """The order each line belongs to, and every column that an
    order's lines share (20,000 orders a file: cheap, so all of them
    whatever is named)."""
    rng = _rng(seed, index, _STRUCTURE)
    orders = max(1, rows // ORDER_LINES)
    if not 8 * orders <= rows <= 16 * orders:
        raise ValueError(f"{rows} rows are no {orders} orders of 8 to 16")
    lines = rng.integers(8, 17, orders)
    off = rows - int(lines.sum())
    while off:
        room = np.flatnonzero(lines < 16 if off > 0 else lines > 8)
        step = min(abs(off), len(room))
        lines[rng.choice(room, step, replace=False)] += np.sign(off)
        off -= step * np.sign(off)
    # the customer who is billed, and another for where a gift ships
    party = {"customer": _tpcds.CUSTOMERS,
             "cdemo": _tpcds.CUSTOMER_DEMOGRAPHICS,
             "hdemo": _tpcds.HOUSEHOLD_DEMOGRAPHICS,
             "addr": _tpcds.ADDRESSES}
    bill, other = ({part: rng.integers(1, top + 1, orders)
                    for part, top in party.items()} for _ in range(2))
    gift = rng.integers(0, GIFT_ONE_IN, orders) == 0
    shared = {
        "ws_sold_date_sk": rng.integers(
            _tpcds.SALES_FIRST_DAY, _tpcds.SALES_LAST_DAY + 1, orders)
        + _tpcds.EPOCH_SK,
        "ws_sold_time_sk": rng.integers(0, SECONDS, orders),
        "ws_web_page_sk": rng.integers(1, WEB_PAGES + 1, orders),
        "ws_web_site_sk": rng.integers(1, WEB_SITES + 1, orders),
        "ws_order_number": np.arange(index * orders + 1,
                                     (index + 1) * orders + 1),
    }
    for part, values in bill.items():
        shared[f"ws_bill_{part}_sk"] = values
        shared[f"ws_ship_{part}_sk"] = np.where(gift, other[part], values)
    return np.repeat(np.arange(orders), lines), shared


def _pricing(seed: int, index: int, rows: int) -> dict:
    """`ws_quantity` and the fifteen money columns by dsdgen's
    `set_pricing`, in whole cents until the end: a wholesale cost of
    1.00-100.00, a markup of 0-200% to the list price, a discount of
    0-100% to the sales price, a coupon on a fifth of the lines, a
    shipping cost of 0-100% of the list price, a tax of 0-9%."""
    rng = _rng(seed, index, _PRICING)
    quantity = rng.integers(1, 101, rows)
    wholesale = rng.integers(100, 10_001, rows)
    list_price = wholesale * (100 + rng.integers(0, 201, rows)) // 100
    sales_price = list_price * (100 - rng.integers(0, 101, rows)) // 100
    ext_sales = sales_price * quantity
    coupon = np.where(rng.integers(0, 5, rows) == 0,
                      ext_sales * rng.integers(0, 101, rows) // 100, 0)
    ship = list_price * rng.integers(0, 101, rows) // 100 * quantity
    net_paid = ext_sales - coupon
    tax = net_paid * rng.integers(0, 10, rows) // 100
    cents = {
        "ws_wholesale_cost": wholesale, "ws_list_price": list_price,
        "ws_sales_price": sales_price,
        "ws_ext_discount_amt": (list_price - sales_price) * quantity,
        "ws_ext_sales_price": ext_sales,
        "ws_ext_wholesale_cost": wholesale * quantity,
        "ws_ext_list_price": list_price * quantity,
        "ws_ext_tax": tax, "ws_coupon_amt": coupon,
        "ws_ext_ship_cost": ship, "ws_net_paid": net_paid,
        "ws_net_paid_inc_tax": net_paid + tax,
        "ws_net_paid_inc_ship": net_paid + ship,
        "ws_net_paid_inc_ship_tax": net_paid + ship + tax,
        "ws_net_profit": net_paid - wholesale * quantity,
    }
    out = {name: values / 100.0 for name, values in cents.items()}
    out["ws_quantity"] = quantity.astype(np.int32)
    return out


def _line(name: str, rng: np.random.Generator, rows: int,
          sold: np.ndarray) -> np.ndarray:
    """A column that is a line's own and no money."""
    if name == "ws_ship_date_sk":
        return sold + rng.integers(1, 121, rows)
    top = {"ws_ship_mode_sk": SHIP_MODES, "ws_warehouse_sk": WAREHOUSES,
           "ws_item_sk": _tpcds.ITEMS, "ws_promo_sk": _tpcds.PROMOTIONS}[name]
    return rng.integers(1, top + 1, rows)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays, the named columns only (all 34
    where none is named), for the plain reference; NULL is -1 in an
    integer column and NaN in a DOUBLE one."""
    wanted = list(COLUMN_BYTES) if columns is None else list(columns)
    of, shared = _orders(seed, index, rows)
    priced = _pricing(seed, index, rows) \
        if any(n in _MONEY or n == "ws_quantity" for n in wanted) else {}
    picked = _rng(seed, index, _PICKED).integers(0, 10_000, rows) < NULL_PCT
    out = {}
    for name in wanted:
        rng = _rng(seed, index, _STREAM[name])
        if name in _ORDER:
            values = shared[name][of]
        elif name in priced:
            values = priced[name]
        else:
            values = _line(name, rng, rows, shared["ws_sold_date_sk"][of])
        if name not in ("ws_item_sk", "ws_order_number"):
            values = _tpcds.blanked(values, picked & (
                rng.integers(0, 2, rows, dtype=np.uint8) == 1))
        out[name] = values
    return out


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    return pa.table({name: _tpcds.arrow(cols[name])
                     for name in COLUMN_BYTES})
