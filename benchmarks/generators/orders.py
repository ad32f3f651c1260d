"""TPC-H `orders`: the nine columns of the specification's clause
1.4.1, filled by the rules of clause 4.2.3 (`_tpch.py`).

File `i` holds the orders of chunk `i`, by ascending sparse
`o_orderkey`.  `o_totalprice` and `o_orderstatus` follow from the
order's lines, so the chunk's lines are drawn again here when a caller
wants them.  `o_totalprice` is a DOUBLE, as `lineitem`'s money columns
are (ROADMAP R1).
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpch

COLUMN_BYTES = {
    "o_orderkey": 8, "o_custkey": 8, "o_orderstatus": 1, "o_totalprice": 8,
    "o_orderdate": 4, "o_orderpriority": 15, "o_clerk": 15,
    "o_shippriority": 4, "o_comment": 79,
}

_FROM_LINES = ("o_orderstatus", "o_totalprice")


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays, for the plain reference: every
    column but the comment, or the named ones and whatever comes with
    them; status and priority as indexes into `_tpch`'s arrays, the
    clerk as his number."""
    cols = _tpch.order_draws(seed, index, rows)
    if columns is None or set(columns) & set(_FROM_LINES):
        lines = _tpch.line_draws(seed, index, cols)
        charge = np.round(lines["l_extendedprice"] * (1.0 + lines["l_tax"])
                          * (1.0 - lines["l_discount"]) * 100.0)
        cols["o_totalprice"] = np.bincount(
            lines["order_of"], charge, minlength=rows) / 100.0
        open_lines = np.bincount(lines["order_of"], lines["l_linestatus"],
                                 minlength=rows)
        # F where no line is open, O where every line is, else P
        cols["o_orderstatus"] = np.where(
            open_lines == 0, 0,
            np.where(open_lines == cols["lines"], 1, 2)).astype(np.int8)
    return cols


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    n = len(cols["o_orderkey"])
    rng = np.random.default_rng([seed, _tpch.ORDERS_ID, index, 1])
    clerks = np.array([f"Clerk#{i:09d}" for i in range(_tpch.CLERKS + 1)])
    return pa.table({
        "o_orderkey": cols["o_orderkey"],
        "o_custkey": cols["o_custkey"],
        "o_orderstatus": _tpch.strings(cols["o_orderstatus"],
                                       _tpch.ORDERSTATUSES),
        "o_totalprice": cols["o_totalprice"],
        "o_orderdate": pa.array(cols["o_orderdate"], pa.date32()),
        "o_orderpriority": _tpch.strings(cols["o_orderpriority"],
                                         _tpch.PRIORITIES),
        "o_clerk": _tpch.strings(cols["o_clerk"], clerks),
        "o_shippriority": cols["o_shippriority"],
        "o_comment": _tpch.comments(rng, n, 19, 78),
    })
