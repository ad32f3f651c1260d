"""TPC-H `lineitem`: the sixteen columns of the specification's clause
1.4.1, filled by the rules of clause 4.2.3 (`_tpch.py`).

File `i` holds the lines of the orders of chunk `i`, clustered by
`l_orderkey` in line-number order, `rows / 4` orders to a file.  One
stated engine limit (ROADMAP R1): the four DECIMAL(15,2) columns are
written as DOUBLE, each value a whole number of cents over 100.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpch

#: bytes one row of each column takes on the device (an identifier or a
#: DOUBLE 8, an integer or a date 4, a CHAR(1) flag 1, a longer string
#: its declared width), for the roofline's byte count
COLUMN_BYTES = {
    "l_orderkey": 8, "l_partkey": 8, "l_suppkey": 8, "l_linenumber": 4,
    "l_quantity": 8, "l_extendedprice": 8, "l_discount": 8, "l_tax": 8,
    "l_returnflag": 1, "l_linestatus": 1, "l_shipdate": 4,
    "l_commitdate": 4, "l_receiptdate": 4, "l_shipinstruct": 25,
    "l_shipmode": 10, "l_comment": 44,
}

_DATES = ("l_shipdate", "l_commitdate", "l_receiptdate")
_CODED = {"l_returnflag": _tpch.RETURNFLAGS,
          "l_linestatus": _tpch.LINESTATUSES,
          "l_shipinstruct": _tpch.INSTRUCTIONS,
          "l_shipmode": _tpch.MODES}


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays, for the plain reference: every
    column but the comment, dates as days since 1970-01-01, the four
    coded strings as indexes into `_tpch`'s arrays."""
    if rows % _tpch.LINES_PER_ORDER:
        raise ValueError(f"{rows} rows are no whole number of orders of "
                         f"{_tpch.LINES_PER_ORDER} lines")
    orders = _tpch.order_draws(seed, index, rows // _tpch.LINES_PER_ORDER)
    return _tpch.line_draws(seed, index, orders)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    """The file as it is written: dates as DATE, the coded columns as
    their strings, the comment drawn here."""
    out = {}
    for name in COLUMN_BYTES:
        if name in _DATES:
            out[name] = pa.array(cols[name], pa.date32())
        elif name in _CODED:
            out[name] = _tpch.strings(cols[name], _CODED[name])
        elif name == "l_comment":
            rng = np.random.default_rng([seed, _tpch.LINEITEM_ID, index, 1])
            out[name] = _tpch.comments(rng, len(cols["l_orderkey"]), 10, 43)
        else:
            out[name] = pa.array(cols[name])
    return pa.table(out)
