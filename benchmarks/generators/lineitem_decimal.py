"""TPC-H `lineitem` with its money as the specification types it: the
rows of `lineitem.py` under the same seed, the four DECIMAL(15,2)
columns of clause 1.4.1 (`l_quantity`, `l_extendedprice`, `l_discount`,
`l_tax`) written as `decimal128(15, 2)` from their integer cents.

`generate` hands the plain reference those columns as int64 cents; the
cents are `lineitem`'s doubles times 100, each a whole number of cents
by construction (`_tpch.line_draws`), so `rint` recovers them exactly.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import lineitem

MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")

#: as `lineitem`'s: a DECIMAL(15,2) is one int64 of cents on the device
COLUMN_BYTES = dict(lineitem.COLUMN_BYTES)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """File `index` as numpy arrays: `lineitem.generate`'s, the money
    columns as int64 cents."""
    cols = lineitem.generate(seed, index, rows, columns)
    for name in MONEY:
        cols[name] = np.rint(cols[name] * 100.0).astype(np.int64)
    return cols


def decimal_cents(cents: np.ndarray) -> pa.Array:
    """decimal128(15, 2) from int64 cents, built on its buffer: each
    value's 16 bytes are its low and high words."""
    cents = np.ascontiguousarray(cents, np.int64)
    words = np.stack([cents, cents >> 63], axis=1)
    return pa.Array.from_buffers(pa.decimal128(15, 2), len(cents),
                                 [None, pa.py_buffer(words)])


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    table = lineitem.to_arrow(cols, seed, index)
    for name in MONEY:
        table = table.set_column(table.schema.get_field_index(name), name,
                                 decimal_cents(cols[name]))
    return table
