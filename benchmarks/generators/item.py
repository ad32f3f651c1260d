"""TPC-DS `item`: the 22 columns of the specification, 102,000 rows at
scale factor 10, one file.

`i_category` is one of the specification's ten, `i_class` one of the
hundred classes nested under them and `i_brand` one of seven brands
of its class (700 in all); `i_product_name` spells the item key's
digits in dsdgen's syllables, so it is distinct by item and at most 30
characters of the declared 50.  The surrogate keys are the revisions
of 51,000 business keys (`i_item_id`).  NULLs at dsdgen's rate: 0.25%
of every column but the two keys.  Money as DOUBLE (ROADMAP R1).

Handed fewer rows than the table has (a rehearsal), it holds the
items with the lowest keys; `store_sales` still draws over all
102,000, so that share of its rows finds an item.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpch, _tpcds

COLUMN_BYTES = {
    "i_item_sk": 8, "i_item_id": 16, "i_rec_start_date": 4,
    "i_rec_end_date": 4, "i_item_desc": 200, "i_current_price": 8,
    "i_wholesale_cost": 8, "i_brand_id": 4, "i_brand": 50, "i_class_id": 4,
    "i_class": 50, "i_category_id": 4, "i_category": 50,
    "i_manufact_id": 4, "i_manufact": 50, "i_size": 20,
    "i_formulation": 20, "i_color": 20, "i_units": 10, "i_container": 10,
    "i_manager_id": 4, "i_product_name": 50,
}

#: the revisions of a business key start on these days (1997-10-27,
#: 2000-10-27, 2001-10-27), and end the day before the next starts
_REVISION_STARTS = np.array([10_161, 11_257, 11_622], np.int32)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table as numpy arrays, for the plain reference: numbers as
    they are, strings as codes (`i_category`, `i_class` index
    `_tpcds.CATEGORIES`, `CLASSES`; `i_brand` goes through
    `_tpcds.brand_names`, `i_product_name` and `i_manufact` through
    `_tpcds.words`); NULL is -1, NaN in a DOUBLE column."""
    return _tpcds.item_draws(seed, rows)


def _named(codes: np.ndarray, names) -> pa.Array:
    """Strings by a function of the code, NULL where the code is -1."""
    return pa.array(names(np.maximum(codes, 0)), mask=codes == -1)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    rows = len(cols["i_item_sk"])
    rng = np.random.default_rng([seed, _tpcds.ITEM_ID, index, 1])
    revision = cols["revision"]
    last = np.r_[revision[1:] == 0, True]
    out = {name: _tpcds.arrow(cols[name]) for name in cols
           if name in COLUMN_BYTES}
    out.update({
        "i_item_id": pa.array(_tpcds.business_ids(cols["i_item_id"])),
        "i_rec_start_date": pa.array(_REVISION_STARTS[revision],
                                     pa.date32()),
        "i_rec_end_date": pa.array(
            _REVISION_STARTS[np.minimum(revision + 1, 2)] - 1, pa.date32(),
            mask=last),
        "i_item_desc": _tpch.comments(rng, rows, 20, 200),
        "i_brand": _named(cols["i_brand"], _tpcds.brand_names),
        "i_class": _tpcds.strings(cols["i_class"], _tpcds.CLASSES),
        "i_category": _tpcds.strings(cols["i_category"], _tpcds.CATEGORIES),
        "i_manufact": _named(cols["i_manufact"], _tpcds.words),
        "i_size": _tpcds.strings(cols["i_size"], _tpcds.SIZES),
        "i_formulation": _named(
            cols["i_formulation"],
            lambda n: np.char.zfill(n.astype(str), 20)),
        "i_color": _tpcds.strings(cols["i_color"], _tpcds.COLORS),
        "i_units": _tpcds.strings(cols["i_units"], _tpcds.UNITS),
        "i_container": pa.array(np.full(rows, "Unknown")),
        "i_product_name": _named(cols["i_product_name"], _tpcds.words),
    })
    return pa.table({name: out[name] for name in COLUMN_BYTES})
