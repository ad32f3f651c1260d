"""TPC-DS `store`: the 29 columns of the specification, 102 rows at
scale factor 10, one file.

`s_store_id`, CHAR(16), is the business key, which the revisions of
one store share: the 102 surrogate keys hold 51 of them, so two or
three `s_store_sk` answer to one `s_store_id` and query 67's last
grouping column is no key of the join.  NULLs at dsdgen's rate (0.5%
of every column but the two keys).

Handed fewer rows than the table has (a rehearsal: 6), it holds the
stores with the lowest keys; `store_sales` still draws over all 102,
so that share of its rows finds a store.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

COLUMN_BYTES = {
    "s_store_sk": 8, "s_store_id": 16, "s_rec_start_date": 4,
    "s_rec_end_date": 4, "s_closed_date_sk": 8, "s_store_name": 50,
    "s_number_employees": 4, "s_floor_space": 4, "s_hours": 20,
    "s_manager": 40, "s_market_id": 4, "s_geography_class": 100,
    "s_market_desc": 100, "s_market_manager": 40, "s_division_id": 4,
    "s_division_name": 50, "s_company_id": 4, "s_company_name": 50,
    "s_street_number": 10, "s_street_name": 60, "s_street_type": 15,
    "s_suite_number": 10, "s_city": 60, "s_county": 30, "s_state": 2,
    "s_zip": 10, "s_country": 20, "s_gmt_offset": 8,
    "s_tax_precentage": 8,
}

_REVISION_STARTS = np.array([10_302, 11_032, 11_397], np.int32)
_HOURS = np.array(["8AM-4PM", "8AM-8AM", "8AM-12AM"])
_STREETS = np.array("Main Oak Park First Second Elm Maple Cedar Hill "
                    "Lake View Spring Ridge Church Walnut".split())
_STREET_TYPES = np.array("Street Ave Blvd Road Lane Court Dr. Way Pkwy "
                         "Circle".split())
_CITIES = np.array(["Midway", "Fairview", "Oak Grove", "Five Points",
                    "Pleasant Hill", "Riverside", "Centerville"])
_COUNTIES = np.array(["Williamson County", "Ziebach County",
                      "Walker County", "Daviess County"])
_STATES = np.array(["TN", "SD", "AL", "IN", "GA", "OH", "TX"])


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table as numpy arrays, for the plain reference:
    `s_store_id` as the business key's number (its string is
    `_tpcds.business_ids` of it); NULL is -1, NaN in a DOUBLE column."""
    return _tpcds.store_draws(seed, rows)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    rows = len(cols["s_store_sk"])
    rng = np.random.default_rng([seed, _tpcds.STORE_ID, index, 1])
    revision = cols["revision"]
    last = np.r_[revision[1:] == 0, True]

    def pick(names):
        return _tpcds.strings(rng.integers(0, len(names), rows), names)

    def spelt(column):
        codes = cols[column]
        return pa.array(_tpcds.words(np.maximum(codes, 0)),
                        mask=codes == -1)

    out = {name: _tpcds.arrow(cols[name]) for name in cols
           if name in COLUMN_BYTES}
    out.update({
        "s_store_id": pa.array(_tpcds.business_ids(cols["s_store_id"])),
        "s_rec_start_date": pa.array(_REVISION_STARTS[revision],
                                     pa.date32()),
        "s_rec_end_date": pa.array(
            _REVISION_STARTS[np.minimum(revision + 1, 2)] - 1, pa.date32(),
            mask=last),
        "s_store_name": pa.array(_tpcds.words(cols["s_store_id"] % 10)),
        "s_hours": pick(_HOURS),
        "s_manager": spelt("s_manager"),
        "s_geography_class": pa.array(np.full(rows, "Unknown")),
        "s_market_desc": spelt("s_floor_space"),
        "s_market_manager": spelt("s_zip"),
        "s_division_name": pa.array(np.full(rows, "Unknown")),
        "s_company_name": pa.array(np.full(rows, "Unknown")),
        "s_street_number": pa.array(
            np.maximum(cols["s_street_number"], 0).astype(str),
            mask=cols["s_street_number"] == -1),
        "s_street_name": pick(_STREETS),
        "s_street_type": pick(_STREET_TYPES),
        "s_suite_number": pa.array(np.char.add(
            "Suite ", rng.integers(0, 500, rows).astype(str))),
        "s_city": pick(_CITIES),
        "s_county": pick(_COUNTIES),
        "s_state": pick(_STATES),
        "s_zip": pa.array(np.maximum(cols["s_zip"], 0).astype(str),
                          mask=cols["s_zip"] == -1),
        "s_country": pa.array(np.full(rows, "United States")),
    })
    return pa.table({name: out[name] for name in COLUMN_BYTES})
