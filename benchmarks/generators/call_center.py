"""TPC-DS `call_center`: the 31 columns of the specification, 24 rows at
scale factor 10 (`catalog_sales.CALL_CENTERS`), one file, drawn by
dsdgen's rules as recalled (no network here; the configuration's file
lists each under `assumed`), with numpy's random streams.

`cc_call_center_id`, CHAR(16), is the business key, which the revisions
of one call center share (`_tpcds.revisions`: 24 rows hold 12).
**`cc_county`** is one of `COUNTIES`, the four that `store` draws from
too, the first of them `Williamson County`, which query 16's
qualification substitution names five times; every county holds the
same share of the rows, a quarter (6 of 24), in an order the seed
permutes.  NULLs by dsdgen's rule (`_tpcds.py`): a row is picked with
the table's `nNullPct`, 1% here, and each nullable column of a picked
row is blanked with probability one half; the two keys never.

`generate` hands the plain reference numbers and codes, -1 for NULL;
`to_arrow` spells the strings.  **Every column has a random stream of
its own.**  Handed fewer rows than the table has (a rehearsal: 1), it
holds the call centers with the lowest keys; `catalog_sales` still
draws over all 24.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

CALL_CENTER_ID = 19

#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = 100

COUNTIES = np.array(["Williamson County", "Ziebach County",
                     "Walker County", "Daviess County"])
STATES = np.array(["TN", "SD", "AL", "IN", "GA", "OH", "TX"])
NAMES = np.array(["NY Metro", "Mid Atlantic", "North Midwest",
                  "Pacific Northwest", "California", "Hawaii/Alaska"])
CLASSES = np.array(["small", "medium", "large"])
HOURS = np.array(["8AM-4PM", "8AM-12AM", "8AM-8AM"])
_STREETS = np.array("Main Oak Park First Second Elm Maple Cedar Hill "
                    "Lake View Spring Ridge Church Walnut".split())
_STREET_TYPES = np.array("Street Ave Blvd Road Lane Court Dr. Way Pkwy "
                         "Circle".split())
_CITIES = np.array(["Midway", "Fairview", "Oak Grove", "Five Points",
                    "Pleasant Hill", "Riverside", "Centerville"])
_REVISION_STARTS = np.array([10_227, 10_957, 11_688], np.int32)

#: bytes one row of each column takes on the device, in the
#: specification's order: a surrogate key 8, an integer 4, a date 4, a
#: CHAR(n) or VARCHAR(n) its n, a DECIMAL(5,2) (a DOUBLE here) 8
COLUMN_BYTES = {
    "cc_call_center_sk": 8, "cc_call_center_id": 16, "cc_rec_start_date": 4,
    "cc_rec_end_date": 4, "cc_closed_date_sk": 8, "cc_open_date_sk": 8,
    "cc_name": 50, "cc_class": 50, "cc_employees": 4, "cc_sq_ft": 4,
    "cc_hours": 20, "cc_manager": 40, "cc_mkt_id": 4, "cc_mkt_class": 50,
    "cc_mkt_desc": 100, "cc_market_manager": 40, "cc_division": 4,
    "cc_division_name": 50, "cc_company": 4, "cc_company_name": 50,
    "cc_street_number": 10, "cc_street_name": 60, "cc_street_type": 15,
    "cc_suite_number": 10, "cc_city": 60, "cc_county": 30, "cc_state": 2,
    "cc_zip": 10, "cc_country": 20, "cc_gmt_offset": 8,
    "cc_tax_percentage": 8,
}
_STREAM = {name: at for at, name in enumerate(COLUMN_BYTES)}
_PICKED = 100
_NEVER_NULL = ("cc_call_center_sk", "cc_call_center_id")
#: what `to_arrow` is handed beside the columns: each row's revision
_REVISION = "revision"
#: the columns that are numbers in the file too
_NUMBERS = ("cc_call_center_sk", "cc_closed_date_sk", "cc_open_date_sk",
            "cc_employees", "cc_sq_ft", "cc_mkt_id", "cc_division",
            "cc_company", "cc_gmt_offset", "cc_tax_percentage")


def _rng(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, CALL_CENTER_ID, index, stream])


def _draw(name: str, rng: np.random.Generator, rows: int) -> np.ndarray:
    """One column, NULLs not yet in: numbers as they are, strings and
    dates as codes (a string's index in its list, a number its words
    spell, a revision's start)."""
    if name == "cc_call_center_sk":
        return np.arange(1, rows + 1, dtype=np.int64)
    if name == "cc_county":
        # a quarter of the rows each, in a seeded order
        return rng.permutation(np.arange(rows) % len(COUNTIES)).astype(
            np.int32)
    if name == "cc_closed_date_sk":
        return np.full(rows, -1, np.int64)  # open: NULL, as dsdgen's
    if name == "cc_open_date_sk":
        return rng.integers(_tpcds.SALES_FIRST_DAY - 3_650,
                            _tpcds.SALES_FIRST_DAY, rows) + _tpcds.EPOCH_SK
    if name == "cc_gmt_offset":
        return -rng.integers(5, 9, rows).astype(np.float64)
    if name == "cc_tax_percentage":
        return rng.integers(0, 13, rows) / 100.0
    low, high = {
        "cc_name": (0, len(NAMES)), "cc_class": (0, len(CLASSES)),
        "cc_employees": (100, 70_001), "cc_sq_ft": (10_000, 1_000_001),
        "cc_hours": (0, len(HOURS)), "cc_manager": (0, 10 ** 6),
        "cc_mkt_id": (1, 7), "cc_mkt_class": (0, 10 ** 6),
        "cc_mkt_desc": (0, 10 ** 9), "cc_market_manager": (0, 10 ** 6),
        "cc_division": (1, 7), "cc_division_name": (0, 10 ** 3),
        "cc_company": (1, 7), "cc_company_name": (0, 10 ** 3),
        "cc_street_number": (1, 1_001), "cc_street_name": (0, len(_STREETS)),
        "cc_street_type": (0, len(_STREET_TYPES)),
        "cc_suite_number": (0, 500), "cc_city": (0, len(_CITIES)),
        "cc_state": (0, len(STATES)), "cc_zip": (10_000, 100_000),
        "cc_country": (0, 1)}[name]
    return rng.integers(low, high, rows).astype(np.int32)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table's first `rows` rows as numpy arrays, the named columns
    only (all 31 where none is named, and each row's revision), for
    the plain reference: `cc_call_center_id` as the business key's
    number, strings as codes; NULL is -1, NaN in a DOUBLE column."""
    wanted = list(COLUMN_BYTES) if columns is None else list(columns)
    number, revision = _tpcds.revisions(rows)
    picked = _rng(seed, index, _PICKED).integers(0, 10_000, rows) < NULL_PCT
    out = {}
    for name in wanted:
        rng = _rng(seed, index, _STREAM[name])
        if name == "cc_call_center_id":
            values = number
        elif name in ("cc_rec_start_date", "cc_rec_end_date"):
            values = revision.astype(np.int32)
        else:
            values = _draw(name, rng, rows)
        if name not in _NEVER_NULL:
            values = _tpcds.blanked(values, picked & (
                rng.integers(0, 2, rows, dtype=np.uint8) == 1))
        out[name] = values
    if columns is None:
        out[_REVISION] = revision
    return out


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    revision = cols[_REVISION]
    last = np.r_[revision[1:] == 0, True]
    start, end = cols["cc_rec_start_date"], cols["cc_rec_end_date"]
    out = {name: _tpcds.arrow(cols[name]) for name in _NUMBERS}
    out.update({
        "cc_call_center_id": pa.array(
            _tpcds.business_ids(cols["cc_call_center_id"])),
        "cc_rec_start_date": pa.array(
            _REVISION_STARTS[np.maximum(start, 0)], pa.date32(),
            mask=start == -1),
        "cc_rec_end_date": pa.array(
            _REVISION_STARTS[np.minimum(np.maximum(end, 0) + 1, 2)] - 1,
            pa.date32(), mask=last | (end == -1)),
        "cc_name": _tpcds.strings(cols["cc_name"], NAMES),
        "cc_class": _tpcds.strings(cols["cc_class"], CLASSES),
        "cc_hours": _tpcds.strings(cols["cc_hours"], HOURS),
        "cc_street_name": _tpcds.strings(cols["cc_street_name"], _STREETS),
        "cc_street_type": _tpcds.strings(cols["cc_street_type"],
                                         _STREET_TYPES),
        "cc_city": _tpcds.strings(cols["cc_city"], _CITIES),
        "cc_county": _tpcds.strings(cols["cc_county"], COUNTIES),
        "cc_state": _tpcds.strings(cols["cc_state"], STATES),
        "cc_country": _tpcds.strings(cols["cc_country"],
                                     np.array(["United States"])),
        "cc_suite_number": pa.array(
            np.char.add("Suite ",
                        np.maximum(cols["cc_suite_number"], 0).astype(str)),
            mask=cols["cc_suite_number"] == -1),
    })
    for name in ("cc_manager", "cc_mkt_class", "cc_mkt_desc",
                 "cc_market_manager", "cc_division_name",
                 "cc_company_name"):
        codes = cols[name]
        out[name] = pa.array(_tpcds.words(np.maximum(codes, 0)),
                             mask=codes == -1)
    for name in ("cc_street_number", "cc_zip"):
        codes = cols[name]
        out[name] = pa.array(np.maximum(codes, 0).astype(str),
                             mask=codes == -1)
    return pa.table({name: out[name] for name in COLUMN_BYTES})
