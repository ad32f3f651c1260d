"""TPC-DS `customer_address`: the 13 columns of the specification,
250,000 rows at scale factor 10, one file, drawn by dsdgen's rules as
recalled (no network here; the configuration's file lists each under
`assumed`), with numpy's random streams.

**The state.**  dsdgen draws an address's county and state from its
list of United States counties, so a state is as common as it has
counties.  Here `ca_state` is one of the 50 states and the District of
Columbia with the weight of its county count (`COUNTIES`, 3,143 in
all, recalled): Texas 254 of 3,143 (8.1%), **Georgia 159 (5.06%)**,
Delaware 3.  The county, city and gmt offset are drawn beside it.

NULLs by dsdgen's rule (`_tpcds.py`): a row is picked with the table's
`nNullPct`, 1% here, and each nullable column of a picked row is
blanked with probability one half; `ca_address_sk` and `ca_address_id`
never.  Strings reach the plain reference as codes, -1 for NULL: a
state's index in `STATES`, an index into the other lists, the number a
word or a business key is spelt from.  **Every column has a random
stream of its own**, so `generate` draws only the named columns.

Handed fewer rows than the table has (a rehearsal), it holds the
addresses with the lowest keys; `catalog_sales` still draws over all
250,000, so that share of its rows finds an address.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

CUSTOMER_ADDRESS_ID = 18

#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = 100

#: state -> its counties (the weight of its draw), recalled
COUNTIES = {
    "AK": 30, "AL": 67, "AR": 75, "AZ": 15, "CA": 58, "CO": 64, "CT": 8,
    "DC": 1, "DE": 3, "FL": 67, "GA": 159, "HI": 5, "IA": 99, "ID": 44,
    "IL": 102, "IN": 92, "KS": 105, "KY": 120, "LA": 64, "MA": 14,
    "MD": 24, "ME": 16, "MI": 83, "MN": 87, "MO": 115, "MS": 82, "MT": 56,
    "NC": 100, "ND": 53, "NE": 93, "NH": 10, "NJ": 21, "NM": 33, "NV": 17,
    "NY": 62, "OH": 88, "OK": 77, "OR": 36, "PA": 67, "RI": 5, "SC": 46,
    "SD": 66, "TN": 95, "TX": 254, "UT": 29, "VA": 133, "VT": 14, "WA": 39,
    "WI": 72, "WV": 55, "WY": 23,
}
STATES = np.array(list(COUNTIES))
STATE_WEIGHTS = np.array(list(COUNTIES.values()), np.float64)
STATE_WEIGHTS /= STATE_WEIGHTS.sum()

COUNTY_NAMES = np.array([name.replace("_", " ") for name in (
    "Williamson Ziebach Walker Daviess Barrow Franklin Jackson Lincoln "
    "Marion Washington Jefferson Madison Clay Monroe Union Wayne Grant "
    "Greene Warren Fairfield Richland Bronx Kings Dona_Ana Luce "
    "Huron Oglala Terrell Mobile Orange").split()])
CITIES = np.array(["Midway", "Fairview", "Oak Grove", "Five Points",
                   "Pleasant Hill", "Riverside", "Centerville",
                   "Mount Zion", "Greenwood", "Shiloh", "Oakland",
                   "Union", "Salem", "Bethel", "Concord", "Glendale"])
STREETS = np.array("Main Oak Park First Second Elm Maple Cedar Hill Lake "
                   "View Spring Ridge Church Walnut Sunset Pine Forest "
                   "Willow Lincoln".split())
STREET_TYPES = np.array("Street Ave Blvd Road Lane Court Dr. Way Pkwy "
                        "Circle".split())
LOCATION_TYPES = np.array(["apartment", "condo", "single family"])

#: bytes one row of each column takes on the device, in the
#: specification's order: the key 8, a CHAR(n) or VARCHAR(n) its n, the
#: gmt offset (DECIMAL(5,2), a DOUBLE here) 8
COLUMN_BYTES = {
    "ca_address_sk": 8, "ca_address_id": 16, "ca_street_number": 10,
    "ca_street_name": 60, "ca_street_type": 15, "ca_suite_number": 10,
    "ca_city": 60, "ca_county": 30, "ca_state": 2, "ca_zip": 10,
    "ca_country": 20, "ca_gmt_offset": 8, "ca_location_type": 20,
}
_STREAM = {name: at for at, name in enumerate(COLUMN_BYTES)}
_PICKED = 100
_NEVER_NULL = ("ca_address_sk", "ca_address_id")


def _rng(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, CUSTOMER_ADDRESS_ID, index, stream])


def _draw(name: str, rng: np.random.Generator, rows: int) -> np.ndarray:
    """One column, NULLs not yet in: numbers as they are, strings as
    codes."""
    if name in ("ca_address_sk", "ca_address_id"):
        return np.arange(1, rows + 1, dtype=np.int64)
    if name == "ca_state":
        return rng.choice(len(STATES), rows, p=STATE_WEIGHTS).astype(
            np.int32)
    if name == "ca_gmt_offset":
        return -rng.integers(5, 11, rows).astype(np.float64)
    if name == "ca_country":
        return np.zeros(rows, np.int32)
    if name == "ca_street_number":
        return rng.integers(1, 1_001, rows).astype(np.int32)
    top = {"ca_street_name": len(STREETS),
           "ca_street_type": len(STREET_TYPES), "ca_suite_number": 500,
           "ca_city": len(CITIES), "ca_county": len(COUNTY_NAMES),
           "ca_zip": 100_000, "ca_location_type": len(LOCATION_TYPES)}[name]
    return rng.integers(0, top, rows).astype(np.int32)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table's first `rows` rows as numpy arrays, the named columns
    only (all 13 where none is named), for the plain reference; NULL
    is -1, NaN in a DOUBLE column."""
    wanted = list(COLUMN_BYTES) if columns is None else list(columns)
    picked = _rng(seed, index, _PICKED).integers(0, 10_000, rows) < NULL_PCT
    out = {}
    for name in wanted:
        rng = _rng(seed, index, _STREAM[name])
        values = _draw(name, rng, rows)
        if name not in _NEVER_NULL:
            values = _tpcds.blanked(values, picked & (
                rng.integers(0, 2, rows, dtype=np.uint8) == 1))
        out[name] = values
    return out


def _numbered(codes: np.ndarray, prefix: str = "", width: int = 0):
    """Each code as its decimal digits (zero-padded to `width`) after
    `prefix`; NULL where the code is -1."""
    text = np.char.zfill(np.maximum(codes, 0).astype(str), width)
    return pa.array(np.char.add(prefix, text), mask=codes == -1)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    out = {
        "ca_address_sk": _tpcds.arrow(cols["ca_address_sk"]),
        "ca_address_id": pa.array(
            _tpcds.business_ids(cols["ca_address_id"])),
        "ca_street_number": _numbered(cols["ca_street_number"]),
        "ca_street_name": _tpcds.strings(cols["ca_street_name"], STREETS),
        "ca_street_type": _tpcds.strings(cols["ca_street_type"],
                                         STREET_TYPES),
        "ca_suite_number": _numbered(cols["ca_suite_number"], "Suite "),
        "ca_city": _tpcds.strings(cols["ca_city"], CITIES),
        "ca_county": _tpcds.strings(
            cols["ca_county"], np.char.add(COUNTY_NAMES, " County")),
        "ca_state": _tpcds.strings(cols["ca_state"], STATES),
        "ca_zip": _numbered(cols["ca_zip"], width=5),
        "ca_country": _tpcds.strings(cols["ca_country"],
                                     np.array(["United States"])),
        "ca_gmt_offset": _tpcds.arrow(cols["ca_gmt_offset"]),
        "ca_location_type": _tpcds.strings(cols["ca_location_type"],
                                           LOCATION_TYPES),
    }
    return pa.table({name: out[name] for name in COLUMN_BYTES})
