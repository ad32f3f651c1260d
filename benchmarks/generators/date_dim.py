"""TPC-DS `date_dim`: the 28 columns of the specification, one row a
day from 1900-01-02, 73,049 of them, by the calendar: nothing is drawn
and nothing is NULL.  `d_date_sk` is the Julian day number (2415022 on
the first day) and `d_month_seq` counts months from January 1900, so
query 67's 1200..1211 is the year 2000.

One file.  Handed fewer rows than the table has (a rehearsal), it
holds that many consecutive days from 1998-01-01, the first day of the
five sales years, or from as much earlier as the count allows: every
date `store_sales` draws is still there.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

COLUMN_BYTES = {
    "d_date_sk": 8, "d_date_id": 16, "d_date": 4, "d_month_seq": 4,
    "d_week_seq": 4, "d_quarter_seq": 4, "d_year": 4, "d_dow": 4,
    "d_moy": 4, "d_dom": 4, "d_qoy": 4, "d_fy_year": 4,
    "d_fy_quarter_seq": 4, "d_fy_week_seq": 4, "d_day_name": 9,
    "d_quarter_name": 6, "d_holiday": 1, "d_weekend": 1,
    "d_following_holiday": 1, "d_first_dom": 8, "d_last_dom": 8,
    "d_same_day_ly": 8, "d_same_day_lq": 8, "d_current_day": 1,
    "d_current_week": 1, "d_current_month": 1, "d_current_quarter": 1,
    "d_current_year": 1,
}

#: dsdgen's "today", 2003-01-08, which the d_current_* flags hang on
_TODAY = 12_060


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table's numeric columns as numpy arrays (the seed and the
    index change nothing: it is a calendar)."""
    return _tpcds.date_draws(rows)


def _flags(which: np.ndarray):
    return _tpcds.strings(which.astype(np.int32), np.array(["N", "Y"]))


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    day = cols["d_date"].astype(np.int64)
    year, qoy, dow = cols["d_year"], cols["d_qoy"], cols["d_dow"]
    # the fixed holidays, enough for the flag to have both values
    holiday = ((cols["d_moy"] == 1) & (cols["d_dom"] == 1)) \
        | ((cols["d_moy"] == 7) & (cols["d_dom"] == 4)) \
        | ((cols["d_moy"] == 12) & (cols["d_dom"] == 25))
    today = np.datetime64(_TODAY, "D")
    date = day.astype("datetime64[D]")
    out = {name: pa.array(cols[name]) for name in cols}
    out.update({
        "d_date": pa.array(cols["d_date"], pa.date32()),
        "d_date_id": pa.array(_tpcds.business_ids(cols["d_date_sk"])),
        "d_fy_year": out["d_year"],
        "d_fy_quarter_seq": out["d_quarter_seq"],
        "d_fy_week_seq": out["d_week_seq"],
        "d_day_name": _tpcds.strings(day % 7, _tpcds.DAY_NAMES),
        "d_quarter_name": pa.array(np.char.add(
            np.char.add(year.astype(str), "Q"), qoy.astype(str))),
        "d_holiday": _flags(holiday),
        "d_weekend": _flags((dow == 0) | (dow == 6)),
        "d_following_holiday": _flags(np.r_[False, holiday[:-1]]),
        "d_same_day_ly": pa.array(cols["d_date_sk"] - 365),
        "d_same_day_lq": pa.array(cols["d_date_sk"] - 91),
        "d_current_day": _flags(day == _TODAY),
        "d_current_week": _flags(cols["d_week_seq"] == (
            (_TODAY - _tpcds.FIRST_DAY + 1) // 7 + 1)),
        "d_current_month": _flags(date.astype("datetime64[M]")
                                  == today.astype("datetime64[M]")),
        "d_current_quarter": _flags(
            (year == 2003) & (qoy == 1)),
        "d_current_year": _flags(year == 2003),
    })
    return pa.table({name: out[name] for name in COLUMN_BYTES})
