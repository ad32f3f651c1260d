"""TPC-DS schema + deterministic mini catalog for the 99-query sweep.

All 24 benchmark tables with their standard column names, populated
with small, seeded, referentially-consistent data (FKs land inside
their dimension's key range; date_dim is a REAL calendar).  The sweep
harness (tools/sweep.py) registers these with the SQL frontend and
classifies every query's fate against the CPU oracle — the point is
grammar/operator coverage and correctness, not scale (benchmarks/
owns scale).

Conventions (aligned with the spec where queries depend on it):

- ``*_sk`` surrogate keys are int64; ``d_date_sk`` uses the spec's
  Julian-day numbering (1998-01-01 = 2450815) so literal sk windows in
  query texts land inside the data;
- ``d_month_seq`` counts months since 1900-01 (2000-01 = 1200),
  ``d_week_seq`` counts weeks since 1900-01-01 — the sequences the
  year-over-year queries join on;
- money columns are float64 rounded to cents; flag columns are
  'Y'/'N'; a few percent of non-key fact FKs are NULL.
"""

from __future__ import annotations

import datetime as _dt

import numpy as np
import pyarrow as pa

#: 1998-01-01 as a TPC-DS date_dim surrogate key (Julian day number)
DATE_SK_EPOCH = 2450815
_D0 = _dt.date(1998, 1, 1)
_DAYS = (_dt.date(2003, 12, 31) - _D0).days + 1

_CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
               "Men", "Music", "Shoes", "Sports", "Women"]
_CLASSES = ["accent", "bedding", "classical", "dresses", "fiction",
            "fragrances", "mens watch", "pants", "pop", "romance",
            "school-uniforms", "shirts"]
_COLORS = ["aquamarine", "azure", "beige", "black", "blue", "brown",
           "chocolate", "coral", "cream", "cyan", "gold", "green",
           "indigo", "ivory", "khaki", "lime", "magenta", "maroon",
           "navy", "olive", "orange", "pink", "plum", "purple", "red",
           "rose", "salmon", "silver", "snow", "tan", "violet", "white"]
_UNITS = ["Box", "Bunch", "Bundle", "Carton", "Case", "Dozen", "Each",
          "Gram", "Lb", "N/A", "Oz", "Pallet", "Pound", "Tbl", "Ton",
          "Unknown"]
_SIZES = ["economy", "extra large", "large", "medium", "N/A", "petite",
          "small"]
_STATES = ["AL", "CA", "GA", "IL", "IN", "KS", "KY", "LA", "MI", "MN",
           "MO", "MS", "NC", "NY", "OH", "OK", "SD", "TN", "TX", "VA",
           "WA", "WI"]
_CITIES = ["Antioch", "Bethel", "Centerville", "Fairview", "Five Points",
           "Friendship", "Glendale", "Greenville", "Liberty", "Midway",
           "Mount Olive", "Mount Zion", "Oak Grove", "Oak Ridge",
           "Oakland", "Pleasant Grove", "Pleasant Hill", "Riverdale",
           "Riverside", "Salem", "Shiloh", "Springfield", "Union",
           "Walnut Grove", "Wilson"]
_COUNTIES = ["Barrow County", "Daviess County", "Fairfield County",
             "Franklin Parish", "Luce County", "Mobile County",
             "Richland County", "Walker County", "Williamson County",
             "Ziebach County"]
_EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
              "4 yr Degree", "Advanced Degree", "Unknown"]
_MARITAL = ["M", "S", "D", "W", "U"]
_CREDIT = ["Good", "High Risk", "Low Risk", "Unknown"]
_BUY_POTENTIAL = [">10000", "5001-10000", "1001-5000", "501-1000",
                  "0-500", "Unknown"]
_DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
              "Friday", "Saturday"]
_STORE_NAMES = ["ought", "able", "pri", "ese", "anti", "cally",
                "ation", "eing", "n st", "bar"]
_FIRST = ["James", "John", "Robert", "Michael", "William", "David",
          "Mary", "Patricia", "Linda", "Barbara", "Elizabeth",
          "Jennifer", "Maria", "Susan", "Margaret", "Dorothy"]
_LAST = ["Smith", "Johnson", "Williams", "Jones", "Brown", "Davis",
         "Miller", "Wilson", "Moore", "Taylor", "Anderson", "Thomas",
         "Jackson", "White", "Harris", "Martin"]
_COUNTRIES = ["United States", "Canada", "Mexico", "Germany", "Japan",
              "United Kingdom", "France", "Brazil", "India", "China"]

#: base row counts at scale=1 (kept deliberately small: the sweep's
#: job is coverage classification, not throughput)
ROWS = {
    "store_sales": 20_000, "catalog_sales": 12_000, "web_sales": 12_000,
    "store_returns": 3_000, "catalog_returns": 2_000,
    "web_returns": 2_000, "inventory": 12_000,
    "customer": 1_000, "customer_address": 800,
    "customer_demographics": 1_920, "household_demographics": 720,
    "item": 1_000, "time_dim": 1_440, "income_band": 20,
    "store": 12, "warehouse": 6, "promotion": 30, "reason": 10,
    "ship_mode": 5, "call_center": 4, "web_site": 6, "web_page": 20,
    "catalog_page": 40,
}


def _money(rng, n, lo=1.0, hi=300.0):
    return np.round(rng.uniform(lo, hi, n), 2)


def _flags(rng, n):
    return np.array(["Y", "N"])[rng.integers(0, 2, n)]


def _pick(rng, pool, n):
    return np.array(pool, dtype=object)[rng.integers(0, len(pool), n)]


def _null_some(rng, arr, frac=0.04, type_=None):
    """pa.array with ~frac of entries nulled (fact-table FK realism)."""
    mask = rng.random(len(arr)) < frac
    vals = [None if m else v for v, m in zip(arr.tolist(), mask)]
    return pa.array(vals, type=type_)


def _date_dim() -> pa.Table:
    n = _DAYS
    dates = [_D0 + _dt.timedelta(days=i) for i in range(n)]
    epoch = _dt.date(1970, 1, 1)
    base_1900 = (_D0 - _dt.date(1900, 1, 1)).days
    sk = np.arange(n, dtype=np.int64) + DATE_SK_EPOCH
    year = np.array([d.year for d in dates], np.int64)
    moy = np.array([d.month for d in dates], np.int64)
    dom = np.array([d.day for d in dates], np.int64)
    dow = np.array([(d.weekday() + 1) % 7 for d in dates], np.int64)
    month_seq = (year - 1900) * 12 + (moy - 1)
    week_seq = (base_1900 + np.arange(n)) // 7 + 1
    qoy = (moy - 1) // 3 + 1
    return pa.table({
        "d_date_sk": sk,
        "d_date_id": pa.array([f"AAAAAAAA{i:08d}" for i in range(n)]),
        "d_date": pa.array(
            np.array([(d - epoch).days for d in dates], np.int32),
            type=pa.date32()),
        "d_month_seq": month_seq,
        "d_week_seq": week_seq,
        "d_quarter_seq": (year - 1900) * 4 + (qoy - 1),
        "d_year": year,
        "d_dow": dow,
        "d_moy": moy,
        "d_dom": dom,
        "d_qoy": qoy,
        "d_fy_year": year,
        "d_fy_quarter_seq": (year - 1900) * 4 + (qoy - 1),
        "d_fy_week_seq": week_seq,
        "d_day_name": pa.array([_DAY_NAMES[x] for x in dow]),
        "d_quarter_name": pa.array(
            [f"{y}Q{q}" for y, q in zip(year, qoy)]),
        "d_holiday": pa.array(
            ["Y" if (m, dm) in ((7, 4), (12, 25), (1, 1)) else "N"
             for m, dm in zip(moy, dom)]),
        "d_weekend": pa.array(
            ["Y" if x in (0, 6) else "N" for x in dow]),
        "d_following_holiday": pa.array(
            ["Y" if (m, dm) in ((7, 5), (12, 26), (1, 2)) else "N"
             for m, dm in zip(moy, dom)]),
        "d_first_dom": sk - (dom - 1),
        "d_last_dom": sk + 27,
        "d_same_day_ly": sk - 365,
        "d_same_day_lq": sk - 91,
        "d_current_day": pa.array(["N"] * n),
        "d_current_week": pa.array(["N"] * n),
        "d_current_month": pa.array(["N"] * n),
        "d_current_quarter": pa.array(["N"] * n),
        "d_current_year": pa.array(["N"] * n),
    })


def _time_dim(n: int) -> pa.Table:
    # one row per minute of the day: t_time is the second-of-day at
    # the minute boundary, t_time_sk == t_time (the spec's identity)
    mins = np.arange(n, dtype=np.int64)
    secs = mins * (86400 // max(n, 1))
    hour = secs // 3600
    return pa.table({
        "t_time_sk": secs,
        "t_time_id": pa.array([f"AAAAAAAA{i:08d}" for i in mins]),
        "t_time": secs,
        "t_hour": hour,
        "t_minute": (secs % 3600) // 60,
        "t_second": secs % 60,
        "t_am_pm": pa.array(["AM" if h < 12 else "PM" for h in hour]),
        "t_shift": pa.array(
            ["first" if h < 8 else "second" if h < 16 else "third"
             for h in hour]),
        "t_sub_shift": pa.array(
            ["morning" if h < 12 else "afternoon" if h < 18
             else "evening" for h in hour]),
        "t_meal_time": pa.array(
            ["breakfast" if 6 <= h < 9 else
             "lunch" if 11 <= h < 13 else
             "dinner" if 17 <= h < 20 else None for h in hour]),
    })


def _item(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    manu_id = rng.integers(1, 200, n)
    brand_id = (rng.integers(1, 10, n) * 1000000
                + rng.integers(1, 10, n) * 10000 + manu_id)
    cat_idx = rng.integers(0, len(_CATEGORIES), n)
    return pa.table({
        "i_item_sk": sk,
        # two sks share one item_id (the spec's SCD pairing the
        # distinct-buyer queries group on)
        "i_item_id": pa.array([f"AAAAAAAA{x // 2:08d}" for x in sk]),
        "i_rec_start_date": pa.array(
            np.full(n, 9131, np.int32), type=pa.date32()),
        "i_rec_end_date": pa.array([None] * n, type=pa.date32()),
        "i_item_desc": pa.array(
            [f"the promise of item {x} landed" for x in sk]),
        "i_current_price": _money(rng, n, 0.5, 100.0),
        "i_wholesale_cost": _money(rng, n, 0.2, 80.0),
        "i_brand_id": brand_id.astype(np.int64),
        "i_brand": pa.array(
            [f"brand#{b % 100}" for b in brand_id]),
        "i_class_id": rng.integers(1, 17, n).astype(np.int64),
        "i_class": _pick(rng, _CLASSES, n),
        "i_category_id": (cat_idx + 1).astype(np.int64),
        "i_category": pa.array([_CATEGORIES[c] for c in cat_idx]),
        "i_manufact_id": manu_id.astype(np.int64),
        "i_manufact": pa.array([f"manufact#{m}" for m in manu_id]),
        "i_size": _pick(rng, _SIZES, n),
        "i_formulation": pa.array(
            [f"form{x:05d}" for x in rng.integers(0, 1000, n)]),
        "i_color": _pick(rng, _COLORS, n),
        "i_units": _pick(rng, _UNITS, n),
        "i_container": pa.array(["Unknown"] * n),
        "i_manager_id": rng.integers(1, 100, n).astype(np.int64),
        "i_product_name": pa.array([f"product{x}" for x in sk]),
    })


def _customer(rng, n: int, n_addr: int, n_cd: int, n_hd: int,
              n_days: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "c_customer_sk": sk,
        "c_customer_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "c_current_cdemo_sk": _null_some(
            rng, rng.integers(1, n_cd + 1, n).astype(np.int64)),
        "c_current_hdemo_sk": _null_some(
            rng, rng.integers(1, n_hd + 1, n).astype(np.int64)),
        "c_current_addr_sk": rng.integers(
            1, n_addr + 1, n).astype(np.int64),
        "c_first_shipto_date_sk": (
            DATE_SK_EPOCH + rng.integers(0, n_days, n)).astype(np.int64),
        "c_first_sales_date_sk": (
            DATE_SK_EPOCH + rng.integers(0, n_days, n)).astype(np.int64),
        "c_salutation": _pick(
            rng, ["Mr.", "Mrs.", "Ms.", "Dr.", "Sir"], n),
        "c_first_name": _pick(rng, _FIRST, n),
        "c_last_name": _pick(rng, _LAST, n),
        "c_preferred_cust_flag": pa.array(list(_flags(rng, n))),
        "c_birth_day": rng.integers(1, 29, n).astype(np.int64),
        "c_birth_month": rng.integers(1, 13, n).astype(np.int64),
        "c_birth_year": rng.integers(1930, 1995, n).astype(np.int64),
        "c_birth_country": _pick(rng, _COUNTRIES, n),
        "c_login": pa.array([f"login{x}" for x in sk]),
        "c_email_address": pa.array(
            [f"c{x}@example.com" for x in sk]),
        "c_last_review_date_sk": (
            DATE_SK_EPOCH + rng.integers(0, n_days, n)).astype(np.int64),
    })


def _customer_address(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "ca_address_sk": sk,
        "ca_address_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "ca_street_number": pa.array(
            [str(x) for x in rng.integers(1, 1000, n)]),
        "ca_street_name": _pick(
            rng, ["Main", "Oak", "Park", "First", "Elm", "Cedar",
                  "Maple", "Lake", "Hill", "Pine"], n),
        "ca_street_type": _pick(
            rng, ["Street", "Ave", "Blvd", "Ct.", "Dr.", "Lane",
                  "Pkwy", "Rd", "Way"], n),
        "ca_suite_number": pa.array(
            [f"Suite {x}" for x in rng.integers(0, 100, n)]),
        "ca_city": _pick(rng, _CITIES, n),
        "ca_county": _pick(rng, _COUNTIES, n),
        "ca_state": _pick(rng, _STATES, n),
        "ca_zip": pa.array(
            [f"{x:05d}" for x in rng.integers(10000, 99999, n)]),
        "ca_country": pa.array(["United States"] * n),
        "ca_gmt_offset": rng.choice(
            [-5.0, -6.0, -7.0, -8.0], n),
        "ca_location_type": _pick(
            rng, ["apartment", "condo", "single family"], n),
    })


def _customer_demographics(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "cd_demo_sk": sk,
        "cd_gender": _pick(rng, ["M", "F"], n),
        "cd_marital_status": _pick(rng, _MARITAL, n),
        "cd_education_status": _pick(rng, _EDUCATION, n),
        "cd_purchase_estimate": (
            rng.integers(1, 20, n) * 500).astype(np.int64),
        "cd_credit_rating": _pick(rng, _CREDIT, n),
        "cd_dep_count": rng.integers(0, 7, n).astype(np.int64),
        "cd_dep_employed_count": rng.integers(0, 7, n).astype(np.int64),
        "cd_dep_college_count": rng.integers(0, 7, n).astype(np.int64),
    })


def _household_demographics(rng, n: int, n_ib: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "hd_demo_sk": sk,
        "hd_income_band_sk": rng.integers(
            1, n_ib + 1, n).astype(np.int64),
        "hd_buy_potential": _pick(rng, _BUY_POTENTIAL, n),
        "hd_dep_count": rng.integers(0, 10, n).astype(np.int64),
        "hd_vehicle_count": rng.integers(-1, 5, n).astype(np.int64),
    })


def _income_band(n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "ib_income_band_sk": sk,
        "ib_lower_bound": (sk - 1) * 10000,
        "ib_upper_bound": sk * 10000,
    })


def _store(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "s_store_sk": sk,
        "s_store_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "s_rec_start_date": pa.array(
            np.full(n, 9131, np.int32), type=pa.date32()),
        "s_rec_end_date": pa.array([None] * n, type=pa.date32()),
        "s_closed_date_sk": pa.array([None] * n, type=pa.int64()),
        "s_store_name": pa.array(
            [_STORE_NAMES[int(x) % len(_STORE_NAMES)] for x in sk]),
        "s_number_employees": rng.integers(
            200, 300, n).astype(np.int64),
        "s_floor_space": rng.integers(
            5000000, 9000000, n).astype(np.int64),
        "s_hours": _pick(rng, ["8AM-8AM", "8AM-4PM", "8AM-12AM"], n),
        "s_manager": _pick(rng, _FIRST, n),
        "s_market_id": rng.integers(1, 11, n).astype(np.int64),
        "s_geography_class": pa.array(["Unknown"] * n),
        "s_market_desc": pa.array(
            [f"market description {x}" for x in sk]),
        "s_market_manager": _pick(rng, _FIRST, n),
        "s_division_id": np.ones(n, np.int64),
        "s_division_name": pa.array(["Unknown"] * n),
        "s_company_id": np.ones(n, np.int64),
        "s_company_name": pa.array(["Unknown"] * n),
        "s_street_number": pa.array(
            [str(x) for x in rng.integers(1, 1000, n)]),
        "s_street_name": _pick(rng, ["Main", "Oak", "Park"], n),
        "s_street_type": _pick(rng, ["Street", "Ave", "Blvd"], n),
        "s_suite_number": pa.array(
            [f"Suite {x}" for x in rng.integers(0, 100, n)]),
        "s_city": _pick(rng, _CITIES[:6], n),
        "s_county": _pick(rng, _COUNTIES, n),
        "s_state": _pick(rng, _STATES[:8], n),
        "s_zip": pa.array(
            [f"{x:05d}" for x in rng.integers(10000, 99999, n)]),
        "s_country": pa.array(["United States"] * n),
        "s_gmt_offset": rng.choice([-5.0, -6.0], n),
        "s_tax_precentage": np.round(rng.uniform(0.0, 0.11, n), 2),
    })


def _warehouse(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "w_warehouse_sk": sk,
        "w_warehouse_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "w_warehouse_name": pa.array(
            [f"Warehouse number {x}" for x in sk]),
        "w_warehouse_sq_ft": rng.integers(
            50000, 1000000, n).astype(np.int64),
        "w_street_number": pa.array(
            [str(x) for x in rng.integers(1, 1000, n)]),
        "w_street_name": _pick(rng, ["Main", "Oak", "Park"], n),
        "w_street_type": _pick(rng, ["Street", "Ave"], n),
        "w_suite_number": pa.array(
            [f"Suite {x}" for x in rng.integers(0, 100, n)]),
        "w_city": _pick(rng, _CITIES[:6], n),
        "w_county": _pick(rng, _COUNTIES, n),
        "w_state": _pick(rng, _STATES[:8], n),
        "w_zip": pa.array(
            [f"{x:05d}" for x in rng.integers(10000, 99999, n)]),
        "w_country": pa.array(["United States"] * n),
        "w_gmt_offset": rng.choice([-5.0, -6.0], n),
    })


def _ship_mode(n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    types = ["EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "LIBRARY"]
    return pa.table({
        "sm_ship_mode_sk": sk,
        "sm_ship_mode_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "sm_type": pa.array([types[int(x - 1) % len(types)]
                             for x in sk]),
        "sm_code": pa.array(["AIR", "SURFACE", "SEA", "AIR", "SURFACE"
                             ][:n]),
        "sm_carrier": pa.array(["UPS", "FEDEX", "AIRBORNE", "USPS",
                                "DHL"][:n]),
        "sm_contract": pa.array([f"contract{x}" for x in sk]),
    })


def _reason(n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    descs = ["Package was damaged", "Stopped working",
             "Did not get it on time", "Not the product that was "
             "ordred", "Parts missing", "Does not work with a product "
             "that I have", "Gift exchange", "Did not like the color",
             "Did not like the model", "Did not fit"]
    return pa.table({
        "r_reason_sk": sk,
        "r_reason_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "r_reason_desc": pa.array(descs[:n]),
    })


def _promotion(rng, n: int, n_item: int, n_days: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "p_promo_sk": sk,
        "p_promo_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "p_start_date_sk": (DATE_SK_EPOCH + rng.integers(
            0, n_days, n)).astype(np.int64),
        "p_end_date_sk": (DATE_SK_EPOCH + rng.integers(
            0, n_days, n)).astype(np.int64),
        "p_item_sk": rng.integers(1, n_item + 1, n).astype(np.int64),
        "p_cost": np.round(rng.uniform(500.0, 2000.0, n), 2),
        "p_response_target": np.ones(n, np.int64),
        "p_promo_name": _pick(
            rng, ["anti", "bar", "cally", "ese", "ought"], n),
        "p_channel_dmail": pa.array(list(_flags(rng, n))),
        "p_channel_email": pa.array(list(_flags(rng, n))),
        "p_channel_catalog": pa.array(list(_flags(rng, n))),
        "p_channel_tv": pa.array(list(_flags(rng, n))),
        "p_channel_radio": pa.array(list(_flags(rng, n))),
        "p_channel_press": pa.array(list(_flags(rng, n))),
        "p_channel_event": pa.array(list(_flags(rng, n))),
        "p_channel_demo": pa.array(list(_flags(rng, n))),
        "p_channel_details": pa.array(
            [f"promo details {x}" for x in sk]),
        "p_purpose": pa.array(["Unknown"] * n),
        "p_discount_active": pa.array(["N"] * n),
    })


def _call_center(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "cc_call_center_sk": sk,
        "cc_call_center_id": pa.array(
            [f"AAAAAAAA{x:08d}" for x in sk]),
        "cc_rec_start_date": pa.array(
            np.full(n, 9131, np.int32), type=pa.date32()),
        "cc_rec_end_date": pa.array([None] * n, type=pa.date32()),
        "cc_name": pa.array(
            [f"call center {x}" for x in sk]),
        "cc_class": _pick(rng, ["small", "medium", "large"], n),
        "cc_employees": rng.integers(100, 700, n).astype(np.int64),
        "cc_sq_ft": rng.integers(10000, 50000, n).astype(np.int64),
        "cc_hours": _pick(rng, ["8AM-8AM", "8AM-4PM"], n),
        "cc_manager": _pick(rng, _FIRST, n),
        "cc_mkt_id": rng.integers(1, 7, n).astype(np.int64),
        "cc_mkt_class": pa.array([f"mkt class {x}" for x in sk]),
        "cc_mkt_desc": pa.array([f"mkt desc {x}" for x in sk]),
        "cc_market_manager": _pick(rng, _FIRST, n),
        "cc_division": np.ones(n, np.int64),
        "cc_division_name": pa.array(["Unknown"] * n),
        "cc_company": np.ones(n, np.int64),
        "cc_company_name": pa.array(["Unknown"] * n),
        "cc_county": _pick(rng, _COUNTIES, n),
        "cc_state": _pick(rng, _STATES[:8], n),
        "cc_country": pa.array(["United States"] * n),
        "cc_gmt_offset": rng.choice([-5.0, -6.0], n),
        "cc_tax_percentage": np.round(rng.uniform(0.0, 0.12, n), 2),
    })


def _web_site(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "web_site_sk": sk,
        "web_site_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "web_name": pa.array([f"site_{x}" for x in sk]),
        "web_mkt_id": rng.integers(1, 7, n).astype(np.int64),
        "web_company_name": _pick(
            rng, ["pri", "able", "ought", "ese", "anti"], n),
        "web_manager": _pick(rng, _FIRST, n),
        "web_county": _pick(rng, _COUNTIES, n),
        "web_state": _pick(rng, _STATES[:8], n),
        "web_country": pa.array(["United States"] * n),
        "web_gmt_offset": rng.choice([-5.0, -6.0], n),
        "web_tax_percentage": np.round(rng.uniform(0.0, 0.12, n), 2),
    })


def _web_page(rng, n: int, n_days: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "wp_web_page_sk": sk,
        "wp_web_page_id": pa.array([f"AAAAAAAA{x:08d}" for x in sk]),
        "wp_creation_date_sk": (DATE_SK_EPOCH + rng.integers(
            0, n_days, n)).astype(np.int64),
        "wp_access_date_sk": (DATE_SK_EPOCH + rng.integers(
            0, n_days, n)).astype(np.int64),
        "wp_autogen_flag": pa.array(list(_flags(rng, n))),
        "wp_customer_sk": _null_some(
            rng, rng.integers(1, 100, n).astype(np.int64), 0.5,
            pa.int64()),
        "wp_url": pa.array(["http://www.foo.com"] * n),
        "wp_type": _pick(
            rng, ["ad", "dynamic", "feedback", "general", "order",
                  "protected", "welcome"], n),
        "wp_char_count": rng.integers(
            1000, 8000, n).astype(np.int64),
        "wp_link_count": rng.integers(2, 25, n).astype(np.int64),
        "wp_image_count": rng.integers(1, 7, n).astype(np.int64),
        "wp_max_ad_count": rng.integers(0, 5, n).astype(np.int64),
    })


def _catalog_page(rng, n: int) -> pa.Table:
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "cp_catalog_page_sk": sk,
        "cp_catalog_page_id": pa.array(
            [f"AAAAAAAA{x:08d}" for x in sk]),
        "cp_start_date_sk": np.full(n, DATE_SK_EPOCH, np.int64),
        "cp_end_date_sk": np.full(n, DATE_SK_EPOCH + 364, np.int64),
        "cp_department": pa.array(["DEPARTMENT"] * n),
        "cp_catalog_number": ((sk - 1) // 10 + 1),
        "cp_catalog_page_number": ((sk - 1) % 10 + 1),
        "cp_description": pa.array([f"catalog page {x}" for x in sk]),
        "cp_type": _pick(
            rng, ["bi-annual", "monthly", "quarterly"], n),
    })


def generate(scale: float = 1.0, seed: int = 7) -> dict:
    """The full mini catalog: {table_name: pa.Table}, deterministic in
    (scale, seed)."""
    rng = np.random.default_rng(seed)
    rows = {k: max(4, int(v * scale)) for k, v in ROWS.items()}
    n_days = _DAYS
    out: dict = {}
    out["date_dim"] = _date_dim()
    out["time_dim"] = _time_dim(rows["time_dim"])
    out["item"] = _item(rng, rows["item"])
    out["customer_address"] = _customer_address(
        rng, rows["customer_address"])
    out["customer_demographics"] = _customer_demographics(
        rng, rows["customer_demographics"])
    out["income_band"] = _income_band(rows["income_band"])
    out["household_demographics"] = _household_demographics(
        rng, rows["household_demographics"], rows["income_band"])
    out["customer"] = _customer(
        rng, rows["customer"], rows["customer_address"],
        rows["customer_demographics"],
        rows["household_demographics"], n_days)
    out["store"] = _store(rng, rows["store"])
    out["warehouse"] = _warehouse(rng, rows["warehouse"])
    out["ship_mode"] = _ship_mode(rows["ship_mode"])
    out["reason"] = _reason(rows["reason"])
    out["promotion"] = _promotion(
        rng, rows["promotion"], rows["item"], n_days)
    out["call_center"] = _call_center(rng, rows["call_center"])
    out["web_site"] = _web_site(rng, rows["web_site"])
    out["web_page"] = _web_page(rng, rows["web_page"], n_days)
    out["catalog_page"] = _catalog_page(rng, rows["catalog_page"])

    def dsk(n):
        # concentrate sales in 1998-2002 so year-filtered queries hit
        return (DATE_SK_EPOCH
                + rng.integers(0, min(n_days, 365 * 5), n)).astype(
                    np.int64)

    def tsk(n):
        return out["time_dim"].column("t_time_sk")[
            0].as_py() + (rng.integers(0, rows["time_dim"], n)
                          * (86400 // rows["time_dim"])).astype(np.int64)

    n = rows["store_sales"]
    qty = rng.integers(1, 101, n).astype(np.int64)
    wcost = _money(rng, n, 1, 100)
    lprice = np.round(wcost * rng.uniform(1.0, 2.0, n), 2)
    sprice = np.round(lprice * rng.uniform(0.3, 1.0, n), 2)
    ext_sales = np.round(sprice * qty, 2)
    ext_wcost = np.round(wcost * qty, 2)
    ext_list = np.round(lprice * qty, 2)
    discount = np.round(ext_list - ext_sales, 2)
    tax = np.round(ext_sales * 0.05, 2)
    coupon = np.round(ext_sales * (rng.random(n) < 0.1)
                      * rng.uniform(0, 0.5, n), 2)
    net_paid = np.round(ext_sales - coupon, 2)
    out["store_sales"] = pa.table({
        "ss_sold_date_sk": _null_some(rng, dsk(n), 0.02, pa.int64()),
        "ss_sold_time_sk": tsk(n),
        "ss_item_sk": rng.integers(
            1, rows["item"] + 1, n).astype(np.int64),
        "ss_customer_sk": _null_some(
            rng, rng.integers(1, rows["customer"] + 1, n)
            .astype(np.int64), 0.03, pa.int64()),
        "ss_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "ss_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "ss_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "ss_store_sk": rng.integers(
            1, rows["store"] + 1, n).astype(np.int64),
        "ss_promo_sk": rng.integers(
            1, rows["promotion"] + 1, n).astype(np.int64),
        "ss_ticket_number": (np.arange(n, dtype=np.int64) // 4 + 1),
        "ss_quantity": qty,
        "ss_wholesale_cost": wcost,
        "ss_list_price": lprice,
        "ss_sales_price": sprice,
        "ss_ext_discount_amt": discount,
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_wcost,
        "ss_ext_list_price": ext_list,
        "ss_ext_tax": tax,
        "ss_coupon_amt": coupon,
        "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": np.round(net_paid + tax, 2),
        "ss_net_profit": np.round(net_paid - ext_wcost, 2),
    })

    n = rows["store_returns"]
    ridx = rng.integers(0, rows["store_sales"], n)
    ss = out["store_sales"]
    ret_amt = _money(rng, n, 1, 300)
    out["store_returns"] = pa.table({
        "sr_returned_date_sk": dsk(n),
        "sr_return_time_sk": tsk(n),
        "sr_item_sk": pa.array(
            [ss.column("ss_item_sk")[i].as_py() for i in ridx],
            pa.int64()),
        "sr_customer_sk": pa.array(
            [ss.column("ss_customer_sk")[i].as_py() for i in ridx],
            pa.int64()),
        "sr_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "sr_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "sr_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "sr_store_sk": pa.array(
            [ss.column("ss_store_sk")[i].as_py() for i in ridx],
            pa.int64()),
        "sr_reason_sk": rng.integers(
            1, rows["reason"] + 1, n).astype(np.int64),
        "sr_ticket_number": pa.array(
            [ss.column("ss_ticket_number")[i].as_py() for i in ridx],
            pa.int64()),
        "sr_return_quantity": rng.integers(1, 20, n).astype(np.int64),
        "sr_return_amt": ret_amt,
        "sr_return_tax": np.round(ret_amt * 0.05, 2),
        "sr_return_amt_inc_tax": np.round(ret_amt * 1.05, 2),
        "sr_fee": _money(rng, n, 0.5, 100),
        "sr_return_ship_cost": _money(rng, n, 0, 50),
        "sr_refunded_cash": np.round(ret_amt * 0.7, 2),
        "sr_reversed_charge": np.round(ret_amt * 0.2, 2),
        "sr_store_credit": np.round(ret_amt * 0.1, 2),
        "sr_net_loss": _money(rng, n, 0.5, 200),
    })

    def _sales(prefix: str, n: int, order_div: int) -> pa.Table:
        qty = rng.integers(1, 101, n).astype(np.int64)
        wcost = _money(rng, n, 1, 100)
        lprice = np.round(wcost * rng.uniform(1.0, 2.0, n), 2)
        sprice = np.round(lprice * rng.uniform(0.3, 1.0, n), 2)
        ext_sales = np.round(sprice * qty, 2)
        ext_wcost = np.round(wcost * qty, 2)
        ext_list = np.round(lprice * qty, 2)
        tax = np.round(ext_sales * 0.05, 2)
        ship = _money(rng, n, 0, 150)
        coupon = np.round(ext_sales * (rng.random(n) < 0.1)
                          * rng.uniform(0, 0.5, n), 2)
        net_paid = np.round(ext_sales - coupon, 2)
        sold = dsk(n)
        cols = {
            "sold_date_sk": sold,
            "sold_time_sk": tsk(n),
            "ship_date_sk": sold + rng.integers(2, 90, n),
            "bill_customer_sk": rng.integers(
                1, rows["customer"] + 1, n).astype(np.int64),
            "bill_cdemo_sk": rng.integers(
                1, rows["customer_demographics"] + 1,
                n).astype(np.int64),
            "bill_hdemo_sk": rng.integers(
                1, rows["household_demographics"] + 1,
                n).astype(np.int64),
            "bill_addr_sk": rng.integers(
                1, rows["customer_address"] + 1, n).astype(np.int64),
            "ship_customer_sk": rng.integers(
                1, rows["customer"] + 1, n).astype(np.int64),
            "ship_cdemo_sk": rng.integers(
                1, rows["customer_demographics"] + 1,
                n).astype(np.int64),
            "ship_hdemo_sk": rng.integers(
                1, rows["household_demographics"] + 1,
                n).astype(np.int64),
            "ship_addr_sk": rng.integers(
                1, rows["customer_address"] + 1, n).astype(np.int64),
            "ship_mode_sk": rng.integers(
                1, rows["ship_mode"] + 1, n).astype(np.int64),
            "warehouse_sk": rng.integers(
                1, rows["warehouse"] + 1, n).astype(np.int64),
            "item_sk": rng.integers(
                1, rows["item"] + 1, n).astype(np.int64),
            "promo_sk": rng.integers(
                1, rows["promotion"] + 1, n).astype(np.int64),
            "order_number": (np.arange(n, dtype=np.int64)
                             // order_div + 1),
            "quantity": qty,
            "wholesale_cost": wcost,
            "list_price": lprice,
            "sales_price": sprice,
            "ext_discount_amt": np.round(ext_list - ext_sales, 2),
            "ext_sales_price": ext_sales,
            "ext_wholesale_cost": ext_wcost,
            "ext_list_price": ext_list,
            "ext_tax": tax,
            "coupon_amt": coupon,
            "ext_ship_cost": ship,
            "net_paid": net_paid,
            "net_paid_inc_tax": np.round(net_paid + tax, 2),
            "net_paid_inc_ship": np.round(net_paid + ship, 2),
            "net_paid_inc_ship_tax": np.round(net_paid + ship + tax, 2),
            "net_profit": np.round(net_paid - ext_wcost, 2),
        }
        return cols

    cs = _sales("cs", rows["catalog_sales"], 3)
    out["catalog_sales"] = pa.table({
        "cs_sold_date_sk": _null_some(rng, cs["sold_date_sk"], 0.02,
                                      pa.int64()),
        "cs_sold_time_sk": cs["sold_time_sk"],
        "cs_ship_date_sk": cs["ship_date_sk"],
        "cs_bill_customer_sk": cs["bill_customer_sk"],
        "cs_bill_cdemo_sk": cs["bill_cdemo_sk"],
        "cs_bill_hdemo_sk": cs["bill_hdemo_sk"],
        "cs_bill_addr_sk": cs["bill_addr_sk"],
        "cs_ship_customer_sk": cs["ship_customer_sk"],
        "cs_ship_cdemo_sk": cs["ship_cdemo_sk"],
        "cs_ship_hdemo_sk": cs["ship_hdemo_sk"],
        "cs_ship_addr_sk": cs["ship_addr_sk"],
        "cs_call_center_sk": rng.integers(
            1, rows["call_center"] + 1,
            rows["catalog_sales"]).astype(np.int64),
        "cs_catalog_page_sk": rng.integers(
            1, rows["catalog_page"] + 1,
            rows["catalog_sales"]).astype(np.int64),
        "cs_ship_mode_sk": cs["ship_mode_sk"],
        "cs_warehouse_sk": cs["warehouse_sk"],
        "cs_item_sk": cs["item_sk"],
        "cs_promo_sk": cs["promo_sk"],
        "cs_order_number": cs["order_number"],
        "cs_quantity": cs["quantity"],
        "cs_wholesale_cost": cs["wholesale_cost"],
        "cs_list_price": cs["list_price"],
        "cs_sales_price": cs["sales_price"],
        "cs_ext_discount_amt": cs["ext_discount_amt"],
        "cs_ext_sales_price": cs["ext_sales_price"],
        "cs_ext_wholesale_cost": cs["ext_wholesale_cost"],
        "cs_ext_list_price": cs["ext_list_price"],
        "cs_ext_tax": cs["ext_tax"],
        "cs_coupon_amt": cs["coupon_amt"],
        "cs_ext_ship_cost": cs["ext_ship_cost"],
        "cs_net_paid": cs["net_paid"],
        "cs_net_paid_inc_tax": cs["net_paid_inc_tax"],
        "cs_net_paid_inc_ship": cs["net_paid_inc_ship"],
        "cs_net_paid_inc_ship_tax": cs["net_paid_inc_ship_tax"],
        "cs_net_profit": cs["net_profit"],
    })

    ws = _sales("ws", rows["web_sales"], 3)
    out["web_sales"] = pa.table({
        "ws_sold_date_sk": _null_some(rng, ws["sold_date_sk"], 0.02,
                                      pa.int64()),
        "ws_sold_time_sk": ws["sold_time_sk"],
        "ws_ship_date_sk": ws["ship_date_sk"],
        "ws_item_sk": ws["item_sk"],
        "ws_bill_customer_sk": ws["bill_customer_sk"],
        "ws_bill_cdemo_sk": ws["bill_cdemo_sk"],
        "ws_bill_hdemo_sk": ws["bill_hdemo_sk"],
        "ws_bill_addr_sk": ws["bill_addr_sk"],
        "ws_ship_customer_sk": ws["ship_customer_sk"],
        "ws_ship_cdemo_sk": ws["ship_cdemo_sk"],
        "ws_ship_hdemo_sk": ws["ship_hdemo_sk"],
        "ws_ship_addr_sk": ws["ship_addr_sk"],
        "ws_web_page_sk": rng.integers(
            1, rows["web_page"] + 1, rows["web_sales"]).astype(np.int64),
        "ws_web_site_sk": rng.integers(
            1, rows["web_site"] + 1, rows["web_sales"]).astype(np.int64),
        "ws_ship_mode_sk": ws["ship_mode_sk"],
        "ws_warehouse_sk": ws["warehouse_sk"],
        "ws_promo_sk": ws["promo_sk"],
        "ws_order_number": ws["order_number"],
        "ws_quantity": ws["quantity"],
        "ws_wholesale_cost": ws["wholesale_cost"],
        "ws_list_price": ws["list_price"],
        "ws_sales_price": ws["sales_price"],
        "ws_ext_discount_amt": ws["ext_discount_amt"],
        "ws_ext_sales_price": ws["ext_sales_price"],
        "ws_ext_wholesale_cost": ws["ext_wholesale_cost"],
        "ws_ext_list_price": ws["ext_list_price"],
        "ws_ext_tax": ws["ext_tax"],
        "ws_coupon_amt": ws["coupon_amt"],
        "ws_ext_ship_cost": ws["ext_ship_cost"],
        "ws_net_paid": ws["net_paid"],
        "ws_net_paid_inc_tax": ws["net_paid_inc_tax"],
        "ws_net_paid_inc_ship": ws["net_paid_inc_ship"],
        "ws_net_paid_inc_ship_tax": ws["net_paid_inc_ship_tax"],
        "ws_net_profit": ws["net_profit"],
    })

    def _returns(sales: pa.Table, pfx: str, n: int,
                 item_col: str, order_col: str, cust_col: str) -> dict:
        ridx = rng.integers(0, sales.num_rows, n)
        amt = _money(rng, n, 1, 300)
        return {
            "returned_date_sk": dsk(n),
            "returned_time_sk": tsk(n),
            "item_sk": pa.array(
                [sales.column(item_col)[i].as_py() for i in ridx],
                pa.int64()),
            "order_number": pa.array(
                [sales.column(order_col)[i].as_py() for i in ridx],
                pa.int64()),
            "customer_sk": pa.array(
                [sales.column(cust_col)[i].as_py() for i in ridx],
                pa.int64()),
            "quantity": rng.integers(1, 20, n).astype(np.int64),
            "amt": amt,
            "tax": np.round(amt * 0.05, 2),
            "amt_inc_tax": np.round(amt * 1.05, 2),
            "fee": _money(rng, n, 0.5, 100),
            "ship_cost": _money(rng, n, 0, 50),
            "refunded_cash": np.round(amt * 0.7, 2),
            "reversed_charge": np.round(amt * 0.2, 2),
            "credit": np.round(amt * 0.1, 2),
            "net_loss": _money(rng, n, 0.5, 200),
        }

    n = rows["catalog_returns"]
    cr = _returns(out["catalog_sales"], "cr", n, "cs_item_sk",
                  "cs_order_number", "cs_bill_customer_sk")
    out["catalog_returns"] = pa.table({
        "cr_returned_date_sk": cr["returned_date_sk"],
        "cr_returned_time_sk": cr["returned_time_sk"],
        "cr_item_sk": cr["item_sk"],
        "cr_refunded_customer_sk": cr["customer_sk"],
        "cr_refunded_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "cr_refunded_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "cr_refunded_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "cr_returning_customer_sk": rng.integers(
            1, rows["customer"] + 1, n).astype(np.int64),
        "cr_returning_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "cr_returning_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "cr_returning_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "cr_call_center_sk": rng.integers(
            1, rows["call_center"] + 1, n).astype(np.int64),
        "cr_catalog_page_sk": rng.integers(
            1, rows["catalog_page"] + 1, n).astype(np.int64),
        "cr_ship_mode_sk": rng.integers(
            1, rows["ship_mode"] + 1, n).astype(np.int64),
        "cr_warehouse_sk": rng.integers(
            1, rows["warehouse"] + 1, n).astype(np.int64),
        "cr_reason_sk": rng.integers(
            1, rows["reason"] + 1, n).astype(np.int64),
        "cr_order_number": cr["order_number"],
        "cr_return_quantity": cr["quantity"],
        "cr_return_amount": cr["amt"],
        "cr_return_tax": cr["tax"],
        "cr_return_amt_inc_tax": cr["amt_inc_tax"],
        "cr_fee": cr["fee"],
        "cr_return_ship_cost": cr["ship_cost"],
        "cr_refunded_cash": cr["refunded_cash"],
        "cr_reversed_charge": cr["reversed_charge"],
        "cr_store_credit": cr["credit"],
        "cr_net_loss": cr["net_loss"],
    })

    n = rows["web_returns"]
    wr = _returns(out["web_sales"], "wr", n, "ws_item_sk",
                  "ws_order_number", "ws_bill_customer_sk")
    out["web_returns"] = pa.table({
        "wr_returned_date_sk": wr["returned_date_sk"],
        "wr_returned_time_sk": wr["returned_time_sk"],
        "wr_item_sk": wr["item_sk"],
        "wr_refunded_customer_sk": wr["customer_sk"],
        "wr_refunded_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "wr_refunded_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "wr_refunded_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "wr_returning_customer_sk": rng.integers(
            1, rows["customer"] + 1, n).astype(np.int64),
        "wr_returning_cdemo_sk": rng.integers(
            1, rows["customer_demographics"] + 1, n).astype(np.int64),
        "wr_returning_hdemo_sk": rng.integers(
            1, rows["household_demographics"] + 1, n).astype(np.int64),
        "wr_returning_addr_sk": rng.integers(
            1, rows["customer_address"] + 1, n).astype(np.int64),
        "wr_web_page_sk": rng.integers(
            1, rows["web_page"] + 1, n).astype(np.int64),
        "wr_reason_sk": rng.integers(
            1, rows["reason"] + 1, n).astype(np.int64),
        "wr_order_number": wr["order_number"],
        "wr_return_quantity": wr["quantity"],
        "wr_return_amt": wr["amt"],
        "wr_return_tax": wr["tax"],
        "wr_return_amt_inc_tax": wr["amt_inc_tax"],
        "wr_fee": wr["fee"],
        "wr_return_ship_cost": wr["ship_cost"],
        "wr_refunded_cash": wr["refunded_cash"],
        "wr_reversed_charge": wr["reversed_charge"],
        "wr_account_credit": wr["credit"],
        "wr_net_loss": wr["net_loss"],
    })

    n = rows["inventory"]
    out["inventory"] = pa.table({
        "inv_date_sk": dsk(n),
        "inv_item_sk": rng.integers(
            1, rows["item"] + 1, n).astype(np.int64),
        "inv_warehouse_sk": rng.integers(
            1, rows["warehouse"] + 1, n).astype(np.int64),
        "inv_quantity_on_hand": rng.integers(
            0, 1000, n).astype(np.int64),
    })
    return out
