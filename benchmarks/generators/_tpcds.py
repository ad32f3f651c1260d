"""What the four TPC-DS tables of `tpcds-sf10` share: the key domains
of `dsdgen -scale 10`, the item hierarchy, the calendar, the draws of
each table as numpy arrays, and the NULL rule.

dsdgen's rules as recalled (no network here; the configuration's file
lists each under `assumed`), with numpy's random streams, not dsdgen's.
File `i` of `store_sales` is drawn from `default_rng([seed, id, i])`
and each dimension, one file, from `default_rng([seed, id, 0])`: a file
depends on the seed and its index alone.

**NULL, in the arrays a generator hands the plain reference:** -1 in an
integer column (every key, count and code is positive or zero) and NaN
in a DOUBLE one.  A string column is a code, an index into the names
below or a number its name is spelt from, and -1 is its NULL too.
dsdgen picks a row for NULLs with the table's `nNullPct` (store_sales
9%, item 0.5%, store 1%, date_dim never) and then blanks each nullable
column of a picked row with probability one half; the primary and
business keys are never blanked.

**A dimension handed fewer rows than its table has** (`--rehearse`
cuts every table to a sixteenth, and `store_sales`' generator cannot
see that) keeps the members with the lowest keys, which the fact table
draws as often as any other: `item` and `store` their first rows, and
`date_dim` a run of days that covers the five sales years, so the date
join loses nothing and the other two keep their share of the rows.
"""

import functools

import numpy as np

STORE_SALES_ID, ITEM_ID, STORE_ID = 11, 12, 14  # date_dim draws nothing

# -- dsdgen -scale 10 ------------------------------------------------- #

ITEMS = 102_000
STORES = 102
DAYS = 73_049  # 1900-01-02 .. 2100-01-01
CUSTOMERS = 500_000
CUSTOMER_DEMOGRAPHICS = 1_920_800
HOUSEHOLD_DEMOGRAPHICS = 7_200
ADDRESSES = 250_000
PROMOTIONS = 500
MANUFACTURERS = 1_000

#: d_date_sk is the Julian day number: days since 1970-01-01 plus this
EPOCH_SK = 2_440_588
FIRST_DAY = -25_566  # 1900-01-02, days since 1970-01-01
SALES_FIRST_DAY = 10_227  # 1998-01-01
SALES_LAST_DAY = 12_052  # 2002-12-31

#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = {"store_sales": 900, "item": 50, "store": 100}

TICKET_LINES = 12  # a ticket has 8..16 lines, uniform

# -- the item hierarchy ------------------------------------------------ #

_HIERARCHY = {
    "Women": "dresses fragrances maternity swimwear",
    "Men": "accessories pants shirts sports-apparel",
    "Children": "infants newborn school-uniforms toddlers",
    "Shoes": "athletic kids mens womens",
    "Music": "classical country pop rock",
    "Jewelry": "birdal bracelets costume custom diamonds earings estate "
               "gold jewelry_boxes loose_stones mens_watch pendants rings "
               "semi-precious womens_watch consignment",
    "Home": "accent bathroom bedding blinds/shades curtains/drapes decor "
            "flatware furniture glassware kids lighting mattresses paint "
            "rugs tables wallpaper",
    "Sports": "archery athletic_shoes baseball basketball camping fishing "
              "fitness football golf guns hockey optics outdoor pools "
              "sailing tennis",
    "Books": "arts business computers cooking entertainments fiction "
             "history home_repair mystery parenting reference romance "
             "science self-help sports travel",
    "Electronics": "audio automotive cameras camcorders dvd/vcr_players "
                   "disk_drives karoke memory monitors musical personal "
                   "portable scanners stereo televisions wireless",
}
CATEGORIES = np.array(list(_HIERARCHY))
#: every class, in category order: a class code is an index into these
CLASSES = np.array([c.replace("_", " ") for names in _HIERARCHY.values()
                    for c in names.split()])
CLASS_CATEGORY = np.repeat(
    np.arange(len(_HIERARCHY)),
    [len(names.split()) for names in _HIERARCHY.values()]).astype(np.int32)
#: the class's number within its category, from 1 (i_class_id)
CLASS_NUMBER = (np.arange(len(CLASSES))
                - np.flatnonzero(np.r_[True, np.diff(CLASS_CATEGORY) > 0])
                [CLASS_CATEGORY] + 1).astype(np.int32)
BRANDS_PER_CLASS = 7
_BRAND_SYLLABLES = np.array(["amalg", "importo", "edu pack", "exporti",
                             "scholar", "univ", "corp", "brand", "maxi",
                             "nameless"])
#: mk_word's syllables: a product's name spells its item key's digits
_SYLLABLES = np.array(["bar", "ought", "able", "pri", "ese", "anti",
                       "cally", "ation", "eing", "n st"])
SIZES = np.array(["petite", "small", "medium", "large", "extra large",
                  "economy", "N/A"])
COLORS = np.array(
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder "
    "puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise violet "
    "wheat white yellow".split())
UNITS = np.array("Bunch Bundle Box Carton Case Cup Dozen Dram Each Gram "
                 "Gross Lb N/A Ounce Oz Pallet Pound Tbl Ton Tsp "
                 "Unknown".split())
DAY_NAMES = np.array(["Thursday", "Friday", "Saturday", "Sunday", "Monday",
                      "Tuesday", "Wednesday"])  # 1970-01-01 was a Thursday


def brand_names(codes: np.ndarray) -> np.ndarray:
    """`i_brand` of each brand code (class code x 16 + the brand's
    number within the class, from 0): two of dsdgen's brand syllables,
    by class and category, and `#<n>`.  Not one to one: classes of one
    category may share a word."""
    cls = codes // 16
    first = _BRAND_SYLLABLES[CLASS_NUMBER[cls] % 10]
    second = _BRAND_SYLLABLES[CLASS_CATEGORY[cls]]
    number = np.char.add(" #", (codes % 16 + 1).astype(str))
    return np.char.add(np.char.add(first, second), number)


def words(numbers: np.ndarray) -> np.ndarray:
    """dsdgen's `mk_word`: one syllable a decimal digit of the number,
    the most significant first (`i_product_name`, `i_manufact`): 12 is
    `oughtable`.  Distinct numbers spell distinct words."""
    numbers = np.asarray(numbers, np.int64)
    out = np.full(len(numbers), "", dtype="U40")
    digits = max(1, len(str(int(numbers.max(initial=0)))))
    for place in range(digits - 1, -1, -1):
        digit = numbers // 10 ** place % 10
        started = numbers >= 10 ** place
        out = np.where(started | (place == 0),
                       np.char.add(out, _SYLLABLES[digit]), out)
    return out


def business_ids(numbers: np.ndarray) -> np.ndarray:
    """dsdgen's `mk_bkey`: a CHAR(16) of eight `A`s and the number in
    letters, the least significant first: 1 is `AAAAAAAABAAAAAAA`."""
    numbers = np.asarray(numbers, np.int64)
    out = np.full(len(numbers), "AAAAAAAA", dtype="U16")
    letters = np.array(list("ABCDEFGHIJKLMNOP"))
    for place in range(8):
        out = np.char.add(out, letters[numbers >> (4 * place) & 15])
    return out


def revisions(rows: int) -> tuple:
    """dsdgen's slowly changing dimensions: surrogate keys 1, 2, 3, ...
    are the revisions of business keys that have one, two and three of
    them in turn, so six rows hold three business keys.  Returns each
    row's business key's number (from 1) and its revision (from 0)."""
    at = np.arange(rows)
    within = at % 6
    number = at // 6 * 3 + np.array([0, 1, 1, 2, 2, 2])[within] + 1
    return number.astype(np.int64), np.array([0, 0, 1, 0, 1, 2])[within]


def null_mask(rng: np.random.Generator, table: str, rows: int,
              columns: int) -> np.ndarray:
    """(rows, columns) of booleans: which values dsdgen would blank."""
    picked = rng.integers(0, 10_000, rows) < NULL_PCT[table]
    return picked[:, None] & (rng.integers(0, 2, (rows, columns),
                                           dtype=np.uint8) == 1)


def blanked(values: np.ndarray, nulls: np.ndarray) -> np.ndarray:
    """The values with -1, or NaN in a DOUBLE column, where `nulls`."""
    if values.dtype == np.float64:
        return np.where(nulls, np.nan, values)
    return np.where(nulls, values.dtype.type(-1), values)


def arrow(values: np.ndarray, type_=None):
    """An Arrow array of the values, NULL where they say so."""
    import pyarrow as pa

    nulls = np.isnan(values) if values.dtype == np.float64 else values == -1
    return pa.array(values, type_, mask=nulls)


def strings(codes: np.ndarray, names: np.ndarray):
    """The Arrow string array `names[codes]`, NULL where a code is -1."""
    import pyarrow as pa

    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32), mask=codes == -1),
        pa.array(names)).cast(pa.string())


# -- the draws --------------------------------------------------------- #

def sales_draws(seed: int, index: int, rows: int, items: int = ITEMS,
                stores: int = STORES) -> dict:
    """File `index` of store_sales: every column of the 23, NULLs in.
    A ticket is 8 to 16 lines that share date, time, customer, his
    demographics and address and the store; then as few tickets as it
    takes move by one line so that `rows // 12` tickets hold exactly
    `rows` lines.  Pricing by dsdgen's `set_pricing`: a wholesale cost of
    1.00-100.00, a markup of 0-200% to the list price, a discount of
    0-100% to the sales price, a coupon on a fifth of the lines, a tax
    of 0-9%."""
    rng = np.random.default_rng([seed, STORE_SALES_ID, index])
    tickets = max(1, rows // TICKET_LINES)
    if not 8 * tickets <= rows <= 16 * tickets:
        raise ValueError(f"{rows} rows are no {tickets} tickets of 8 to 16")
    lines = rng.integers(8, 17, tickets)
    off = rows - int(lines.sum())
    while off:
        room = np.flatnonzero(lines < 16 if off > 0 else lines > 8)
        step = min(abs(off), len(room))
        lines[rng.choice(room, step, replace=False)] += np.sign(off)
        off -= step * np.sign(off)
    of = np.repeat(np.arange(tickets), lines)
    quantity = rng.integers(1, 101, rows)
    wholesale = rng.integers(100, 10_001, rows)  # whole cents
    list_price = wholesale * (100 + rng.integers(0, 201, rows)) // 100
    sales_price = list_price * (100 - rng.integers(0, 101, rows)) // 100
    ext_sales = sales_price * quantity
    coupon = np.where(rng.integers(0, 5, rows) == 0,
                      ext_sales * rng.integers(0, 101, rows) // 100, 0)
    net_paid = ext_sales - coupon
    tax = net_paid * rng.integers(0, 10, rows) // 100
    per_ticket = {
        "ss_sold_date_sk": rng.integers(SALES_FIRST_DAY, SALES_LAST_DAY + 1,
                                        tickets) + EPOCH_SK,
        "ss_sold_time_sk": rng.integers(28_800, 75_600, tickets),
        "ss_customer_sk": rng.integers(1, CUSTOMERS + 1, tickets),
        "ss_cdemo_sk": rng.integers(1, CUSTOMER_DEMOGRAPHICS + 1, tickets),
        "ss_hdemo_sk": rng.integers(1, HOUSEHOLD_DEMOGRAPHICS + 1, tickets),
        "ss_addr_sk": rng.integers(1, ADDRESSES + 1, tickets),
        "ss_store_sk": rng.integers(1, stores + 1, tickets),
        "ss_ticket_number": np.arange(index * tickets + 1,
                                      (index + 1) * tickets + 1),
    }
    cols = {name: values[of] for name, values in per_ticket.items()}
    cols.update({
        "ss_item_sk": rng.integers(1, items + 1, rows),
        "ss_promo_sk": rng.integers(1, PROMOTIONS + 1, rows),
        "ss_quantity": quantity.astype(np.int32),
        "ss_wholesale_cost": wholesale / 100.0,
        "ss_list_price": list_price / 100.0,
        "ss_sales_price": sales_price / 100.0,
        "ss_ext_discount_amt": (list_price - sales_price) * quantity / 100.0,
        "ss_ext_sales_price": ext_sales / 100.0,
        "ss_ext_wholesale_cost": wholesale * quantity / 100.0,
        "ss_ext_list_price": list_price * quantity / 100.0,
        "ss_ext_tax": tax / 100.0,
        "ss_coupon_amt": coupon / 100.0,
        "ss_net_paid": net_paid / 100.0,
        "ss_net_paid_inc_tax": (net_paid + tax) / 100.0,
        "ss_net_profit": (net_paid - wholesale * quantity) / 100.0,
    })
    nullable = [n for n in cols
                if n not in ("ss_item_sk", "ss_ticket_number")]
    nulls = null_mask(rng, "store_sales", rows, len(nullable))
    for at, name in enumerate(nullable):
        cols[name] = blanked(cols[name], nulls[:, at])
    return cols


@functools.lru_cache(maxsize=4)
def item_draws(seed: int, rows: int) -> dict:
    """`item`'s first `rows` rows: keys 1..rows, the class drawn and the
    category and brand nested under it, the product's name spelt from
    the key.  The strings as codes; -1 is NULL."""
    rng = np.random.default_rng([seed, ITEM_ID, 0])
    sk = np.arange(1, rows + 1, dtype=np.int64)
    number, revision = revisions(rows)
    cls = rng.integers(0, len(CLASSES), rows).astype(np.int32)
    brand = cls * 16 + rng.integers(0, BRANDS_PER_CLASS, rows)
    price = rng.integers(9, 10_000, rows)  # whole cents
    cols = {
        "i_item_sk": sk, "i_item_id": number, "revision": revision,
        "i_current_price": price / 100.0,
        "i_wholesale_cost": price * rng.integers(30, 91, rows) // 100
        / 100.0,
        "i_brand_id": (CLASS_CATEGORY[cls] + 1) * 1_000_000
        + CLASS_NUMBER[cls] * 1_000 + brand % 16 + 1,
        "i_brand": brand.astype(np.int32),
        "i_class_id": CLASS_NUMBER[cls], "i_class": cls,
        "i_category_id": CLASS_CATEGORY[cls] + 1,
        "i_category": CLASS_CATEGORY[cls],
        "i_manufact_id": rng.integers(1, MANUFACTURERS + 1,
                                      rows).astype(np.int32),
        "i_size": rng.integers(0, len(SIZES), rows).astype(np.int32),
        "i_formulation": rng.integers(0, 10 ** 9, rows),
        "i_color": rng.integers(0, len(COLORS), rows).astype(np.int32),
        "i_units": rng.integers(0, len(UNITS), rows).astype(np.int32),
        "i_manager_id": rng.integers(1, 101, rows).astype(np.int32),
        "i_product_name": sk.astype(np.int32),
    }
    cols["i_manufact"] = cols["i_manufact_id"]
    nullable = [n for n in cols
                if n not in ("i_item_sk", "i_item_id", "revision")]
    nulls = null_mask(rng, "item", rows, len(nullable))
    for at, name in enumerate(nullable):
        cols[name] = blanked(cols[name], nulls[:, at])
    return cols


def first_day(rows: int) -> int:
    """The first day of a `date_dim` of `rows` days, since 1970-01-01:
    1900-01-02 for the whole table; for a cut one as much later as
    keeps the sales years' first day in."""
    return FIRST_DAY + min(DAYS - rows, SALES_FIRST_DAY - FIRST_DAY)


@functools.lru_cache(maxsize=4)
def date_draws(rows: int) -> dict:
    """`rows` consecutive days by the calendar (no draw, no NULL):
    `d_month_seq` is 0 in January 1900, so 1200..1211 is the year
    2000."""
    day = np.arange(first_day(rows), first_day(rows) + rows)
    date = day.astype("datetime64[D]")
    month = date.astype("datetime64[M]")
    year = month.astype("datetime64[Y]").astype(np.int64) + 1970
    moy = month.astype(np.int64) % 12 + 1
    qoy = (moy - 1) // 3 + 1
    dow = (day - 3) % 7  # 0 on a Sunday
    week = (day - FIRST_DAY + 1) // 7 + 1  # 1900-01-01 was a Monday
    return {
        "d_date_sk": day + EPOCH_SK, "d_date": day.astype(np.int32),
        "d_month_seq": ((year - 1900) * 12 + moy - 1).astype(np.int32),
        "d_week_seq": week.astype(np.int32),
        "d_quarter_seq": ((year - 1900) * 4 + qoy).astype(np.int32),
        "d_year": year.astype(np.int32), "d_dow": dow.astype(np.int32),
        "d_moy": moy.astype(np.int32),
        "d_dom": (date - month + 1).astype(np.int32),
        "d_qoy": qoy.astype(np.int32),
        "d_first_dom": (month.astype("datetime64[D]").astype(np.int64)
                        + EPOCH_SK),
        "d_last_dom": ((month + 1).astype("datetime64[D]").astype(np.int64)
                       - 1 + EPOCH_SK),
    }


@functools.lru_cache(maxsize=4)
def store_draws(seed: int, rows: int) -> dict:
    """`store`'s first `rows` rows: keys 1..rows; `s_store_id` is the
    number of the business key, which the revisions of one store share
    (102 rows hold 51)."""
    rng = np.random.default_rng([seed, STORE_ID, 0])
    number, revision = revisions(rows)
    cols = {
        "s_store_sk": np.arange(1, rows + 1, dtype=np.int64),
        "s_store_id": number.astype(np.int32), "revision": revision,
        "s_closed_date_sk": rng.integers(SALES_FIRST_DAY, SALES_LAST_DAY,
                                         rows) + EPOCH_SK,
        "s_number_employees": rng.integers(200, 301, rows).astype(np.int32),
        "s_floor_space": rng.integers(5_000_000, 10_000_001,
                                      rows).astype(np.int32),
        "s_market_id": rng.integers(1, 11, rows).astype(np.int32),
        "s_division_id": np.ones(rows, np.int32),
        "s_company_id": np.ones(rows, np.int32),
        "s_gmt_offset": -rng.integers(5, 9, rows).astype(np.float64),
        "s_tax_precentage": rng.integers(0, 12, rows) / 100.0,
        "s_manager": rng.integers(0, 10 ** 6, rows),
        "s_street_number": rng.integers(1, 1_001, rows),
        "s_zip": rng.integers(10_000, 100_000, rows),
    }
    nullable = [n for n in cols
                if n not in ("s_store_sk", "s_store_id", "revision")]
    nulls = null_mask(rng, "store", rows, len(nullable))
    for at, name in enumerate(nullable):
        cols[name] = blanked(cols[name], nulls[:, at])
    return cols
