"""TPC-DS query 67 (`query67.tpl`), DMS = 1200, as the specification
writes it: store_sales joined to date_dim, store and item, the sales
of the twelve months from `d_month_seq` 1200 summed by ROLLUP over
eight columns, ranked by that sum within `i_category`, the hundred
best of each category kept, and the first hundred of those by the ten
output columns returned.

`build` is the query through the DataFrame API.  The month filter
stands on `date_dim` under the join, where Spark's optimizer puts it
(the engine's planner moves no filter through a join).

The plain reference sums in whole cents: the money columns hold whole
cents over 100, so `rint(price x 100) x quantity` is exact, and a sum
of them stays under 2^53 (the grand total at SF10 is about 3e12
cents), exact in numpy's float64 `bincount` too.  The sums are divided
by 100 once, at the end.  So equal row sets have equal sums, as in
the specification's DECIMAL: under DMS = 1200 every sale falls in the
year 2000, the level (category, class, brand, product) and the level
below it (..., d_year) sum the same rows, and `rank()` gives such a
pair one rank.  The groups are made from the columns' values, not
from the surrogate keys: two `s_store_sk` share an `s_store_id`.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

ORDERED = True
COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_quantity", "ss_sales_price"],
    "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_qoy", "d_moy"],
    "store": ["s_store_sk", "s_store_id"],
    "item": ["i_item_sk", "i_category", "i_class", "i_brand",
             "i_product_name"],
}
DRIVER = "store_sales"

DMS = 1200
KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
BEST = 100


def _refuse_a_program_that_cannot_hold_it() -> None:
    """Before PR 28 the engine sorted the 1,100 rows that `rk <= 100`
    keeps at the capacity of the window's output: a 5.55 GB program
    that the chip had no room for in the second round, after which
    every collect was answered by the CPU engine, over hours (my chip
    run, PR 28, call 1).  Such a program cannot run this cell; it is
    told so at once, before a round, and exits non-zero."""
    from spark_rapids_tpu.execs.sort import TpuSortExec

    if not getattr(TpuSortExec, "sizes_counted_input", False):
        raise SystemExit(
            "benchmarks.run REFUSED: this program's TpuSortExec sorts a "
            "filter's output at its input's capacity; q67 at this size "
            "does not fit the chip's memory with it (PERF.md section 6, "
            "PR 28)")


def build(session, frames):
    _refuse_a_program_that_cannot_hold_it()
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.exprs.predicates import Coalesce
    from spark_rapids_tpu.exprs.window import Window, rank
    from spark_rapids_tpu.session import col, sum_

    months = frames["date_dim"].where(
        (col("d_month_seq") >= lit(DMS)) & (col("d_month_seq")
                                            <= lit(DMS + 11)))
    sales = (frames["store_sales"]
             .join(months, left_on=[col("ss_sold_date_sk")],
                   right_on=[col("d_date_sk")])
             .join(frames["store"], left_on=[col("ss_store_sk")],
                   right_on=[col("s_store_sk")])
             .join(frames["item"], left_on=[col("ss_item_sk")],
                   right_on=[col("i_item_sk")]))
    dw1 = sales.rollup(*KEYS).agg(
        (sum_(Coalesce(col("ss_sales_price") * col("ss_quantity"), lit(0))),
         "sumsales"))
    by_category = Window.partition_by("i_category").order_by(
        "sumsales", desc=True)
    columns = [col(k) for k in KEYS] + [col("sumsales")]
    dw2 = dw1.select(*columns, rank().over(by_category).alias("rk"))
    return (dw2.where(col("rk") <= lit(BEST))
            .order_by(*columns, col("rk"))
            .limit(BEST))


# -- the plain reference ------------------------------------------------ #

def _rows_of(keys: np.ndarray, table_keys: np.ndarray) -> np.ndarray:
    """The table's row that holds each key, -1 where none does (a NULL
    key is -1 and no table holds it).  `table_keys` ascend."""
    at = np.minimum(np.searchsorted(table_keys, keys), len(table_keys) - 1)
    return np.where(table_keys[at] == keys, at, -1)


def partial(cols: dict, side: dict) -> tuple:
    """Over one file: the inner joins (a NULL or unmatched key drops
    the row), the month filter, and cents summed per (item row, month,
    store row), which fix all eight columns.  Returns the eight
    columns' codes per group, an (n, 8) array, and the cents."""
    dates, stores, items = side["date_dim"], side["store"], side["item"]
    day = _rows_of(cols["ss_sold_date_sk"], dates["d_date_sk"])
    store = _rows_of(cols["ss_store_sk"], stores["s_store_sk"])
    item = _rows_of(cols["ss_item_sk"], items["i_item_sk"])
    month = dates["d_month_seq"][day].astype(np.int64) - DMS
    keep = (day >= 0) & (store >= 0) & (item >= 0) \
        & (month >= 0) & (month < 12)
    day, store, item, month = (x[keep] for x in (day, store, item, month))
    # coalesce(ss_sales_price * ss_quantity, 0): a NULL operand adds 0,
    # and the row still makes its group
    price, quantity = cols["ss_sales_price"][keep], cols["ss_quantity"][keep]
    missing = np.isnan(price) | (quantity < 0)
    cents = np.where(missing, 0.0,
                     np.rint(np.where(missing, 0.0, price) * 100.0)
                     * quantity)
    n_stores = len(stores["s_store_sk"])
    group, first, member = np.unique(
        (item * 12 + month) * n_stores + store, return_index=True,
        return_inverse=True)
    summed = np.bincount(member, cents, minlength=len(group))
    item, day, store = item[first], day[first], store[first]
    codes = np.stack(
        [items[name][item] for name in KEYS[:4]]
        + [dates[name][day] for name in KEYS[4:7]]
        + [stores["s_store_id"][store]], axis=1).astype(np.int32)
    return codes, summed


#: how each string column's codes are spelt
_SPELL = {"i_category": lambda c: _tpcds.CATEGORIES[c],
          "i_class": lambda c: _tpcds.CLASSES[c],
          "i_brand": _tpcds.brand_names,
          "i_product_name": _tpcds.words,
          "s_store_id": _tpcds.business_ids}


def _by_value(name: str, codes: np.ndarray) -> tuple:
    """A column's codes as ranks of its values, 0 for NULL and 1.. in
    ascending order of the value (of the string, where the column is
    one), and the values by rank, None first."""
    known = np.unique(codes[codes >= 0])
    spelt = _SPELL[name](known) if name in _SPELL else known
    values, rank_of = np.unique(spelt, return_inverse=True)
    ranks = np.zeros(len(codes), np.int64)
    ranks[codes >= 0] = rank_of[np.searchsorted(known, codes[codes >= 0])] \
        + 1
    return ranks, [None] + values.tolist()


def combine(partials: list) -> pa.Table:
    codes = np.concatenate([p[0] for p in partials])
    cents = np.concatenate([p[1] for p in partials])
    ranks, values = zip(*(_by_value(name, codes[:, at])
                          for at, name in enumerate(KEYS)))
    # one number a group: the eight ranks as digits, the first column
    # the most significant, so numbers ascend as ORDER BY does, NULLs
    # first; place[k] is the weight of column k - 1's digit
    place = [1]
    for v in reversed(values):
        place.insert(0, place[0] * len(v))
    assert place[0] < 2 ** 62, place
    packed = sum(r * w for r, w in zip(ranks, place[1:]))
    # the nine levels, each from the one below it: level k keeps the
    # first k columns and blanks the rest (digit 0, which reads NULL)
    levels = []
    for k in range(len(KEYS), -1, -1):
        packed, member = np.unique(packed - packed % place[k],
                                   return_inverse=True)
        cents = np.bincount(member, cents, minlength=len(packed))
        levels.append((packed, cents))
    packed = np.concatenate([p for p, _ in levels])
    cents = np.concatenate([c for _, c in levels]).astype(np.int64)
    # rank() over (partition by i_category order by sumsales desc): one
    # more than the rows of the partition with a larger sum
    category = packed // place[1]
    order = np.lexsort((-cents, category))
    at = np.arange(len(order))
    starts = np.r_[True, np.diff(category[order]) != 0]
    peers = starts | np.r_[True, np.diff(cents[order]) != 0]
    rk = np.empty(len(order), np.int64)
    rk[order] = (np.maximum.accumulate(np.where(peers, at, 0))
                 - np.maximum.accumulate(np.where(starts, at, 0)) + 1)
    best = np.flatnonzero(rk <= BEST)
    best = best[np.lexsort((rk[best], cents[best], packed[best]))][:BEST]
    out = {}
    for at, name in enumerate(KEYS):
        digit = packed[best] // place[at + 1] % len(values[at])
        kind = pa.string() if name in _SPELL else pa.int32()
        out[name] = pa.array([values[at][d] for d in digit], kind)
    out["sumsales"] = cents[best] / 100.0
    out["rk"] = rk[best]
    return pa.table(out)
