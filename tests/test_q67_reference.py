"""TPC-DS q67 as the benchmark's cell `tpcds-sf10.q67` runs it:
`benchmarks/queries/q67.py:build` through `TpuSession` against the same
file's plain reference (`partial`, `combine`: numpy, whole cents), on
seeded tables of a few thousand rows with the configuration's shapes:
NULL keys and measures in the fact table, NULL strings in `item`, two
stores under one `s_store_id`, and every sale of the filter in one
year, so that the rollup's level (category, class, brand, product) and
the level below it (..., d_year) sum the same rows and have to rank
equal."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import _tpcds, date_dim, item, store, store_sales
from benchmarks.harness import check
from benchmarks.queries import q67
from benchmarks.selfcheck import _f32_control

SEEDS = [67, 2800000067, 3]
ROWS, ITEMS, STORES, DAYS = 6000, 240, 6, 4565


@pytest.fixture(scope="module")
def small_tables(tmp_path_factory):
    """seed -> (the four tables' files, the reference's answer).  The
    item table blanks a twentieth of its rows' columns, not dsdgen's
    two-hundredth, so that 240 items hold NULL names."""
    made = {}
    rate = _tpcds.NULL_PCT["item"]

    def get(seed: int):
        if seed in made:
            return made[seed]
        _tpcds.NULL_PCT["item"] = 1000
        _tpcds.item_draws.cache_clear()
        try:
            cols = {
                "store_sales": _tpcds.sales_draws(seed, 0, ROWS, ITEMS,
                                                  STORES),
                "item": item.generate(seed, 0, ITEMS),
                "date_dim": date_dim.generate(seed, 0, DAYS),
                "store": store.generate(seed, 0, STORES),
            }
        finally:
            _tpcds.NULL_PCT["item"] = rate
            _tpcds.item_draws.cache_clear()
        work = tmp_path_factory.mktemp(f"q67-{seed}")
        paths = {}
        for name, gen in (("store_sales", store_sales), ("item", item),
                          ("date_dim", date_dim), ("store", store)):
            paths[name] = str(work / f"{name}.parquet")
            pq.write_table(gen.to_arrow(cols[name], seed, 0), paths[name])
        side = {role: cols[role] for role in q67.COLUMNS
                if role != q67.DRIVER}
        want = q67.combine([q67.partial(cols["store_sales"], side)])
        made[seed] = paths, want
        return made[seed]
    return get


def _collect(paths: dict, engine=None) -> pa.Table:
    from spark_rapids_tpu.session import TpuSession

    session = TpuSession()
    frames = {role: session.read_parquet(paths[role], columns=columns)
              for role, columns in q67.COLUMNS.items()}
    return q67.build(session, frames).collect(engine=engine)


@pytest.fixture(scope="module")
def answers(small_tables):
    """seed -> (the device engine's answer, the reference's)."""
    got = {}

    def get(seed: int):
        if seed not in got:
            paths, want = small_tables(seed)
            got[seed] = _collect(paths), want
        return got[seed]
    return get


def _level(table: pa.Table, row: int) -> int:
    """How many of the eight columns the row's group kept: a rollup
    row is NULL from some column on (a NULL value further left reads
    one level lower, which no assertion here minds)."""
    values = [table.column(k)[row].as_py() for k in q67.KEYS]
    return max((at + 1 for at, v in enumerate(values) if v is not None),
               default=0)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_engine_answers_as_the_reference(answers, seed):
    got, want = answers(seed)
    assert want.num_rows == q67.BEST
    assert want.schema.names == q67.KEYS + ["sumsales", "rk"]
    why, gap = check.compare(got, want, q67.ORDERED)
    assert why is None, why
    assert gap <= check.REL_TOL


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_the_cpu_engine_answers_as_the_reference(small_tables, seed):
    paths, want = small_tables(seed)
    assert check.difference(_collect(paths, engine="cpu"), want,
                            q67.ORDERED) is None


@pytest.mark.parametrize("seed", SEEDS)
def test_the_tables_have_what_the_query_exists_for(small_tables, seed):
    paths, want = small_tables(seed)
    sales = pq.read_table(paths["store_sales"])
    for name in ("ss_sold_date_sk", "ss_store_sk", "ss_quantity",
                 "ss_sales_price"):
        assert 0 < sales[name].null_count < ROWS // 10, name
    assert sales["ss_item_sk"].null_count == 0
    items = pq.read_table(paths["item"])
    assert items["i_category"].null_count and \
        items["i_product_name"].null_count
    stores = pq.read_table(paths["store"])
    assert stores.num_rows == STORES
    assert len(set(stores["s_store_id"].to_pylist())) == STORES // 2
    # the rollup's answer starts with its NULLs: the grand total and
    # the items without a category rank among themselves
    assert want["i_category"][0].as_py() is None
    assert 1 in want["rk"].to_pylist()
    levels = {_level(want, r) for r in range(want.num_rows)}
    assert {4, 5} <= levels


@pytest.mark.parametrize("seed", SEEDS)
def test_equal_row_sets_rank_equal(answers, seed):
    """Every sale of the filter is in the year 2000, so a product's
    row and the same product's row for the year sum the same rows: one
    sum, one rank, in the reference's cents and in the engine's
    DOUBLE alike."""
    got, want = answers(seed)
    pairs = 0
    for table in (want, got):
        rows = {}
        for r in range(table.num_rows):
            key = tuple(table.column(k)[r].as_py() for k in q67.KEYS)
            rows[key] = (table["sumsales"][r].as_py(),
                         table["rk"][r].as_py())
        for key, (total, rk) in rows.items():
            if key[3] is not None and key[4:] == (None,) * 4:
                below = rows.get(key[:4] + (2000, None, None, None))
                if below is not None:
                    assert below == (total, rk), key
                    pairs += 1
    assert pairs >= 2


def test_a_float32_sum_is_told(answers):
    got, want = answers(SEEDS[0])
    why, gap = check.compare(_f32_control.stored(got), want, q67.ORDERED)
    assert why is not None
    assert gap is None or gap > 10 * check.REL_TOL


def test_rank_leaves_a_gap_after_a_tie():
    """`combine` on hand-made partials: two files' rows of one group
    are summed, a group whose only measure was NULL still stands with
    0.00, and rank() skips after equal sums."""
    codes = np.array([
        # category, class, brand, product, year, qoy, moy, store id
        [0, 0, 0, 11, 2000, 1, 1, 1],
        [0, 0, 0, 11, 2000, 1, 1, 1],  # the same group, another file
        [0, 0, 0, 12, 2000, 1, 2, 2],
        [-1, 0, 0, 13, 2000, 1, 2, 2],  # no category
    ], np.int32)
    cents = np.array([100.0, 250.0, 350.0, 0.0])
    out = q67.combine([(codes[:2], cents[:2]), (codes[2:], cents[2:])])
    rows = [(tuple(out.column(k)[r].as_py() for k in q67.KEYS),
             (out["sumsales"][r].as_py(), out["rk"][r].as_py()))
            for r in range(out.num_rows)]
    women = str(_tpcds.CATEGORIES[0])
    dresses = str(_tpcds.CLASSES[0])
    brand = str(_tpcds.brand_names(np.array([0]))[0])
    assert ((women,) + (None,) * 7, (7.0, 1)) in rows
    assert ((women, dresses) + (None,) * 6, (7.0, 1)) in rows
    assert ((women, dresses, brand) + (None,) * 5, (7.0, 1)) in rows
    # the two products tie at 3.50 on five levels each: all fourth,
    # behind the three rows of 7.00
    for name in _tpcds.words(np.array([11, 12])):
        assert ((women, dresses, brand, str(name)) + (None,) * 4,
                (3.5, 4)) in rows
    assert [v for _, v in rows if v[0] == 3.5] == [(3.5, 4)] * 10
    # the grand total and the item without a category share the NULL
    # partition; that item's eight rows of 0.00 all rank second
    assert rows[:2] == [((None,) * 8, (0.0, 2)), ((None,) * 8, (7.0, 1))]
    assert [v for k, v in rows if k[0] is None] \
        == [(0.0, 2), (7.0, 1)] + [(0.0, 2)] * 7
