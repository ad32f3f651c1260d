"""TPC-DS q38 and q87 as the benchmark's cell `tpcds-sf10-setops.q38-q87`
runs them: `benchmarks/queries/q38.py:build` and `q87.py:build` through
`TpuSession` against the same files' plain reference, and what the two
queries lean on that no other cell does: INTERSECT and EXCEPT, which
are null-safe semi and anti joins on two string keys and a date, over
three DISTINCTs in which a NULL name is a value."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import (
    _tpcds,
    catalog_sales,
    customer,
    date_dim,
    store_sales,
    web_sales,
)
from benchmarks.harness import check, engine, spec
from benchmarks.queries import q38, q87

SEEDS = [38, 3400000087, 5]
GENERATORS = {"store_sales": store_sales, "catalog_sales": catalog_sales,
              "web_sales": web_sales, "customer": customer,
              "date_dim": date_dim}
FULL = {"store_sales": 960_000, "catalog_sales": 480_000,
        "web_sales": 240_000, "customer": _tpcds.CUSTOMERS,
        "date_dim": _tpcds.DAYS}
# the rehearsal's cut: each table's first file at a sixteenth of its rows
REHEARSAL = {name: rows // spec.REHEARSAL_CUT for name, rows in FULL.items()}
QUERIES = {"q38": q38, "q87": q87}


def _tables(seed: int, work, rows: dict) -> tuple:
    """The five tables' files, and what the reference is handed: the
    driver's columns and the side tables'."""
    cols = {name: gen.generate(seed, 0, rows[name])
            for name, gen in GENERATORS.items()}
    paths = {}
    for name, gen in GENERATORS.items():
        paths[name] = str(work / f"{name}.parquet")
        pq.write_table(gen.to_arrow(cols[name], seed, 0), paths[name])
    side = {role: cols[role] for role in q38.COLUMNS if role != q38.DRIVER}
    return paths, [q38.partial(cols[q38.DRIVER], side)]


def _frames(session, paths: dict) -> dict:
    return {role: session.read_parquet(paths[role], columns=columns)
            for role, columns in q38.COLUMNS.items()}


def _collect(query, paths: dict, engine_=None) -> pa.Table:
    from spark_rapids_tpu.session import TpuSession

    session = TpuSession()
    return query.build(session, _frames(session, paths)).collect(
        engine=engine_)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_engine_answers_as_the_reference(tmp_path, seed):
    """Both queries at the rehearsal's cut and the specification's
    domains: a sixteenth of the customers, so a sixteenth of the sales
    find one; the three DISTINCTs are some tens of triples."""
    paths, partials = _tables(seed, tmp_path, REHEARSAL)
    sold, _, _, sides = q38.united(partials)
    assert 30 < len(sold) < 200 and min(sides) > 5
    for query in (q38, q87):
        want = query.combine(partials)
        assert want.schema.names == [q38.ANSWER] and want.num_rows == 1
        why, _ = check.compare(_collect(query, paths), want, query.ORDERED)
        assert why is None, (query.__name__, why)
    assert q87.combine(partials)[q38.ANSWER][0].as_py() <= len(sold)


@pytest.fixture
def crowded(monkeypatch):
    """400 customers who bear one of three first and three last names
    or, 15% of them each, none: the channels share hundreds of
    (last name, first name, date) triples, some tens of them with a NULL
    name."""
    monkeypatch.setattr(_tpcds, "CUSTOMERS", 400)
    monkeypatch.setattr(customer, "NAMES", 3)
    monkeypatch.setattr(customer, "WEIGHTS", np.full(3, 1 / 3))
    monkeypatch.setattr(customer, "NULL_PCT", 3_000)
    return {**{name: rows // 4 for name, rows in FULL.items()},
            "customer": 400, "date_dim": REHEARSAL["date_dim"]}


def _nameless(triples: np.ndarray) -> np.ndarray:
    """Which of the reference's packed triples have a NULL name."""
    return ((triples >> 40) == 0) | (((triples >> 20) & 0xFFFFF) == 0)


def _count(table: pa.Table) -> int:
    return table[q38.ANSWER][0].as_py()


@pytest.mark.parametrize("engine_", [None, "cpu"])
def test_crowded_channels_count_as_the_reference(tmp_path, crowded, engine_):
    """Both engines, where every count is large and NULL names decide
    hundreds of rows of either answer."""
    paths, partials = _tables(SEEDS[0], tmp_path, crowded)
    sold, in_catalog, in_web, sides = q38.united(partials)
    nameless = _nameless(sold)
    assert (nameless & in_catalog & in_web).sum() > 10
    assert (nameless & (in_catalog | in_web)).sum() > 100
    names = pq.read_table(paths["customer"])
    assert names["c_first_name"].null_count > 40
    assert names["c_last_name"].null_count > 40
    for query in (q38, q87):
        want = query.combine(partials)
        assert 100 < _count(want) < len(sold) - 100
        why, _ = check.compare(_collect(query, paths, engine_), want,
                               query.ORDERED)
        assert why is None, (query.__name__, why)


@pytest.mark.parametrize("name", ["q38", "q87"])
def test_joins_made_with_plain_equality_would_differ(tmp_path, crowded,
                                                     name):
    """The same three DISTINCTs met in semi and anti joins whose keys
    compare with `=`: a triple with a NULL name then matches nothing,
    q38 loses those and q87 keeps them, and the comparison that decides
    `correct` says so.  (So the tests above would catch a set operation
    that lost its `<=>`.)"""
    from spark_rapids_tpu.session import TpuSession, col, count_star

    paths, partials = _tables(SEEDS[0], tmp_path, crowded)
    session = TpuSession()
    out = None
    for channel in q38.channels(session, _frames(session, paths)):
        keys = [col(n) for n in q38.TRIPLE]
        out = channel if out is None else out.join(
            channel, how="left_semi" if name == "q38" else "left_anti",
            left_on=keys, right_on=keys)
    got = out.agg((count_star(), q38.ANSWER)).collect()
    want = QUERIES[name].combine(partials)
    why, _ = check.compare(got, want, True)
    assert why is not None and "exact column" in why
    # what `=` gives, from the reference's own partials
    sold, in_catalog, in_web, _ = q38.united(partials)
    nameless = _nameless(sold)
    plain = (in_catalog & in_web & ~nameless) if name == "q38" \
        else ((~in_catalog | nameless) & (~in_web | nameless))
    assert _count(got) == int(plain.sum()) != _count(want)


def test_the_reference_counts_triples_not_sales():
    """The plain reference against Python sets, file by file as
    `datagen` hands it the driver table; -1 is NULL."""
    dates = {"d_date_sk": np.arange(10, 20),
             "d_date": np.arange(100, 110, dtype=np.int32),
             "d_month_seq": np.r_[[1199] * 2, [1200] * 6, [1212] * 2]}
    # customers 1..6: (first, last); 1 and 2 share both names, 3 and 4
    # a last name and a NULL first name, 6 has no name at all
    names = {"c_customer_sk": np.arange(1, 7),
             "c_first_name": np.array([7, 7, -1, -1, 2, -1]),
             "c_last_name": np.array([9, 9, 4, 4, 9, -1])}
    # (date, customer); dates 12..17 are in the year
    store = [[(12, 1), (12, 2), (13, 3), (11, 1), (14, 6), (-1, 5)],
             [(12, 1), (15, 5), (16, -1), (16, 4), (17, 6), (14, 8)]]
    catalog = [(12, 2), (13, 4), (14, 6), (15, 1), (19, 5)]
    web = [(12, 1), (13, 3), (17, 6), (16, 2)]

    def table(rows, fact):
        return {key: np.array([row[at] for row in rows])
                for at, key in enumerate(q38.CHANNELS[fact])}

    side = {"date_dim": dates, "customer": names,
            "catalog_sales": table(catalog, "catalog_sales"),
            "web_sales": table(web, "web_sales")}
    partials = [q38.partial(table(rows, "store_sales"), side)
                for rows in store]

    def triples(rows):
        return {(int(names["c_last_name"][c - 1]),
                 int(names["c_first_name"][c - 1]), d)
                for d, c in rows if 12 <= d <= 17 and 1 <= c <= 6}

    s, c, w = triples(store[0] + store[1]), triples(catalog), triples(web)
    assert len(s) == 6 and (4, -1, 13) in s & c & w
    sold, _, _, sides = q38.united(partials)
    assert len(sold) == len(s) and sides == (len(c), len(w))
    assert q38.combine(partials).to_pylist() == [{"count": len(s & c & w)}]
    assert q87.combine(partials).to_pylist() == [{"count": len(s - c - w)}]
    assert len(s & c & w) == 2 and len(s - c - w) == 2


def test_the_plan_that_runs(tmp_path):
    """Every operator on the device, the set operations as null-safe
    shuffled-hash joins over `[complete]` DISTINCTs (one task a scan,
    as at the listed cut), and what the traffic's `plan_has` names."""
    import json

    from spark_rapids_tpu.session import TpuSession

    paths, _ = _tables(SEEDS[0], tmp_path, REHEARSAL)
    with open(spec.PACKAGE / "traffic" / "q38-q87.json") as f:
        steps = {s["query"]: s for s in json.load(f)["round"]}
    for name, join_type in (("q38", "left_semi"), ("q87", "left_anti")):
        session = TpuSession()
        QUERIES[name].build(session, _frames(session, paths)).collect()
        event = session.history.events[-1]
        assert engine.off_device(event.explain) == []
        assert "[degraded to CPU engine" not in event.explain
        assert engine.lacking(event.root, steps[name]["plan_has"]) == []
        held, todo = [], [event.root]
        while todo:
            node = todo.pop()
            held.append(node.desc)
            todo += node.children
        setops = [d for d in held if f" {join_type} " in d]
        assert len(setops) == 2 and all(
            d.startswith("TpuShuffledHashJoinExec") and
            "c_last_name<=>c_last_name, c_first_name<=>c_first_name, "
            "d_date<=>d_date" in d for d in setops)
        distincts = [d for d in held
                     if d.startswith("TpuHashAggregateExec[complete] keys=["
                                     "c_last_name, c_first_name, d_date]")]
        assert len(distincts) == 3


@pytest.mark.parametrize("table,rows", [("web_sales", 24_000),
                                        ("customer", 5_000)])
def test_a_column_is_drawn_alike_whatever_else_is_named(table, rows):
    """A random stream a column group: the plain reference's workers
    draw the columns the queries read and no other, and get what the
    file holds."""
    gen = GENERATORS[table]
    whole = gen.generate(7, 3, rows)
    assert list(whole) == list(gen.COLUMN_BYTES)
    read = q38.COLUMNS[table]
    some = gen.generate(7, 3, rows, read)
    assert list(some) == read
    for name in read:
        assert np.array_equal(some[name], whole[name]), name
    other = gen.generate(8, 3, rows, read)
    assert any(not np.array_equal(other[n], some[n]) for n in read)
    assert gen.to_arrow(whole, 7, 3).schema.names == list(gen.COLUMN_BYTES)
