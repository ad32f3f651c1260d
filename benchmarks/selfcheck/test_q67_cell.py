"""The cell `tpcds-sf10.q67`: its tables held to the specification's
shapes, what its files say, a round of it on the CPU at the
rehearsal's size with the timed path sound and broken, the float32
control, and the readers of its per-layer metrics on spans and a trace
made by hand.  (`test_datagen.py` already holds the plain reference
equal to `collect(engine="cpu")`, for every cell.)"""

import shutil
import tempfile
import types

import numpy as np
import pyarrow.compute as pc
import pytest

from benchmarks.generators import _tpcds, date_dim, item, store, store_sales
from benchmarks.harness import datagen, engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (
    agg_busy_s,
    agg_groups,
    expand_rows,
    join_busy_s,
    sort_busy_s,
    window_busy_s,
    window_rows,
)

CELL = "tpcds-sf10.q67"
MS = 1_000_000


# -- (a) the generators -------------------------------------------------- #

def test_the_tables_have_the_specifications_columns():
    counts = {store_sales: 23, item: 22, date_dim: 28, store: 29}
    rows = {store_sales: 24_000, item: 2_000, date_dim: 4_565, store: 12}
    for gen, n in counts.items():
        table = gen.to_arrow(gen.generate(5, 0, rows[gen]), 5, 0)
        assert table.schema.names == list(gen.COLUMN_BYTES)
        assert table.num_columns == n and table.num_rows == rows[gen]


def test_store_sales_draws_dsdgens_domains_and_nulls():
    rows = 960_000
    cols = store_sales.generate(11, 3, rows)
    table = store_sales.to_arrow(cols, 11, 3)
    never = {"ss_item_sk", "ss_ticket_number"}
    for name in table.schema.names:
        share = table[name].null_count / rows
        assert share == 0 if name in never else 0.04 < share < 0.05, name
    assert str(table.schema.field("ss_quantity").type) == "int32"
    assert str(table.schema.field("ss_item_sk").type) == "int64"
    assert str(table.schema.field("ss_sales_price").type) == "double"
    quantity = cols["ss_quantity"][cols["ss_quantity"] >= 0]
    assert quantity.min() == 1 and quantity.max() == 100
    assert cols["ss_item_sk"].min() == 1
    assert cols["ss_item_sk"].max() == _tpcds.ITEMS
    known = cols["ss_store_sk"] >= 0
    assert set(np.unique(cols["ss_store_sk"][known])) \
        == set(range(1, _tpcds.STORES + 1))
    day = cols["ss_sold_date_sk"][cols["ss_sold_date_sk"] >= 0] \
        - _tpcds.EPOCH_SK
    assert day.min() == _tpcds.SALES_FIRST_DAY
    assert day.max() == _tpcds.SALES_LAST_DAY
    # whole cents, and the price never above the list price
    price = cols["ss_sales_price"]
    paid = ~np.isnan(price)
    assert np.allclose(np.rint(price[paid] * 100), price[paid] * 100)
    both = paid & ~np.isnan(cols["ss_list_price"])
    assert np.all(price[both] <= cols["ss_list_price"][both])
    # a ticket's 8 to 16 lines share date and store
    lines = np.bincount(cols["ss_ticket_number"]
                        - cols["ss_ticket_number"].min())
    assert lines.min() >= 8 and lines.max() <= 16 and lines.sum() == rows
    first = np.r_[0, np.cumsum(lines)[:-1]]
    for name in ("ss_store_sk", "ss_sold_date_sk"):
        # NULL is -1, blanked a line: the others equal the ticket's one
        of_ticket = np.repeat(np.maximum.reduceat(cols[name], first), lines)
        assert np.all((cols[name] == of_ticket) | (cols[name] == -1)), name
    # file i depends on (seed, i) alone
    again = store_sales.generate(11, 3, rows)
    assert all(np.array_equal(cols[k], again[k], equal_nan=True)
               for k in cols)
    other = store_sales.generate(11, 4, rows)
    assert other["ss_ticket_number"].min() > cols["ss_ticket_number"].max()


def test_month_1200_is_january_2000_and_the_year_is_a_fifth():
    days = date_dim.generate(0, 0, _tpcds.DAYS)
    assert len(days["d_date_sk"]) == 73_049
    assert days["d_date_sk"][0] == 2_415_022 and days["d_month_seq"][0] == 0
    table = date_dim.to_arrow(days, 0, 0)
    assert str(table["d_date"][0]) == "1900-01-02"
    assert str(table["d_date"][-1]) == "2100-01-01"
    january = table.filter(pc.equal(table["d_month_seq"], 1200))
    assert january.num_rows == 31
    assert str(january["d_date"][0]) == "2000-01-01"
    assert set(january["d_year"].to_pylist()) == {2000}
    assert set(january["d_qoy"].to_pylist()) == {1}
    year = (days["d_month_seq"] >= 1200) & (days["d_month_seq"] <= 1211)
    assert year.sum() == 366
    assert set(days["d_moy"][year]) == set(range(1, 13))
    assert set(days["d_qoy"][year]) == {1, 2, 3, 4}
    sales_days = _tpcds.SALES_LAST_DAY - _tpcds.SALES_FIRST_DAY + 1
    assert sales_days == 1826 and 0.19 < 366 / sales_days < 0.21
    assert sum(table[c].null_count for c in table.schema.names) == 0


def test_item_nests_class_and_brand_under_the_ten_categories():
    cols = item.generate(9, 0, _tpcds.ITEMS)
    table = item.to_arrow(cols, 9, 0)
    assert len(_tpcds.CATEGORIES) == 10 and len(_tpcds.CLASSES) == 100
    seen = {}
    for cat, cls, brand in zip(table["i_category"].to_pylist(),
                               table["i_class"].to_pylist(),
                               table["i_brand"].to_pylist()):
        if cat is not None and cls is not None and brand is not None:
            seen.setdefault(cat, {}).setdefault(cls, set()).add(brand)
    assert len(seen) == 10
    assert sorted(len(v) for v in seen.values()) == [4] * 5 + [16] * 5
    assert all(len(b) == 7 for v in seen.values() for b in v.values())
    names = table["i_product_name"].drop_null().to_pylist()
    assert len(set(names)) == len(names) and max(map(len, names)) <= 50
    assert len(set(table["i_item_id"].to_pylist())) == 51_000
    for name in ("i_category", "i_class", "i_brand", "i_product_name"):
        assert 0.001 < table[name].null_count / _tpcds.ITEMS < 0.005
    assert table["i_item_sk"].null_count == 0


def test_two_stores_share_an_id():
    table = store.to_arrow(store.generate(9, 0, _tpcds.STORES), 9, 0)
    ids = table["s_store_id"].to_pylist()
    assert len(ids) == 102 and len(set(ids)) == 51
    assert all(len(i) == 16 for i in ids) and ids[0] == "AAAAAAAABAAAAAAA"
    assert ids[1] == ids[2] != ids[0]


def test_a_cut_dimension_keeps_what_the_fact_table_draws():
    """`--rehearse` hands every generator a sixteenth of its rows:
    the days still cover the sales years, the items and stores are the
    lowest keys."""
    cut = spec.load_cell(CELL, rehearse=True)
    tables = {t.name: t for t in cut.tables()}
    days = date_dim.generate(0, 0, tables["date_dim"].rows_per_file)
    first, last = days["d_date_sk"][[0, -1]] - _tpcds.EPOCH_SK
    assert first <= _tpcds.SALES_FIRST_DAY and last >= _tpcds.SALES_LAST_DAY
    year = (days["d_month_seq"] >= 1200) & (days["d_month_seq"] <= 1211)
    assert year.sum() == 366
    few = store.generate(0, 0, tables["store"].rows_per_file)
    assert few["s_store_sk"].tolist() == [1, 2, 3, 4, 5, 6]
    assert len(set(few["s_store_id"].tolist())) == 3


# -- (b) the files and a round ------------------------------------------- #

def test_the_cell_loads_at_the_listed_sizes():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 1
    assert [s.query for s in cell.round] == ["q67"]
    step = cell.round[0]
    sales = step.table("store_sales")
    assert sales.rows_per_file == 960_000 and sales.files in (4, 8, 15, 30)
    assert sales.files == 30 or sales.name in cell.config["reduced"]
    assert step.table("item").rows == 102_000
    assert step.table("date_dim").rows == 73_049
    assert step.table("store").rows == 102
    # the fact columns the query reads: 36 bytes a row
    assert spec.column_bytes(sales, spec.module("queries", "q67").COLUMNS[
        "store_sales"]) == sales.rows * 36
    assert {"TpuBroadcastHashJoinExec", "TpuExpandExec",
            "TpuWindowExec"} <= set(step.plan_has)
    assert "conf" not in cell.config
    assert cell.config["guarantees"] == spec.load_cell(
        "tpch-sf10.scan").config["guarantees"]
    names = {m["name"] for m in cell.per_layer}
    assert {"expand_rows", "agg_groups", "window_rows", "join_busy_s",
            "agg_busy_s", "window_busy_s", "sort_busy_s", "decode_s",
            "scan_wait_s"} <= names
    assert "hbm_roofline_share" not in names
    # the cut never touches a dimension
    assert set(cell.config["reduced"]) <= {"store_sales_h", "store_sales_q",
                                           "store_sales_e"}


def _verdict(runner, done) -> dict:
    runner.check(done)
    return {"correct": not any(c.failure or c.plan_fault
                               for c in done.collects),
            "compared": reduce.compared(done.collects),
            "faults": [(c.failure, c.plan_fault) for c in done.collects]}


@pytest.fixture(scope="module")
def rounds():
    """A sound round, one whose query leaves the rollup out (a plain
    GROUP BY of the eight columns: no Expand in the plan, and another
    answer), one with an answer rounded to float32, a sound one again."""
    import jax

    from benchmarks.queries import q67
    from benchmarks.selfcheck import _f32_control
    from spark_rapids_tpu import session as engine_session

    cell = spec.load_cell(CELL, rehearse=True)
    work = tempfile.mkdtemp(prefix="q67-round-")
    out = {}
    try:
        data = datagen.generate(cell, 2800000401, work)
        runner = engine.Runner(cell, data, jax.devices(), trace=False)
        out["sound"] = _verdict(runner, runner.run_round())

        build = q67.build

        def no_rollup(session, frames):
            whole = engine_session.DataFrame.rollup
            engine_session.DataFrame.rollup = \
                lambda self, *keys: self.group_by(*keys)
            try:
                return build(session, frames)
            finally:
                engine_session.DataFrame.rollup = whole

        q67.build = no_rollup
        try:
            out["no_rollup"] = _verdict(runner, runner.run_round())
        finally:
            q67.build = build

        collect = engine_session.DataFrame.collect
        engine_session.DataFrame.collect = lambda self, *a, **kw: \
            _f32_control.stored(collect(self, *a, **kw))
        try:
            out["answer_altered"] = _verdict(runner, runner.run_round())
        finally:
            engine_session.DataFrame.collect = collect
        out["sound_again"] = _verdict(runner, runner.run_round())
        out["expected_rows"] = data.expected[0].num_rows
        runner.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def test_a_sound_round_reads_correct(rounds):
    assert rounds["expected_rows"] == 100
    for case in ("sound", "sound_again"):
        found = rounds[case]
        assert found["correct"] is True, found
        assert found["compared"]["double_rel_gap"]["value"] \
            <= found["compared"]["double_rel_gap"]["limit"]
        assert found["compared"]["answers_differing"]["value"] == 0
        assert found["compared"]["plans_at_fault"]["value"] == 0


def test_a_round_without_the_rollup_reads_not_correct(rounds):
    found = rounds["no_rollup"]
    assert found["correct"] is False
    assert found["compared"]["plans_at_fault"]["value"] == 1
    assert found["compared"]["answers_differing"]["value"] == 1
    assert "TpuExpandExec" in found["faults"][0][1]


def test_an_answer_in_float32_reads_not_correct(rounds):
    found = rounds["answer_altered"]
    assert found["correct"] is False
    assert found["compared"]["answers_differing"]["value"] == 1
    assert found["compared"]["plans_at_fault"]["value"] == 0


def test_the_float32_control_reads_the_stored_answer_as_failing():
    """At the rehearsal's size.  `computed` has nothing to round
    there: the reference sums whole cents, and a few hundred rows' sums
    stay under 2^24, which float32 holds exactly; at the listed size
    the totals pass it (PERF.md section 6, PR 28)."""
    from benchmarks.selfcheck import _f32_control

    found = _f32_control.readings(spec.load_cell(CELL, rehearse=True),
                                  2800000402)
    why, gap = found["stored"]["q67"]
    assert why is not None
    assert gap is None or gap > 10 * _f32_control.check.REL_TOL
    assert set(found["computed"]) == {"q67"}


# -- (c) the readers ------------------------------------------------------ #

def _operator(desc, rows_out, rows_in, **more):
    return types.SimpleNamespace(
        name="query.operator", ts_ns=0, dur_ns=0, thread_name="history",
        attrs={"op": desc.split(" ", 1)[0], "desc": desc,
               "numOutputRows": rows_out, "rows_in": rows_in, **more})


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace,
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def test_the_row_counts_come_from_the_operators_instants():
    spans = []
    for _ in range(2):  # two rounds, one query each
        spans += [
            _operator("TpuExpandExec [9 projections]", 900, 100),
            _operator("TpuHashAggregateExec[complete] keys=[a]", 700, 900),
            _operator("TpuHashAggregateExec[partial] keys=[a]", 50, 60),
            _operator("TpuWindowExec [rank->rk] over (...)", 700, 700),
            _operator("TpuProjectExec [a]", 700, 700),
        ]
    run = _run(spans)
    assert expand_rows.reduce(run) == 900
    assert agg_groups.reduce(run) == 700
    assert window_rows.reduce(run) == 700
    # a program that stamps no such instant (the parent commit): the
    # metrics are left out, and nothing raises
    assert expand_rows.reduce(_run()) is None
    assert agg_groups.reduce(_run()) is None
    assert window_rows.reduce(_run()) is None


def test_the_busy_seconds_follow_the_programs_names():
    def chip(modules):
        spans = np.array([m[1:] for m in modules],
                         dtype=np.float64).reshape(-1, 2) * MS
        return tr.Chip(0, np.zeros((0, 2)), [], spans,
                       [m[0] for m in modules])

    modules = [
        ("jit_tpu__TpuBroadcastHashJoinExec__join(1)", 0, 10),
        ("jit_tpu__TpuShuffledHashJoinExec__join(2)", 10, 30),
        ("jit_tpu__TpuHashAggregateExec__agg(3)", 30, 130),
        # overlapping intervals count once, and only inside the window
        ("jit_tpu__TpuHashAggregateExec__agg(4)", 120, 140),
        ("jit_tpu__TpuWindowExec__window(5)", 140, 150),
        ("jit_tpu__TpuSortExec__sort(6)", 150, 152),
        ("jit_tpu__TpuHashAggregateExec__agg(3)", 195, 230),
        ("jit__take(7)", 152, 153),
    ]
    trace = tr.Trace([chip(modules)], [("bench.round 0", 0, 100 * MS),
                                        ("bench.round 1", 100 * MS,
                                         200 * MS)])
    run = _run(trace=trace)
    assert join_busy_s.reduce(run) == pytest.approx(0.030 / 2)
    assert agg_busy_s.reduce(run) == pytest.approx(0.115 / 2)
    assert window_busy_s.reduce(run) == pytest.approx(0.010 / 2)
    assert sort_busy_s.reduce(run) == pytest.approx(0.002 / 2)
    # no such program in the trace, or no trace: left out
    bare = tr.Trace([chip(modules[-1:])], trace.annotations)
    assert join_busy_s.reduce(_run(trace=bare)) is None
    assert agg_busy_s.reduce(_run()) is None


def test_every_reader_declares_what_benchmark_json_says():
    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for mod in (expand_rows, agg_groups, window_rows, join_busy_s,
                agg_busy_s, window_busy_s, sort_busy_s):
        entry = declared[mod.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("Operators",
                                                    "round_wall_s")
