"""The cell `tpcds-sf10-channels.q97`: `catalog_sales` held to the
specification's shape, what the cell's files say, a round of its
listed plan on the CPU at a sixteenth of the rows with the timed path
sound and broken twice
(NULL keys taken for values; the build side's unmatched rows dropped),
and the readers of its three per-layer metrics on spans and a trace
made by hand.  (`test_datagen.py` already holds the plain reference
equal to `collect(engine="cpu")`, for every cell.)"""

import dataclasses
import shutil
import tempfile
import types

import numpy as np
import pytest

from benchmarks.generators import _tpcds, catalog_sales, store_sales
from benchmarks.harness import datagen, engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (
    agg_merge_rows,
    outer_join_busy_s,
    outer_join_rows,
)

CELL = "tpcds-sf10-channels.q97"
MS = 1_000_000

#: the specification's 34 columns, in its order
COLUMNS = """cs_sold_date_sk cs_sold_time_sk cs_ship_date_sk
cs_bill_customer_sk cs_bill_cdemo_sk cs_bill_hdemo_sk cs_bill_addr_sk
cs_ship_customer_sk cs_ship_cdemo_sk cs_ship_hdemo_sk cs_ship_addr_sk
cs_call_center_sk cs_catalog_page_sk cs_ship_mode_sk cs_warehouse_sk
cs_item_sk cs_promo_sk cs_order_number cs_quantity cs_wholesale_cost
cs_list_price cs_sales_price cs_ext_discount_amt cs_ext_sales_price
cs_ext_wholesale_cost cs_ext_list_price cs_ext_tax cs_coupon_amt
cs_ext_ship_cost cs_net_paid cs_net_paid_inc_tax cs_net_paid_inc_ship
cs_net_paid_inc_ship_tax cs_net_profit""".split()


# -- (a) the generator ---------------------------------------------------- #

def test_catalog_sales_has_the_specifications_columns_and_types():
    rows = 480_000
    cols = catalog_sales.generate(11, 3, rows)
    table = catalog_sales.to_arrow(cols, 11, 3)
    assert table.schema.names == COLUMNS == list(catalog_sales.COLUMN_BYTES)
    assert table.num_columns == 34 and table.num_rows == rows
    for at, name in enumerate(COLUMNS):
        kind = str(table.schema.field(name).type)
        assert kind == ("int64" if at < 18 else "int32" if at == 18
                        else "double"), name
        share = table[name].null_count / rows
        never = name in ("cs_item_sk", "cs_order_number")
        assert share == 0 if never else 0.004 < share < 0.006, name
    # the key domains of dsdgen -scale 10
    for name, top in (("cs_bill_customer_sk", _tpcds.CUSTOMERS),
                      ("cs_ship_customer_sk", _tpcds.CUSTOMERS),
                      ("cs_item_sk", _tpcds.ITEMS),
                      ("cs_bill_addr_sk", _tpcds.ADDRESSES),
                      ("cs_promo_sk", _tpcds.PROMOTIONS),
                      ("cs_call_center_sk", 24), ("cs_ship_mode_sk", 20),
                      ("cs_warehouse_sk", 10), ("cs_catalog_page_sk", 12_000),
                      ("cs_quantity", 100)):
        known = cols[name][cols[name] >= 0]
        # a file's 53,333 orders reach the ends of the small domains
        # and come near those of the large ones
        assert 1 <= known.min() and known.max() <= top, name
        assert known.min() <= 1 + top // 1_000, name
        assert known.max() >= top - top // 1_000, name
    day = cols["cs_sold_date_sk"][cols["cs_sold_date_sk"] >= 0] \
        - _tpcds.EPOCH_SK
    assert day.min() == _tpcds.SALES_FIRST_DAY
    assert day.max() == _tpcds.SALES_LAST_DAY
    # whole cents; a sale never above its list price; the net with its
    # shipping above the net
    price, listed = cols["cs_sales_price"], cols["cs_list_price"]
    paid = ~np.isnan(price)
    assert np.allclose(np.rint(price[paid] * 100), price[paid] * 100)
    both = paid & ~np.isnan(listed)
    assert np.all(price[both] <= listed[both])
    net, shipped = cols["cs_net_paid"], cols["cs_net_paid_inc_ship"]
    both = ~np.isnan(net) & ~np.isnan(shipped)
    assert np.all(shipped[both] >= net[both])


def test_an_orders_lines_share_what_the_order_fixes():
    rows = 480_000
    cols = catalog_sales.generate(11, 3, rows)
    number = cols["cs_order_number"]
    lines = np.bincount(number - number.min())
    assert lines.min() >= 4 and lines.max() <= 14 and lines.sum() == rows
    assert len(lines) == rows // 9
    first = np.r_[0, np.cumsum(lines)[:-1]]
    for name in ("cs_sold_date_sk", "cs_bill_customer_sk",
                 "cs_ship_customer_sk", "cs_ship_addr_sk",
                 "cs_call_center_sk"):
        # NULL is -1, blanked a line: the others equal the order's one
        of_order = np.repeat(np.maximum.reduceat(cols[name], first), lines)
        assert np.all((cols[name] == of_order) | (cols[name] == -1)), name
    # (cs_item_sk, cs_order_number) is the primary key, but for the
    # orders that drew an item twice (dsdgen permutes; `assumed.orders`)
    pairs = np.unique(number * (_tpcds.ITEMS + 1) + cols["cs_item_sk"])
    assert len(pairs) > 0.9995 * rows
    # six orders in seven ship to the customer who is billed
    known = (cols["cs_bill_customer_sk"] >= 0) \
        & (cols["cs_ship_customer_sk"] >= 0)
    same = cols["cs_bill_customer_sk"][known] \
        == cols["cs_ship_customer_sk"][known]
    assert 0.84 < same.mean() < 0.88
    ship = cols["cs_ship_date_sk"] - cols["cs_sold_date_sk"]
    dated = (cols["cs_ship_date_sk"] >= 0) & (cols["cs_sold_date_sk"] >= 0)
    assert ship[dated].min() == 2 and ship[dated].max() == 90


def test_a_file_depends_on_its_seed_and_index_and_not_on_the_columns_named():
    rows = 60_000
    whole = catalog_sales.generate(11, 3, rows)
    again = catalog_sales.generate(11, 3, rows)
    assert all(np.array_equal(whole[k], again[k], equal_nan=True)
               for k in whole)
    wanted = spec.module("queries", "q97").COLUMNS["catalog_sales"]
    few = catalog_sales.generate(11, 3, rows, wanted)
    assert list(few) == wanted
    assert all(np.array_equal(few[k], whole[k]) for k in wanted)
    money = catalog_sales.generate(11, 3, rows, ["cs_net_profit"])
    assert np.array_equal(money["cs_net_profit"], whole["cs_net_profit"],
                          equal_nan=True)
    other = catalog_sales.generate(11, 4, rows)
    assert other["cs_order_number"].min() > whole["cs_order_number"].max()
    assert not np.array_equal(other["cs_item_sk"], whole["cs_item_sk"])
    assert not np.array_equal(
        catalog_sales.generate(12, 3, rows, ["cs_item_sk"])["cs_item_sk"],
        whole["cs_item_sk"])


def test_one_seed_gives_the_twin_configurations_store_sales():
    """`tpcds-sf10-channels` reads `tpcds-sf10`'s `store_sales`: the
    same generator under the same name, so the same files."""
    ours = spec.load_cell(CELL).config["tables"]
    twins = spec.load_cell("tpcds-sf10.q67").config["tables"]
    for name in ("store_sales", "store_sales_h", "store_sales_q",
                 "store_sales_e", "date_dim"):
        assert ours[name] == twins[name], name
    assert _tpcds.STORE_SALES_ID != catalog_sales.CATALOG_SALES_ID
    assert store_sales.generate(7, 2, 24_000)["ss_customer_sk"].max() \
        <= _tpcds.CUSTOMERS


# -- (b) the files and a round ------------------------------------------- #

def test_the_cell_loads_at_the_listed_sizes():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 1
    assert [s.query for s in cell.round] == ["q97"]
    step = cell.round[0]
    sales, billed = step.table("store_sales"), step.table("catalog_sales")
    assert sales.rows_per_file == 960_000 and billed.rows_per_file == 480_000
    # the cut is of the file count only, both fact tables by one fraction
    assert sales.files == billed.files and sales.files in (4, 8, 15, 30)
    for table in (sales, billed):
        assert table.files == 30 or table.name in cell.config["reduced"]
    assert set(cell.config["reduced"]) <= {
        f"{t}_{cut}" for t in ("store_sales", "catalog_sales")
        for cut in "hqe"}
    assert step.table("date_dim").rows == 73_049
    wanted = spec.module("queries", "q97").COLUMNS
    # three int64 columns of each fact table: 24 bytes a row
    assert spec.column_bytes(sales, wanted["store_sales"]) == sales.rows * 24
    assert spec.column_bytes(billed, wanted["catalog_sales"]) \
        == billed.rows * 24
    assert {"TpuBroadcastHashJoinExec", "TpuShuffledHashJoinExec",
            "TpuShuffleExchangeExec", "TpuHashAggregateExec[partial]",
            "TpuHashAggregateExec[final]"} == set(step.plan_has)
    assert "conf" not in cell.config
    assert cell.config["guarantees"] == spec.load_cell(
        "tpcds-sf10.q67").config["guarantees"]
    names = {m["name"] for m in cell.per_layer}
    assert {"outer_join_busy_s", "outer_join_rows", "agg_merge_rows",
            "join_busy_s", "agg_busy_s", "agg_groups", "decode_s",
            "scan_wait_s", "wire_bytes", "idle_scan_s", "idle_upload_s",
            "encode_s", "put_s"} <= names
    assert not {"hbm_roofline_share", "expand_rows", "window_rows",
                "collective_s"} & names


def _file_bytes(tmp_path, role: str, gen, rows: int) -> int:
    import os

    import pyarrow.parquet as pq

    path = str(tmp_path / f"{role}-{rows}.parquet")
    pq.write_table(gen.to_arrow(gen.generate(7, 1, rows), 7, 1), path,
                   row_group_size=rows)
    return os.path.getsize(path)


def test_the_listed_files_are_two_scan_tasks_a_side(tmp_path):
    """The listed plan (partial aggregates, hash exchanges, final
    aggregates, a partition-wise join) rests on each fact scan being
    MORE than one task: the listed files of a table pass the scan's
    byte target for a task, and stay under twice it.  (Up to 10
    `store_sales` files are one task, and the plan then has
    `[complete]` aggregates and no exchange: PERF.md section 6, PR
    34.)"""
    from spark_rapids_tpu.io.scan import FILES_PER_TASK_BYTES

    step = spec.load_cell(CELL).round[0]
    for role, gen in (("store_sales", store_sales),
                      ("catalog_sales", catalog_sales)):
        table = step.table(role)
        size = _file_bytes(tmp_path, role, gen, table.rows_per_file)
        # a file's size moves by a thousandth with the seed
        assert FILES_PER_TASK_BYTES.default * 1.01 < table.files * size \
            < 2 * FILES_PER_TASK_BYTES.default * 0.99, role


def _verdict(runner, done) -> dict:
    runner.check(done)
    return {"correct": not any(c.failure or c.plan_fault
                               for c in done.collects),
            "compared": reduce.compared(done.collects),
            "faults": [(c.failure, c.plan_fault) for c in done.collects]}


@pytest.fixture(scope="module")
def rounds():
    """A sound round; one whose two DISTINCTs put 0 where a customer is
    NULL, so that a NULL key is a value: it matches and it counts; one
    whose join drops the build rows no stream row matched; a sound one
    again."""
    import jax

    from benchmarks.queries import q97
    from spark_rapids_tpu import session as engine_session
    from spark_rapids_tpu.execs import join as engine_join
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.exprs.predicates import Coalesce

    from spark_rapids_tpu.io.scan import FILES_PER_TASK_BYTES

    # every listed file at a sixteenth of its rows, and the scan's byte
    # target for a task cut alike, so that the files split into the
    # tasks they split into at size and the round runs the LISTED plan
    # (`--rehearse` keeps one file a table, which is one task and the
    # `[complete]` plan, and reads `plans_at_fault`)
    listed = spec.load_cell(CELL)
    cell = dataclasses.replace(listed, round=tuple(
        dataclasses.replace(step, tables=tuple(
            (role, t if t.files == 1 else dataclasses.replace(
                t, rows_per_file=t.rows_per_file // spec.REHEARSAL_CUT))
            for role, t in step.tables)) for step in listed.round))
    work = tempfile.mkdtemp(prefix="q97-round-")
    out = {}
    try:
        data = datagen.generate(cell, 3400000401, work)
        runner = engine.Runner(cell, data, jax.devices(), trace=False)
        runner.session.conf.set(
            FILES_PER_TASK_BYTES.key,
            FILES_PER_TASK_BYTES.default // spec.REHEARSAL_CUT)
        out["sound"] = _verdict(runner, runner.run_round())

        group_by = engine_session.DataFrame.group_by

        def nulls_are_values(self, *keys):
            filled = [Coalesce(k, lit(0)).alias(k.name) for k in keys]
            return group_by(self.select(*filled), *keys)

        engine_session.DataFrame.group_by = nulls_are_values
        try:
            out["nulls_matched"] = _verdict(runner, runner.run_round())
        finally:
            engine_session.DataFrame.group_by = group_by

        emit = engine_join._HashJoinBase._emit_unmatched_build
        engine_join._HashJoinBase._emit_unmatched_build = \
            lambda self, build, matched_b: iter(())
        try:
            out["unmatched_dropped"] = _verdict(runner, runner.run_round())
        finally:
            engine_join._HashJoinBase._emit_unmatched_build = emit
        out["sound_again"] = _verdict(runner, runner.run_round())
        out["expected"] = data.expected[0].to_pylist()[0]
        out["answer"] = q97.ANSWER
        runner.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def test_a_sound_round_reads_correct(rounds):
    want = rounds["expected"]
    assert list(want) == rounds["answer"]
    # a sixteenth of fifteen files a side, a fifth of it in the year
    assert want["store_only"] > 150_000 and want["catalog_only"] > 75_000
    for case in ("sound", "sound_again"):
        found = rounds[case]
        assert found["correct"] is True, found
        assert found["compared"]["answers_differing"]["value"] == 0
        assert found["compared"]["plans_at_fault"]["value"] == 0


def test_a_round_that_matches_null_keys_reads_not_correct(rounds):
    found = rounds["nulls_matched"]
    assert found["correct"] is False
    assert found["compared"]["answers_differing"]["value"] == 1
    assert "store_only" in found["faults"][0][0]


def test_a_round_that_drops_the_unmatched_build_rows_reads_not_correct(
        rounds):
    found = rounds["unmatched_dropped"]
    assert found["correct"] is False
    assert found["compared"]["answers_differing"]["value"] == 1
    assert found["compared"]["plans_at_fault"]["value"] == 0
    assert "catalog_only" in found["faults"][0][0]


# -- (c) the readers ------------------------------------------------------ #

def _span(name, **attrs):
    return types.SimpleNamespace(name=name, ts_ns=0, dur_ns=0,
                                 thread_name="t", attrs=attrs)


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace,
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def test_the_joins_rows_come_from_its_operators_instant():
    spans = []
    for _ in range(2):  # two rounds
        spans += [
            _span("query.operator", op="TpuShuffledHashJoinExec",
                  desc="TpuShuffledHashJoinExec full_outer [a=b, c=d]",
                  numOutputRows=2_300, streamRows=1_500,
                  unmatchedBuildRows=790),
            _span("query.operator", op="TpuBroadcastHashJoinExec",
                  desc="TpuBroadcastHashJoinExec inner [a=b]",
                  numOutputRows=1_500),
        ]
    assert outer_join_rows.reduce(_run(spans)) == 2_300
    assert outer_join_rows.reduce(_run()) is None


def test_the_merged_rows_are_the_merge_spans_capacities():
    spans = [_span("agg.merge", capacity=2_048, rows=None, pending=2),
             _span("agg.merge", capacity=4_096, rows=3_000, pending=3),
             _span("agg.update", capacity=1_024),
             _span("agg.merge", capacity=2_048, rows=None, pending=2)]
    assert agg_merge_rows.reduce(_run(spans)) == 4_096
    # the aggregates ran and merged nothing: 0; a program without
    # either span: left out
    assert agg_merge_rows.reduce(_run(spans[2:3])) == 0
    assert agg_merge_rows.reduce(_run()) is None


def test_the_outer_joins_seconds_follow_its_programs_names():
    modules = [
        ("jit_tpu__TpuBroadcastHashJoinExec__join(1)", 0, 10),
        ("jit_tpu__TpuShuffledHashJoinExec__join(2)", 10, 30),
        ("jit_tpu__TpuShuffledHashJoinExec__join(3)", 25, 40),
        ("jit_tpu__TpuShuffledHashJoinExec__join(2)", 110, 130),
        ("jit_tpu__TpuHashAggregateExec__agg(4)", 130, 150),
    ]
    spans = np.array([m[1:] for m in modules], np.float64) * MS
    chip = tr.Chip(0, np.zeros((0, 2)), [], spans, [m[0] for m in modules])
    trace = tr.Trace([chip], [("bench.round 0", 0, 100 * MS),
                              ("bench.round 1", 100 * MS, 200 * MS)])
    assert outer_join_busy_s.reduce(_run(trace=trace)) \
        == pytest.approx(0.050 / 2)
    bare = tr.Trace([tr.Chip(0, np.zeros((0, 2)), [], spans[:1],
                             [modules[0][0]])], trace.annotations)
    assert outer_join_busy_s.reduce(_run(trace=bare)) is None
    assert outer_join_busy_s.reduce(_run()) is None


def test_the_new_readers_declare_what_benchmark_json_says():
    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for mod in (outer_join_busy_s, outer_join_rows, agg_merge_rows):
        entry = declared[mod.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"]) \
            == (mod.UNIT, mod.BETTER, mod.SOURCE)
        assert (entry["layer"], entry["moves"]) == (mod.LAYER, mod.MOVES) \
            == ("Operators", "round_wall_s")
    for name in ("join_busy_s", "agg_busy_s", "agg_groups", "decode_s"):
        assert declared[name]["workloads"][-1] == CELL
