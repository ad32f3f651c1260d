"""The cell `tpcds-sf10-returns.q16`: `catalog_returns`,
`customer_address` and `call_center` held to the specification's
shapes and to what the configuration says of them (a return is a real
line of its own file; orders never cross files), the cell's files at
their listed sizes, the float32 control against the comparison that
decides `correct`, and the readers of its four per-layer metrics on
spans and a trace made by hand.  (`test_datagen.py` already holds the
plain reference equal to `collect(engine="cpu")` at the rehearsal size,
for every cell; `tests/test_tpcds_q16.py` holds the program to it and
three broken programs apart from it.)"""

import types

import numpy as np
import pytest

from benchmarks.generators import (
    _tpcds,
    call_center,
    catalog_returns,
    catalog_sales,
    customer_address,
)
from benchmarks.harness import check, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (
    semi_build_rows,
    semi_cond_pairs,
    semi_cond_roofline,
    semi_join_busy_s,
)
from benchmarks.queries import q16

CELL = "tpcds-sf10-returns.q16"
MS = 1_000_000
READERS = (semi_join_busy_s, semi_build_rows, semi_cond_pairs,
           semi_cond_roofline)

#: the specification's columns, in its order
CATALOG_RETURNS = """cr_returned_date_sk cr_returned_time_sk cr_item_sk
cr_refunded_customer_sk cr_refunded_cdemo_sk cr_refunded_hdemo_sk
cr_refunded_addr_sk cr_returning_customer_sk cr_returning_cdemo_sk
cr_returning_hdemo_sk cr_returning_addr_sk cr_call_center_sk
cr_catalog_page_sk cr_ship_mode_sk cr_warehouse_sk cr_reason_sk
cr_order_number cr_return_quantity cr_return_amount cr_return_tax
cr_return_amt_inc_tax cr_fee cr_return_ship_cost cr_refunded_cash
cr_reversed_charge cr_store_credit cr_net_loss""".split()
CUSTOMER_ADDRESS = """ca_address_sk ca_address_id ca_street_number
ca_street_name ca_street_type ca_suite_number ca_city ca_county ca_state
ca_zip ca_country ca_gmt_offset ca_location_type""".split()
CALL_CENTER = """cc_call_center_sk cc_call_center_id cc_rec_start_date
cc_rec_end_date cc_closed_date_sk cc_open_date_sk cc_name cc_class
cc_employees cc_sq_ft cc_hours cc_manager cc_mkt_id cc_mkt_class
cc_mkt_desc cc_market_manager cc_division cc_division_name cc_company
cc_company_name cc_street_number cc_street_name cc_street_type
cc_suite_number cc_city cc_county cc_state cc_zip cc_country
cc_gmt_offset cc_tax_percentage""".split()


# -- (a) the generators --------------------------------------------------- #

def test_catalog_returns_has_the_specifications_columns_and_types():
    rows = 48_000
    cols = catalog_returns.generate(11, 3, rows)
    table = catalog_returns.to_arrow(cols, 11, 3)
    assert table.schema.names == CATALOG_RETURNS \
        == list(catalog_returns.COLUMN_BYTES)
    assert table.num_columns == 27 and table.num_rows == rows
    for at, name in enumerate(CATALOG_RETURNS):
        kind = str(table.schema.field(name).type)
        assert kind == ("int64" if at < 17 else "int32" if at == 17
                        else "double"), name
        share = table[name].null_count / rows
        never = name in ("cr_item_sk", "cr_order_number")
        # 1% of the rows are picked, half of a picked row's columns
        assert share == 0 if never else 0.003 < share < 0.007, name
    amount = cols["cr_return_amount"]
    paid = ~np.isnan(amount)
    assert np.allclose(np.rint(amount[paid] * 100), amount[paid] * 100)
    parts = (cols["cr_refunded_cash"] + cols["cr_reversed_charge"]
             + cols["cr_store_credit"])
    whole = paid & ~np.isnan(parts)
    assert np.allclose(parts[whole], amount[whole])
    reason = cols["cr_reason_sk"][cols["cr_reason_sk"] >= 0]
    assert reason.min() == 1 and reason.max() == catalog_returns.REASONS


def test_a_return_is_a_line_of_its_own_file():
    """Every return's (order, item) is a line of `catalog_sales` file
    `i` under the same seed, no line is returned twice, the order's
    call center and customers come with it, and the return is dated
    after the line shipped."""
    rows = 48_000
    lines = rows * catalog_returns.LINES_PER_RETURN
    sold = catalog_sales.generate(11, 3, lines)
    back = catalog_returns.generate(11, 3, rows)
    key = sold["cs_order_number"] * (_tpcds.ITEMS + 1) + sold["cs_item_sk"]
    ret = back["cr_order_number"] * (_tpcds.ITEMS + 1) + back["cr_item_sk"]
    assert np.isin(ret, key).all()
    # no line twice: a key twice only where an order sold its item twice
    keys, sold_times = np.unique(key, return_counts=True)
    rets, returned_times = np.unique(ret, return_counts=True)
    assert np.all(returned_times
                  <= sold_times[np.searchsorted(keys, rets)])
    assert len(rets) > 0.9995 * rows
    # the first 500 returns' lines, by their primary key
    line = {k: at for at, k in enumerate(key.tolist())}
    at = np.array([line[r] for r in ret[:500].tolist()])
    for mine, theirs in (("cr_call_center_sk", "cs_call_center_sk"),
                         ("cr_refunded_customer_sk", "cs_bill_customer_sk"),
                         ("cr_returning_addr_sk", "cs_ship_addr_sk"),
                         ("cr_warehouse_sk", "cs_warehouse_sk")):
        a, b = back[mine][:500], sold[theirs][at]
        known = (a >= 0) & (b >= 0)
        assert np.array_equal(a[known], b[known]), mine
    late = back["cr_returned_date_sk"][:500] - sold["cs_ship_date_sk"][at]
    known = (back["cr_returned_date_sk"][:500] >= 0) \
        & (sold["cs_ship_date_sk"][at] >= 0)
    assert late[known].min() >= 1 and late[known].max() <= 90
    other = catalog_returns.generate(11, 4, rows, ["cr_order_number"])
    assert other["cr_order_number"].min() > back["cr_order_number"].max()


def test_orders_never_cross_files():
    """What the plain reference's per-file EXISTS rests on: file `i`'s
    order numbers are `i * orders + 1 ..`, disjoint from every other
    file's."""
    rows = 480_000
    orders = rows // catalog_sales.ORDER_LINES
    for i in (0, 1, 29):
        number = catalog_sales.generate(5, i, rows,
                                        ["cs_order_number"])["cs_order_number"]
        assert number.min() == i * orders + 1
        assert number.max() == (i + 1) * orders


def test_customer_address_and_call_center_have_their_shapes():
    rows = _tpcds.ADDRESSES
    cols = customer_address.generate(11, 0, rows)
    table = customer_address.to_arrow(cols, 11, 0)
    assert table.schema.names == CUSTOMER_ADDRESS \
        == list(customer_address.COLUMN_BYTES)
    assert table.num_rows == rows == 250_000
    for name in CUSTOMER_ADDRESS:
        share = table[name].null_count / rows
        never = name in ("ca_address_sk", "ca_address_id")
        assert share == 0 if never else 0.004 < share < 0.006, name
    assert sum(customer_address.COUNTIES.values()) == 3_143
    georgia = (table["ca_state"].to_numpy(zero_copy_only=False) == "GA")
    assert 0.049 < georgia.mean() < 0.052  # 159 of 3,143, NULLs aside
    said = spec.load_cell(CELL).config["shapes"]["customer_address"]
    assert "5.06%" in said and "3,143" in said

    centers = call_center.generate(11, 0, 24)
    table = call_center.to_arrow(centers, 11, 0)
    assert table.schema.names == CALL_CENTER \
        == list(call_center.COLUMN_BYTES)
    assert table.num_rows == 24 == catalog_sales.CALL_CENTERS
    county = table["cc_county"].to_pylist()
    # a quarter each, where a NULL blanked none (0.5% of the rows)
    assert county.count("Williamson County") == 6 or None in county
    assert len(set(table["cc_call_center_id"].to_pylist())) == 12
    assert "6 of 24" in spec.load_cell(CELL).config["shapes"]["call_center"]


@pytest.mark.parametrize("table,rows", [("catalog_returns", 48_000),
                                        ("customer_address", 25_000),
                                        ("call_center", 24)])
def test_a_column_is_drawn_alike_whatever_else_is_named(table, rows):
    gen = spec.module("generators", table)
    whole = gen.generate(7, 3, rows)
    read = q16.COLUMNS[table]
    some = gen.generate(7, 3, rows, read)
    assert list(some) == read
    for name in read:
        assert np.array_equal(some[name], whole[name]), name
    assert gen.to_arrow(whole, 7, 3).schema.names == list(gen.COLUMN_BYTES)


# -- (b) the cell and its limit ------------------------------------------ #

def test_the_cell_loads_at_the_listed_sizes():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 1
    (step,) = cell.round
    assert step.query == "q16"
    sales, returns = step.table("catalog_sales"), step.table(
        "catalog_returns")
    # orders and their returns stay file-aligned under any cut
    assert sales.files == returns.files
    assert sales.rows_per_file == 480_000
    assert returns.rows_per_file * catalog_returns.LINES_PER_RETURN \
        == sales.rows_per_file
    # the size rule's cut, the same half of both fact tables
    assert sales.files == 15
    assert {sales.name, returns.name} == set(cell.config["reduced"])
    assert step.table("customer_address").rows == 250_000
    assert step.table("call_center").rows == 24
    assert step.table("date_dim").rows == 73_049
    assert "conf" not in cell.config
    twin = spec.load_cell("tpcds-sf10-channels.q97").config
    for name in ("catalog_sales", sales.name, "date_dim"):
        # one seed gives the configurations the same files
        assert cell.config["tables"][name] == twin["tables"][name], name
    assert cell.config["guarantees"][0] == twin["guarantees"][0]
    names = {m["name"] for m in cell.per_layer}
    assert {r.NAME for r in READERS} | {
        "join_busy_s", "agg_busy_s", "agg_groups", "decode_s",
        "scan_wait_s", "wire_bytes", "idle_scan_s", "idle_upload_s",
        "encode_s", "put_s"} <= names
    assert not {"hbm_roofline_share", "setop_join_rows",
                "outer_join_rows", "collective_s"} & names


def test_the_float32_control_reads_not_correct():
    """The plain reference in float32 put in the program's place, at
    the listed size, two seeds: each control reads above the limit."""
    from benchmarks.selfcheck import _f32_control

    cell = spec.load_cell(CELL)
    for seed in (5, 2147483901):
        for control, found in _f32_control.readings(cell, seed).items():
            why, gap = found["q16"]
            assert why is not None, (control, seed)
            assert gap is None or gap > 10 * check.REL_TOL, (control, gap)


# -- (c) the readers ------------------------------------------------------ #

def _span(name, **attrs):
    return types.SimpleNamespace(name=name, ts_ns=0, dur_ns=0,
                                 thread_name="t", attrs=attrs)


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace, peaks={"hbm_bytes_per_s": 819e9},
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def test_the_counts_come_from_the_semi_and_anti_joins_instants():
    cond = " cond=Not(EqualTo(cs_warehouse_sk, cs2_warehouse_sk))"
    spans = []
    for _ in range(2):  # two rounds
        spans += [
            _span("query.operator", op="TpuAdaptiveJoinExec",
                  desc="TpuAdaptiveJoinExec [left_semi] strategy=shuffled"
                  + cond, buildRows=14_400_000, condPairs=50_000,
                  streamRows=6_000),
            _span("query.operator", op="TpuShuffledHashJoinExec",
                  desc="TpuShuffledHashJoinExec left_anti "
                  "[cs_order_number=cr_order_number] partition_wise",
                  buildRows=1_440_000, streamRows=6_000),
            _span("query.operator", op="TpuBroadcastHashJoinExec",
                  desc="TpuBroadcastHashJoinExec inner [a=b]",
                  buildRows=61, streamRows=480_000),
            _span("query.operator", op="TpuAdaptiveJoinExec",
                  desc="TpuAdaptiveJoinExec [inner] strategy=broadcast",
                  buildRows=12_000),
        ]
    assert semi_build_rows.reduce(_run(spans)) == 14_400_000 + 1_440_000
    assert semi_cond_pairs.reduce(_run(spans)) == 50_000
    assert semi_cond_pairs.reduce(_run(spans[1:4])) is None
    assert semi_build_rows.reduce(_run(spans[2:4])) is None
    assert semi_build_rows.reduce(_run()) is None


def _trace(modules):
    spans = np.array([m[1:] for m in modules], np.float64) * MS
    chip = tr.Chip(0, np.zeros((0, 2)), [], spans, [m[0] for m in modules])
    return tr.Trace([chip], [("bench.round 0", 0, 100 * MS),
                             ("bench.round 1", 100 * MS, 200 * MS)])


MODULES = [
    ("jit_tpu__TpuShuffledHashJoinExec__join(1)", 0, 10),
    ("jit_tpu__TpuShuffledHashJoinExec__left_semi_probe(2)", 10, 30),
    ("jit_tpu__TpuShuffledHashJoinExec__semi_cond(3)", 30, 34),
    ("jit_tpu__TpuShuffledHashJoinExec__semi_compact(4)", 34, 40),
    ("jit_tpu__TpuShuffledHashJoinExec__left_anti_probe(5)", 110, 130),
    ("jit_tpu__TpuShuffledHashJoinExec__semi_cond(6)", 130, 134),
    ("jit_tpu__TpuHashAggregateExec__agg(7)", 134, 150),
    ("jit_tpu__TpuShuffleExchangeExec__split(8)", 150, 190),
]


def test_the_semi_joins_seconds_follow_their_programs_tags():
    trace = _trace(MODULES)
    assert semi_join_busy_s.reduce(_run(trace=trace)) \
        == pytest.approx((0.030 + 0.024) / 2)
    # a program without semi joins: nothing to read
    assert semi_join_busy_s.reduce(_run(trace=_trace(MODULES[:1]))) is None
    assert semi_join_busy_s.reduce(_run()) is None


def test_the_roofline_share_is_the_least_bytes_over_the_cond_programs():
    trace = _trace(MODULES)
    events = [_span("join.semi_cond", pairs=40_000, cond_row_bytes=18,
                    stream_capacity=8_192, capacity=65_536)
              for _ in range(2)]
    least = semi_cond_roofline.least_bytes(events)
    assert least == 2 * (40_000 * 18 + 8_192)
    share = semi_cond_roofline.reduce(_run(events, trace))
    # per round: the bytes over 819 GB/s over 4 ms of semi_cond
    assert share == pytest.approx(
        100 * least / 2 / 819e9 / 0.004)
    assert 0 < share <= 100
    assert semi_cond_roofline.reduce(_run(events)) is None
    assert semi_cond_roofline.reduce(_run(trace=trace)) is None


def test_the_engine_names_the_programs_and_events_the_readers_read():
    """The tags and the event are the engine's: a conditional semi
    join's chunk program leads its key with `semi_cond`, and each chunk
    says what the roofline reckons with."""
    import pyarrow as pa

    from spark_rapids_tpu import trace as engine_trace
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.execs import jit_cache
    from spark_rapids_tpu.session import TpuSession, col

    conf = get_conf()
    engine_trace.clear()
    conf.set("spark.rapids.tpu.trace.enabled", "true")
    try:
        session = TpuSession(conf)
        a = session.create_dataframe(pa.table({"k": [1, 1, 2],
                                               "w": [1, 2, 3]}))
        b = session.create_dataframe(pa.table({"k2": [1, 2, 2],
                                               "w2": [1, 3, 4]}))
        for how in ("left_semi", "left_anti"):
            a.join(b, how=how, left_on=[col("k")], right_on=[col("k2")],
                   condition=col("w").ne(col("w2"))).collect()
        events = [e for e in engine_trace.snapshot()
                  if e.name == "join.semi_cond"]
    finally:
        conf.set("spark.rapids.tpu.trace.enabled", "false")
        engine_trace.disable()
        engine_trace.clear()
    tags = {key[0] for key in jit_cache._CACHE if isinstance(key[0], str)}
    assert set(semi_join_busy_s.TAGS) <= tags
    assert len(events) == 2
    assert all({"pairs", "cond_row_bytes", "stream_capacity"}
               <= set(e.attrs) for e in events)


def test_the_new_readers_declare_what_benchmark_json_says():
    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for mod in READERS:
        entry = declared[mod.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"]) \
            == (mod.UNIT, mod.BETTER, mod.SOURCE)
        assert (entry["layer"], entry["moves"]) == (mod.LAYER, mod.MOVES) \
            == ("Operators", "round_wall_s")
    for name in ("join_busy_s", "agg_busy_s", "agg_groups", "decode_s",
                 "scan_wait_s", "wire_bytes", "idle_upload_s",
                 "idle_scan_s", "encode_s", "put_s"):
        assert declared[name]["workloads"][-1] == CELL, name
