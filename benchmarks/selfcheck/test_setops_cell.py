"""The cell `tpcds-sf10-setops.q38-q87`: `web_sales` and `customer` held
to the specification's shape, the name lists to what the
configuration's `shapes` says of them, what the cell's files say, a
round of its listed plan on the CPU at an eighth of the fact tables'
rows with the timed path sound and broken twice (NULL names matched to
nothing; EXCEPT keeping the rows it should take away), and the readers
of its two per-layer metrics on spans and a trace made by hand.
(`test_datagen.py` already holds the plain reference equal to
`collect(engine="cpu")`, for every cell.)"""

import dataclasses
import shutil
import tempfile
import types

import numpy as np
import pytest

from benchmarks.generators import _tpcds, customer, web_sales
from benchmarks.harness import datagen, engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import setop_join_busy_s, setop_join_rows

CELL = "tpcds-sf10-setops.q38-q87"
MS = 1_000_000
FACTS = ("store_sales", "catalog_sales", "web_sales")

#: the specification's columns, in its order
WEB_SALES = """ws_sold_date_sk ws_sold_time_sk ws_ship_date_sk ws_item_sk
ws_bill_customer_sk ws_bill_cdemo_sk ws_bill_hdemo_sk ws_bill_addr_sk
ws_ship_customer_sk ws_ship_cdemo_sk ws_ship_hdemo_sk ws_ship_addr_sk
ws_web_page_sk ws_web_site_sk ws_ship_mode_sk ws_warehouse_sk ws_promo_sk
ws_order_number ws_quantity ws_wholesale_cost ws_list_price ws_sales_price
ws_ext_discount_amt ws_ext_sales_price ws_ext_wholesale_cost
ws_ext_list_price ws_ext_tax ws_coupon_amt ws_ext_ship_cost ws_net_paid
ws_net_paid_inc_tax ws_net_paid_inc_ship ws_net_paid_inc_ship_tax
ws_net_profit""".split()
CUSTOMER = {
    "c_customer_sk": "int64", "c_customer_id": "string",
    "c_current_cdemo_sk": "int64", "c_current_hdemo_sk": "int64",
    "c_current_addr_sk": "int64", "c_first_shipto_date_sk": "int64",
    "c_first_sales_date_sk": "int64", "c_salutation": "string",
    "c_first_name": "string", "c_last_name": "string",
    "c_preferred_cust_flag": "string", "c_birth_day": "int32",
    "c_birth_month": "int32", "c_birth_year": "int32",
    "c_birth_country": "string", "c_login": "string",
    "c_email_address": "string", "c_last_review_date_sk": "int64",
}


# -- (a) the generators --------------------------------------------------- #

def test_web_sales_has_the_specifications_columns_and_types():
    rows = 240_000
    cols = web_sales.generate(11, 3, rows)
    table = web_sales.to_arrow(cols, 11, 3)
    assert table.schema.names == WEB_SALES == list(web_sales.COLUMN_BYTES)
    assert table.num_columns == 34 and table.num_rows == rows
    for at, name in enumerate(WEB_SALES):
        kind = str(table.schema.field(name).type)
        assert kind == ("int64" if at < 18 else "int32" if at == 18
                        else "double"), name
        share = table[name].null_count / rows
        never = name in ("ws_item_sk", "ws_order_number")
        # 0.05% of the rows are picked, half of a picked row's columns
        assert share == 0 if never else 0.0001 < share < 0.0005, name
    for name, top in (("ws_bill_customer_sk", _tpcds.CUSTOMERS),
                      ("ws_ship_customer_sk", _tpcds.CUSTOMERS),
                      ("ws_item_sk", _tpcds.ITEMS),
                      ("ws_bill_addr_sk", _tpcds.ADDRESSES),
                      ("ws_promo_sk", _tpcds.PROMOTIONS),
                      ("ws_web_page_sk", 200), ("ws_web_site_sk", 42),
                      ("ws_ship_mode_sk", 20), ("ws_warehouse_sk", 10),
                      ("ws_quantity", 100)):
        known = cols[name][cols[name] >= 0]
        assert 1 <= known.min() and known.max() <= top, name
        assert known.min() <= 1 + top // 500, name
        assert known.max() >= top - top // 500, name
    day = cols["ws_sold_date_sk"][cols["ws_sold_date_sk"] >= 0] \
        - _tpcds.EPOCH_SK
    assert day.min() == _tpcds.SALES_FIRST_DAY
    assert day.max() == _tpcds.SALES_LAST_DAY
    price, listed = cols["ws_sales_price"], cols["ws_list_price"]
    paid = ~np.isnan(price)
    assert np.allclose(np.rint(price[paid] * 100), price[paid] * 100)
    both = paid & ~np.isnan(listed)
    assert np.all(price[both] <= listed[both])


def test_a_web_orders_lines_share_what_the_order_fixes():
    rows = 240_000
    cols = web_sales.generate(11, 3, rows)
    number = cols["ws_order_number"]
    lines = np.bincount(number - number.min())
    assert lines.min() >= 8 and lines.max() <= 16 and lines.sum() == rows
    assert len(lines) == rows // 12
    first = np.r_[0, np.cumsum(lines)[:-1]]
    for name in ("ws_sold_date_sk", "ws_sold_time_sk", "ws_bill_customer_sk",
                 "ws_ship_customer_sk", "ws_ship_addr_sk", "ws_web_page_sk",
                 "ws_web_site_sk"):
        of_order = np.repeat(np.maximum.reduceat(cols[name], first), lines)
        assert np.all((cols[name] == of_order) | (cols[name] == -1)), name
    pairs = np.unique(number * (_tpcds.ITEMS + 1) + cols["ws_item_sk"])
    assert len(pairs) > 0.9995 * rows
    known = (cols["ws_bill_customer_sk"] >= 0) \
        & (cols["ws_ship_customer_sk"] >= 0)
    same = cols["ws_bill_customer_sk"][known] \
        == cols["ws_ship_customer_sk"][known]
    assert 0.84 < same.mean() < 0.88
    other = web_sales.generate(11, 4, rows, ["ws_order_number"])
    assert other["ws_order_number"].min() > number.max()
    assert web_sales.WEB_SALES_ID not in (
        _tpcds.STORE_SALES_ID, _tpcds.ITEM_ID, _tpcds.STORE_ID,
        customer.CUSTOMER_ID)


def test_customer_has_the_specifications_columns_and_types():
    rows = _tpcds.CUSTOMERS
    cols = customer.generate(11, 0, rows)
    table = customer.to_arrow(cols, 11, 0)
    assert table.schema.names == list(CUSTOMER) \
        == list(customer.COLUMN_BYTES)
    assert table.num_columns == 18 and table.num_rows == rows
    for name, kind in CUSTOMER.items():
        assert str(table.schema.field(name).type) == kind, name
        share = table[name].null_count / rows
        if name in ("c_customer_sk", "c_customer_id"):
            assert share == 0, name
        elif name == "c_login":
            assert share == 1
        else:  # 7% of the rows are picked, half of their columns
            assert 0.033 < share < 0.037, name
    assert np.array_equal(cols["c_customer_sk"], np.arange(1, rows + 1))
    assert len(set(table["c_customer_id"].slice(0, 5_000).to_pylist())) \
        == 5_000
    for name, low, top in (
            ("c_current_cdemo_sk", 1, _tpcds.CUSTOMER_DEMOGRAPHICS),
            ("c_current_hdemo_sk", 1, _tpcds.HOUSEHOLD_DEMOGRAPHICS),
            ("c_current_addr_sk", 1, _tpcds.ADDRESSES),
            ("c_birth_day", 1, 28), ("c_birth_month", 1, 12),
            ("c_birth_year", 1924, 1992)):
        known = cols[name][cols[name] >= 0]
        # 500,000 draws reach the ends of a domain or come near them
        assert low <= known.min() <= low + top // 10_000, name
        assert top - top // 10_000 <= known.max() <= top, name
    sold, shipped = (cols["c_first_sales_date_sk"],
                     cols["c_first_shipto_date_sk"])
    both = (sold >= 0) & (shipped >= 0)
    late = (shipped - sold)[both]
    assert late.min() == 0 and late.max() == 30
    # a name is never padded in the file, and is of the stated lengths
    for name, (low, top) in (("c_first_name", customer.FIRST_LETTERS),
                             ("c_last_name", customer.LAST_LETTERS)):
        spelt = [s for s in table[name].slice(0, 50_000).to_pylist()
                 if s is not None]
        assert all(s == s.strip() and s.isalpha() for s in spelt)
        assert min(map(len, spelt)) == low and max(map(len, spelt)) == top
    mail = [m for m in table["c_email_address"].slice(0, 20_000).to_pylist()
            if m is not None]
    assert all("@" in m and len(m) <= 50 for m in mail)


def test_the_name_lists_collide_as_the_configuration_says():
    """`shapes.names`: two lists of 5,000 distinct names that depend on
    no seed, Zipf's weights, and how many customers share a full
    name."""
    said = spec.load_cell(CELL).config["shapes"]["names"]
    for names, (low, top) in ((customer.FIRST_NAMES, customer.FIRST_LETTERS),
                              (customer.LAST_NAMES, customer.LAST_LETTERS)):
        assert len(names) == len(set(names)) == customer.NAMES == 5_000
        assert {len(n) for n in names} == set(range(low, top + 1))
    assert "5,000" in said and "1 / (r + 1)" in said
    weights = customer.WEIGHTS
    assert abs(weights.sum() - 1) < 1e-12
    assert np.allclose(weights[0] / weights[9], 10)
    assert 3.9e-4 < customer.COLLIDE < 4.0e-4 and "4.0e-4" in said
    for seed in (3, 4):
        cols = customer.generate(seed, 0, _tpcds.CUSTOMERS,
                                 ["c_first_name", "c_last_name"])
        first, last = cols["c_first_name"], cols["c_last_name"]
        named = (first >= 0) & (last >= 0)
        _, bearers = np.unique(first[named].astype(np.int64) * 10_000
                               + last[named], return_counts=True)
        # "some 224,000 distinct pairs, the commonest borne by some 5,600"
        assert 220_000 < len(bearers) < 228_000 and "224,000" in said
        assert 5_300 < bearers.max() < 5_900 and "5,600" in said
        # a NULL first name is as common as the list's third name
        assert (first == 1).sum() > (first == -1).sum() > (first == 4).sum()
        measured = (bearers.astype(np.float64) ** 2).sum() / named.sum() ** 2
        assert 0.9 * customer.COLLIDE < measured < 1.1 * customer.COLLIDE
    again = customer.generate(3, 0, 1_000, ["c_first_name"])["c_first_name"]
    assert np.array_equal(again, customer.generate(
        3, 0, 1_000)["c_first_name"])


# -- (b) the files and a round ------------------------------------------- #

def test_the_cell_loads_at_the_listed_sizes():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 1
    assert [s.query for s in cell.round] == ["q38", "q87"]
    wanted = spec.module("queries", "q38").COLUMNS
    assert spec.module("queries", "q87").COLUMNS is wanted
    for step in cell.round:
        facts = [step.table(role) for role in FACTS]
        assert [t.rows_per_file for t in facts] == [960_000, 480_000,
                                                    240_000]
        # the cut is of the file count only, the three by one fraction
        assert len({t.files for t in facts}) == 1
        assert facts[0].files in (4, 8, 15, 30)
        for t in facts:
            assert t.files == 30 or t.name in cell.config["reduced"]
            # two int64 columns of each fact table: 16 bytes a row
            assert spec.column_bytes(t, wanted[t.generator]) == t.rows * 16
        assert step.table("customer").rows == 500_000
        assert step.table("date_dim").rows == 73_049
        assert spec.column_bytes(step.table("customer"),
                                 wanted["customer"]) == 500_000 * 58
        assert {"TpuBroadcastHashJoinExec", "TpuShuffledHashJoinExec"} \
            <= set(step.plan_has)
    assert cell.round[0].tables == cell.round[1].tables
    assert set(cell.config["reduced"]) <= {
        f"{t}_{cut}" for t in FACTS for cut in "hqe"}
    assert "conf" not in cell.config
    twin = spec.load_cell("tpcds-sf10-channels.q97").config
    assert cell.config["guarantees"][0] == twin["guarantees"][0]
    for name in ("store_sales", "store_sales_h", "store_sales_q",
                 "store_sales_e", "catalog_sales", "catalog_sales_q",
                 "date_dim"):  # one seed gives the twins the same files
        assert cell.config["tables"][name] == twin["tables"][name], name
    names = {m["name"] for m in cell.per_layer}
    assert {"setop_join_busy_s", "setop_join_rows", "join_busy_s",
            "agg_busy_s", "agg_groups", "decode_s", "scan_wait_s",
            "wire_bytes", "idle_scan_s", "idle_upload_s", "encode_s",
            "put_s"} <= names
    assert not {"hbm_roofline_share", "expand_rows", "window_rows",
                "collective_s", "outer_join_rows"} & names


def _verdict(runner, done) -> dict:
    runner.check(done)
    return {"correct": not any(c.failure or c.plan_fault
                               for c in done.collects),
            "compared": reduce.compared(done.collects),
            "faults": {c.query: (c.failure, c.plan_fault)
                       for c in done.collects}}


@pytest.fixture(scope="module")
def rounds():
    """A sound round; one whose set operations compare with `=`, so
    that a NULL name matches nothing; one whose EXCEPT keeps the rows
    it should take away (a semi join where the anti join stands); a
    sound one again."""
    import jax

    from spark_rapids_tpu import session as engine_session
    from spark_rapids_tpu.plan import logical

    # every listed file of a fact table at an eighth of its rows, the
    # dimensions whole: the scans are one task each, as at the listed
    # size, so the round runs the LISTED plan, and the channels share
    # some tens of triples with a NULL name (a sixteenth shares two)
    listed = spec.load_cell(CELL)
    cell = dataclasses.replace(listed, round=tuple(
        dataclasses.replace(step, tables=tuple(
            (role, t if t.files == 1 else dataclasses.replace(
                t, rows_per_file=t.rows_per_file // 8))
            for role, t in step.tables)) for step in listed.round))
    work = tempfile.mkdtemp(prefix="setops-round-")
    out = {}
    try:
        data = datagen.generate(cell, 3400000438, work)
        runner = engine.Runner(cell, data, jax.devices(), trace=False)
        out["sound"] = _verdict(runner, runner.run_round())

        set_operation = logical.set_operation

        def nulls_match_nothing(left, right, join_type):
            keys = logical._all_columns(left)
            return logical.distinct(logical.Join(
                left, right, keys, logical._all_columns(right), join_type))

        logical.set_operation = nulls_match_nothing
        try:
            out["nulls_unmatched"] = _verdict(runner, runner.run_round())
        finally:
            logical.set_operation = set_operation

        subtract = engine_session.DataFrame.subtract
        engine_session.DataFrame.subtract = \
            engine_session.DataFrame.intersect
        try:
            out["anti_keeps_matched"] = _verdict(runner, runner.run_round())
        finally:
            engine_session.DataFrame.subtract = subtract
        out["sound_again"] = _verdict(runner, runner.run_round())
        out["expected"] = [t.to_pylist()[0] for t in data.expected]
        runner.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def test_a_sound_round_reads_correct(rounds):
    q38, q87 = (want["count"] for want in rounds["expected"])
    # an eighth of eight files: some 15,000 store triples
    assert q38 > 0 and 10_000 < q87 < 16_000
    for case in ("sound", "sound_again"):
        found = rounds[case]
        assert found["correct"] is True, found
        assert found["compared"]["answers_differing"]["value"] == 0
        assert found["compared"]["plans_at_fault"]["value"] == 0


def test_a_round_whose_null_names_match_nothing_reads_not_correct(rounds):
    found = rounds["nulls_unmatched"]
    assert found["correct"] is False
    assert found["compared"]["answers_differing"]["value"] >= 1
    assert found["compared"]["plans_at_fault"]["value"] == 0
    # EXCEPT kept the store triples with a NULL name that the other
    # channels hold too
    assert "count" in found["faults"]["q87"][0]


def test_a_round_whose_anti_join_keeps_matched_rows_reads_not_correct(
        rounds):
    found = rounds["anti_keeps_matched"]
    assert found["correct"] is False
    assert found["compared"]["answers_differing"]["value"] == 1
    assert found["faults"]["q38"] == (None, None)
    assert "count" in found["faults"]["q87"][0]


# -- (c) the readers ------------------------------------------------------ #

def _span(name, **attrs):
    return types.SimpleNamespace(name=name, ts_ns=0, dur_ns=0,
                                 thread_name="t", attrs=attrs)


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace,
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def test_the_probed_rows_come_from_the_semi_and_anti_joins_instants():
    keys = "[c_last_name<=>c_last_name, d_date<=>d_date]"
    spans = []
    for _ in range(2):  # two rounds
        spans += [
            _span("query.operator", op="TpuShuffledHashJoinExec",
                  desc=f"TpuShuffledHashJoinExec left_semi {keys}",
                  numOutputRows=40, streamRows=1_200, buildRows=800),
            _span("query.operator", op="TpuShuffledHashJoinExec",
                  desc=f"TpuShuffledHashJoinExec left_anti {keys}",
                  numOutputRows=1_100, streamRows=1_160, buildRows=300),
            _span("query.operator", op="TpuBroadcastHashJoinExec",
                  desc=f"TpuBroadcastHashJoinExec left_semi {keys}",
                  numOutputRows=5, streamRows=40, buildRows=300),
            _span("query.operator", op="TpuShuffledHashJoinExec",
                  desc="TpuShuffledHashJoinExec inner [a=b]",
                  numOutputRows=9_000, streamRows=9_000),
        ]
    assert setop_join_rows.reduce(_run(spans)) == 1_200 + 1_160 + 40
    assert setop_join_rows.reduce(_run(spans[3:4])) is None
    assert setop_join_rows.reduce(_run()) is None


def test_the_set_operations_seconds_follow_their_programs_tags():
    modules = [
        ("jit_tpu__TpuShuffledHashJoinExec__join(1)", 0, 10),
        ("jit_tpu__TpuShuffledHashJoinExec__left_semi_probe(2)", 10, 30),
        ("jit_tpu__TpuShuffledHashJoinExec__semi_compact(3)", 25, 40),
        ("jit_tpu__TpuBroadcastHashJoinExec__left_anti_probe(4)", 110, 130),
        ("jit_tpu__TpuHashAggregateExec__agg(5)", 130, 150),
        ("jit_tpu__TpuBroadcastHashJoinExec__join(6)", 150, 190),
    ]
    spans = np.array([m[1:] for m in modules], np.float64) * MS
    chip = tr.Chip(0, np.zeros((0, 2)), [], spans, [m[0] for m in modules])
    trace = tr.Trace([chip], [("bench.round 0", 0, 100 * MS),
                              ("bench.round 1", 100 * MS, 200 * MS)])
    assert setop_join_busy_s.reduce(_run(trace=trace)) \
        == pytest.approx(0.050 / 2)
    # a program from before PR 38: inner probes, nothing to read
    bare = tr.Trace([tr.Chip(0, np.zeros((0, 2)), [], spans[:1],
                             [modules[0][0]])], trace.annotations)
    assert setop_join_busy_s.reduce(_run(trace=bare)) is None
    assert setop_join_busy_s.reduce(_run()) is None


def test_the_engine_names_the_programs_the_reader_looks_for():
    """The tags are the engine's: a semi join's and an anti join's
    probe, and the compaction both share, as `cached_jit` keys them."""
    import pyarrow as pa

    from spark_rapids_tpu.execs import jit_cache
    from spark_rapids_tpu.session import TpuSession

    session = TpuSession()
    a = session.create_dataframe(pa.table({"x": [1, None, 2]}))
    a.intersect(a).collect()
    a.subtract(a).collect()
    tags = {key[0] for key in jit_cache._CACHE if isinstance(key[0], str)}
    assert set(setop_join_busy_s.TAGS) <= tags


def test_the_new_readers_declare_what_benchmark_json_says():
    declared = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for mod in (setop_join_busy_s, setop_join_rows):
        entry = declared[mod.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"]) \
            == (mod.UNIT, mod.BETTER, mod.SOURCE)
        assert (entry["layer"], entry["moves"]) == (mod.LAYER, mod.MOVES) \
            == ("Operators", "round_wall_s")
    for name in ("join_busy_s", "agg_busy_s", "agg_groups", "decode_s"):
        assert CELL in declared[name]["workloads"]
