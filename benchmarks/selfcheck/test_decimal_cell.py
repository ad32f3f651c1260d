"""The cell `tpch-sf10-decimal.resident`: `lineitem_decimal` holds the
specification's types and the rows `lineitem` holds under the same
seed, the plain reference equals `collect(engine="cpu")` at the
rehearsal size, and the readers of the cell's three per-layer metrics
read spans and a trace made by hand.  (`tests/test_tpch_decimal.py`
holds the program to the reference exactly, on the device path.)"""

import decimal
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import lineitem, lineitem_decimal
from benchmarks.harness import check, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (
    decimal_agg_busy_s,
    decimal_agg_roofline,
    wide_decimal_rows,
)
from benchmarks.queries import q1_decimal, q6_decimal

CELL = "tpch-sf10-decimal.resident"
SEED = 3100000045
MS = 1_000_000


# -- (a) the generator ---------------------------------------------------- #

@pytest.fixture(scope="module")
def rehearsal():
    return spec.load_cell(CELL, rehearse=True).tables()[0]


def test_the_money_is_decimal_15_2_and_the_rest_is_the_twins(rehearsal):
    rows = rehearsal.rows_per_file
    cols = lineitem_decimal.generate(SEED, 0, rows)
    twin = lineitem.generate(SEED, 0, rows)
    table = lineitem_decimal.to_arrow(cols, SEED, 0)
    same = lineitem.to_arrow(twin, SEED, 0)
    assert table.schema.names == same.schema.names
    for f in table.schema:
        if f.name in lineitem_decimal.MONEY:
            assert f.type == pa.decimal128(15, 2)
            assert cols[f.name].dtype == np.int64
            # the cents are the twin's doubles times 100
            assert np.array_equal(cols[f.name] / 100.0, twin[f.name])
            assert table.column(f.name).to_pylist()[:50] == [
                decimal.Decimal(int(c)).scaleb(-2)
                for c in cols[f.name][:50]]
        else:
            assert f.type == same.schema.field(f.name).type
            assert table.column(f.name).equals(same.column(f.name))


def test_the_cell_is_the_twins_size():
    cell = spec.load_cell(CELL)
    twin = spec.load_cell("tpch-sf10.resident")
    assert cell.chips == 1 and cell.resident
    assert cell.input_rows() == twin.input_rows() == 125_829_120
    assert [s.query for s in cell.round] == ["q6_decimal", "q1_decimal"]
    table = cell.tables()[0]
    assert (table.files, table.rows_per_file) == (60, 1 << 20)


# -- (b) the plain reference ---------------------------------------------- #

@pytest.mark.parametrize("query", [q6_decimal, q1_decimal])
def test_the_reference_is_the_cpu_engines(tmp_path, rehearsal, query):
    from spark_rapids_tpu.session import TpuSession

    rows = rehearsal.rows_per_file
    cols = lineitem_decimal.generate(SEED, 0, rows)
    path = str(tmp_path / "lineitem-0.parquet")
    pq.write_table(lineitem_decimal.to_arrow(cols, SEED, 0), path)
    session = TpuSession()
    df = query.build(session, {"lineitem": session.read_parquet(
        path, columns=query.COLUMNS["lineitem"])})
    got = df.collect(engine="cpu")
    want = query.combine([query.partial(cols, {})])
    assert {f.name: f.type for f in got.schema} == query.TYPES
    reason, _ = check.compare(got, want, query.ORDERED)
    assert reason is None, reason


# -- (c) the readers ------------------------------------------------------ #

def _span(name, **attrs):
    return types.SimpleNamespace(name=name, ts_ns=0, dur_ns=0,
                                 thread_name="t", attrs=attrs)


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace, peaks={"hbm_bytes_per_s": 819e9},
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def _trace(modules):
    spans = np.array([m[1:] for m in modules], np.float64) * MS
    chip = tr.Chip(0, np.zeros((0, 2)), [], spans, [m[0] for m in modules])
    return tr.Trace([chip], [("bench.round 0", 0, 100 * MS),
                             ("bench.round 1", 100 * MS, 200 * MS)])


MODULES = [
    ("jit_tpu__TpuHashAggregateExec__agg(1)", 0, 30),
    ("jit_tpu__TpuHashAggregateExec__agg(2)", 30, 40),
    ("jit_tpu__TpuFilterExec__filter(3)", 40, 60),
    ("jit_tpu__TpuHashAggregateExec__agg(1)", 100, 140),
]

#: one round's events: q6's update of a batch and q1's
ROUND = [
    _span("agg.update", capacity=1 << 20, wide_decimal=1,
          decimal_input_words=3),
    _span("decimal.wide", op="multiply", rows=1 << 20),
    _span("decimal.wide", op="sum_update", rows=1 << 20),
    _span("agg.update", capacity=1 << 20, wide_decimal=5,
          decimal_input_words=4),
    _span("decimal.wide", op="multiply", rows=3 << 20),
    _span("decimal.wide", op="sum_update", rows=5 << 20),
    _span("decimal.wide", op="avg_finish", rows=3 * 8),
    _span("agg.update", capacity=1 << 20),  # an aggregate without them
]


def test_wide_decimal_rows_sums_the_events():
    assert wide_decimal_rows.reduce(_run(ROUND * 2)) \
        == (10 << 20) + 24
    assert wide_decimal_rows.reduce(_run(ROUND[-1:])) is None


def test_the_busy_seconds_are_the_aggregates_where_wide_arithmetic_ran():
    trace = _trace(MODULES)
    assert decimal_agg_busy_s.reduce(_run(ROUND * 2, trace)) \
        == pytest.approx(0.080 / 2)
    # no 128-bit arithmetic in the round: nothing to read
    assert decimal_agg_busy_s.reduce(_run(ROUND[-1:], trace)) is None
    assert decimal_agg_busy_s.reduce(_run(ROUND * 2)) is None


def test_the_roofline_share_is_the_least_bytes_over_the_busy_seconds():
    trace = _trace(MODULES)
    least = decimal_agg_roofline.least_bytes(
        _decimal_updates(ROUND * 2))
    assert least == 2 * ((1 << 20) * 3 * 8 + 16 + (1 << 20) * 4 * 8 + 80)
    share = decimal_agg_roofline.reduce(_run(ROUND * 2, trace))
    assert share == pytest.approx(100 * least / 2 / 819e9 / 0.040)
    assert 0 < share <= 100
    assert decimal_agg_roofline.reduce(_run(ROUND * 2)) is None
    assert decimal_agg_roofline.reduce(_run(trace=trace)) is None


def _decimal_updates(spans):
    from benchmarks.layer_metrics import _decimal

    return _decimal.updates(_run(spans))


def test_the_engine_emits_what_the_readers_read():
    """The events and attributes are the engine's: a traced q6 over
    DECIMAL(15,2) money notes its product and its sum, and its update
    span the decimal words it reads and its 128-bit accumulator."""
    from spark_rapids_tpu import trace as engine_trace
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.trace import TRACE_ENABLED

    cols = lineitem_decimal.generate(SEED, 0, 4096)
    session = TpuSession()
    session.conf.set(TRACE_ENABLED.key, True)
    try:
        frame = session.create_dataframe(
            lineitem_decimal.to_arrow(cols, SEED, 0).select(
                q6_decimal.COLUMNS["lineitem"]))
        q6_decimal.build(session, {"lineitem": frame}).collect()
        spans = engine_trace.snapshot()
    finally:
        session.conf.set(TRACE_ENABLED.key, False)
    wide = {s.attrs["op"] for s in spans if s.name == "decimal.wide"}
    assert {"multiply", "sum_update"} <= wide
    updates = _decimal_updates(spans)
    assert updates and updates[-1].attrs["decimal_input_words"] == 3
    assert updates[-1].attrs["wide_decimal"] == 1
