"""`tpcds-sf10-x4.q67`, TPC-DS q67 on four executors: what
`BENCHMARK.json` and its files say of the cell, that its tables are
`tpcds-sf10`'s byte for byte, a round under the mesh on four virtual
CPU devices, sound and broken (`_q67_mesh_round.py`, in a process of
its own), the float32 control, and the three readers this cell brings
on spans and a trace made by hand."""

import filecmp
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import datagen, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (
    exchange_busy_s,
    exchange_bytes,
    exchange_rows,
)

CELL, TWIN = "tpcds-sf10-x4.q67", "tpcds-sf10.q67"
ROOT = str(spec.ROOT)
MS = 1_000_000


# -- (a) the files ------------------------------------------------------ #

def test_the_cell_loads_at_the_twins_sizes():
    cell, twin = spec.load_cell(CELL), spec.load_cell(TWIN)
    assert cell.chips == cell.config["chips"] == 4
    assert [s.query for s in cell.round] == ["q67_mesh"]
    assert cell.round[0].tables == twin.round[0].tables
    assert cell.input_rows() == twin.input_rows() == 4_015_151
    assert cell.round[0].plan_has == (
        "TpuBroadcastHashJoinExec", "TpuCollectiveHashJoinExec",
        "TpuCollectiveHashAggregateExec", "TpuCollectiveWindowExec",
        "TpuCollectiveSortExec")
    # the default conf: no key, no join threshold
    assert "conf" not in cell.config
    for key in ("tables", "shapes"):
        assert cell.config[key] == twin.config[key]
    assert set(cell.config["reduced"]) == {"store_sales_e"}
    assert "placement" in cell.config["assumed"]
    assert {k: v for k, v in cell.config["assumed"].items()
            if k != "placement"} == twin.config["assumed"]


def test_benchmark_json_lists_it_beside_the_others():
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpcds-sf10-x4")
    assert len(entry["source"]) <= 200 and entry["reduced"] == [
        "store_sales_e"]
    assert bench["configs"][-1] is entry
    listed = bench["workloads"][-1]
    assert listed["name"] == CELL and listed["chips"] == 4
    assert len(listed["why"]) <= 200
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["tpch-sf10-x4.exchange", CELL]
    cell = spec.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"exchange_busy_s", "exchange_rows", "exchange_bytes",
            "collective_s", "busy_skew"} <= names
    # the one-chip operators' readers stay the twin's alone
    assert not names & {"agg_groups", "window_rows", "agg_busy_s",
                        "join_busy_s", "window_busy_s", "sort_busy_s",
                        "hbm_roofline_share"}
    for reader in (exchange_busy_s, exchange_rows, exchange_bytes):
        said = next(m for m in bench["per_layer"]
                    if m["name"] == reader.NAME)
        assert (said["unit"], said["better"], said["layer"],
                said["source"], said["moves"]) == (
            reader.UNIT, reader.BETTER, reader.LAYER, reader.SOURCE,
            reader.MOVES)
        assert said["workloads"] == [CELL]


def test_the_tables_are_the_twins_byte_for_byte(tmp_path):
    """The same generators under the same names: one seed gives the
    two cells the same files and the same expected answer."""
    made = {}
    for name in (CELL, TWIN):
        work = tmp_path / name
        work.mkdir()
        made[name] = datagen.generate(
            spec.load_cell(name, rehearse=True), 3200000401, str(work))
    ours, twins = made[CELL], made[TWIN]
    assert set(ours.paths) == set(twins.paths) == {
        "store_sales_e", "item", "date_dim", "store"}
    for table, files in ours.paths.items():
        assert len(files) == len(twins.paths[table])
        for mine, theirs in zip(files, twins.paths[table]):
            assert filecmp.cmp(mine, theirs, shallow=False), table
    assert ours.expected[0].equals(twins.expected[0])


def test_the_query_is_q67s_and_refuses_a_program_without_the_window(
        monkeypatch):
    """`q67_mesh.py` brings no reference of its own; its `build` is
    q67's behind one look at the program."""
    from benchmarks.queries import q67, q67_mesh
    from spark_rapids_tpu.execs import collective

    for name in ("partial", "combine", "COLUMNS", "KEYS", "DRIVER",
                 "ORDERED"):
        assert getattr(q67_mesh, name) is getattr(q67, name)
    called = []
    monkeypatch.setattr(q67, "build", lambda s, f: called.append((s, f)))
    q67_mesh.build("session", "frames")
    assert called == [("session", "frames")]
    # the parent's execs/collective.py has no such operator
    monkeypatch.delattr(collective, "TpuCollectiveWindowExec")
    with pytest.raises(SystemExit) as refused:
        q67_mesh.build("session", "frames")
    assert "REFUSED" in str(refused.value) and len(called) == 1


# -- (b) a round under the mesh ----------------------------------------- #

@pytest.fixture(scope="module")
def mesh_round():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.selfcheck._q67_mesh_round"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_sound_round_runs_under_a_mesh_of_four(mesh_round):
    assert mesh_round["devices"] == 4
    assert mesh_round["mesh"] == [0, 1, 2, 3]
    assert mesh_round["conf"] == {}
    for case in ("sound", "sound_again"):
        found = mesh_round[case]
        assert found["correct"] is True, found
        gap = found["compared"]["double_rel_gap"]
        assert gap["value"] <= gap["limit"] == 1e-9
        assert found["compared"]["answers_differing"]["value"] == 0
        assert found["compared"]["plans_at_fault"]["value"] == 0
    assert mesh_round["mesh_after_close"] is False


@pytest.mark.parametrize("case, number", [
    # an operator the step names and no plan holds
    ("plan_lacks", "plans_at_fault"),
    # the float32 control on the answer: every double rounded once
    ("answer_altered", "answers_differing"),
    # no mesh: the answer of one executor, and not this cell's plan
    ("exchange_left_out", "plans_at_fault"),
])
def test_a_broken_round_reads_not_correct(mesh_round, case, number):
    found = mesh_round[case]
    assert found["correct"] is False
    assert found["compared"][number] == {"value": 1, "limit": 0}
    if case == "plan_lacks":
        assert "TpuNoSuchExec" in found["faults"][0][2]
        assert found["compared"]["answers_differing"]["value"] == 0
    if case == "exchange_left_out":
        for op in ("TpuCollectiveHashJoinExec", "TpuCollectiveWindowExec"):
            assert op in found["faults"][0][2]
        # the answer of one executor is the answer of four
        assert found["compared"]["answers_differing"]["value"] == 0


def test_the_float32_control_reads_the_stored_answer_as_failing():
    """At the rehearsal's size, as the twin's (`test_q67_cell.py`):
    the reference's answer with each double rounded once to float32
    fails the limit this cell shares with it."""
    from benchmarks.selfcheck import _f32_control

    found = _f32_control.readings(spec.load_cell(CELL, rehearse=True),
                                  3200000402)
    why, gap = found["stored"]["q67_mesh"]
    assert why is not None
    assert gap is None or gap > 10 * _f32_control.check.REL_TOL
    assert set(found["computed"]) == {"q67_mesh"}


# -- (c) the readers ----------------------------------------------------- #

def _operator(desc, **counts):
    return types.SimpleNamespace(
        name="query.operator", ts_ns=0, dur_ns=0, thread_name="history",
        attrs={"op": desc.split(" ", 1)[0], "desc": desc, **counts})


def _run(spans=(), trace=None, rounds=2):
    return types.SimpleNamespace(
        spans=list(spans), trace=trace,
        rounds=[types.SimpleNamespace(counters={})] * rounds)


def test_rows_and_bytes_are_summed_over_the_collective_operators():
    spans = [
        _operator("TpuCollectiveSortExec [a]", numOutputRows=100,
                  collectiveRows=1_100, collectiveBytes=4_000),
        _operator("TpuCollectiveWindowExec [rank]", numOutputRows=1_800,
                  collectiveRows=1_800, collectiveBytes=40_000),
        _operator("TpuCollectiveHashAggregateExec keys=[a]",
                  collectiveRows=1_800, collectiveBytes=20_000,
                  collectivePartialRows=2_000),
        _operator("TpuCollectiveHashJoinExec inner [a=b]",
                  collectiveRows=820, collectiveBytes=8_000),
        # not a collective operator: its count is not an exchange's
        _operator("TpuHashAggregateExec[complete] keys=[a]",
                  collectiveRows=7, collectiveBytes=7),
    ] * 2  # two rounds
    run = _run(spans, rounds=2)
    assert exchange_rows.reduce(run) == 1_100 + 1_800 + 1_800 + 820
    assert exchange_bytes.reduce(run) == 72_000


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """The parent's operators count no bytes, and its join and sort no
    rows: the readers give nothing and do not raise."""
    spans = [_operator("TpuCollectiveHashJoinExec inner [a=b]",
                       numOutputRows=5, collectiveRounds=1)]
    run = _run(spans)
    assert exchange_rows.reduce(run) is None
    assert exchange_bytes.reduce(run) is None
    assert exchange_busy_s.reduce(_run()) is None
    one_chip = _run([_operator("TpuWindowExec [rank]", numOutputRows=9)])
    assert exchange_rows.reduce(one_chip) is None


def test_the_exchange_seconds_follow_the_programs_names():
    names = [
        "jit_tpu__TpuCollectiveHashAggregateExec__spmdxchg(1)",
        "jit_tpu__TpuCollectiveHashAggregateExec__spmdrollupsort(2)",
        "jit_tpu__TpuCollectiveWindowExec__spmdwinroute(3)",
        "jit_tpu__TpuCollectiveWindowExec__spmdroutecount(4)",
        "jit_tpu__TpuCollectiveWindowExec__spmdtail(5)",
        "jit_tpu__TpuCollectiveHashJoinExec__spmdxchg(6)",
        "jit_tpu__TpuCollectiveSortExec__spmdsortroute(7)",
        "jit_tpu__TpuHashAggregateExec__agg(8)",
        "jit_broadcast_in_dim(9)",
    ]
    assert [exchange_busy_s.exchanges(n) for n in names] == [
        True, False, True, True, False, True, True, False, False]
    # one round of 100 ms; each program 10 ms, one after another
    modules = np.array([[at * 10 * MS, (at + 1) * 10 * MS]
                        for at in range(len(names))], dtype=np.float64)
    chip = tr.Chip(0, np.zeros((0, 2)), [], modules, names)
    other = tr.Chip(1, np.zeros((0, 2)), [], modules[:1] + 50 * MS,
                    names[:1])
    trace = tr.Trace([chip, other], [("bench.round 0", 0, 100 * MS)])
    # five of the first chip's programs exchange: 50 ms
    assert exchange_busy_s.reduce(_run(trace=trace, rounds=1)) \
        == pytest.approx(0.05)
    plain = tr.Trace([tr.Chip(0, np.zeros((0, 2)), [], modules[7:],
                              names[7:])],
                     [("bench.round 0", 0, 100 * MS)])
    assert exchange_busy_s.reduce(_run(trace=plain, rounds=1)) is None
