"""The cell of several chips: what `BENCHMARK.json` and its files say of
it, a round under the mesh on four virtual CPU devices with the timed
path sound and broken (`_mesh_round.py`, in a process of its own), and
the two readers of the "Several chips" layer on a trace made by hand
and on `harness/testdata/small_x4.xplane.pb`, recorded on four chips of
a v5e host (`harness/testdata/record.py x4`): against `small_x4.json`,
which `describe_x4` wrote off the chip with the same readers, and
against an answer worked out by hand from the raw event times."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import busy_skew, collective_s

CELL = "tpch-sf10-x4.exchange"
ROOT = str(spec.ROOT)
DATA = os.path.join(os.path.dirname(tr.__file__), "testdata")
MS = 1_000_000


# -- (a) the files ------------------------------------------------------ #

def test_the_cell_loads_at_the_listed_sizes():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 4
    assert [s.query for s in cell.round] == ["q1", "q3"]
    tables = {t.name: t for t in cell.tables()}
    assert set(tables) == {"lineitem_x4", "orders_x4"}
    # as many files as chips, four lines an order
    assert tables["lineitem_x4"].files == tables["orders_x4"].files == 4
    assert tables["lineitem_x4"].rows == 4 * tables["orders_x4"].rows
    assert tables["lineitem_x4"].rows_per_file >= 1 << 18
    assert cell.input_rows() == (2 * tables["lineitem_x4"].rows
                                 + tables["orders_x4"].rows)
    assert cell.round[0].plan_has == ("TpuCollectiveHashAggregateExec",)
    assert set(cell.round[1].plan_has) >= {"TpuCollectiveHashJoinExec",
                                           "TpuCollectiveHashAggregateExec"}
    assert not cell.resident


def test_the_configuration_names_what_exists():
    cell = spec.load_cell(CELL)
    for entry in cell.config["tables"].values():
        gen = spec.module("generators", entry["generator"])
        assert callable(gen.generate) and gen.COLUMN_BYTES
    assert set(cell.config["reduced"]) <= set(cell.config["tables"])
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == cell.config["name"])
    assert len(entry["source"]) <= 200
    names = {m["name"] for m in cell.per_layer}
    assert {"collective_s", "busy_skew", "decode_s", "put_s"} <= names
    assert "hbm_roofline_share" not in names
    # of the cells, one alone takes four chips
    assert [w["name"] for w in spec.benchmark()["workloads"]
            if w["chips"] != 1] == [CELL]


def test_a_one_chip_cell_is_left_as_it_was():
    for w in spec.benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        if cell.chips == 1:
            assert all(step.plan_has == () for step in cell.round)
            assert "conf" not in cell.config
            names = {m["name"] for m in cell.per_layer}
            assert not names & {"collective_s", "busy_skew"}


# -- (b) a round under the mesh ----------------------------------------- #

def _python(*args: str, devices: int):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-m", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="module")
def mesh_round():
    done = _python("benchmarks.selfcheck._mesh_round", CELL, devices=4)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_sound_round_runs_under_a_mesh_of_four(mesh_round):
    assert mesh_round["devices"] == 4
    assert mesh_round["mesh"] == [0, 1, 2, 3]
    cell = spec.load_cell(CELL)
    assert mesh_round["conf"] == cell.config.get("conf", {})
    for case in ("sound", "sound_again"):
        found = mesh_round[case]
        assert found["correct"] is True, found
        assert found["compared"]["double_rel_gap"]["value"] \
            <= found["compared"]["double_rel_gap"]["limit"]
        assert found["compared"]["answers_differing"]["value"] == 0
        assert found["compared"]["plans_at_fault"]["value"] == 0
    # close() hands the process back without a mesh
    assert mesh_round["mesh_after_close"] is False


@pytest.mark.parametrize("case, number, count", [
    # an operator the step names and no plan holds: that collect alone
    ("plan_lacks", "plans_at_fault", 1),
    # an answer altered where it is produced: rounded once to float32
    ("answer_altered", "answers_differing", 2),
    # the exchange between chips left out
    ("exchange_left_out", "plans_at_fault", 2),
])
def test_a_broken_round_reads_not_correct(mesh_round, case, number, count):
    found = mesh_round[case]
    assert found["correct"] is False
    assert found["compared"][number]["value"] == count
    assert found["compared"][number]["limit"] == 0
    if case == "plan_lacks":
        assert [bool(f[2]) for f in found["faults"]] == [True, False]
        assert "TpuNoSuchExec" in found["faults"][0][2]
    if case == "answer_altered":
        gap = found["compared"]["double_rel_gap"]
        # half a unit of float32's last place at the most, 2^-24, and
        # over some forty doubles never far under it
        assert 1e-8 < gap["value"] <= 2.0 ** -24
        assert gap["value"] > 10 * gap["limit"]
    if case == "exchange_left_out":
        assert "TpuCollectiveHashJoinExec" in found["faults"][1][2]
        # on one chip the answers are still right: only the plan tells
        assert found["compared"]["answers_differing"]["value"] == 0


@pytest.mark.parametrize("control", ["stored", "computed"])
def test_the_float32_control_reads_not_correct(control):
    """The plain reference in float32 put in the program's place, at
    the rehearsal's size (`_f32_control.py` reads the listed size):
    every query's answer fails the comparison."""
    from benchmarks.selfcheck import _f32_control

    found = _f32_control.readings(spec.load_cell(CELL, rehearse=True),
                                  2700000402)[control]
    assert set(found) == {"q1", "q3"}
    for why, gap in found.values():
        assert why is not None
        # an order of rows that the rounding moved has no gap to give
        assert gap is None or gap > 10 * _f32_control.check.REL_TOL


def test_with_one_device_the_cell_is_refused():
    done = _python("benchmarks.run", "--workload", CELL, "--seed", "3",
                   "--seconds", "1", "--trace", "0", "--rehearse",
                   devices=1)
    assert done.returncode != 0 and done.stdout == ""
    assert "the cell needs 4 chips, JAX found 1" in done.stderr


def test_operators_are_looked_for_in_the_tree_that_ran():
    def node(desc, *kids):
        return types.SimpleNamespace(desc=desc, children=list(kids))

    root = node("TpuCollectLimitExec n=10", node(
        "TpuCollectiveHashAggregateExec keys=[a] [all_to_all x4]",
        node("TpuBroadcastHashJoinExec inner [a=b]",
             node("ParquetScanExec [1 files]"),
             node("ParquetScanExec [1 files]"))))
    assert engine.lacking(root, ("TpuCollectiveHashAggregateExec",)) == []
    assert engine.lacking(root, ("TpuCollectiveHashJoinExec",
                                 "ParquetScanExec")) \
        == ["TpuCollectiveHashJoinExec"]
    # a name is an operator's whole first word, not a part of one
    assert engine.lacking(root, ("TpuCollective",)) == ["TpuCollective"]


def test_a_conf_key_has_to_be_accounted_for():
    key = "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes"
    assert engine.stated_conf({}) == {}
    assert engine.stated_conf(spec.load_cell(CELL).config) == {key: -1}
    with pytest.raises(engine.Refused, match="does not say why"):
        engine.stated_conf({"conf": {key: -1},
                            "assumed": {"conf": "some other key"}})


# -- (c) the readers ---------------------------------------------------- #

A2A = "%all-to-all.3 = (f32[16]{0}, f32[16]{0}) all-to-all(f32[16]{0} %a)"


def _chip(index, ops, async_ops=()):
    """`ops` and `async_ops`: (name, start ms, end ms)."""
    def spans(events):
        return np.array([e[1:] for e in events],
                        dtype=np.float64).reshape(-1, 2) * MS
    return tr.Chip(index, spans(ops), [e[0] for e in ops],
                   np.zeros((0, 2)), [], spans(async_ops),
                   [e[0] for e in async_ops])


def _run(chips, rounds=2):
    trace = tr.Trace(chips, [("bench.round 0", 100 * MS, 200 * MS),
                             ("bench.round 1", 200 * MS, 300 * MS)])
    return types.SimpleNamespace(trace=trace, rounds=[None] * rounds)


def test_collective_seconds_and_skew_by_hand():
    fusion = "%fusion.7 = f32[16]{0} fusion(f32[16]{0} %all-to-all.3)"
    loop = "%while.2 = (s32[], f32[16]{0}) while(%tuple.1)"
    chips = [
        # 30 ms of collectives: 10 alone, then two that overlap over
        # 20; the while that covers them and the fusion that reads one
        # are none.  Busy 100-180: 80 ms
        _chip(0, [(loop, 100, 180), (A2A, 110, 120),
                  ("%all-reduce.1 = f32[] all-reduce(f32[] %x)", 130, 145),
                  ("%all-gather-start.2 = (f32[4]) all-gather-start(%y)",
                   140, 150),
                  (fusion, 150, 180)]),
        # 10 ms on the ops line and 10 on the async line alone, 5 of
        # which lie before the window.  Busy 40 ms
        _chip(1, [(A2A, 210, 220), (fusion, 220, 250)],
              [("%collective-permute-start.1 = (f32[4]) "
                "collective-permute-start(%z)", 95, 105),
               ("%copy-start.3 = (f32[4]) copy-start(%z)", 260, 290),
               ("%reduce-scatter.4 = f32[4] reduce-scatter(%z)", 230, 235)]),
        # no collective at all.  Busy 20 ms
        _chip(2, [(fusion, 100, 120)]),
        _chip(3, [(A2A, 100, 120)]),  # busy 20 ms
    ]
    run = _run(chips)
    # per chip 30, 10 + 5 + 5, 0, 20 ms: mean 17.5 over two rounds
    assert collective_s.reduce(run) == pytest.approx(17.5e-3 / 2)
    # busiest 80 ms over the mean of 80, 40, 20, 20
    assert busy_skew.reduce(run) == pytest.approx(80 / 40)
    assert reduce.chips_at_work(run, 4) == pytest.approx(
        [0.080, 0.040, 0.020, 0.020])


def test_one_chip_doing_everything_has_skew_four_and_is_refused():
    idle = _chip(1, []), _chip(2, []), _chip(3, [])
    run = _run([_chip(0, [(A2A, 100, 150)]), *idle])
    assert busy_skew.reduce(run) == pytest.approx(4.0)
    with pytest.raises(ValueError, match="device work on 1"):
        reduce.chips_at_work(run, 4)
    # a trace that holds fewer chips than the mesh
    with pytest.raises(ValueError, match="mesh has 4 chips"):
        reduce.chips_at_work(_run([_chip(0, [(A2A, 100, 150)])]), 4)


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    one = _run([_chip(0, [(A2A, 100, 150)])])
    assert busy_skew.reduce(one) is None
    quiet = _run([_chip(0, [("%fusion.1 = f32[] fusion()", 100, 150)]),
                  _chip(1, [("%fusion.1 = f32[] fusion()", 100, 150)])])
    assert collective_s.reduce(quiet) is None
    assert busy_skew.reduce(quiet) == pytest.approx(1.0)
    for reader in (collective_s, busy_skew):
        assert reader.reduce(types.SimpleNamespace(trace=None,
                                                   rounds=[None])) is None


@pytest.fixture(scope="module")
def recorded_x4():
    with open(os.path.join(DATA, "small_x4.json")) as f:
        meta = json.load(f)
    return tr.load(os.path.join(DATA, "small_x4.xplane.pb")), meta


def test_recorded_trace_of_four_chips(recorded_x4):
    """Two rounds of one program with an `all_to_all` and a `psum` on
    four v5e chips.  The rounds take a millisecond each and the device's
    clock leads the host's by 0.7 ms in this trace, so the window of
    the two `bench.round` annotations holds each chip's second program
    alone; the benchmark's rounds take seconds."""
    trace, meta = recorded_x4
    assert meta["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    assert [c.index for c in trace.chips] == meta["chips"] == [0, 1, 2, 3]
    assert len(trace.named("bench.round")) == meta["rounds"] == 2
    lo, hi = tr.window(trace)
    for chip, names in zip(trace.chips, meta["collectives_per_chip"]):
        assert "Async XLA Ops" in meta["lines"][f"/device:TPU:{chip.index}"]
        assert len(chip.async_ops) == 0 and len(chip.modules) == 2
        found = [n for n in chip.op_names
                 if collective_s.COLLECTIVE.search(n)]
        # JAX names the instruction after its primitive (`all_to_all`);
        # the opcode is what tells (`all-to-all(`)
        assert sorted(n.split(" = ")[0] for n in found) == names \
            == ["%all-reduce.1"] * 2 + ["%all_to_all.7"] * 2
        assert {collective_s.COLLECTIVE.search(n).group(1)
                for n in found} == {"all-to-all", "all-reduce"}
        # the fusions that read the collectives' results are none
        assert sum("all_to_all.7" in n for n in chip.op_names) > len(found) / 2
        spans = collective_s.collective_spans(chip)
        inside = spans[(spans[:, 0] >= lo) & (spans[:, 1] <= hi)]
        assert len(spans) == 4 and len(inside) == 2
    run = types.SimpleNamespace(trace=trace, rounds=[None] * meta["rounds"])
    busy = reduce.chips_at_work(run, 4)
    assert busy == pytest.approx(meta["chip_busy_s"], rel=1e-9)
    got = collective_s.reduce(run)
    assert got == pytest.approx(meta["collective_s"], rel=1e-9)
    # an all-to-all of 256 KB and an all-reduce of a scalar: 5 to 20 us
    # a chip in the window, most of the 14 to 16 us a chip is busy
    assert 5e-6 < got * meta["rounds"] < min(busy)
    assert busy_skew.reduce(run) == pytest.approx(meta["busy_skew"],
                                                  rel=1e-9)
    assert 1.0 <= meta["busy_skew"] < 1.2


#: the collective events of the recorded trace's window, by hand: the
#: (start, duration) in ns of each chip's `all-to-all(` and
#: `all-reduce(` events on its `XLA Ops` line that lie between the start
#: of `bench.round 0` (137,431,837) and the end of `bench.round 1`
#: (138,746,387 + 1,011,350), picked by eye from a dump of
#: `ProfileData`'s events and not by `collective_s`
BY_HAND_X4 = [
    [(137923096, 7893), (137932101, 3728)],
    [(137924523, 6638), (137932274, 3501)],
    [(137924608, 6545), (137932264, 3523)],
    [(137924918, 5657), (137931688, 4100)],
]


def test_recorded_trace_against_its_raw_event_times(recorded_x4):
    """`small_x4.json` was written by the readers it checks
    (`record.py:describe_x4`, off the chip); this answer was not.  No
    chip's two events overlap (the all-to-all ends before the
    all-reduce starts), so a chip's seconds are the sum of its two
    durations: 11,621, 10,139, 10,068 and 9,757 ns; their mean is
    10,396.25 ns, over two rounds 5,198.125 ns."""
    from jax.profiler import ProfileData

    trace, meta = recorded_x4
    # the file still holds the events the arithmetic was made from
    raw = {}
    for plane in ProfileData.from_file(
            os.path.join(DATA, "small_x4.xplane.pb")).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
            raw[int(plane.name.rsplit(":", 1)[1])] = {
                (int(ev.start_ns), int(ev.duration_ns)): ev.name
                for ev in ops.events}
    for index, events in enumerate(BY_HAND_X4):
        (a2a, reduce_), names = events, raw[index]
        assert " all-to-all(" in names[a2a]
        assert " all-reduce(" in names[reduce_]
        assert a2a[0] + a2a[1] < reduce_[0]
    per_chip = [sum(d for _, d in events) for events in BY_HAND_X4]
    assert per_chip == [11621, 10139, 10068, 9757]
    lo, hi = tr.window(trace)
    assert (lo, hi) == (137431837, 138746387 + 1011350)
    for chip, want in zip(trace.chips, per_chip):
        assert tr.busy_ns(collective_s.collective_spans(chip), lo, hi) \
            == want
    run = types.SimpleNamespace(trace=trace, rounds=[None, None])
    assert collective_s.reduce(run) == pytest.approx(5198.125e-9, rel=1e-12)
