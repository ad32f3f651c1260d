"""The idle seconds put down to the host's work between a mesh's stage
programs (`layer_metrics/_mesh_idle.py`) and the three readers beside
them (`mesh_host_s`, `mesh_syncs`, `mesh_d2d_bytes`), on a trace,
spans and counters made by hand: one chip, two rounds, three
threads."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import _idle, _mesh_idle

MS = 1_000_000
#: trace clock = perf_counter + OFFSET
OFFSET = -900 * MS
MESH_IDLE = ("idle_mesh_stack_s", "idle_mesh_shrink_s", "idle_mesh_launch_s")
OLD_IDLE = ("idle_upload_s", "idle_sync_s", "idle_scan_s", "idle_dispatch_s")
NEW = MESH_IDLE + ("mesh_host_s", "mesh_syncs", "mesh_d2d_bytes")

#: the chip's operations, in the trace's clock: idle for 40 ms from
#: 110, 40 ms from 160 and 55 ms from 205
OPS = [(100, 110), (150, 160), (200, 205), (260, 300)]
STAGE, MAIN, POOL = "tpu-pipe-result.fetch", "MainThread", "tpu-exchange-map_0"
AGG, SORT = "TpuCollectiveHashAggregateExec", "TpuCollectiveSortExec"
#: (name, thread, start, end, attrs) in the trace's clock, ms
SPANS = [
    (f"exec.{AGG}", STAGE, 105, 190, {"op": AGG}),
    ("mesh.stack", STAGE, 108, 125, {"op": AGG, "d2d_bytes": 1000}),
    # inside the stack: the upload comes first
    ("wire.put", STAGE, 112, 116, {}),
    ("mesh.launch", STAGE, 125, 128, {"op": AGG, "program": "spmdupdate"}),
    ("mesh.shrink", STAGE, 128, 150, {"op": AGG}),
    # inside the shrink: the sync comes first
    ("pipe.readback", STAGE, 130, 140, {"tag": "mesh.counts", "op": AGG}),
    # while the chip works: host seconds, no idle ones
    ("pipe.readback", STAGE, 152, 155, {"tag": "mesh.drain", "op": AGG}),
    ("pipe.readback", STAGE, 156, 158, {"tag": "join.probe"}),
    ("mesh.stack", STAGE, 160, 170, {"op": AGG, "d2d_bytes": 0}),
    # the stage's last cut, after its timed region has closed at 190
    ("mesh.shrink", STAGE, 188, 198, {"op": AGG}),
    ("query.fetch.batch", MAIN, 205, 215, {}),
    (f"exec.{SORT}", STAGE, 215, 250, {"op": SORT}),
    # 224-226 lies under the stack as well: the stack comes first
    ("mesh.launch", POOL, 220, 226, {"op": SORT, "program": "spmdtail"}),
    ("mesh.stack", STAGE, 224, 230, {"op": SORT, "d2d_bytes": 500}),
    ("pipe.scan.upload.wait_empty", STAGE, 240, 245, {}),
]
#: by hand, ms in the window.  The gap from 110: stack 15 less the put's
#: 4, launch 3, shrink 22 less the readback's 10.  From 160: stack 10,
#: dispatch 18, shrink 10 (two of them under the exec span too), nobody
#: 2.  From 205: sync 10, dispatch 5, launch 4, stack 6, dispatch 10,
#: scan 5, dispatch 5, nobody 10
BY_HAND_MS = {"upload": 4, "sync": 20, "scan": 5, "mesh.stack": 27,
              "mesh.shrink": 22, "mesh.launch": 7, "dispatch": 38}
IDLE_MS, NOBODY_MS = 135, 12
#: what `_idle.py` makes of the same: dispatch holds what the mesh's
#: three have under an exec span, and nobody the shrink from 190 on
OLD_DISPATCH_MS, OLD_NOBODY_MS = 86, 20
COUNTERS = [{"stage.mesh.counts.readbacks": 12, "stage.mesh.drain.readbacks": 3,
             "stage.join.probe.readbacks": 1},
            {"stage.mesh.counts.readbacks": 12, "stage.mesh.drain.readbacks": 2,
             "stage.join.probe.readbacks": 1}]


def _span(name, thread, start, end, attrs):
    return types.SimpleNamespace(
        name=name, thread_name=thread, ts_ns=start * MS - OFFSET,
        dur_ns=(end - start) * MS, attrs=attrs)


def _run(spans=SPANS, skew_ns=0, counters=COUNTERS):
    """Two rounds, 100-200 and 200-300 ms of the trace's clock, as
    `test_idle_attribution.py` makes them, in a cell of four chips."""
    chip = tr.Chip(0, np.array(OPS, dtype=np.float64) * MS,
                   ["%fusion"] * len(OPS), np.zeros((0, 2)), [])
    trace = tr.Trace([chip], [("bench.round 0", 100 * MS, 200 * MS),
                              ("bench.round 1", 200 * MS, 300 * MS)])
    rounds = [
        engine.Round(0, 0.1, 100 * MS - OFFSET - 2000,
                     200 * MS - OFFSET + 2000, [], counters[0]),
        engine.Round(1, 0.1, 200 * MS - OFFSET - 2000,
                     300 * MS - OFFSET + 2000 + skew_ns, [], counters[1])]
    return reduce.Run(spec.load_cell("tpch-sf10-x4.exchange"), [], rounds,
                      0.0, "TPU v5 lite", 0, [_span(*s) for s in spans],
                      trace)


def _read(name, run):
    return spec.module("layer_metrics", name).reduce(run)


def _per_round(ms):
    return pytest.approx(ms / 1e3 / 2, abs=1e-9)


@pytest.mark.parametrize("name", MESH_IDLE)
def test_each_mesh_span_gets_its_exact_seconds(name):
    span = name[len("idle_"):-len("_s")].replace("_", ".")
    assert _read(name, _run()) == _per_round(BY_HAND_MS[span])


def test_the_causes_are_idles_with_the_mesh_before_dispatch():
    assert [c for c, _ in _mesh_idle.CAUSES] == [
        "upload", "sync", "scan", "mesh.stack", "mesh.shrink",
        "mesh.launch", "dispatch"]
    ours, theirs = dict(_mesh_idle.CAUSES), dict(_idle.CAUSES)
    assert all(ours[c] is theirs[c] for c in theirs)
    found = _mesh_idle.idle_by_cause(_run())
    assert found == {c: _per_round(ms) for c, ms in BY_HAND_MS.items()}


def test_the_order_decides_where_spans_overlap():
    ops = np.array(OPS, dtype=np.float64)
    every = [("wire.put", 110, 150), ("pipe.readback", 110, 150),
             ("pipe.scan.decode.wait_empty", 110, 150),
             ("mesh.stack", 110, 150), ("mesh.shrink", 110, 150),
             ("mesh.launch", 110, 150), ("exec.X", 110, 150)]
    for first in range(len(every)):
        got = _mesh_idle.attribute(ops, every[first:], 100, 300)
        want = [0.0] * first + [40.0] + [0.0] * (len(every) - 1 - first)
        assert [got[c] for c, _ in _mesh_idle.CAUSES] == want
    # a thread's name decides nothing: the same spans on one thread
    one = [(n, MAIN, s, e, a) for n, _, s, e, a in SPANS]
    assert [_read(n, _run(one)) for n in MESH_IDLE] \
        == [_read(n, _run()) for n in MESH_IDLE]


def test_the_three_split_what_dispatch_or_nobody_held():
    run = _run()
    old = {n: _read(n, run) * 2 * 1e3 for n in OLD_IDLE}
    # upload, sync and scan read what they read without the mesh spans
    assert old == {"idle_upload_s": pytest.approx(4),
                   "idle_sync_s": pytest.approx(20),
                   "idle_scan_s": pytest.approx(5),
                   "idle_dispatch_s": pytest.approx(OLD_DISPATCH_MS)}
    assert IDLE_MS - sum(old.values()) == pytest.approx(OLD_NOBODY_MS)
    new = _mesh_idle.idle_by_cause(run)
    three = sum(_read(n, run) for n in MESH_IDLE) * 2 * 1e3
    left = new["dispatch"] * 2 * 1e3
    assert IDLE_MS - sum(new.values()) * 2 * 1e3 \
        == pytest.approx(NOBODY_MS)
    # the three and the dispatch left over are the old dispatch and
    # the part of nobody's that a mesh span covers
    assert three + left == pytest.approx(
        OLD_DISPATCH_MS + OLD_NOBODY_MS - NOBODY_MS)
    assert three <= OLD_DISPATCH_MS + OLD_NOBODY_MS


def test_offsets_that_disagree_give_nothing(capsys):
    assert [_read(n, _run(skew_ns=2 * MS)) for n in MESH_IDLE] == [None] * 3
    assert "share no clock" in capsys.readouterr().err
    # 0.9 ms is inside the limit
    assert _read("idle_mesh_stack_s", _run(skew_ns=-900_000)) is not None
    untraced = _run()
    untraced.trace = None
    assert [_read(n, untraced) for n in MESH_IDLE] == [None] * 3
    # what needs no device trace is still read
    assert _read("mesh_host_s", untraced) is not None


def test_mesh_host_seconds_are_a_union_over_threads():
    # 108-150 with the counts fetch inside, the drain's 152-155 (the
    # probe's readback is not the mesh's), 160-170, 188-198, and
    # 220-230 once though a launch and a stack overlap there
    assert _read("mesh_host_s", _run()) == _per_round(42 + 3 + 10 + 10 + 10)


def test_mesh_syncs_are_the_two_counters_and_part_of_host_syncs():
    run = _run()
    assert _read("mesh_syncs", run) == 12 + 2.5
    # the old reader counts them with the rest: one probe readback a
    # round and the one result batch fetched in two rounds
    assert _read("host_syncs", run) == 12 + 2.5 + 1 + 0.5
    # a stage whose child hands it host counts never drains
    no_drain = [{k: v for k, v in c.items() if "drain" not in k}
                for c in COUNTERS]
    assert _read("mesh_syncs", _run(counters=no_drain)) == 12


def test_mesh_d2d_bytes_sums_the_stack_spans():
    assert _read("mesh_d2d_bytes", _run()) == (1000 + 0 + 500) / 2


def test_a_program_without_the_spans_reports_nothing():
    """The parent's side of the driver's comparison: the readers run
    over a program that has no `mesh.*` span and no `stage.mesh.*`
    counter, and leave their metrics out without raising."""
    old = [s for s in SPANS if not s[0].startswith("mesh.")
           and s[4].get("tag", "").split(".")[0] != "mesh"]
    run = _run(old, counters=[{"stage.join.probe.readbacks": 1}] * 2)
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)
    # and what the mesh's three held falls back to dispatch
    assert _read("idle_dispatch_s", run) == _per_round(
        OLD_DISPATCH_MS + 10)  # the counts fetch's 10 is no sync now
    # one kind of span alone is read alone
    run = _run([s for s in SPANS if s[0] != "mesh.shrink"])
    assert _read("idle_mesh_shrink_s", run) is None
    assert _read("idle_mesh_stack_s", run) == _per_round(27)


def test_benchmark_json_lists_the_six_in_the_four_chip_cells():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        mod = spec.module("layer_metrics", name)
        assert listed[name] == {
            "name": mod.NAME, "unit": mod.UNIT, "better": mod.BETTER,
            "source": mod.SOURCE, "layer": "Several chips",
            "moves": "round_wall_s",
            "workloads": ["tpch-sf10-x4.exchange", "tpcds-sf10-x4.q67"]}
        assert mod.LAYER == "Several chips" and mod.MOVES == "round_wall_s"
    in_cell = {m["name"] for m in
               spec.load_cell("tpcds-sf10-x4.q67").per_layer}
    assert set(NEW) <= in_cell
    assert set(NEW).isdisjoint(
        m["name"] for m in spec.load_cell("tpch-sf10.join").per_layer)
