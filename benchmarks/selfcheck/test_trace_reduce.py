"""The trace reduction, on interval arithmetic by hand and on the small
trace recorded on a TPU v5e (`harness/testdata/record.py` says what it
holds: two rounds; in each a chain of matrix products, 30 ms of host
sleep inside a `fixture.sleep` span, a sort)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(tr.__file__), "testdata")


def test_union_and_gaps_by_hand():
    spans = np.array([[0, 10], [5, 20], [30, 40], [35, 38], [50, 60.0]])
    assert tr.merged(spans, 0, 100).tolist() == [[0, 20], [30, 40], [50, 60]]
    assert tr.busy_ns(spans, 0, 100) == 40
    assert tr.gaps(spans, 0, 100).tolist() == [[20, 30], [40, 50], [60, 100]]
    # cut to a window that starts and ends inside an interval
    assert tr.merged(spans, 7, 55).tolist() == [[7, 20], [30, 40], [50, 55]]
    assert tr.gaps(spans, 7, 55).tolist() == [[20, 30], [40, 50]]
    assert tr.busy_ns(np.zeros((0, 2)), 0, 10) == 0
    assert tr.gaps(np.zeros((0, 2)), 0, 10).tolist() == [[0, 10]]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small.json")) as f:
        meta = json.load(f)
    return tr.load(os.path.join(DATA, "small.xplane.pb")), meta


def test_recorded_trace_busy_union_and_idle_share(recorded):
    trace, meta = recorded
    assert meta["device"]["platform"] == "tpu"
    assert [c.index for c in trace.chips] == [0]
    chip = trace.chips[0]
    lo, hi = tr.window(trace)
    rounds = trace.named("bench.round")
    assert len(rounds) == meta["rounds"]
    assert (lo, hi) == (rounds[0][1], rounds[-1][2])
    busy = tr.chip_busy_s(trace, chip)
    window_s = (hi - lo) / 1e9
    # the host slept 30 ms in each round while the chip had nothing
    assert 0 < busy < window_s - meta["rounds"] * meta["sleep_s"] * 0.9
    # ops nest (a fusion inside a while), so their sum may pass the
    # union; the programs do not overlap on one chip, so theirs is it
    inside = (chip.modules[:, 0] >= lo) & (chip.modules[:, 1] <= hi)
    programs = float(np.sum(chip.modules[inside, 1]
                            - chip.modules[inside, 0])) / 1e9
    assert busy <= programs * 1.001
    assert busy >= programs * 0.5
    top = tr.top_modules(trace, chip)
    assert {name.split("(")[0] for name, _ in top} == {
        "jit_fixture_products", "jit_fixture_sort"}
    assert sum(s for _, s in top) == pytest.approx(programs, rel=1e-6)


def test_recorded_trace_gaps_are_labelled(recorded):
    trace, meta = recorded
    offset = tr.clock_offset_ns(trace, meta["marker_perf_ns"])
    assert offset is not None
    spans = [(s["name"], s["ts_ns"] + offset,
              s["ts_ns"] + s["dur_ns"] + offset) for s in meta["spans"]]
    # the clocks agree: each fixture.round span lies on its annotation
    for (_, a0, a1), (_, s0, s1) in zip(
            trace.named("bench.round"),
            [s for s in spans if s[0] == "fixture.round"]):
        assert abs(a0 - s0) < 1e6 and abs(a1 - s1) < 1e6
    found = tr.longest_gaps(trace, trace.chips[0], spans, n=2)
    assert [label for label, _ in found] == [
        "bench.collect products round 0 | fixture.sleep",
        "bench.collect products round 1 | fixture.sleep"] or \
        [label for label, _ in found] == [
        "bench.collect products round 1 | fixture.sleep",
        "bench.collect products round 0 | fixture.sleep"]
    for _, seconds in found:
        assert meta["sleep_s"] * 0.9 < seconds < meta["sleep_s"] * 3
