"""The idle seconds put down to a cause (`layer_metrics/_idle.py`) and
the count of programs from outside `cached_jit`, on a trace and spans
made by hand: one chip, two rounds, three threads."""

import types

import numpy as np
import pytest

from benchmarks.harness import engine, reduce, spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import _idle

MS = 1_000_000
#: trace clock = perf_counter + OFFSET
OFFSET = -900 * MS
IDLE = ("idle_upload_s", "idle_sync_s", "idle_scan_s", "idle_dispatch_s")

#: the chip's operations, in the trace's clock: idle for 30 ms from
#: 120, 30 ms from 160 and 50 ms from 210
OPS = [(100, 120), (150, 160), (190, 210), (260, 300)]
#: (name, thread, start, end) in the trace's clock, ms
SPANS = [
    ("exec.TpuHashAggregateExec", "tpu-pipe-result.fetch", 115, 185),
    ("wire.put", "tpu-pipe-result.fetch", 125, 135),
    # not a cause: decode on its own thread idles nothing by itself
    ("scan.decode.file", "tpu-scan-decode_0", 100, 150),
    # 130-135 lies under the put as well: the upload comes first
    ("pipe.scan.upload.wait_empty", "tpu-pipe-result.fetch", 130, 148),
    ("query.fetch.batch", "MainThread", 170, 230),
    ("pipe.readback", "tpu-pipe-result.fetch", 215, 220),
    # 225-230 lies under the fetch as well: the upload comes first
    ("wire.encode", "tpu-pipe-scan.upload", 225, 240),
    ("exec.TpuSortExec", "tpu-pipe-result.fetch", 245, 250),
    ("query.plan", "MainThread", 252, 258),
]
#: by hand, ms in the window: the gap from 120 gives upload 10, scan 13
#: (135-148), dispatch 7; the gap from 160 dispatch 10, sync 20; the gap
#: from 210 sync 15, upload 15, dispatch 5, and 15 ms to nobody
BY_HAND_MS = {"idle_upload_s": 25, "idle_sync_s": 35, "idle_scan_s": 13,
              "idle_dispatch_s": 22}
IDLE_MS = 110


def _span(name, thread, start, end):
    return types.SimpleNamespace(
        name=name, thread_name=thread, ts_ns=start * MS - OFFSET,
        dur_ns=(end - start) * MS)


def _run(spans=SPANS, skew_ns=0, modules=()):
    """Two rounds, 100-200 and 200-300 ms of the trace's clock; the
    host reads its own clock 2 us before a round's annotation opens and
    2 us after it closes; `skew_ns` moves the last reading."""
    chip = tr.Chip(0, np.array(OPS, dtype=np.float64) * MS,
                   ["%fusion"] * len(OPS),
                   np.array([m[1:] for m in modules],
                            dtype=np.float64).reshape(-1, 2) * MS,
                   [m[0] for m in modules])
    trace = tr.Trace([chip], [("bench.round 0", 100 * MS, 200 * MS),
                              ("bench.round 1", 200 * MS, 300 * MS)])
    rounds = [
        engine.Round(0, 0.1, 100 * MS - OFFSET - 2000,
                     200 * MS - OFFSET + 2000, [], {}),
        engine.Round(1, 0.1, 200 * MS - OFFSET - 2000,
                     300 * MS - OFFSET + 2000 + skew_ns, [], {})]
    return reduce.Run(spec.load_cell("tpch-sf10.scan"), [], rounds, 0.0,
                      "TPU v5 lite", 0, [_span(*s) for s in spans], trace)


def _read(name, run):
    return spec.module("layer_metrics", name).reduce(run)


@pytest.mark.parametrize("name", IDLE)
def test_each_cause_gets_its_exact_seconds(name):
    # per round: the window holds two
    assert _read(name, _run()) == pytest.approx(
        BY_HAND_MS[name] / 1e3 / 2, abs=1e-9)


def test_the_order_of_causes_decides_where_spans_overlap():
    ops = np.array(OPS, dtype=np.float64)
    both = [("wire.put", 120, 150), ("pipe.readback", 120, 150),
            ("pipe.scan.decode.wait_empty", 120, 150), ("exec.X", 120, 150)]
    for first in range(4):
        got = _idle.attribute(ops, both[first:], 100, 300)
        want = [0.0] * first + [30.0] + [0.0] * (3 - first)
        assert [got[c] for c, _ in _idle.CAUSES] == want
    # a thread's name decides nothing: the same spans on one thread
    one = [(n, "MainThread", s, e) for n, _, s, e in SPANS]
    assert [_read(n, _run(one)) for n in IDLE] \
        == [_read(n, _run()) for n in IDLE]


def test_the_causes_never_pass_the_idle_time():
    run = _run()
    idle_s = tr.gaps(run.trace.chips[0].ops, *tr.window(run.trace))
    idle_s = float(np.sum(idle_s[:, 1] - idle_s[:, 0])) / 1e9
    assert idle_s == pytest.approx(IDLE_MS / 1e3)
    told = sum(_read(n, run) for n in IDLE) * len(run.rounds)
    assert told == pytest.approx(0.095) and told <= idle_s
    # spans over the whole window explain every idle second and no more
    run = _run(SPANS + [("exec.all", "t", 0, 400)])
    assert sum(_read(n, run) for n in IDLE) * 2 == pytest.approx(idle_s)
    assert _read("idle_dispatch_s", run) == pytest.approx(
        (22 + 15) / 1e3 / 2)


def test_offsets_that_disagree_give_nothing(capsys):
    assert _idle.clock_offset_ns(_run()) == OFFSET
    assert [_read(n, _run(skew_ns=2 * MS)) for n in IDLE] == [None] * 4
    assert "share no clock" in capsys.readouterr().err
    # 0.9 ms is inside the limit
    assert _read("idle_sync_s", _run(skew_ns=-900_000)) is not None


def test_a_program_without_the_spans_reports_nothing():
    old = [s for s in SPANS if not s[0].startswith(("wire.", "scan."))]
    run = _run(old)
    assert _read("idle_upload_s", run) is None
    assert _read("decode_s", run) is None
    assert _read("encode_s", run) is None and _read("put_s", run) is None
    # what the upload held now falls to the next cause that holds
    assert _read("idle_scan_s", run) == pytest.approx(0.018 / 2)
    assert _read("eager_programs", run) is None
    untraced = _run()
    untraced.trace = None
    assert [_read(n, untraced) for n in IDLE] == [None] * 4


def test_span_seconds_are_thread_seconds_per_round():
    run = _run(SPANS + [("scan.decode.file", "tpu-scan-decode_1", 100, 150)])
    assert _read("decode_s", run) == pytest.approx(0.100 / 2)
    assert _read("encode_s", run) == pytest.approx(0.015 / 2)
    assert _read("put_s", run) == pytest.approx(0.010 / 2)


def test_eager_programs_counts_by_prefix():
    modules = [("jit_tpu__TpuHashAggregateExec__agg(1)", 100, 120),
               ("jit__take(2)", 150, 155), ("jit_clip(3)", 155, 160),
               ("jit_tpu__none__rangepid(4)", 190, 210),
               ("tpu__not_a_jit(5)", 260, 300),
               # before and after the window: not counted
               ("jit_reshape(6)", 90, 95), ("jit_reshape(6)", 300, 310)]
    assert _read("eager_programs", _run(modules=modules)) == 3 / 2
    # a program that names nothing: every module would count
    assert _read("eager_programs", _run(modules=modules[1:3])) is None
