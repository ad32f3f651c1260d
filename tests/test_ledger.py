"""Device-utilization ledger + live telemetry sampler (trace/ledger.py,
trace/telemetry.py; docs/device_ledger.md).

The acceptance surface:
- an enabled ledger attributes >=1 program per query with nonzero
  cost-model bytes AND dispatch count, and the attributed device time
  never exceeds the query wall (run_ledger_smoke, wired into tier-1
  here and into the bench_smoke CLI);
- the per-query `programs` event-log section round-trips through
  tools/history EQUAL to the in-process snapshot;
- both features OFF are bit-identical and effectively free: the
  dispatch wrapper never touches ledger state, no sampler thread
  exists;
- the telemetry sampler starts/stops leak-free under concurrent
  sessions and its counter samples export as Chrome-trace ph="C"
  counter tracks (Perfetto counter tracks).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace
from spark_rapids_tpu.config import TpuConf, get_conf
from spark_rapids_tpu.session import TpuSession, col, sum_
from spark_rapids_tpu.trace import ledger, telemetry

LEDGER_KEY = "spark.rapids.tpu.trace.ledger.enabled"
TELEMETRY_KEY = "spark.rapids.tpu.telemetry.enabled"


@pytest.fixture(autouse=True)
def _clean_ledger_and_sampler():
    """The ledger and the sampler are process-global: every test
    starts and ends with both disabled and empty."""
    ledger.disable()
    ledger.reset_stats()
    telemetry.SAMPLER.stop()
    yield
    ledger.disable()
    ledger.reset_stats()
    telemetry.SAMPLER.stop()
    trace.disable()
    trace.clear()


def _table(n: int = 4096, seed: int = 0x1ED) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 32, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })


def _agg(session: TpuSession, t: pa.Table):
    return (session.create_dataframe(t)
            .group_by(col("k"))
            .agg((sum_(col("v")), "sv"))
            .order_by(col("k")))


# -- attribution core --------------------------------------------------- #

def test_ledger_attributes_programs_with_cost_model():
    """THE core contract: an enabled ledger records every dispatched
    program with invocation count, settled device time, the XLA cost
    model (flops/bytes) and an op label for per-operator rollups."""
    ledger.enable()
    session = TpuSession()
    _agg(session, _table()).collect(engine="tpu")
    assert ledger.LEDGER.flush(timeout=30.0)
    snap = ledger.snapshot()
    assert snap, "no programs recorded"
    assert any(p["dispatches"] > 0 and p["bytes_accessed"] > 0
               for p in snap.values()), snap
    assert any(p["device_ms"] > 0 for p in snap.values()), snap
    ops = {p["op"] for p in snap.values() if p["op"]}
    assert "TpuHashAggregateExec" in ops, ops


def test_ledger_smoke():
    """The CI smoke (also a bench_smoke CLI stage): >=1 program with
    nonzero cost bytes + dispatches, attributed device time within the
    query wall."""
    from spark_rapids_tpu.tools.bench_smoke import run_ledger_smoke

    out = run_ledger_smoke()
    assert out["ledger_programs"] >= 1
    assert out["ledger_dispatches"] >= 1


def test_ledger_delta_isolates_query_window():
    ledger.enable()
    session = TpuSession()
    t = _table()
    _agg(session, t).collect(engine="tpu")
    ledger.LEDGER.flush(timeout=30.0)
    before = ledger.snapshot()
    # second run of the SAME template: cached programs, new dispatches
    _agg(session, t).collect(engine="tpu")
    ledger.LEDGER.flush(timeout=30.0)
    d = ledger.delta(before, ledger.snapshot())
    assert d, "second collect attributed nothing"
    for p in d.values():
        assert p["dispatches"] >= 1
    # a delta over an idle window is empty
    assert ledger.delta(ledger.snapshot(), ledger.snapshot()) == {}


def test_summarize_math_and_top_programs():
    """summarize() arithmetic on a synthetic delta: attributed
    bytes/s, roofline fractions against explicit peaks, dispatch
    overhead, totals and top-N shares."""
    programs = {
        "fused#aa": {"tag": "fused", "op": "A", "dispatches": 4,
                     "dispatch_ms": 2.0, "device_ms": 100.0,
                     "flops": 1e6, "bytes_accessed": 1e6},
        "sort#bb": {"tag": "sort", "op": "B", "dispatches": 1,
                    "dispatch_ms": 1.0, "device_ms": 300.0,
                    "flops": 0.0, "bytes_accessed": 0.0},
    }
    s = ledger.summarize(programs, top_n=1,
                         hbm_bytes_per_s=1e9, peak_flops=1e12)
    a = s["programs"]["fused#aa"]
    # 1e6 bytes x 4 dispatches over 0.1s = 4e7 B/s; /1e9 = 0.04
    assert a["bytes_per_s"] == pytest.approx(4e7)
    assert a["roofline"] == pytest.approx(0.04)
    assert a["flops_per_s"] == pytest.approx(4e7)
    assert a["dispatch_overhead"] == pytest.approx(0.02)
    b = s["programs"]["sort#bb"]
    assert b["roofline"] is None  # no cost model -> no attribution
    t = s["totals"]
    assert t["programs"] == 2 and t["dispatches"] == 5
    assert t["device_ms"] == pytest.approx(400.0)
    # device-time-weighted over programs with a KNOWN cost model only
    assert t["roofline"] == pytest.approx(0.04)
    assert len(t["top"]) == 1
    assert t["top"][0]["key"] == "sort#bb"  # most device time
    assert t["top"][0]["share"] == pytest.approx(0.75)


def test_per_op_aggregation():
    programs = {
        "x#1": {"tag": "x", "op": "A", "dispatches": 2,
                "dispatch_ms": 1.0, "device_ms": 50.0,
                "flops": 10.0, "bytes_accessed": 1e6},
        "x#2": {"tag": "x", "op": "A", "dispatches": 1,
                "dispatch_ms": 1.0, "device_ms": 50.0,
                "flops": 10.0, "bytes_accessed": 2e6},
        "y#1": {"tag": "y", "op": None, "dispatches": 9,
                "dispatch_ms": 1.0, "device_ms": 5.0,
                "flops": 0.0, "bytes_accessed": 0.0},
    }
    per = ledger.per_op(programs, hbm_bytes_per_s=1e9)
    assert set(per) == {"A"}  # op-less programs stay out
    # (1e6*2 + 2e6*1) bytes over 0.1s = 4e7 B/s over 1e9 peak
    assert per["A"]["roofline"] == pytest.approx(0.04)
    assert per["A"]["dispatches"] == 3


def test_program_key_str_is_stable_and_distinct():
    k1 = ("fused", ("a", "b"), True)
    assert ledger.program_key_str(k1) == ledger.program_key_str(k1)
    assert ledger.program_key_str(k1).startswith("fused#")
    assert ledger.program_key_str(k1) != \
        ledger.program_key_str(("fused", ("a", "c"), True))


def test_reset_rekeys_wrapper_cells():
    """reset() drops entries; live cached wrappers re-register on
    their next dispatch (the per-query bench discipline)."""
    ledger.enable()
    session = TpuSession()
    t = _table()
    _agg(session, t).collect(engine="tpu")
    ledger.LEDGER.flush(timeout=30.0)
    assert ledger.snapshot()
    ledger.reset_stats()
    assert ledger.snapshot() == {}
    _agg(session, t).collect(engine="tpu")  # same cached programs
    ledger.LEDGER.flush(timeout=30.0)
    snap = ledger.snapshot()
    assert snap and all(p["dispatches"] >= 1 for p in snap.values())


# -- donation-safe settlement ------------------------------------------- #

@contextlib.contextmanager
def _pending():
    """``(sharded, one)``: a leaf over devices 0 and 1 and its shard on
    device 0, neither complete until the block exits — the program
    waits on a host callback.  Two devices, because XLA's CPU client
    runs a one-device program with a callback inline, at dispatch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    gate = threading.Event()

    def wait(x):
        gate.wait(30.0)
        return x

    sharding = NamedSharding(Mesh(np.array(devices[:2]), ("d",)),
                             PartitionSpec("d"))
    held = jax.jit(lambda x: jax.pure_callback(
        wait, jax.ShapeDtypeStruct(x.shape, x.dtype), x),
        out_shardings=sharding)(jnp.arange(16))
    try:
        yield held, held.addressable_shards[0].data
    finally:
        gate.set()
        held.block_until_ready()


def test_derive_sentinels_retains_live_leaves():
    """THE donation-attribution regression (ISSUE 11 satellite): a
    program output mixing a dead (deleted/donated) leaf with live
    leaves must keep sentinels for the live ones — the old
    all-or-nothing derivation settled the whole dispatch 'as host',
    silently dropping a donated fused program's device-busy time."""
    import jax.numpy as jnp

    dead = jnp.arange(8) + 1
    dead.block_until_ready()
    dead.delete()
    with _pending() as (_, live):
        sentinels = ledger.derive_sentinels({"a": dead, "b": live,
                                             "n": 7})
        assert len(sentinels) == 1  # the live leaf survives the dead one
        assert sentinels[0].shape == (0,)
    # all-dead (or host-only) outputs degrade to no sentinels, never
    # raise
    assert ledger.derive_sentinels({"a": dead}) == []
    assert ledger.derive_sentinels(42) == []


def test_one_sentinel_settles_every_leaf_of_a_device():
    """The sentinel is itself a program queued after everything the
    region dispatched on its device: 20 unfinished leaves (and a
    finished one beside them) take one."""
    import jax
    import jax.numpy as jnp

    done = jax.block_until_ready(jnp.arange(4))
    with _pending() as (_, one):
        leaves = jax.jit(lambda a: [a + i for i in range(20)])(one)
        assert not any(x.is_ready() for x in leaves)
        sentinels, live = ledger.sentinels_of({"done": done,
                                               "leaves": leaves})
        assert live == 21 and len(sentinels) == 1
        assert sentinels[0].sharding.device_set == one.sharding.device_set
        assert not sentinels[0].is_ready()


def test_the_sentinel_is_the_last_leaf_whichever_is_unfinished():
    """The slice's shape follows the output's structure, not the
    timing: an unfinished leaf before a finished one still takes the
    finished one's slice, so warm-up has compiled it.  A 0-d leaf (a
    batch's row count, last in its pytree) is passed over for one of
    rank one or more, whose slice is one program where its is two."""
    import jax
    import jax.numpy as jnp

    done = jax.block_until_ready(jnp.ones((4, 3), jnp.float32))
    count = jax.block_until_ready(jnp.int32(4))
    with _pending() as (_, one):
        pending = one + 1
        for out, sliced in (([pending, done], done),
                            ([done, pending], pending),
                            ([pending, done, count], done)):
            (sentinel,) = ledger.derive_sentinels(out)
            assert sentinel.shape == (0,) + sliced.shape[1:]
            assert sentinel.dtype == sliced.dtype
        (sentinel,) = ledger.derive_sentinels([one.sum()])
        assert sentinel.shape == (0,)


def test_a_complete_output_needs_no_sentinel():
    import jax
    import jax.numpy as jnp

    out = jax.block_until_ready({"a": jnp.arange(8), "b": jnp.ones(4),
                                 "n": 3})
    assert ledger.derive_sentinels(out) == []
    assert ledger.sentinels_of(out) == ([], 2)


def test_each_device_set_takes_its_own_sentinel():
    """A sharded leaf and a one-device leaf: two device sets, two
    sentinels (a mesh array is ONE leaf over all its chips)."""
    with _pending() as (sharded, one):
        assert len(ledger.derive_sentinels([sharded, sharded * 2])) == 1
        assert len(ledger.derive_sentinels([sharded, one,
                                            sharded * 2])) == 2


def test_a_timed_region_over_a_complete_batch_ticks_at_once():
    """A cache hit of resident arrays: the region's time is known when
    it closes, so the metric ticks on this thread and the reaper stays
    idle; an unfinished batch still ticks only at completion."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.execs.base import (MetricTimer, TpuMetric,
                                             _MetricReaper)

    reaper = _MetricReaper.get()
    reaper.flush()
    batch = jax.block_until_ready([jnp.arange(64), jnp.ones(64)])
    metric = TpuMetric("totalTime", "ESSENTIAL")
    with MetricTimer(metric) as t:
        t.observe(batch)
    assert metric.value > 0
    assert reaper._q.unfinished_tasks == 0
    reaper.flush()

    pending = TpuMetric("totalTime", "ESSENTIAL")
    with _pending() as (_, one):
        with MetricTimer(pending) as t:
            t.observe([one + 1, one + 2])
        assert reaper._q.unfinished_tasks == 1
        assert pending.value == 0  # dispatch-to-completion, not dispatch
    reaper.flush()
    assert pending.value > 0


def test_donated_program_settles_device_time():
    """End-to-end through the settle worker: a dispatch whose output
    pytree holds a DEAD leaf next to a live one still settles its
    exclusive busy interval via the retained sibling sentinel (and
    the entry carries the donated marker for the footer)."""
    import time as _time

    import jax.numpy as jnp

    ledger.enable()
    entry = ledger.LEDGER.entry(("fusedenc", "t"), "T", donated=True)
    dead = jnp.arange(8)
    dead.block_until_ready()
    dead.delete()
    with _pending() as (_, held):
        live = held * 3
        # THE regression contract: per-leaf fault isolation.  The old
        # all-or-nothing derivation returned [] the moment any leaf was
        # dead, so the settle worker stamped completion at submit time
        # ("as host") and the fused program's busy time vanished.  The
        # live sibling must survive as a sentinel.
        sentinels = ledger.derive_sentinels({"a": dead, "b": live})
        assert len(sentinels) == 1 and sentinels[0].shape == (0,)
        t0 = _time.perf_counter_ns()
        ledger.LEDGER._settle.submit(entry, t0, {"a": dead, "b": live},
                                     None)
    assert ledger.LEDGER.flush(timeout=30.0)
    snap = ledger.snapshot()
    e = snap[ledger.program_key_str(("fusedenc", "t"))]
    assert e["donated"] is True
    assert e["device_ms"] >= 0.0  # settled through the live sentinel


# -- off = free and bit-identical --------------------------------------- #

def test_ledger_disabled_dispatches_touch_nothing(monkeypatch):
    """Disabled-path contract: the cached_jit wrapper's only cost is
    the enabled-flag read — it must never create or look up a ledger
    entry (asserted by making entry creation explode)."""
    assert not ledger.LEDGER.enabled

    def boom(*a, **k):  # pragma: no cover - failing is the assert
        raise AssertionError("ledger touched while disabled")

    monkeypatch.setattr(ledger.LEDGER, "entry", boom)
    session = TpuSession()
    _agg(session, _table()).collect(engine="tpu")
    assert ledger.snapshot() == {}


def test_ledger_off_on_results_bit_identical():
    """The ledger is observation only: integer-exact query digests
    match bit-for-bit with the feature off and on."""
    from spark_rapids_tpu.eventlog import table_digest

    t = _table()
    session = TpuSession()
    off = table_digest(_agg(session, t).collect(engine="tpu"))
    ledger.enable()
    on = table_digest(_agg(session, t).collect(engine="tpu"))
    assert off == on


def test_sync_conf_ownership():
    """Conf-driven enable follows the tracer's ownership rule: only
    the enabling conf's `off` disables; a forced enable() wins."""
    conf_a = TpuConf({LEDGER_KEY: True})
    conf_b = TpuConf()  # defaults: ledger off
    ledger.sync_conf(conf_a)
    assert ledger.LEDGER.enabled
    ledger.sync_conf(conf_b)  # another session's defaults: no-op
    assert ledger.LEDGER.enabled
    conf_a.set(LEDGER_KEY, False)
    ledger.sync_conf(conf_a)  # the owner turns it off
    assert not ledger.LEDGER.enabled
    ledger.enable()  # forced
    ledger.sync_conf(conf_a)
    assert ledger.LEDGER.enabled


# -- surfacing: analyze / eventlog / history ---------------------------- #

def test_analyze_shows_roofline_column_and_ledger_footer():
    conf = TpuConf({LEDGER_KEY: True})
    session = TpuSession(conf)
    out = _agg(session, _table()).explain("analyze")
    assert "roofline=" in out, out
    assert "device ledger:" in out, out
    assert "top:" in out, out


def test_eventlog_programs_roundtrip_equals_inprocess(tmp_path):
    """THE round-trip contract: the query record's `programs` section
    reloaded through tools/history equals the in-process ledger
    snapshot for that query's window."""
    conf = TpuConf({
        LEDGER_KEY: True,
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    })
    session = TpuSession(conf)
    _agg(session, _table()).collect(engine="tpu")
    _ = session.history.events  # drain the snapshot worker
    ledger.LEDGER.flush(timeout=30.0)
    in_process = ledger.summarize(ledger.snapshot())

    from spark_rapids_tpu.tools.history import load_application

    app = load_application(session.event_log_path)
    assert len(app.queries) == 1
    q = app.queries[0]
    assert q.programs == in_process, (q.programs, in_process)
    assert q.program_totals()["dispatches"] >= 1


def test_history_compare_reports_program_deltas():
    """Per-program device-time deltas in compare: a 3x slower program
    is pinned by its structural key; appeared/vanished programs read
    as churn."""
    from spark_rapids_tpu.tools.history import (
        ApplicationInfo,
        QueryRecord,
        compare_applications,
        render_compare_md,
    )

    def q(programs, wall):
        return QueryRecord(
            query_id=1, plan="p", plan_hash="h", engine="tpu",
            wall_s=wall, start_ts=0, end_ts=0, conf_hash="c",
            counters={}, operators=None, spans=None, pipeline=None,
            faults=None, result_digest=None, rows=1, raw={},
            programs={"programs": programs, "totals": {}})

    base_p = {"fused#aa": {"op": "A", "dispatches": 3,
                           "device_ms": 100.0},
              "sort#bb": {"op": "B", "dispatches": 1,
                          "device_ms": 50.0}}
    run_p = {"fused#aa": {"op": "A", "dispatches": 3,
                          "device_ms": 300.0},
             "agg#cc": {"op": "C", "dispatches": 2,
                        "device_ms": 10.0}}
    base = ApplicationInfo("base", "eventlog", {}, [q(base_p, 1.0)])
    run = ApplicationInfo("run", "eventlog", {}, [q(run_p, 1.1)])
    result = compare_applications([base, run], threshold=1.25)
    (row,) = result["rows"]
    pd = {d["program"]: d for d in row["program_deltas"]}
    assert pd["fused#aa"]["change"] == "ratio"
    assert pd["fused#aa"]["ratio"] == pytest.approx(3.0)
    assert pd["sort#bb"]["change"] == "vanished"
    assert pd["agg#cc"]["change"] == "appeared"
    md = render_compare_md(result)
    assert "fused#aa" in md and "vanished" in md


def _qrec(programs_totals, wall_s):
    from spark_rapids_tpu.tools.history import QueryRecord

    return QueryRecord(
        query_id=7, plan="p", plan_hash="h", engine="tpu",
        wall_s=wall_s, start_ts=0, end_ts=0, conf_hash="c",
        counters={}, operators=None, spans=None, pipeline=None,
        faults=None, result_digest=None, rows=1, raw={},
        programs={"programs": {}, "totals": programs_totals})


def test_hc010_dispatch_overhead_rule():
    from spark_rapids_tpu.tools.history import (
        _hc_dispatch_overhead,
    )

    # 100 dispatches, 50ms device in a 1s query: overhead-dominated
    assert _hc_dispatch_overhead(
        _qrec({"dispatches": 100, "device_ms": 50.0}, 1.0))
    # same dispatches but the chip was busy 80% of the wall: healthy
    assert _hc_dispatch_overhead(
        _qrec({"dispatches": 100, "device_ms": 800.0}, 1.0)) is None
    # few dispatches: not this rule's business
    assert _hc_dispatch_overhead(
        _qrec({"dispatches": 3, "device_ms": 1.0}, 1.0)) is None
    # no ledger section at all: silent
    from spark_rapids_tpu.tools.history import QueryRecord

    bare = QueryRecord(
        query_id=1, plan="p", plan_hash="h", engine="tpu", wall_s=1.0,
        start_ts=0, end_ts=0, conf_hash="", counters={},
        operators=None, spans=None, pipeline=None, faults=None,
        result_digest=None, rows=1, raw={})
    assert _hc_dispatch_overhead(bare) is None


def test_hc011_roofline_budget_rule():
    from spark_rapids_tpu.tools.history import _hc_roofline_budget

    get_conf().set(
        "spark.rapids.tpu.trace.ledger.health.rooflineFloor", 0.01)
    # real device time at 0.001 roofline, floor 0.01: flagged
    assert _hc_roofline_budget(
        _qrec({"device_ms": 200.0, "roofline": 0.001}, 1.0))
    # above the floor: healthy
    assert _hc_roofline_budget(
        _qrec({"device_ms": 200.0, "roofline": 0.02}, 1.0)) is None
    # unit-test-sized device time: silent by design
    assert _hc_roofline_budget(
        _qrec({"device_ms": 3.0, "roofline": 0.0001}, 1.0)) is None
    # no attribution: silent
    assert _hc_roofline_budget(
        _qrec({"device_ms": 200.0, "roofline": None}, 1.0)) is None


# -- telemetry sampler -------------------------------------------------- #

def _telemetry_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("tpu-telemetry")]


def test_telemetry_disabled_no_thread():
    assert not telemetry.SAMPLER.enabled
    assert _telemetry_threads() == []


def test_telemetry_counter_tracks_export_to_chrome_trace():
    """Sampler output is Perfetto-loadable: ph='C' counter events with
    numeric args on the telemetry.* tracks, riding the same trace
    export as spans."""
    from spark_rapids_tpu.trace.export import chrome_trace

    trace.enable()
    s0 = telemetry.SAMPLER.samples  # cumulative across starts
    telemetry.start(hz=200)
    deadline = time.monotonic() + 5.0
    while telemetry.SAMPLER.samples < s0 + 3 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    telemetry.stop()
    doc = chrome_trace()
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert counters, "no counter events exported"
    names = {e["name"] for e in counters}
    assert "telemetry.store_bytes" in names
    assert "telemetry.admission" in names
    # the ops-plane gauges ride the same counter-track export
    assert "telemetry.queries" in names
    assert "telemetry.result_cache_bytes" in names
    for e in counters:
        assert "dur" not in e and "s" not in e
        assert all(isinstance(v, (int, float))
                   for v in e["args"].values()), e
    json.dumps(doc)  # serializable whole


def test_telemetry_sampler_leakfree_under_concurrent_sessions(
        tmp_path):
    """Start/stop discipline under many sessions: one thread ever, the
    owner's off stops it, repeated cycles leave nothing behind, and
    attached sessions' event logs receive telemetry records."""
    assert _telemetry_threads() == []
    confs = [TpuConf({
        TELEMETRY_KEY: True,
        "spark.rapids.tpu.telemetry.hz": 100,
        "spark.rapids.tpu.telemetry.eventLogEvery": 1,
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    }) for _ in range(4)]
    sessions = [TpuSession(c) for c in confs]
    s0 = telemetry.SAMPLER.samples  # cumulative across starts

    def run(s):
        _agg(s, _table(512)).collect(engine="tpu")

    threads = [threading.Thread(target=run, args=(s,))
               for s in sessions]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(_telemetry_threads()) == 1  # ONE process sampler
    # give it a few periods so every attached log receives records
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if telemetry.SAMPLER.samples >= s0 + 4:
            break
        time.sleep(0.01)
    # a non-owner conf's off is a no-op; the owner's off stops it
    owner = telemetry.SAMPLER._enabled_by()
    other = next(c for c in confs if c is not owner)
    other.set(TELEMETRY_KEY, False)
    telemetry.sync_conf(other)
    assert telemetry.SAMPLER.enabled
    owner.set(TELEMETRY_KEY, False)
    telemetry.sync_conf(owner)
    assert not telemetry.SAMPLER.enabled
    assert _telemetry_threads() == []
    # forced cycles do not accumulate threads
    for _ in range(3):
        telemetry.start(hz=200)
        assert len(_telemetry_threads()) == 1
        telemetry.stop()
    assert _telemetry_threads() == []
    # the attached sessions' logs carry validated telemetry records
    from spark_rapids_tpu.eventlog.reader import iter_records

    telem_total = 0
    for s in sessions:
        _ = s.history.events  # drain query records first
        recs = list(iter_records(s.event_log_path, strict=True))
        telem_total += sum(1 for r in recs
                           if r["type"] == "telemetry")
        for r in recs:
            if r["type"] == "telemetry":
                assert "store.device_bytes" in r["counters"]
                assert "admission.waiting" in r["counters"]
                assert "queries.in_flight" in r["counters"]
                assert "result_cache.bytes" in r["counters"]
    assert telem_total > 0, "no telemetry records landed in any log"


def test_telemetry_history_roundtrip(tmp_path):
    """tools/history loads telemetry records alongside queries."""
    conf = TpuConf({
        TELEMETRY_KEY: True,
        "spark.rapids.tpu.telemetry.hz": 200,
        "spark.rapids.tpu.telemetry.eventLogEvery": 1,
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    })
    session = TpuSession(conf)
    s0 = telemetry.SAMPLER.samples  # cumulative across starts
    _agg(session, _table(512)).collect(engine="tpu")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if telemetry.SAMPLER.samples >= s0 + 2:
            break
        time.sleep(0.01)
    conf.set(TELEMETRY_KEY, False)
    telemetry.sync_conf(conf)  # owner off: sampler stops, log settles
    _ = session.history.events

    from spark_rapids_tpu.tools.history import load_application

    app = load_application(session.event_log_path)
    assert len(app.queries) == 1
    assert app.telemetry, "history dropped the telemetry records"
    assert "pipeline.occupancy" in app.telemetry[0]["counters"]
    assert "queries.in_flight" in app.telemetry[0]["counters"]
