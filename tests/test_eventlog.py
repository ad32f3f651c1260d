"""Persistent event log + tools/history analysis layer.

Covers the PR's acceptance surface:
- schema golden test: every emitted record validates strictly against
  the versioned schema, and the emitted field set is FROZEN (drift
  must be a conscious schema_version decision);
- forward compat: unknown fields and unknown record types from a
  newer writer load fine;
- compare/health round trip: a synthetic 2x slowdown and a
  CPU-fallback run are both flagged, end to end through the CLI
  `report` command;
- chaos: a fault-injected run's log records recovered-fault counts
  AND a result digest bit-identical to the fault-free run's;
- the default-off path adds zero per-query overhead beyond one
  attribute check (no writer thread, no counter snapshots);
- the bench_smoke eventlog contract (per-operator rows in the file ==
  the settled in-process metrics) wired into tier-1.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.session import TpuSession, col, sum_

ENABLED = "spark.rapids.tpu.eventLog.enabled"
DIR = "spark.rapids.tpu.eventLog.dir"
COMPRESS = "spark.rapids.tpu.eventLog.compress"
SIDECAR = "spark.rapids.tpu.eventLog.traceSidecar"


def _logging_session(tmp_path, **extra) -> TpuSession:
    conf = get_conf()
    conf.set(ENABLED, True)
    conf.set(DIR, str(tmp_path / "log"))
    for k, v in extra.items():
        conf.set(k, v)
    return TpuSession()


def _table(n: int = 512, seed: int = 7) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 16, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })


def _agg(session: TpuSession, t: pa.Table):
    return (session.create_dataframe(t)
            .group_by(col("k"))
            .agg((sum_(col("v")), "sv"))
            .order_by(col("k")))


def _drain(session: TpuSession) -> str:
    """Reading history.events drains the snapshot worker, which also
    appends the event-log records; the file is complete after."""
    _ = session.history.events
    return session.event_log_path


# ------------------------------------------------------------------ #
# Schema: golden + forward compat
# ------------------------------------------------------------------ #

#: THE emitted field sets.  Changing either is a schema decision:
#: removing/renaming a field (or retyping it) requires a
#: SCHEMA_VERSION bump; additions must stay optional for readers.
GOLDEN_HEADER_FIELDS = frozenset({
    "type", "schema_version", "ts", "session", "pid", "env", "conf",
    "conf_hash", "mesh"})
GOLDEN_QUERY_FIELDS = frozenset({
    "type", "schema_version", "query_id", "plan", "plan_hash",
    "engine", "wall_s", "start_ts", "end_ts", "start_ns", "end_ns",
    "conf_hash", "counters", "operators", "spans", "pipeline",
    "faults", "serving", "sharing", "connect", "programs",
    "result_digest", "rows", "trace_file"})


def test_schema_golden_every_record_validates(tmp_path):
    from spark_rapids_tpu.eventlog.reader import iter_records
    from spark_rapids_tpu.eventlog.schema import SCHEMA_VERSION

    session = _logging_session(tmp_path)
    t = _table()
    _agg(session, t).collect(engine="tpu")
    (session.create_dataframe(t).where(col("v") > 10)
     .select(col("k")).collect(engine="tpu"))
    path = _drain(session)
    recs = list(iter_records(path, strict=True))  # validates each
    assert [r["type"] for r in recs] == ["header", "query", "query"]
    hdr, q1, q2 = recs
    assert set(hdr) == GOLDEN_HEADER_FIELDS, set(hdr)
    assert set(q1) == set(q2) == GOLDEN_QUERY_FIELDS, set(q1)
    assert hdr["schema_version"] == SCHEMA_VERSION == 1
    assert hdr["conf"][ENABLED] == "True"
    assert q1["query_id"] != q2["query_id"]
    assert q1["plan_hash"] != q2["plan_hash"]  # different templates
    assert q1["conf_hash"] == hdr["conf_hash"]
    # the counter surface is complete
    from spark_rapids_tpu.eventlog import MONOTONIC_COUNTERS

    for key in MONOTONIC_COUNTERS:
        assert key in q1["counters"], key


def test_forward_compat_unknown_fields_and_types(tmp_path):
    from spark_rapids_tpu.eventlog.reader import iter_records, read_log
    from spark_rapids_tpu.eventlog.schema import validate_record

    session = _logging_session(tmp_path)
    _agg(session, _table()).collect(engine="tpu")
    path = _drain(session)
    future = str(tmp_path / "future.jsonl")
    with open(path) as f, open(future, "w") as out:
        for line in f:
            rec = json.loads(line)
            rec["future_field"] = {"from": "a newer writer"}
            out.write(json.dumps(rec) + "\n")
        out.write(json.dumps({"type": "gc_hint", "v": 1}) + "\n")
    # permissive read: unknown record type skipped, extras preserved
    recs = list(iter_records(future))
    assert [r["type"] for r in recs] == ["header", "query"]
    assert recs[1]["future_field"] == {"from": "a newer writer"}
    # strict validation tolerates unknown EXTRA fields by contract
    for r in recs:
        validate_record(r)
    header, queries = read_log(future)
    assert header is not None and len(queries) == 1


def test_corrupt_trailing_line_is_dropped(tmp_path):
    from spark_rapids_tpu.eventlog.reader import read_log

    session = _logging_session(tmp_path)
    _agg(session, _table()).collect(engine="tpu")
    path = _drain(session)
    with open(path, "a") as f:
        f.write('{"type": "query", "torn mid-')  # crash mid-write
    header, queries = read_log(path)
    assert header is not None and len(queries) == 1


def test_torn_trailing_gzip_member_keeps_prefix(tmp_path):
    """A process killed mid-append leaves a truncated final gzip
    member; the complete prefix members must still load (the whole
    point of one-member-per-append)."""
    from spark_rapids_tpu.eventlog.reader import read_log

    session = _logging_session(tmp_path, **{COMPRESS: True})
    _agg(session, _table()).collect(engine="tpu")
    path = _drain(session)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 7)  # tear the final member's trailer
    header, queries = read_log(path)
    assert header is not None
    # the header member decoded; the torn query record is dropped or
    # kept depending on where the tear landed — never an exception
    assert len(queries) <= 1


def test_failed_append_warns_but_does_not_poison_history(
        tmp_path, monkeypatch):
    """An event-log append failure (disk full, revoked dir) must not
    re-raise out of every later history read — the query succeeded."""
    from spark_rapids_tpu.eventlog import EventLogWriter

    session = _logging_session(tmp_path)

    def boom(self, rec):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(EventLogWriter, "append", boom)
    with pytest.warns(RuntimeWarning, match="on_event hook failed"):
        out = _agg(session, _table()).collect(engine="tpu")
        events = session.history.events  # drains without raising
    assert out.num_rows > 0
    assert len(events) == 1  # history itself intact
    # and the header retries on the next (now healthy) append
    monkeypatch.undo()
    _agg(session, _table()).collect(engine="tpu")
    from spark_rapids_tpu.eventlog.reader import read_log

    header, queries = read_log(_drain(session))
    assert header is not None and len(queries) == 1


def test_compressed_log_roundtrip(tmp_path):
    from spark_rapids_tpu.eventlog.reader import iter_records

    session = _logging_session(tmp_path, **{COMPRESS: True})
    assert session.event_log_path.endswith(".jsonl.gz")
    _agg(session, _table()).collect(engine="tpu")
    path = _drain(session)
    recs = list(iter_records(path, strict=True))
    assert [r["type"] for r in recs] == ["header", "query"]


# ------------------------------------------------------------------ #
# compare / health / report round trip
# ------------------------------------------------------------------ #


def _two_runs(tmp_path):
    """(logA, logB): a real two-query log and a doctored copy with a
    2.2x slowdown on every query plus one CPU-fallback record."""
    from spark_rapids_tpu.eventlog.reader import iter_records

    session = _logging_session(tmp_path)
    t = _table()
    _agg(session, t).collect(engine="tpu")
    (session.create_dataframe(t).where(col("v") > 10)
     .select(col("k")).collect(engine="tpu"))
    log_a = _drain(session)
    log_b = str(tmp_path / "runB.jsonl")
    recs = list(iter_records(log_a))
    last_qid = recs[-1]["query_id"]
    with open(log_b, "w") as f:
        for r in recs:
            if r["type"] == "query":
                r = dict(r)
                r["wall_s"] *= 2.2
                if r["query_id"] == last_qid:
                    r["engine"] = "cpu_fallback"
                    r["counters"] = dict(
                        r["counters"], **{"retry.cpu_fallbacks": 1})
            f.write(json.dumps(r) + "\n")
    return log_a, log_b


def test_compare_flags_synthetic_slowdown(tmp_path):
    from spark_rapids_tpu.tools.history import (
        compare_applications,
        load_application,
    )

    log_a, log_b = _two_runs(tmp_path)
    apps = [load_application(log_a), load_application(log_b)]
    result = compare_applications(apps, threshold=1.25)
    assert len(result["rows"]) == 2
    assert all(r["flag"] == "regression" for r in result["rows"])
    assert not result["unmatched"]  # plan hashes matched across runs
    # and below the threshold nothing is flagged
    calm = compare_applications(apps, threshold=3.0)
    assert not calm["regressions"]


def test_health_flags_cpu_fallback_run(tmp_path):
    from spark_rapids_tpu.tools.history import (
        health_check,
        load_application,
    )

    log_a, log_b = _two_runs(tmp_path)
    clean = health_check(load_application(log_a))
    assert not any(f.severity == "error" for f in clean), clean
    assert not any(f.rule == "HC001" for f in clean), clean
    findings = health_check(load_application(log_b))
    assert any(f.rule == "HC001" and f.severity == "error"
               for f in findings), findings


def test_health_rule_registry_thresholds():
    """Rules fire off the record's counters alone — build synthetic
    QueryRecords for each unhealthy pattern."""
    from spark_rapids_tpu.tools.history import (
        QueryRecord,
        _query_from_record,
        health_check,
        ApplicationInfo,
    )

    def q(counters, pipeline=None, engine="tpu") -> QueryRecord:
        return _query_from_record({
            "query_id": 0, "plan": "", "plan_hash": "x",
            "engine": engine, "wall_s": 1.0, "counters": counters,
            "pipeline": pipeline})

    cases = {
        "HC002": q({"retry.splits": 2, "retry.task_retries": 1}),
        "HC003": q({"spill.device_to_host_bytes": 64 << 20}),
        "HC004": q({"jit.misses": 40}),
        "HC005": q({"pipeline.readbacks": 50}),
        "HC006": q({}, pipeline={"s": {
            "items": 64, "occupancy_fraction": 0.01}}),
        "HC007": q({"rf.filters_built": 1, "rf.pruned_rows": 0}),
        "HC008": q({"faults.recovered": 2}),
    }
    for rule, rec in cases.items():
        app = ApplicationInfo("x", "eventlog", {}, [rec])
        got = {f.rule for f in health_check(app)}
        assert rule in got, (rule, got)
    healthy = ApplicationInfo("x", "eventlog", {}, [q({})])
    assert health_check(healthy) == []


def test_report_cli_flags_regression_and_fallback(tmp_path, capsys):
    """THE acceptance criterion: `history report` over two logs — one
    clean, one with an injected regression + CPU fallback — produces a
    markdown report whose compare section flags the >=threshold
    slowdown and whose health section flags the fallback run."""
    from spark_rapids_tpu.tools.history import main

    log_a, log_b = _two_runs(tmp_path)
    out = str(tmp_path / "report.md")
    rc = main(["report", log_a, log_b, "--threshold", "1.25",
               "-o", out])
    assert rc == 0
    text = open(out).read()
    assert text.startswith("# Fleet regression report")
    assert "REGRESSION" in text and "2.200x" in text
    assert "HC001" in text and "degraded to the CPU engine" in text
    # compare exits nonzero on regressions; health on error findings
    assert main(["compare", log_a, log_b]) == 1
    capsys.readouterr()
    assert main(["health", log_b]) == 1
    capsys.readouterr()


def test_dot_from_event_log(tmp_path, capsys):
    from spark_rapids_tpu.tools.history import (
        generate_dot,
        load_application,
        main,
    )

    session = _logging_session(tmp_path)
    _agg(session, _table()).collect(engine="tpu")
    path = _drain(session)
    app = load_application(path)
    dot = generate_dot(app.queries[0])
    assert dot.startswith("digraph plan {") and "->" in dot
    assert "TpuHashAggregateExec" in dot and "rows=" in dot
    assert main(["dot", path]) == 0
    assert "digraph plan {" in capsys.readouterr().out


def test_bench_round_ingest(tmp_path):
    """Bench-round JSON lines load as pseudo-apps and compare against
    each other (the perf-trajectory use case)."""
    from spark_rapids_tpu.tools.history import (
        compare_applications,
        load_application,
    )

    r1 = {"metric": "tpch_q6_e2e_throughput",
          "tpu_s_per_query": 1.0, "q1_tpu_s_per_query": 4.0,
          "q1_retry_splits": 0, "rows": 100}
    r2 = dict(r1, tpu_s_per_query=2.0, q1_tpu_s_per_query=1.0)
    p1, p2 = str(tmp_path / "r1.json"), str(tmp_path / "r2.json")
    json.dump(r1, open(p1, "w"))
    json.dump(r2, open(p2, "w"))
    apps = [load_application(p1), load_application(p2)]
    assert [a.kind for a in apps] == ["bench", "bench"]
    result = compare_applications(apps, threshold=1.5)
    by_q = {r["query"]: r for r in result["rows"]}
    assert by_q["q6"]["flag"] == "regression"
    assert by_q["q1"]["flag"] == "improvement"


# ------------------------------------------------------------------ #
# Chaos: recovered faults + bit-identical results in the log
# ------------------------------------------------------------------ #


def test_chaos_run_records_recovery_with_identical_digest(tmp_path):
    from spark_rapids_tpu.robustness import faults
    from spark_rapids_tpu.tools.history import load_application

    conf = get_conf()
    conf.set("spark.rapids.tpu.sql.batchSizeRows", 256)
    t = _table(n=2048, seed=11)

    # fault-free baseline run
    clean = _logging_session(tmp_path)
    _agg(clean, t).collect(engine="tpu")
    clean_path = _drain(clean)

    # chaos run of the SAME query under an injected upload fault
    conf.set(DIR, str(tmp_path / "chaos"))
    chaos = TpuSession()
    faults.install("transfer.upload:nth=1", forced=True)
    try:
        _agg(chaos, t).collect(engine="tpu")
    finally:
        faults.disarm()
    chaos_path = _drain(chaos)

    q_clean = load_application(clean_path).queries[0]
    q_chaos = load_application(chaos_path).queries[0]
    assert q_chaos.counter("faults.injected") >= 1
    assert q_chaos.counter("faults.recovered") >= 1
    assert q_chaos.faults["transfer.upload"]["recovered"] >= 1
    assert q_clean.counter("faults.injected") == 0
    # recovery changed NOTHING: integer sums, deterministic order
    assert q_clean.result_digest == q_chaos.result_digest
    assert q_clean.rows == q_chaos.rows
    assert q_clean.engine == q_chaos.engine == "tpu"


# ------------------------------------------------------------------ #
# Disabled path: zero overhead
# ------------------------------------------------------------------ #


def test_disabled_is_zero_overhead(tmp_path, monkeypatch):
    """eventLog.enabled=false (the default): no writer object, no
    writer thread, no counter snapshots during collect — the whole
    per-query cost is _collect_tpu's one `is not None` check."""
    import spark_rapids_tpu.eventlog as EL

    conf = get_conf()
    assert conf.get(ENABLED) is False  # default-off
    calls = {"n": 0}
    real = EL.counters_snapshot

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(EL, "counters_snapshot", counting)
    session = TpuSession()
    assert session._eventlog is None
    assert session.event_log_path is None
    out = _agg(session, _table()).collect(engine="tpu")
    assert out.num_rows > 0
    _ = session.history.events
    assert calls["n"] == 0, "disabled path took a counter snapshot"
    assert not any("eventlog" in th.name.lower()
                   for th in threading.enumerate())
    assert not (tmp_path / "log").exists()


# ------------------------------------------------------------------ #
# QueryHistory timestamps / conf-epoch (satellite)
# ------------------------------------------------------------------ #


def test_history_event_timestamps_roundtrip_into_log(tmp_path):
    """QueryEvent now carries start/end monotonic+epoch and the conf
    hash; the event-log record must round-trip all five exactly."""
    import time

    from spark_rapids_tpu.eventlog.reader import read_log

    session = _logging_session(tmp_path)
    df = _agg(session, _table())
    _out, qid = df._collect_tpu()
    path = _drain(session)
    ev = next(e for e in session.history.events if e.query_id == qid)
    assert ev.start_ns > 0 and ev.end_ns >= ev.start_ns
    assert 0 < ev.start_ts <= ev.end_ts
    assert abs(ev.end_ts - time.time()) < 300
    assert ev.conf_hash
    _hdr, queries = read_log(path, strict=True)
    rec = next(r for r in queries if r["query_id"] == qid)
    for field in ("start_ts", "end_ts", "start_ns", "end_ns",
                  "conf_hash"):
        assert rec[field] == getattr(ev, field), field


# ------------------------------------------------------------------ #
# Trace integration: spans + sidecar pointer
# ------------------------------------------------------------------ #


def test_spans_and_trace_sidecar_recorded(tmp_path):
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.eventlog.reader import read_log

    session = _logging_session(tmp_path, **{SIDECAR: True})
    trace.enable()
    try:
        _agg(session, _table()).collect(engine="tpu")
        path = _drain(session)
    finally:
        trace.disable()
        trace.clear()
    _hdr, (rec,) = read_log(path, strict=True)
    assert rec["spans"], "span stats missing despite tracing on"
    assert any(op.startswith("Tpu") for op in rec["spans"]), rec["spans"]
    assert rec["trace_file"] and os.path.exists(rec["trace_file"])
    doc = json.load(open(rec["trace_file"]))
    assert doc["traceEvents"], "sidecar Chrome trace is empty"


# ------------------------------------------------------------------ #
# bench_smoke wiring (tier-1)
# ------------------------------------------------------------------ #


def test_bench_smoke_eventlog_matches_settled_metrics():
    """run_eventlog_smoke: reload-through-history per-operator metrics
    == the session's settled QueryHistory snapshot."""
    from spark_rapids_tpu.tools.bench_smoke import run_eventlog_smoke

    out = run_eventlog_smoke()
    assert out["eventlog"] > 0 and out["eventlog_operators"] >= 2


# ------------------------------------------------------------------ #
# analyze footer (satellite): PR6 + PR5 counters ride along
# ------------------------------------------------------------------ #


def test_explain_analyze_footer_has_recovery_and_rf_counters():
    session = TpuSession()
    out = _agg(session, _table()).explain("analyze")
    assert "jit cache:" in out
    assert "retry: splits=0" in out, out
    assert "cpu_fallbacks=0" in out
    assert "recovered_faults=0" in out
    assert "runtime filters: built=0" in out


def test_explain_analyze_footer_counts_recovered_faults():
    from spark_rapids_tpu.robustness import faults

    session = TpuSession()
    df = _agg(session, _table(seed=23))
    faults.install("transfer.upload:nth=1", forced=True)
    try:
        out = df.explain("analyze")
    finally:
        faults.disarm()
    assert "recovered_faults=1" in out, out
