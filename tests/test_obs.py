"""Live ops plane (spark_rapids_tpu/obs/, docs/ops_plane.md).

Covers the PR's acceptance surface:
- disabled by default: no thread, no socket, no registry entry — a
  collect under the default conf pays one conf read and nothing else;
- the bench_smoke ops contract wired into tier-1: a real HTTP scrape
  of /metrics parses as OpenMetrics and parity-matches the in-process
  eventlog counters_snapshot, /queries empties after the query, and
  the owning conf's off leaves no tpu-obs-* thread and a refused
  socket;
- live registry mid-stream: an in-flight streamed query is visible
  under /queries and /queries/<id> (rendered plan, batches-so-far)
  while the stream is being drained, and deregisters on exhaustion;
- the SLO watchdog loop end to end: a breached wall budget emits
  `slo` event-log records (strict-schema validated), loads back
  through tools/history into ApplicationInfo.slo, raises the HC016
  health finding, and serves at /slo.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import obs
from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.obs.slo import WATCHDOG
from spark_rapids_tpu.session import TpuSession, col, sum_

OBS_ENABLED = "spark.rapids.tpu.obs.enabled"
OBS_PORT = "spark.rapids.tpu.obs.port"
SLO_WALL = "spark.rapids.tpu.obs.slo.wallBudgetMs"
SLO_INTERVAL = "spark.rapids.tpu.obs.slo.checkIntervalMs"
EL_ENABLED = "spark.rapids.tpu.eventLog.enabled"
EL_DIR = "spark.rapids.tpu.eventLog.dir"


def _obs_threads() -> list[str]:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("tpu-obs")]


def _table(n: int = 4096, seed: int = 0x0B5) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 16, n).astype(np.int64),
        "v": rng.random(n),
    })


def _agg(session: TpuSession, t: pa.Table):
    return (session.create_dataframe(t)
            .group_by(col("k"))
            .agg((sum_(col("v")), "sv"))
            .order_by(col("k")))


def _get_json(url: str):
    return json.loads(
        urllib.request.urlopen(url, timeout=10).read().decode())


def test_disabled_by_default_no_thread_no_registry():
    """The whole disabled-path cost is one conf read: a collect under
    the default conf must leave the plane off, the registry empty and
    no tpu-obs-* thread alive."""
    session = TpuSession()
    result = _agg(session, _table()).collect(engine="tpu")
    assert result.num_rows == 16
    assert not obs.is_enabled()
    assert obs.plane().port is None
    assert obs.REGISTRY.count() == 0
    assert not obs.REGISTRY.enabled
    assert _obs_threads() == []


def test_ops_smoke_tier1():
    """The bench_smoke contract in the fast tier: scrape == snapshot
    parity, registry empties, conf off leaves no thread/socket."""
    from spark_rapids_tpu.tools.bench_smoke import run_ops_smoke

    out = run_ops_smoke()
    assert out["ops_rows"] == 16
    assert out["ops_scrape_families"] > 0
    assert out["ops_parity_counters"] > 0
    assert out["ops_stopped_clean"] is True


def test_live_registry_visible_mid_stream():
    """An in-flight streamed query shows under /queries with its
    rendered plan and batches-so-far, then deregisters when the
    stream drains (the /queries/<id> 404 afterwards)."""
    conf = get_conf()
    saved_batch = conf.get("spark.rapids.tpu.sql.batchSizeRows")
    obs.start(port=0)  # forced: survives the sessions' sync_conf
    try:
        conf.set("spark.rapids.tpu.sql.batchSizeRows", 512)
        session = TpuSession(tenant="streamer")
        pq = session.prepare(_agg(session, _table()))
        gen = pq.execute_stream()
        first = next(gen)  # at least one batch retired, still in flight
        assert first.num_rows > 0
        assert obs.REGISTRY.count() == 1
        snap = obs.REGISTRY.snapshot()
        assert len(snap) == 1
        entry = snap[0]
        qid = entry["query_id"]
        assert entry["tenant"] == "streamer"
        assert entry["batches"] >= 1
        assert entry["elapsed_ms"] >= 0
        assert "plan" not in entry  # list view elides plans

        base = f"http://127.0.0.1:{obs.plane().port}"
        wire = _get_json(base + "/queries")
        assert [e["query_id"] for e in wire] == [qid]
        one = _get_json(base + f"/queries/{qid}")
        assert one["query_id"] == qid
        assert one["plan"], "detail view is missing the rendered plan"
        assert one["plan_hash"]

        rest = list(gen)  # drain: the epilogue deregisters
        assert first.num_rows + sum(b.num_rows for b in rest) == 16
        assert obs.REGISTRY.count() == 0
        assert _get_json(base + "/queries") == []
        try:
            urllib.request.urlopen(base + f"/queries/{qid}",
                                   timeout=10)
            raise AssertionError("finished query still served")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # liveness probe while we're here
        body = urllib.request.urlopen(
            base + "/healthz", timeout=10).read().decode()
        assert body == "ok\n"
    finally:
        conf.set("spark.rapids.tpu.sql.batchSizeRows", saved_batch)
        obs.stop()
    assert _obs_threads() == []


def test_scrape_under_storm_monotone_and_zero_impact():
    """The scrape-under-storm contract: /metrics scraped concurrently
    with running queries never shows a monotone counter stepping
    backwards, and the scraped
    queries' digests stay bit-identical to the obs-off reference."""
    from spark_rapids_tpu.eventlog import MONOTONIC_COUNTERS, \
        table_digest
    from spark_rapids_tpu.obs import metrics as om

    t = _table()
    ref = table_digest(
        _agg(TpuSession(), t).collect(engine="tpu"))  # plane off
    assert not obs.is_enabled()

    obs.start(port=0)
    try:
        stop = threading.Event()
        violations: list = []
        scrapes = [0]
        digests: list = []
        errors: list = []

        def scraper() -> None:
            base = f"http://127.0.0.1:{obs.plane().port}"
            prev: dict = {}
            while True:
                try:
                    parsed = om.parse_openmetrics(
                        urllib.request.urlopen(
                            base + "/metrics",
                            timeout=10).read().decode())
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                    return
                for key in MONOTONIC_COUNTERS:
                    v = om.scrape_value(
                        parsed, om.counter_metric_name(key))
                    if v is None:
                        continue
                    if key in prev and v < prev[key]:
                        violations.append((key, prev[key], v))
                    prev[key] = v
                scrapes[0] += 1
                if stop.wait(0.005):
                    return

        def worker() -> None:
            try:
                s = TpuSession()
                for _ in range(2):
                    digests.append(table_digest(
                        _agg(s, t).collect(engine="tpu")))
            except BaseException as e:  # noqa: BLE001 — reported
                errors.append(repr(e))

        ths = [threading.Thread(target=worker) for _ in range(2)]
        sth = threading.Thread(target=scraper)
        sth.start()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        stop.set()
        sth.join()
        assert not errors, errors
        assert scrapes[0] >= 1, "scraper never completed a scrape"
        assert not violations, (
            f"monotone counter stepped backwards: {violations}")
        assert set(digests) == {ref}, \
            "scraping changed query results vs the obs-off reference"
    finally:
        obs.stop()
    assert _obs_threads() == []


def test_slo_breach_lands_in_eventlog_and_hc016(tmp_path):
    """The watchdog loop end to end: an impossible wall budget
    (0.001ms) must breach on the first completed query; the breach is
    returned by evaluate_now(), appended to the session event log as a
    strict-schema-valid `slo` record, served at /slo, loaded back by
    tools/history and flagged by the HC016 health rule."""
    from spark_rapids_tpu.eventlog.reader import iter_records
    from spark_rapids_tpu.tools.history import (
        health_check,
        load_application,
    )

    conf = get_conf()
    keys = (OBS_ENABLED, OBS_PORT, SLO_WALL, SLO_INTERVAL,
            EL_ENABLED, EL_DIR)
    saved = {k: conf.get(k) for k in keys}
    try:
        conf.set(OBS_ENABLED, True)
        conf.set(OBS_PORT, 0)
        conf.set(SLO_WALL, 0.001)  # every real query breaches
        # park the watchdog thread: the test drives evaluate_now()
        # itself, so breach counts stay deterministic
        conf.set(SLO_INTERVAL, 600000.0)
        conf.set(EL_ENABLED, True)
        conf.set(EL_DIR, str(tmp_path / "log"))
        session = TpuSession(tenant="slower")
        _agg(session, _table()).collect(engine="tpu")
        # reading events drains the snapshot worker (query record is
        # in the file before the breach record we emit next)
        assert session.history.events[-1].query_id is not None

        breaches = WATCHDOG.evaluate_now()
        assert breaches, "0.001ms budget did not breach"
        b = breaches[0]
        assert b["tenant"] == "slower"
        assert b["metric"] == "wall_p99_ms"
        assert b["observed_ms"] > b["budget_ms"] == 0.001

        snap = WATCHDOG.snapshot()
        assert snap["budgets"]["wall_p99_ms"] == 0.001
        assert snap["breach_count"] >= 1
        assert snap["tenants"]["slower"]["n"] >= 1
        wire = _get_json(
            f"http://127.0.0.1:{obs.plane().port}/slo")
        assert wire["breach_count"] >= 1
        assert wire["budgets"]["wall_p99_ms"] == 0.001

        # file surface: strict schema + history + HC016
        path = session.event_log_path
        recs = list(iter_records(path, strict=True))
        slo_recs = [r for r in recs if r["type"] == "slo"]
        assert slo_recs, "no slo record in the event log"
        assert slo_recs[0]["tenant"] == "slower"
        assert slo_recs[0]["metric"] == "wall_p99_ms"
        assert slo_recs[0]["observed_ms"] > 0.001

        app = load_application(path)
        assert app.slo, "history did not load the slo records"
        hc016 = [f for f in health_check(app) if f.rule == "HC016"]
        assert hc016, "HC016 did not fire on a breached run"
        assert hc016[0].severity == "warning"
        assert "tenant:slower" in hc016[0].query
    finally:
        for k, v in saved.items():
            conf.set(k, v)
        obs.sync_conf(conf)  # the owning conf's off stops the plane
        obs.stop()
        WATCHDOG.reset()
    assert _obs_threads() == []
