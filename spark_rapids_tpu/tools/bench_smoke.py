"""Bench smoke: one tiny query per hot exec (join, aggregate,
exchange), each collected with speculative sizing ON and OFF, asserting
result equality.

The acceptance contract of the speculation layer is that it is a pure
latency optimization — `speculation.enabled=false` must reproduce the
same results bit-for-bit.  This driver is the cheap CI hook for that
contract: `scripts/bench_smoke.sh` runs it standalone, and
`tests/test_speculation.py::test_bench_smoke_queries_match` runs the
same function inside the tier-1 `not slow` suite.

`run_rf_smoke` holds the twin contract for runtime join filters
(plan/runtime_filter.py): a parquet-backed q3-shaped join must return
identical rows with `runtimeFilter.enabled` on and off, AND must have
actually pruned probe rows when on (tier-1 via
tests/test_runtime_filter.py).

`run_eventlog_smoke` holds the persistence contract for the event log
(spark_rapids_tpu/eventlog/): a query collected with
`eventLog.enabled` must reload through tools/history with per-operator
metrics identical to the session's settled QueryHistory snapshot
(tier-1 via tests/test_eventlog.py).

`run_ledger_smoke` holds the device-ledger contract
(spark_rapids_tpu/trace/ledger.py, docs/device_ledger.md): a tiny
query collected with `trace.ledger.enabled` must attribute at least
one program with nonzero cost-model bytes and dispatch count, and the
attributed device time must stay within the query wall (tier-1 via
tests/test_ledger.py).

`run_serving_smoke` holds the serving-tier contract
(spark_rapids_tpu/serving/, docs/serving.md): a prepared template's
second execution is a plan-cache hit that never re-enters plan_query,
a streamed fetch equals collect() to the bit, and two sessions under
maxConcurrent=1 admission both complete with identical digests
(tier-1 via tests/test_serving.py).

`run_ops_smoke` holds the live ops-plane contract
(spark_rapids_tpu/obs/, docs/ops_plane.md): with `obs.enabled` a real
HTTP scrape of /metrics must parse as OpenMetrics and EQUAL the
in-process counters_snapshot (the registry-adapter parity gate), the
live query registry must empty back to zero after the query, and
turning the conf off must leave no ops thread and no listening socket
(tier-1 via tests/test_obs.py).

`run_sharing_smoke` holds the cross-tenant work-sharing contract
(serving/work_share.py, docs/work_sharing.md): a second session's
identical parquet-backed template performs ZERO scan decodes (tapped
counter), its digest is bit-identical to sharing-off and to serial,
and rewriting the input file invalidates the cached result on the
content-digest change (tier-1 via tests/test_work_share.py).

Run: python -m spark_rapids_tpu.tools.bench_smoke
"""

from __future__ import annotations


def _queries(session):
    """(name, DataFrame) per hot exec, tiny enough for seconds-scale
    CPU runs but multi-batch so the stream loops actually stream."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.session import col, sum_

    rng = np.random.default_rng(0x5BEC)
    n = 4096
    lineitem = pa.table({
        "k": rng.integers(0, 64, n).astype(np.int64),
        "v": rng.random(n),
    })
    dim = pa.table({
        "k": np.arange(64, dtype=np.int64),
        "w": rng.integers(0, 9, 64).astype(np.int64),
    })
    li = session.create_dataframe(lineitem)
    joined = li.join(session.create_dataframe(dim),
                     left_on=[col("k")], right_on=[col("k")])
    yield "join", joined
    yield "aggregate", li.group_by(col("k")).agg((sum_(col("v")), "sv"))
    # the grouped aggregate above plans partial -> exchange -> final;
    # an ORDER BY adds the range-partitioned exchange shape too
    yield "exchange", (li.group_by(col("k"))
                       .agg((sum_(col("v")), "sv"))
                       .order_by(col("k")))


def _assert_rows_match(name: str, on, off) -> None:
    """Row-set equality with float tolerance: the engine documents
    run-to-run float aggregation order variability
    (spark.rapids.tpu.sql.variableFloatAgg.enabled), so exact float
    equality would flake at the ULP level regardless of speculation."""
    assert on.num_rows == off.num_rows, (name, on.num_rows,
                                         off.num_rows)
    on_rows = sorted(map(tuple, zip(*on.to_pydict().values())))
    off_rows = sorted(map(tuple, zip(*off.to_pydict().values())))
    for a, b in zip(on_rows, off_rows):
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert abs(x - y) <= 1e-9 * max(1.0, abs(y)), \
                    f"{name}: speculation on/off results differ: {a} {b}"
            else:
                assert x == y, \
                    f"{name}: speculation on/off results differ: {a} {b}"


def count_upload_rows(df) -> int:
    """One TPU collect with ParquetScanExec._upload tapped: total rows
    actually crossing the host->device wire — the number runtime join
    filters exist to shrink (the test-suite acceptance assertions
    read it)."""
    import spark_rapids_tpu.io.scan as scan_mod

    counted = [0]
    orig = scan_mod.ParquetScanExec._upload

    def upload(inner_self, tables):
        counted[0] += sum(t.num_rows for t in tables
                          if not isinstance(t, int))
        return orig(inner_self, tables)

    scan_mod.ParquetScanExec._upload = upload
    try:
        df.collect(engine="tpu")
    finally:
        scan_mod.ParquetScanExec._upload = orig
    return counted[0]


def count_upload_bytes(df) -> int:
    """One TPU collect over the tapped batched-upload counter
    (columnar/transfer.upload_stats): total bytes actually crossing
    the H2D wire — compressed components count their packed size, so
    the wire-codec on/off delta IS the bytes the codec kept off the
    slow link (the wire-codec acceptance tests read it)."""
    from spark_rapids_tpu.columnar import transfer

    transfer.reset_upload_stats()
    df.collect(engine="tpu")
    return transfer.upload_stats()["wire_bytes"]


def run_rf_smoke() -> dict:
    """Runtime-filter acceptance contract, cheap CI form: a q3-shaped
    parquet join (date-filtered build side, larger probe side)
    collected with spark.rapids.tpu.sql.runtimeFilter.enabled on and
    off must return identical rows — the filter is a pure IO
    optimization.  With filters on, the probe scan must actually have
    pruned rows (asserted via the runtime_filter stats registry), so
    the q3 win this subsystem targets stays measurable."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.plan import runtime_filter
    from spark_rapids_tpu.session import TpuSession, col, sum_

    key = "spark.rapids.tpu.sql.runtimeFilter.enabled"
    conf = get_conf()
    saved = conf.get(key)
    session = TpuSession()
    out: dict = {}
    rng = np.random.default_rng(0xF11)
    with tempfile.TemporaryDirectory(prefix="rf_smoke_") as d:
        n = 8192
        li = pa.table({
            "l_orderkey": rng.integers(0, 512, n).astype(np.int64),
            "l_price": rng.random(n),
        })
        li_path = os.path.join(d, "li.parquet")
        pq.write_table(li, li_path, row_group_size=2048)
        orders = pa.table({
            "o_orderkey": np.arange(512, dtype=np.int64),
            "o_date": rng.integers(0, 100, 512).astype(np.int32),
        })
        o_path = os.path.join(d, "orders.parquet")
        pq.write_table(orders, o_path)

        def q():
            lidf = session.read_parquet(li_path)
            odf = (session.read_parquet(o_path)
                   .where(col("o_date") < lit(20)))
            return (lidf.join(odf, left_on=[col("l_orderkey")],
                              right_on=[col("o_orderkey")])
                    .group_by(col("l_orderkey"))
                    .agg((sum_(col("l_price")), "rev")))

        try:
            conf.set(key, True)
            runtime_filter.reset_stats()
            on = q().collect(engine="tpu")
            st = runtime_filter.stats()
            assert st["filters_built"] >= 1, \
                "runtime filter did not build on the q3-shaped join"
            assert st["pruned_rows"] > 0, \
                "runtime filter pruned nothing on a selective build"
            conf.set(key, False)
            off = q().collect(engine="tpu")
            _assert_rows_match("runtime_filter", on, off)
            out["runtime_filter"] = on.num_rows
            out["runtime_filter_pruned_rows"] = st["pruned_rows"]
        finally:
            conf.set(key, saved)
    return out


def run_eventlog_smoke() -> dict:
    """Event-log acceptance contract, cheap CI form (tier-1 via
    tests/test_eventlog.py): a tiny grouped aggregate collected with
    ``spark.rapids.tpu.eventLog.enabled`` must produce a log that
    reloads through tools/history into an ApplicationInfo whose
    per-operator metric tree EQUALS the session's settled QueryHistory
    snapshot — what the file says must be what the process measured."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.session import TpuSession, col, sum_
    from spark_rapids_tpu.tools.history import load_application

    conf = get_conf()
    keys = ("spark.rapids.tpu.eventLog.enabled",
            "spark.rapids.tpu.eventLog.dir")
    saved = {k: conf.get(k) for k in keys}
    out: dict = {}
    with tempfile.TemporaryDirectory(prefix="eventlog_smoke_") as d:
        try:
            conf.set(keys[0], True)
            conf.set(keys[1], os.path.join(d, "log"))
            session = TpuSession()
            rng = np.random.default_rng(0xE7)
            n = 2048
            t = pa.table({
                "k": rng.integers(0, 32, n).astype(np.int64),
                "v": rng.random(n),
            })
            df = (session.create_dataframe(t)
                  .group_by(col("k"))
                  .agg((sum_(col("v")), "sv")))
            result = df.collect(engine="tpu")
            # reading events DRAINS the snapshot worker, which also
            # appends the event-log record — the file is complete now
            ev = session.history.events[-1]
            app = load_application(session.event_log_path)
            assert app.header, "event log is missing its header record"
            assert len(app.queries) == 1, len(app.queries)
            q = app.queries[0]
            assert q.query_id == ev.query_id, (q.query_id, ev.query_id)
            assert q.rows == result.num_rows, (q.rows, result.num_rows)
            assert q.conf_hash == ev.conf_hash and q.conf_hash

            def check(node, snap):
                assert node.desc == snap.desc, (node.desc, snap.desc)
                assert node.metrics == snap.metrics, \
                    (node.desc, node.metrics, snap.metrics)
                assert len(node.children) == len(snap.children)
                for c, sc in zip(node.children, snap.children):
                    check(c, sc)

            check(q.operators, ev.root)
            out["eventlog"] = q.rows
            out["eventlog_operators"] = sum(
                1 for _ in q.operators.walk())
        finally:
            for k, v in saved.items():
                conf.set(k, v)
    return out


def run_serving_smoke() -> dict:
    """Serving-tier acceptance contract, cheap CI form (tier-1 via
    tests/test_serving.py): two concurrent sessions under admission
    control (maxConcurrent=1, so one of them measurably waits), a
    prepared SQL template whose SECOND execution is a plan-cache hit
    that performs no plan/tag/lower work, and a streamed fetch whose
    concatenation equals collect() to the bit."""
    import threading

    import pyarrow as pa

    import numpy as np

    from spark_rapids_tpu.config import TpuConf, get_conf, set_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.frontends.sql import SqlSession
    from spark_rapids_tpu.serving import plan_cache as plan_cache_mod
    from spark_rapids_tpu.serving import scheduler as scheduler_mod
    from spark_rapids_tpu.plan import planner as planner_mod

    rng = np.random.default_rng(0x5E17)
    n = 4096
    t = pa.table({
        "k": rng.integers(0, 32, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })
    out: dict = {}
    base = dict(get_conf()._values)
    scheduler_mod.reset()
    plan_cache_mod.reset_stats()
    try:
        # -- prepared SQL template: second execution must be a HIT
        # that never re-enters plan_query -------------------------- #
        conf = TpuConf(base)
        set_conf(conf)
        ss = SqlSession(conf)
        ss.register_table("t", t)
        pq = ss.prepare("select k, sum(v) as sv, count(*) as n from t "
                        "where k < :kmax group by k order by k")
        first = pq.execute(params={"kmax": 16})
        calls = [0]
        orig_plan_query = planner_mod.plan_query

        def counting_plan_query(*a, **kw):
            calls[0] += 1
            return orig_plan_query(*a, **kw)

        # patch EVERY import binding: session.py binds plan_query at
        # module level, so patching only the planner module would let
        # a hit path that regressed to re-lowering pass unobserved
        import spark_rapids_tpu.session as session_mod

        planner_mod.plan_query = counting_plan_query
        session_mod.plan_query = counting_plan_query
        try:
            second = pq.execute(params={"kmax": 16})
        finally:
            planner_mod.plan_query = orig_plan_query
            session_mod.plan_query = orig_plan_query
        assert calls[0] == 0, \
            f"plan-cache hit re-lowered the template ({calls[0]}x)"
        assert table_digest(first) == table_digest(second)
        pc = plan_cache_mod.stats()
        assert pc["hits"] >= 1, pc
        out["serving_plan_cache_hits"] = pc["hits"]

        # -- stream == collect, to the bit ------------------------- #
        batches = list(pq.execute_stream(params={"kmax": 16}))
        stream_tbl = pa.Table.from_batches(batches,
                                           schema=first.schema)
        assert table_digest(stream_tbl) == table_digest(first), \
            "streamed result != collected result"
        out["serving_stream_rows"] = stream_tbl.num_rows

        # -- two sessions, one admission slot ---------------------- #
        over = dict(base)
        over["spark.rapids.tpu.serving.maxConcurrent"] = 1
        over["spark.rapids.tpu.serving.queueDepth"] = 8
        scheduler_mod.reset()
        results: list = []
        errors: list = []

        def run(i: int) -> None:
            try:
                c = TpuConf(over)
                set_conf(c)
                from spark_rapids_tpu.session import TpuSession, col
                from spark_rapids_tpu.session import sum_ as _sum

                sess = TpuSession(c, tenant=f"tenant{i}")
                df = (sess.create_dataframe(t)
                      .group_by(col("k"))
                      .agg((_sum(col("v")), "sv"))
                      .order_by(col("k")))
                spq = sess.prepare(df)
                for _ in range(3):
                    results.append(table_digest(spq.execute()))
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        ths = [threading.Thread(target=run, args=(i,))
               for i in range(2)]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        assert not errors, errors
        assert len(set(results)) == 1, \
            "concurrent sessions produced diverging results"
        st = scheduler_mod.scheduler_stats()
        assert st["admitted"] >= 6, st
        assert st["rejected"] == 0, st
        out["serving_admitted"] = st["admitted"]
    finally:
        conf = get_conf()
        conf._values.clear()
        conf._values.update(base)
        set_conf(conf)
        scheduler_mod.reset()
    return out


def run_sharing_smoke() -> dict:
    """Cross-tenant work-sharing acceptance contract, cheap CI form
    (tier-1 via tests/test_work_share.py; docs/work_sharing.md): two
    sessions execute the same parquet-backed golden template —

    - the second execution performs ZERO scan decodes (the tapped
      scan_units_decoded counter stays flat: it is served from the
      process-wide result cache);
    - its digest is bit-identical to the sharing-off run and to the
      serial reference (sharing must be invisible in the bytes);
    - a content-mutation probe rewrites the input file and proves the
      cache INVALIDATES on digest change: the next execution decodes
      again and returns the new file's answer."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq_mod

    from spark_rapids_tpu.config import TpuConf, get_conf, set_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.serving import work_share as ws
    from spark_rapids_tpu.session import TpuSession, col, count_star
    from spark_rapids_tpu.session import sum_ as _sum

    def _template(session, path):
        return (session.read_parquet(path)
                .group_by(col("k"))
                .agg((_sum(col("v")), "sv"), (count_star(), "n"))
                .order_by(col("k")))

    def _write(path, seed: int) -> None:
        rng = np.random.default_rng(seed)
        n = 8192
        pq_mod.write_table(pa.table({
            "k": rng.integers(0, 16, n).astype(np.int64),
            "v": rng.integers(0, 1000, n).astype(np.int64),
        }), path)

    out: dict = {}
    base = dict(get_conf()._values)
    ws.reset()
    try:
        with tempfile.TemporaryDirectory(prefix="share_smoke_") as d:
            path = os.path.join(d, "t.parquet")
            _write(path, seed=0x5A5A)

            # serial sharing-off reference: THE ground truth
            off_conf = TpuConf(base)
            set_conf(off_conf)
            d_serial = table_digest(
                _template(TpuSession(off_conf), path)
                .collect(engine="tpu"))

            on = dict(base)
            on["spark.rapids.tpu.serving.sharing.enabled"] = True

            # session 1 (sharing on): decodes + populates the cache
            c1 = TpuConf(on)
            set_conf(c1)
            d1 = table_digest(
                _template(TpuSession(c1, tenant="a"), path)
                .collect(engine="tpu"))
            assert d1 == d_serial, \
                "sharing-on digest != serial sharing-off digest"
            st1 = ws.stats()
            assert st1["scan_units_decoded"] >= 1, st1
            assert st1["result_inserts"] >= 1, st1

            # session 2, same template: served from the result cache
            # with ZERO scan decodes (the tapped counter stays flat)
            c2 = TpuConf(on)
            set_conf(c2)
            d2 = table_digest(
                _template(TpuSession(c2, tenant="b"), path)
                .collect(engine="tpu"))
            st2 = ws.stats()
            assert d2 == d_serial, \
                "second session's digest != serial digest"
            assert st2["result_hits"] == st1["result_hits"] + 1, \
                (st1, st2)
            assert st2["scan_units_decoded"] == \
                st1["scan_units_decoded"], (
                    "result-cache hit decoded scan units", st1, st2)
            out["sharing_second_exec_decodes"] = (
                st2["scan_units_decoded"]
                - st1["scan_units_decoded"])
            out["sharing_result_hits"] = st2["result_hits"]

            # content-mutation probe: rewrite the file — the stale
            # entry must invalidate on the digest change, and the
            # fresh execution must answer for the NEW content
            _write(path, seed=0xB0B0)
            set_conf(off_conf)
            d_serial2 = table_digest(
                _template(TpuSession(off_conf), path)
                .collect(engine="tpu"))
            assert d_serial2 != d_serial, \
                "mutation probe wrote identical content"
            set_conf(c2)
            d3 = table_digest(
                _template(TpuSession(c2, tenant="b"), path)
                .collect(engine="tpu"))
            st3 = ws.stats()
            assert d3 == d_serial2, \
                "post-mutation digest != fresh serial digest"
            assert st3["result_invalidations"] >= 1, st3
            assert st3["scan_units_decoded"] > \
                st2["scan_units_decoded"], (
                    "post-mutation execution did not re-decode", st3)
            out["sharing_invalidations"] = st3["result_invalidations"]
    finally:
        conf = get_conf()
        conf._values.clear()
        conf._values.update(base)
        set_conf(conf)
        ws.reset()
    return out


def run_ledger_smoke() -> dict:
    """Device-ledger acceptance contract, cheap CI form (tier-1 via
    tests/test_ledger.py): a tiny grouped aggregate collected with the
    ledger on must attribute >=1 program with a nonzero cost-model
    byte count AND a nonzero dispatch count, and the sum of attributed
    device time must not exceed the query's wall clock (attribution
    may under-count — dispatch gaps are real — but it must never
    invent device time; the ledger credits EXCLUSIVE busy intervals,
    so overlapping async-dispatch windows cannot double-count the one
    chip).  Pipelining/speculation are pinned OFF so the stream loop
    stays serial, and the wall is measured through the settle flush —
    every credited interval lies inside the measured window."""
    import time

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.session import TpuSession, col, sum_
    from spark_rapids_tpu.trace import ledger

    conf = get_conf()
    keys = ("spark.rapids.tpu.trace.ledger.enabled",
            "spark.rapids.tpu.sql.pipeline.enabled",
            "spark.rapids.tpu.sql.speculation.enabled")
    saved = {k: conf.get(k) for k in keys}
    out: dict = {}
    try:
        conf.set(keys[0], True)
        conf.set(keys[1], False)
        conf.set(keys[2], False)
        ledger.reset_stats()
        session = TpuSession()
        rng = np.random.default_rng(0x1ED6)
        n = 4096
        t = pa.table({
            "k": rng.integers(0, 32, n).astype(np.int64),
            "v": rng.random(n),
        })
        df = (session.create_dataframe(t)
              .group_by(col("k"))
              .agg((sum_(col("v")), "sv")))
        t0 = time.perf_counter()
        result = df.collect(engine="tpu")
        assert ledger.LEDGER.flush(timeout=30.0), \
            "ledger settlement did not drain"
        wall_ms = (time.perf_counter() - t0) * 1e3
        s = ledger.summarize(ledger.snapshot())
        progs = s["programs"]
        assert progs, "ledger recorded no programs"
        assert any(p["dispatches"] > 0 and p["bytes_accessed"] > 0
                   for p in progs.values()), \
            f"no program has cost-model bytes + dispatches: {progs}"
        total = s["totals"]
        assert total["device_ms"] <= wall_ms, (
            f"attributed device time {total['device_ms']}ms exceeds "
            f"the query wall {wall_ms:.1f}ms")
        out["ledger_programs"] = total["programs"]
        out["ledger_dispatches"] = total["dispatches"]
        out["ledger_rows"] = result.num_rows
    finally:
        for k, v in saved.items():
            conf.set(k, v)
        ledger.reset_stats()
        if not ledger.LEDGER.forced:
            # conf-owned enable from this smoke: drop it now instead
            # of waiting for the next query boundary (a FORCED enable
            # belongs to someone else — leave it alone)
            ledger.disable()
    return out


def run_wire_codec_smoke() -> dict:
    """Wire-compression acceptance contract, cheap CI form (tier-1 via
    tests/test_wire_compression.py): a q3-shaped scan->join->aggregate
    over a COMPRESSIBLE parquet fixture must return bit-identical rows
    with spark.rapids.tpu.sql.wireCompression on and off (the codec is
    lossless re-encoding, never approximation), and with compression
    on the tapped upload counter must show ratio > 1 — fewer bytes
    actually crossed the H2D wire.  Aggregates are integer-exact
    (sums of integers, counts) with pinned output order, so the
    equality gate is bit-for-bit, not tolerance-based."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import TpuSession, col, count_star, sum_

    key = "spark.rapids.tpu.sql.wireCompression.enabled"
    conf = get_conf()
    saved = conf.get(key)
    session = TpuSession()
    out: dict = {}
    rng = np.random.default_rng(0xC0DEC)
    with tempfile.TemporaryDirectory(prefix="wire_codec_smoke_") as d:
        n = 1 << 15
        # q3 shape, deliberately compressible the way real fact tables
        # are: clustered keys, sorted dates, small-range quantities
        li = pa.table({
            "l_orderkey": np.sort(rng.integers(0, 2048, n)).astype(
                np.int64),
            "l_shipdate": np.sort(rng.integers(8766, 10957, n)).astype(
                np.int32),
            "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        })
        li_path = os.path.join(d, "li.parquet")
        pq.write_table(li, li_path, row_group_size=n)
        orders = pa.table({
            "o_orderkey": np.arange(2048, dtype=np.int64),
            "o_priority": rng.integers(0, 5, 2048).astype(np.int32),
        })
        o_path = os.path.join(d, "orders.parquet")
        pq.write_table(orders, o_path)

        def q():
            lidf = (session.read_parquet(li_path)
                    .where(col("l_shipdate") > lit(9000)))
            odf = session.read_parquet(o_path)
            return (lidf.join(odf, left_on=[col("l_orderkey")],
                              right_on=[col("o_orderkey")])
                    .group_by(col("o_priority"))
                    .agg((sum_(col("l_quantity")), "qty"),
                         (count_star(), "cnt"))
                    .order_by(col("o_priority")))

        try:
            conf.set(key, True)
            on_bytes = count_upload_bytes(q())
            on = q().collect(engine="tpu")
            conf.set(key, False)
            off_bytes = count_upload_bytes(q())
            off = q().collect(engine="tpu")
        finally:
            conf.set(key, saved)
    assert on.to_pydict() == off.to_pydict(), (
        "wire compression changed query results: "
        f"{on.to_pydict()} != {off.to_pydict()}")
    ratio = off_bytes / max(on_bytes, 1)
    assert ratio > 1.0, (
        f"wire compression saved nothing on a compressible fixture: "
        f"{off_bytes} raw vs {on_bytes} compressed")
    out["wire_codec_rows"] = on.num_rows
    out["wire_codec_upload_ratio"] = round(ratio, 2)
    return out


def run_fusion_smoke() -> dict:
    """Whole-stage fusion acceptance contract, cheap CI form (tier-1
    via tests/test_fusion.py, docs/fusion.md): a q1-shaped
    scan->filter->agg parquet query, multi-batch, run with the device
    ledger on.

    - the WARM pass (second fusion-enabled collect) compiles nothing:
      0 jit-cache misses in its window;
    - the warm pass dispatches STRICTLY fewer ledger programs than the
      unfused baseline (`spark.rapids.tpu.sql.fusion.enabled=false`) —
      decode+filter+agg-update collapse into one program per batch;
    - results are bit-identical across fusion on, fusion off, and
      donation on (the three-way digest gate);
    - the warm dispatch count respects the conf budget
      (`spark.rapids.tpu.sql.fusion.warmDispatchBudget`) — the
      regression gate ROADMAP #2's dispatch-soup diagnosis asked for.

    Returns the warm/unfused dispatch counts, the warm roofline
    fraction and the top-programs footer so callers (and the committed
    smoke artifact) can show WHERE the device time went."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.execs.base import fusion_stats, \
        reset_fusion_stats
    from spark_rapids_tpu.execs.jit_cache import cache_stats
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import TpuSession, col, count_star, sum_
    from spark_rapids_tpu.trace import ledger

    # force-register the lazily-registered fusion confs BEFORE the
    # save/restore snapshot: saving an unregistered key reads None,
    # and restoring that None would permanently shadow the registered
    # default for the rest of the process
    from spark_rapids_tpu.execs.base import _budget_conf, _fusion_conf

    _fusion_conf()
    _budget_conf()
    conf = get_conf()
    keys = ("spark.rapids.tpu.sql.fusion.enabled",
            "spark.rapids.tpu.sql.fusion.donation.enabled",
            "spark.rapids.tpu.sql.pipeline.enabled",
            "spark.rapids.tpu.sql.speculation.enabled",
            "spark.rapids.tpu.sql.batchSizeRows",
            "spark.rapids.tpu.sql.shuffle.partitions")
    saved = {k: conf.get(k) for k in keys}
    out: dict = {}
    ledger_was_on = ledger.LEDGER.enabled
    rng = np.random.default_rng(0xF05E)
    with tempfile.TemporaryDirectory(prefix="fusion_smoke_") as d:
        n = 1 << 14
        t = pa.table({
            # q1 shape: date filter, string-ish group keys (small int
            # domain stands in — keeps the fixture seconds-scale),
            # summed measures
            "l_shipdate": rng.integers(8766, 10957, n).astype(np.int32),
            "l_key": rng.integers(0, 4, n).astype(np.int64),
            "l_quantity": rng.integers(1, 51, n).astype(np.int64),
            "l_price": rng.integers(900, 105000, n).astype(np.int64),
        })
        path = os.path.join(d, "li.parquet")
        pq.write_table(t, path, row_group_size=n // 4)

        def q(session):
            return (session.read_parquet(path)
                    .where(col("l_shipdate") <= lit(10471))
                    .group_by(col("l_key"))
                    .agg((sum_(col("l_quantity")), "sum_qty"),
                         (sum_(col("l_price")), "sum_price"),
                         (count_star(), "n"))
                    .order_by(col("l_key")))

        def collect_counted(session):
            """(digest, ledger dispatch count, jit misses) for one
            collect, ledger window isolated."""
            ledger.reset_stats()
            j0 = cache_stats()
            r = q(session).collect(engine="tpu")
            assert ledger.LEDGER.flush(timeout=30.0), \
                "ledger settlement did not drain"
            s = ledger.summarize(ledger.snapshot())
            j1 = cache_stats()
            return (table_digest(r), s, j1["misses"] - j0["misses"])

        try:
            # pipelining/speculation pinned off so dispatch counts are
            # deterministic; small batches so the stream actually
            # streams (4 row groups -> 4 wire batches)
            conf.set(keys[2], False)
            conf.set(keys[3], False)
            conf.set(keys[4], n // 4)
            conf.set(keys[5], 1)
            conf.set(keys[0], True)
            conf.set(keys[1], False)
            ledger.enable()
            reset_fusion_stats()
            session = TpuSession()
            cold_digest, cold_sum, _ = collect_counted(session)
            # isolate the warm window: chains/saved_dispatches below
            # describe ONE collect
            reset_fusion_stats()
            warm_digest, warm_sum, warm_misses = \
                collect_counted(session)
            fstats = fusion_stats()
            assert warm_misses == 0, (
                f"warm pass re-compiled {warm_misses} program(s): "
                "jit keys are unstable across identical collects")
            assert warm_digest == cold_digest
            warm_d = warm_sum["totals"]["dispatches"]

            # unfused baseline: fresh session, fusion off
            conf.set(keys[0], False)
            unfused_digest, unfused_sum, _ = \
                collect_counted(TpuSession())
            unfused_d = unfused_sum["totals"]["dispatches"]
            assert unfused_digest == warm_digest, \
                "fusion.enabled changed query results"
            assert warm_d < unfused_d, (
                f"fusion saved no dispatches: warm {warm_d} vs "
                f"unfused {unfused_d}")

            # donation on: digest identical, consumed-state bookkeeping
            # exercised end to end
            conf.set(keys[0], True)
            conf.set(keys[1], True)
            donated_digest, _ds, _ = collect_counted(TpuSession())
            assert donated_digest == warm_digest, \
                "donation.enabled changed query results"

            # the dispatch-budget regression gate
            from spark_rapids_tpu.execs.base import (
                warm_dispatch_budget,
            )

            budget = warm_dispatch_budget()
            if budget > 0:
                assert warm_d <= budget, (
                    f"warm dispatch count {warm_d} exceeds the "
                    f"budget {budget} "
                    "(spark.rapids.tpu.sql.fusion.warmDispatchBudget)")

            top = warm_sum["totals"].get("top") or []
            out["fusion_warm_dispatches"] = warm_d
            out["fusion_unfused_dispatches"] = unfused_d
            out["fusion_dispatch_savings_ratio"] = round(
                unfused_d / max(warm_d, 1), 2)
            out["fusion_warm_jit_misses"] = warm_misses
            out["fusion_chains"] = fstats["chains"]
            out["fusion_saved_dispatches"] = fstats["saved_dispatches"]
            out["fusion_warm_roofline"] = \
                warm_sum["totals"]["roofline"]
            out["fusion_warm_device_ms"] = \
                warm_sum["totals"]["device_ms"]
            out["fusion_top_programs"] = [
                {"key": p["key"], "op": p["op"],
                 "dispatches": p["dispatches"],
                 "device_ms": p["device_ms"], "share": p["share"]}
                for p in top]
        finally:
            for k, v in saved.items():
                conf.set(k, v)
            ledger.reset_stats()
            if not ledger_was_on:
                # this smoke's own force-enable: release it (an outer
                # caller's enable — bench, a wrapping test — survives)
                ledger.disable()
    return out


def run_warm_start_smoke() -> dict:
    """Warm-start acceptance contract, cheap CI form (tier-1 via
    tests/test_persist.py, docs/warm_start.md): one child process
    populates a persist directory with the fusion-smoke query's AOT
    programs, then a second FRESH child runs the same query against
    the warm directory and must

    - compile NOTHING: the jit cache's `compiles` counter stays 0 in
      the child (restored programs dispatch deserialized jax.export
      artifacts; the counter bumps only at a fresh wrapper's first
      real invocation);
    - restore from disk: `persist.hits` > 0;
    - agree bit-for-bit: the child's digest equals both the
      populating child's and an in-process reference run with
      persistence OFF;
    - keep ledger attribution: the warm child's dispatch count equals
      the populating child's (restored programs still meter)."""
    import os
    import tempfile

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.execs.base import _budget_conf, _fusion_conf
    from spark_rapids_tpu.tools import cold_start as cs
    from spark_rapids_tpu.trace import ledger

    # force-register lazily-registered confs BEFORE the snapshot (the
    # fusion smoke's save/restore caveat applies here too)
    _fusion_conf()
    _budget_conf()
    conf = get_conf()
    keys = ("spark.rapids.tpu.sql.pipeline.enabled",
            "spark.rapids.tpu.sql.speculation.enabled",
            "spark.rapids.tpu.sql.batchSizeRows",
            "spark.rapids.tpu.sql.shuffle.partitions",
            "spark.rapids.tpu.sql.fusion.enabled",
            "spark.rapids.tpu.sql.fusion.donation.enabled")
    saved = {k: conf.get(k) for k in keys}
    ledger_was_on = ledger.LEDGER.enabled
    with tempfile.TemporaryDirectory(prefix="warm_smoke_") as d:
        data = os.path.join(d, "data")
        warm = os.path.join(d, "persist")
        os.makedirs(data)
        os.makedirs(warm)
        cs.make_fixture(data)
        try:
            ledger.reset_stats()
            ref = cs.run_once(data, None)  # in-process, persist OFF
        finally:
            for k, v in saved.items():
                conf.set(k, v)
            ledger.reset_stats()
            if not ledger_was_on:
                ledger.disable()
        populate = cs.run_subprocess(data, warm)
        child = cs.run_subprocess(data, warm)
    assert child["compiles"] == 0, (
        f"warm child compiled {child['compiles']} programs; a warm "
        "disk cache must restore every invoked program")
    assert child["persist"]["hits"] > 0, (
        "warm child restored nothing from the persist directory")
    assert child["digest"] == populate["digest"] == ref["digest"], (
        f"digest drift across persist modes: in-process "
        f"{ref['digest']}, populate {populate['digest']}, warm child "
        f"{child['digest']}")
    assert child["dispatches"] == populate["dispatches"], (
        f"restored programs lost ledger attribution: warm child "
        f"dispatched {child['dispatches']} vs populate "
        f"{populate['dispatches']}")
    return {
        "warm_start_child_compiles": child["compiles"],
        "warm_start_persist_hits": child["persist"]["hits"],
        "warm_start_dispatches": child["dispatches"],
        "warm_start_digest_ok": True,
    }


def run_coalesce_smoke() -> dict:
    """Batch-coalescing acceptance contract, cheap CI form (tier-1 via
    tests/test_coalesce.py, docs/occupancy.md): many tiny cached
    batches through a q1-shaped filter->group-by->agg chain.

    - results digest bit-identical with sql.coalesce.enabled on vs off
      (coalescing only re-buckets rows);
    - the coalesced run dispatches STRICTLY fewer ledger programs —
      the fused chain runs once over one dense block instead of once
      per starved input batch;
    - the coalesced window's aggregate live/capacity ratio sits at or
      above the HC015 occupancy floor
      (trace.ledger.health.occupancyFloor): the chip ran dense;
    - under a SHRUNK device budget the retry ladder bisects a
      coalesced batch back along its input seams (`coalesce_seams`),
      so recovery dispatches land on the producer's original batch
      granularity, with row order preserved."""
    import os
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.execs import retry as R
    from spark_rapids_tpu.execs.basic import TpuBatchSourceExec
    from spark_rapids_tpu.execs.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import TpuSession, col, count_star, \
        sum_
    from spark_rapids_tpu.trace import ledger
    from spark_rapids_tpu.trace.ledger import LEDGER_OCCUPANCY_FLOOR

    conf = get_conf()
    keys = ("spark.rapids.tpu.sql.coalesce.enabled",
            "spark.rapids.tpu.sql.coalesce.targetRows",
            "spark.rapids.tpu.sql.batchSizeRows",
            "spark.rapids.tpu.sql.shuffle.partitions",
            "spark.rapids.tpu.sql.pipeline.enabled",
            "spark.rapids.tpu.sql.speculation.enabled",
            R.SPLIT_MIN_ROWS.key)
    saved = {k: conf.get(k) for k in keys}
    out: dict = {}
    ledger_was_on = ledger.LEDGER.enabled
    rng = np.random.default_rng(0xC0A1)
    with tempfile.TemporaryDirectory(prefix="coalesce_smoke_") as d:
        # 16 part-full batches: 384 live rows each ride a 512 bucket
        # (live/cap 0.75 uncoalesced); coalesced they pack one dense
        # 6144-row block in the 8192 bucket
        group, n_batches = 384, 16
        n = group * n_batches
        t = pa.table({
            "l_shipdate": rng.integers(8766, 10957, n).astype(np.int32),
            "l_key": rng.integers(0, 4, n).astype(np.int64),
            "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        })
        path = os.path.join(d, "li.parquet")
        pq.write_table(t, path, row_group_size=group)

        def q(cached):
            return (cached
                    .where(col("l_shipdate") <= lit(10471))
                    .group_by(col("l_key"))
                    .agg((sum_(col("l_quantity")), "sum_qty"),
                         (count_star(), "cnt"))
                    .order_by(col("l_key")))

        def collect_counted(enabled: bool):
            """(digest, ledger summary) for one warm collect against a
            device-resident cache, coalesce as given.  A fresh session
            per config: the planner decides insertion at plan time."""
            conf.set(keys[0], enabled)
            session = TpuSession()
            cached = session.read_parquet(path).cache()
            df = q(cached)
            try:
                df.collect(engine="tpu")  # fill the cache + compile
                ledger.reset_stats()
                r = df.collect(engine="tpu")
                assert ledger.LEDGER.flush(timeout=30.0), \
                    "ledger settlement did not drain"
                s = ledger.summarize(ledger.snapshot())
            finally:
                cached.unpersist()
            return table_digest(r), s

        try:
            # pipelining/speculation pinned off so dispatch counts are
            # deterministic; tiny batches so the chain actually starves
            conf.set(keys[2], group)
            conf.set(keys[3], 1)
            conf.set(keys[4], False)
            conf.set(keys[5], False)
            conf.set(keys[1], 1 << 20)  # one flush per partition
            ledger.enable()
            off_digest, off_sum = collect_counted(False)
            on_digest, on_sum = collect_counted(True)
            assert on_digest == off_digest, \
                "sql.coalesce.enabled changed query results"
            off_d = off_sum["totals"]["dispatches"]
            on_d = on_sum["totals"]["dispatches"]
            assert on_d < off_d, (
                f"coalescing saved no dispatches: on {on_d} vs "
                f"off {off_d}")
            ratio = on_sum["totals"].get("live_capacity_ratio")
            floor = float(conf.get(LEDGER_OCCUPANCY_FLOOR))
            assert ratio is not None and ratio >= floor, (
                f"coalesced live/capacity ratio {ratio} below the "
                f"{floor} occupancy floor")
            out["coalesce_off_dispatches"] = off_d
            out["coalesce_on_dispatches"] = on_d
            out["coalesce_dispatch_savings_ratio"] = round(
                off_d / max(on_d, 1), 2)
            out["coalesce_live_capacity_ratio"] = ratio
            out["coalesce_off_live_capacity_ratio"] = \
                off_sum["totals"].get("live_capacity_ratio")

            # shrunk-budget split: the coalesced block must bisect
            # back along its input seams, not at the arbitrary midpoint
            schema = T.Schema([T.Field("x", T.LONG)])
            sizes = (300, 500, 200, 400)  # midpoint 700; seam cut 800
            offs = np.cumsum((0,) + sizes)
            parts = [ColumnarBatch.from_numpy(
                {"x": np.arange(offs[i], offs[i + 1],
                                dtype=np.int64)}, schema)
                for i in range(len(sizes))]
            co = TpuCoalesceBatchesExec(
                TpuBatchSourceExec(parts, schema))
            outs = list(co.execute())
            assert len(outs) == 1 and \
                outs[0].coalesce_seams == sizes
            conf.set(R.SPLIT_MIN_ROWS.key, 64)

            class _ShrunkBudget(RuntimeError):
                def __str__(self):
                    return ("RESOURCE_EXHAUSTED: shrunk device "
                            "budget (coalesce smoke)")

            budget_rows, seen, got = 900, [], []

            def run(batch):
                nr = batch.concrete_num_rows()
                if nr > budget_rows:
                    raise _ShrunkBudget()
                seen.append(nr)
                yield batch

            for b in R.with_split_retry(run, outs[0],
                                        desc="coalesce_smoke"):
                got.extend(b.to_pydict()["x"])
            # seam-aligned halves (300+500 | 200+400), not 700/700
            assert seen == [800, 600], seen
            assert got == list(range(sum(sizes))), \
                "seam split lost or reordered rows"
            out["coalesce_split_chunks"] = seen
        finally:
            for k, v in saved.items():
                conf.set(k, v)
            ledger.reset_stats()
            if not ledger_was_on:
                ledger.disable()
    return out


def run_ops_smoke() -> dict:
    """Live ops-plane acceptance contract, cheap CI form (tier-1 via
    tests/test_obs.py; docs/ops_plane.md):

    - `spark.rapids.tpu.obs.enabled` starts the endpoint at the next
      query boundary; after the query the LIVE registry is empty again
      (/queries serves []);
    - a real HTTP scrape of /metrics parses as OpenMetrics (terminated
      by `# EOF`) and every eventlog counters_snapshot family equals
      the in-process snapshot value — asserted only for counters that
      are QUIESCENT across the scrape (bracketing snapshots on both
      sides), so a background settle cannot flake the gate while a
      drifting scrape implementation still fails it;
    - the owning conf's off stops BOTH threads (http + slo watchdog)
      and releases the socket: no tpu-obs-* thread survives, and a
      fresh connect to the old port is refused."""
    import json as _json
    import socket
    import threading
    import urllib.request

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu import obs
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.eventlog import (
        MONOTONIC_COUNTERS,
        counters_snapshot,
    )
    from spark_rapids_tpu.obs import metrics as om
    from spark_rapids_tpu.session import TpuSession, col, sum_

    def _obs_threads():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("tpu-obs")]

    conf = get_conf()
    keys = ("spark.rapids.tpu.obs.enabled",
            "spark.rapids.tpu.obs.port")
    saved = {k: conf.get(k) for k in keys}
    out: dict = {}
    try:
        conf.set(keys[0], True)
        conf.set(keys[1], 0)  # ephemeral: parallel CI runs never clash
        session = TpuSession()
        rng = np.random.default_rng(0x0B5)
        n = 2048
        t = pa.table({
            "k": rng.integers(0, 16, n).astype(np.int64),
            "v": rng.random(n),
        })
        df = (session.create_dataframe(t)
              .group_by(col("k"))
              .agg((sum_(col("v")), "sv")))
        result = df.collect(engine="tpu")
        assert obs.is_enabled(), "obs.enabled did not start the plane"
        port = obs.plane().port
        assert port, "ops endpoint bound no port"
        assert obs.REGISTRY.count() == 0, \
            "live query registry did not empty after the query"

        # -- scrape == snapshot parity ------------------------------ #
        base = f"http://127.0.0.1:{port}"
        before = counters_snapshot()
        body = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        after = counters_snapshot()
        assert body.rstrip().endswith("# EOF"), \
            "scrape is missing the OpenMetrics EOF marker"
        parsed = om.parse_openmetrics(body)
        mono = set(MONOTONIC_COUNTERS)
        checked = 0
        for key, val in before.items():
            name = om.counter_metric_name(key) if key in mono \
                else om.metric_name(key)
            got = om.scrape_value(parsed, name)
            assert got is not None, f"/metrics is missing {name}"
            if after.get(key) == val:  # quiescent across the scrape
                assert got == float(val), (
                    f"scrape parity broken for {key}: "
                    f"/metrics says {got}, snapshot says {val}")
                checked += 1
        assert checked > 0, "no quiescent counter to parity-check"

        # -- live registry JSON surface ----------------------------- #
        qbody = urllib.request.urlopen(
            base + "/queries", timeout=10).read().decode()
        assert _json.loads(qbody) == [], \
            "/queries is not empty between queries"
        out["ops_rows"] = result.num_rows
        out["ops_scrape_families"] = len(parsed)
        out["ops_parity_counters"] = checked

        # -- off: no thread, no socket ------------------------------ #
        conf.set(keys[0], False)
        obs.sync_conf(conf)
        assert not obs.is_enabled()
        assert _obs_threads() == [], \
            f"ops threads survived the off: {_obs_threads()}"
        with socket.socket() as probe:
            probe.settimeout(0.5)
            assert probe.connect_ex(("127.0.0.1", port)) != 0, \
                "ops socket still listening after stop"
        out["ops_stopped_clean"] = True
    finally:
        for k, v in saved.items():
            conf.set(k, v)
        obs.stop()
    return out


def run_connect_smoke() -> dict:
    """The wire front-door contract (spark_rapids_tpu/connect/,
    docs/connect.md): an in-process ConnectServer thread serves one
    wire query — a Substrait plan over real TCP framing — and the
    Arrow batches reassembled by the engine-free client must digest
    bit-identical to the SAME plan collected in-process, with the
    repeat request hitting the prepared-plan cache (tier-1 via
    tests/test_connect.py)."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.connect.client import (
        ConnectClient,
        table_digest,
    )
    from spark_rapids_tpu.connect.server import ConnectServer
    from spark_rapids_tpu.frontends.substrait import SubstraitFrontend

    rng = np.random.default_rng(41)
    n = 4096
    t = pa.table({
        "k": (rng.integers(0, 9, n)).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.float64),
    })
    plan = {
        "extensions": [
            {"extensionFunction": {"functionAnchor": 1,
                                   "name": "gt:any_any"}},
            {"extensionFunction": {"functionAnchor": 2,
                                   "name": "sum:fp64"}},
        ],
        "relations": [{"root": {"names": ["k", "total"], "input": {
            "aggregate": {
                "input": {"filter": {
                    "input": {"read": {
                        "namedTable": {"names": ["t"]},
                        "baseSchema": {"names": ["k", "v"]}}},
                    "condition": {"scalarFunction": {
                        "functionReference": 1, "arguments": [
                            {"value": {"selection": {
                                "directReference": {
                                    "structField": {"field": 1}}}}},
                            {"value": {"literal": {"fp64": 10.0}}},
                        ]}}}},
                "groupings": [{"groupingExpressions": [
                    {"selection": {"directReference": {
                        "structField": {"field": 0}}}}]}],
                "measures": [{"measure": {
                    "functionReference": 2,
                    "arguments": [{"value": {"selection": {
                        "directReference": {
                            "structField": {"field": 1}}}}}]}}],
            }}}}],
    }
    srv = ConnectServer()
    srv.register_table("t", t)
    srv.start()
    try:
        host, port = srv.address
        with ConnectClient(host, port, tenant="smoke") as cli:
            assert cli.ping(), "connect ping failed"
            wire1 = cli.execute_plan(plan)
            wire2 = cli.execute_plan(plan)  # prepared-plan cache hit
        local = SubstraitFrontend()
        local.register_table("t", t)
        in_proc = local.execute_plan(plan).combine_chunks()
        d_wire, d_local = table_digest(wire1), table_digest(in_proc)
        assert d_wire == d_local, (
            f"wire digest {d_wire} != in-process {d_local}")
        assert table_digest(wire2) == d_local, "repeat wire mismatch"
    finally:
        srv.shutdown()
    return {"connect_smoke_rows": wire1.num_rows,
            "connect_smoke_digest": d_wire}


def run_smoke() -> dict:
    """Collect each smoke query with speculation on, then off, assert
    table equality, and return {query_name: rows}."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.session import TpuSession

    key = "spark.rapids.tpu.sql.speculation.enabled"
    batch_key = "spark.rapids.tpu.sql.batchSizeRows"
    conf = get_conf()
    saved = {k: conf.get(k) for k in (key, batch_key)}
    session = TpuSession()
    # small batches so every stream loop sees several batches (the
    # warm-up -> steady-state transition is the interesting part)
    conf.set(batch_key, 512)
    out: dict = {}
    try:
        for name, df in _queries(session):
            conf.set(key, True)
            on = df.collect(engine="tpu")
            conf.set(key, False)
            off = df.collect(engine="tpu")
            _assert_rows_match(name, on, off)
            out[name] = on.num_rows
    finally:
        for k, v in saved.items():
            conf.set(k, v)
    return out


def main() -> int:
    import json

    # stand-alone runs ride the CPU backend: this is a correctness
    # smoke of counters and digests, not a measurement
    import jax

    jax.config.update("jax_platforms", "cpu")
    results = run_smoke()
    results.update(run_rf_smoke())
    results.update(run_eventlog_smoke())
    results.update(run_serving_smoke())
    results.update(run_sharing_smoke())
    results.update(run_ledger_smoke())
    results.update(run_wire_codec_smoke())
    results.update(run_fusion_smoke())
    results.update(run_warm_start_smoke())
    results.update(run_coalesce_smoke())
    results.update(run_connect_smoke())
    results.update(run_ops_smoke())
    print(json.dumps({"bench_smoke": results, "ok": True}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
