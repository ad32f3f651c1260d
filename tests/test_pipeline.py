"""Software-pipelined executor (parallel/pipeline.py): stage order /
error / cancellation contracts, the deferred-readback lookahead in the
join stream loop (ISSUE 2's acceptance test), and a CPU smoke run of
the whole scan->filter->aggregate->sort pipeline with stages on vs off.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.parallel import pipeline as P
from spark_rapids_tpu.session import TpuSession, col, sum_
from tests.differential import assert_tables_equal, assert_tpu_cpu_equal


@pytest.fixture
def session():
    return TpuSession()


# -- prefetch: the bounded background stage ----------------------------- #

def test_prefetch_preserves_order():
    got = list(P.prefetch(iter(range(200)), depth=3, stage="t.order"))
    assert got == list(range(200))


def test_prefetch_propagates_producer_exception_in_stream_order():
    def gen():
        yield 1
        yield 2
        raise ValueError("decode failed")

    it = P.prefetch(gen(), depth=2, stage="t.err")
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(ValueError, match="decode failed"):
        next(it)


def test_prefetch_cancels_cleanly_on_early_consumer_exit():
    """Early consumer exit must close the producer's generator (its
    finally runs on the producer thread) and join the thread — the
    join-on-abort handshake that replaced the 10ms poll-drain."""
    closed = threading.Event()
    started = threading.Event()

    def gen():
        try:
            for i in range(10_000):
                started.set()
                yield i
        finally:
            closed.set()

    before = threading.active_count()
    it = P.prefetch(gen(), depth=2, stage="t.cancel")
    assert next(it) == 0
    assert started.wait(2)
    t0 = time.perf_counter()
    it.close()  # abort: wakes the blocked producer, joins it
    assert time.perf_counter() - t0 < 1.0, "abort took poll-drain time"
    assert closed.is_set(), "producer generator was not closed on abort"
    # the stage thread is gone (give the OS a beat to reap it)
    deadline = time.time() + 2
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_prefetch_propagates_thread_local_conf():
    """conf is THREAD-LOCAL; the stage must install the caller's
    snapshot on the producer thread (a bare thread would silently read
    defaults — the scan's old hand-rolled snapshot, generalized)."""
    key = "spark.rapids.tpu.sql.pipeline.depth"
    get_conf().set(key, 5)

    def gen():
        yield get_conf().get(key)

    assert list(P.prefetch(gen(), depth=1, stage="t.conf")) == [5]


def test_prefetch_depth_zero_runs_inline():
    main_thread = threading.current_thread()
    seen = []

    def gen():
        seen.append(threading.current_thread())
        yield 1

    assert list(P.prefetch(gen(), depth=0, stage="t.inline")) == [1]
    assert seen == [main_thread]


def test_stage_metrics_accumulate():
    name = "t.metrics"
    list(P.prefetch(iter(range(32)), depth=4, stage=name))
    snap = P.stage_snapshot()[name]
    assert snap["items"] == 32
    assert snap["depth"] == 4
    assert 0.0 <= snap["occupancy_fraction"] <= 1.0


# -- pipelined: the deferred-readback lookahead ------------------------- #

def test_pipelined_dispatches_ahead_of_readback():
    """The generic contract: with lookahead k>=1, dispatch(i+1) happens
    before retire(i)'s blocking readback."""
    def dispatch(i):
        return i, jnp.asarray(i * 10, jnp.int32)

    def retire(entry):
        i, x = entry
        yield (i, P.device_read_int(x, tag="t.look"))

    with P.trace_events() as events:
        got = list(P.pipelined(range(5), dispatch, retire, depth=1,
                               tag="t.look"))
    assert got == [(i, i * 10) for i in range(5)]
    ev = [k for k, tag in events if tag == "t.look"]
    assert ev == ["dispatch", "dispatch", "readback", "dispatch",
                  "readback", "dispatch", "readback", "dispatch",
                  "readback", "readback"]


def test_pipelined_depth_zero_is_serial():
    with P.trace_events() as events:
        list(P.pipelined(range(3), lambda i: i, lambda i: [i], depth=0,
                         tag="t.serial"))
    ev = [k for k, _ in events]
    assert ev == ["dispatch", "readback"] * 0 + [
        "dispatch", "dispatch", "dispatch"]


def test_device_read_passes_host_scalars_through():
    with P.trace_events() as events:
        assert P.device_read_int(7, tag="t.host") == 7
        assert P.device_read_many([1, 2], tag="t.host") == [1, 2]
    assert events == []  # no device traffic, no readback event


# -- the join stream loop (ISSUE 2 acceptance) -------------------------- #

def _join_exec(n_stream=200, batch_rows=32, dup=2):
    """A wide shuffled hash join whose stream side arrives in several
    batches: stream (left) k in [0, 50), build (right) each key
    repeated `dup` times."""
    from spark_rapids_tpu.execs.join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.io.scan import ArrowSourceExec

    rng = np.random.default_rng(11)
    left = pa.table({
        "k": rng.integers(0, 50, n_stream).astype(np.int64),
        "v": rng.random(n_stream),
    })
    right = pa.table({
        "k": np.repeat(np.arange(50, dtype=np.int64), dup),
        "w": np.arange(50 * dup, dtype=np.int64),
    })
    lsrc = ArrowSourceExec(left, batch_rows=batch_rows)
    rsrc = ArrowSourceExec(right)
    join = TpuShuffledHashJoinExec([col("k")], [col("k")], "inner",
                                   lsrc, rsrc)
    n_batches = lsrc.num_partitions
    return join, left, right, n_batches


def _drain_to_table(exec_):
    from spark_rapids_tpu.columnar.arrow import to_arrow

    tables = [to_arrow(b) for b in exec_.execute()]
    return pa.concat_tables(tables)


def _got_rows(tbl: pa.Table):
    """Join output columns are [k, v, k, w] (stream ++ build, Spark
    keeps both key columns) — canonicalize to sorted (k, v, w)."""
    k = tbl.column(0).to_pylist()
    v = tbl.column(1).to_pylist()
    w = tbl.column(3).to_pylist()
    return sorted(zip(k, (round(x, 9) for x in v), w))


def _expected_rows(left: pa.Table, right: pa.Table):
    from collections import defaultdict

    m = defaultdict(list)
    for k, w in zip(right["k"].to_pylist(), right["w"].to_pylist()):
        m[k].append(w)
    out = []
    for k, v in zip(left["k"].to_pylist(), left["v"].to_pylist()):
        for w in m.get(k, ()):
            out.append((k, round(v, 9), w))
    return sorted(out)


def test_join_stream_loop_one_readback_per_batch_with_lookahead():
    """THE acceptance criterion (PR 2, the pipelined contract): at
    most one blocking device->host readback per stream batch, and
    batch k's readback happens only after batch k+1's probe is already
    dispatched."""
    join, left, right, n_batches = _join_exec()
    assert n_batches >= 4
    with P.trace_events() as events:
        got = _drain_to_table(join)
    ev = [kind for kind, tag in events if tag == "join.probe"]
    dispatches = ev.count("dispatch")
    readbacks = ev.count("readback")
    assert dispatches == n_batches
    assert readbacks <= n_batches, \
        "more than one blocking readback per stream batch"
    # ordering: before the k-th readback retires, k+2 probes must have
    # been dispatched (the lookahead window) — except at stream end
    seen_d = 0
    seen_r = 0
    for kind in ev:
        if kind == "dispatch":
            seen_d += 1
        else:
            seen_r += 1
            assert seen_d >= min(seen_r + 1, n_batches), (
                f"readback #{seen_r} before probe #{seen_r + 1} was "
                f"dispatched: {ev}")
    assert _got_rows(got) == _expected_rows(left, right)


def test_join_lookahead_disabled_still_correct():
    get_conf().set("spark.rapids.tpu.sql.pipeline.enabled", False)
    join, left, right, _ = _join_exec()
    got = _drain_to_table(join)
    assert _got_rows(got) == _expected_rows(left, right)


def test_join_output_chunk_boundary_with_lookahead():
    """Join output larger than JOIN_OUTPUT_CHUNK_ROWS per stream batch:
    the expansion must chunk correctly while the next probe is already
    in flight."""
    get_conf().set("spark.rapids.tpu.sql.join.outputChunkRows", 64)
    join, left, right, n_batches = _join_exec(
        n_stream=128, batch_rows=64, dup=8)
    # each stream batch matches ~64*8 = 512 pairs >> 64-row chunks
    got = _drain_to_table(join)
    want = _expected_rows(left, right)
    assert got.num_rows == len(want)
    assert _got_rows(got) == want


# -- whole-pipeline smoke (tier-1, CPU) --------------------------------- #

def _smoke_query(session, tmp_path):
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    for i in range(3):
        t = pa.table({
            "k": rng.integers(0, 9, 4000).astype(np.int64),
            "v": rng.random(4000),
        })
        pq.write_table(t, str(tmp_path / f"part-{i}.parquet"))
    paths = [str(tmp_path / f"part-{i}.parquet") for i in range(3)]
    from spark_rapids_tpu.exprs.base import lit

    return (session.read_parquet(*paths)
            .where(col("v") > lit(0.25))
            .group_by(col("k"))
            .agg((sum_(col("v")), "sv"))
            .order_by(col("k")))


def test_pipeline_smoke_scan_agg_sort(session, tmp_path):
    """Exercises every inserted stage on CPU: scan decode/upload
    prefetch, aggregate update lookahead, result-fetch stage."""
    df = _smoke_query(session, tmp_path)
    assert_tpu_cpu_equal(df, approx_float=True)
    snap = P.stage_snapshot()
    assert snap.get("scan.decode", {}).get("items", 0) > 0
    assert snap.get("result.fetch", {}).get("items", 0) > 0


def test_pipeline_disabled_same_results(session, tmp_path):
    df = _smoke_query(session, tmp_path)
    on = df.collect(engine="tpu")
    get_conf().set("spark.rapids.tpu.sql.pipeline.enabled", False)
    off = df.collect(engine="tpu")
    assert_tables_equal(on, off, approx_float=True)


def test_explain_shows_pipeline_stages(session, tmp_path):
    df = _smoke_query(session, tmp_path)
    out = df.explain()
    assert "Pipeline:" in out
    assert "scan->decode" in out
    assert "last-exec->fetch" in out
    get_conf().set("spark.rapids.tpu.sql.pipeline.enabled", False)
    assert "Pipeline:" not in df.explain()


def test_pipeline_kill_switch_holds_on_map_task_threads(session,
                                                        tmp_path):
    """conf is thread-local: with the pipeline DISABLED, execs running
    on exchange map-task pool threads must also see the kill switch
    (the exchange installs the session conf snapshot per task) — no
    stage queue may record a single pop."""
    import pyarrow.parquet as pq

    rng = np.random.default_rng(17)
    paths = []
    for i in range(3):
        t = pa.table({
            "k": rng.integers(0, 7, 2000).astype(np.int64),
            "v": rng.random(2000),
        })
        p = str(tmp_path / f"mt-{i}.parquet")
        pq.write_table(t, p)
        paths.append(p)
    # one scan task per file -> several concurrent map tasks
    get_conf().set("spark.rapids.tpu.sql.scan.taskTargetBytes", 1)
    get_conf().set("spark.rapids.tpu.sql.pipeline.enabled", False)
    df = (session.read_parquet(*paths)
          .group_by(col("k")).agg((sum_(col("v")), "sv")))

    def items(snap):
        return sum(v["items"] for v in snap.values())

    before = items(P.stage_snapshot())
    got = df.collect(engine="tpu")
    assert items(P.stage_snapshot()) == before, \
        "a pipeline stage ran on a pool thread despite enabled=False"
    get_conf().set("spark.rapids.tpu.sql.pipeline.enabled", True)
    assert_tables_equal(got, df.collect(engine="cpu"),
                        approx_float=True)


def test_exchange_map_pipeline_correct(session):
    """Hash exchange map tasks retire split counts one batch behind
    dispatch; the shuffle must still route every row exactly once."""
    get_conf().set("spark.rapids.tpu.sql.batchSizeRows", 256)
    rng = np.random.default_rng(5)
    t = pa.table({
        "k": rng.integers(0, 64, 2048).astype(np.int64),
        "v": rng.random(2048),
    })
    df = (session.create_dataframe(t)
          .group_by(col("k")).agg((sum_(col("v")), "sv")))
    assert_tpu_cpu_equal(df, approx_float=True)
