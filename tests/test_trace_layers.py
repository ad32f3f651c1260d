"""Spans on the thread that does the work (`scan.decode.file`,
`wire.encode`, `wire.put`) and program names from `cached_jit`: what
the benchmark's per-layer readers (benchmarks/layer_metrics/) read."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import trace
from spark_rapids_tpu.columnar.transfer import upload_stats
from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.execs import jit_cache
from spark_rapids_tpu.session import TpuSession, col, sum_

ROWS = 400


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


@pytest.fixture
def two_files(tmp_path):
    paths = []
    for i in range(2):
        p = str(tmp_path / f"f{i}.parquet")
        pq.write_table(pa.table({"k": [1, 2, 3, 4] * (ROWS // 4),
                                 "v": [float(i)] * ROWS}), p)
        paths.append(p)
    return paths


def _collect(paths, traced: bool = True, **conf_keys):
    """A grouped sum over the files; returns (events, session).  The
    scan reads the thread's conf, which conftest restores."""
    conf = get_conf()
    if traced:
        conf.set("spark.rapids.tpu.trace.enabled", "true")
    for k, v in conf_keys.items():
        conf.set(k, v)
    s = TpuSession(conf)
    out = s.read_parquet(*paths).group_by(col("k")).agg(
        (sum_(col("v")), "s")).collect(engine="tpu")
    assert out.num_rows == 4
    return trace.snapshot(), s


# -- scan.decode.file --------------------------------------------------- #

DECODE_THREADS = "spark.rapids.tpu.sql.scan.decodeThreads"
FAST_DECODE = "spark.rapids.tpu.sql.scan.fastDecode"
BATCH_ROWS = "spark.rapids.tpu.sql.batchSizeRows"
TASK_BYTES = "spark.rapids.tpu.sql.scan.taskTargetBytes"


@pytest.mark.parametrize("conf_keys,thread,path,per_file", [
    ({}, "tpu-scan-decode", "fast", 1),
    ({DECODE_THREADS: 1}, "tpu-pipe-scan.decode", "fast", 1),
    ({FAST_DECODE: "false"}, "tpu-scan-decode", "pyarrow", 1),
    # a file larger than a batch streams: one span for each `next()`
    ({FAST_DECODE: "false", BATCH_ROWS: ROWS // 4},
     "tpu-scan-decode", "pyarrow", 4),
    # a task per file, on TpuCoalescePartitionsExec's task threads: the
    # query's context has one more hop to make
    ({TASK_BYTES: 1}, "tpu-pipe-scan.decode", "fast", 1),
], ids=["pool", "stage-thread", "pyarrow-whole-file", "pyarrow-streamed",
        "task-per-file"])
def test_decode_spans_sit_on_the_thread_that_decodes(
        two_files, conf_keys, thread, path, per_file):
    events, session = _collect(two_files, **conf_keys)
    decodes = [e for e in events if e.name == "scan.decode.file"]
    assert sorted(e.attrs["file"] for e in decodes) \
        == [0] * per_file + [1] * per_file
    query_id = session.history.events[-1].query_id
    for e in decodes:
        assert e.attrs["path"] == path
        assert e.attrs["query_id"] == query_id
        assert e.thread_name.startswith(thread)
        assert e.attrs["bytes"] > 0 and e.dur_ns > 0
    for fi in (0, 1):
        assert sum(e.attrs["rows"] for e in decodes
                   if e.attrs["file"] == fi) == ROWS
    # closed before the table is handed on: a span never holds the
    # consumer's time, so on one thread they do not overlap
    by_thread: dict = {}
    for e in decodes:
        by_thread.setdefault(e.tid, []).append(e)
    for evs in by_thread.values():
        for a, b in zip(evs, evs[1:]):
            assert a.end_ns <= b.ts_ns


# -- wire.encode / wire.put --------------------------------------------- #

def test_wire_spans_sit_on_the_plan_thread_and_count_the_put_bytes(
        two_files):
    before = upload_stats()["wire_bytes"]
    events, session = _collect(two_files)
    moved = upload_stats()["wire_bytes"] - before
    puts = [e for e in events if e.name == "wire.put"]
    encodes = [e for e in events if e.name == "wire.encode"]
    assert puts and encodes and moved > 0
    assert sum(e.attrs["bytes"] for e in puts) == moved
    # what the encoder says it made is what the put then counts
    assert sum(e.attrs["wire_bytes"] for e in encodes) == moved
    assert sum(e.attrs["rows"] for e in encodes) == 2 * ROWS
    assert all(e.attrs["columns"] == 2 and e.attrs["host_bytes"] > 0
               for e in encodes)
    plan_threads = {e.tid for e in events if e.name.startswith("exec.")}
    query_id = session.history.events[-1].query_id
    for e in puts + encodes:
        assert e.tid in plan_threads
        assert e.attrs["query_id"] == query_id
        assert e.attrs.get("comps", 1) > 0


def test_task_threads_are_named_and_carry_the_query(two_files):
    """A group-by's map tasks run on the exchange's pool, an ungrouped
    sum's on TpuCoalescePartitionsExec's task threads: both named."""
    events, session = _collect(two_files, **{TASK_BYTES: 1})
    query_id = session.history.events[-1].query_id
    puts = [e for e in events if e.name == "wire.put"]
    assert len(puts) == 2
    for e in puts:
        assert e.thread_name.startswith("tpu-exchange-map")
        assert e.attrs["query_id"] == query_id
    trace.clear()
    out = session.read_parquet(*two_files).agg(
        (sum_(col("v")), "s")).collect(engine="tpu")
    assert out.column("s").to_pylist() == [float(ROWS)]
    query_id = session.history.events[-1].query_id
    puts = [e for e in trace.snapshot() if e.name == "wire.put"]
    assert len(puts) == 2
    for e in puts:
        assert e.thread_name.startswith("tpu-coalesce-task-")
        assert e.attrs["query_id"] == query_id


def test_deriving_sentinels_is_a_span_of_the_operators_thread(two_files):
    """A timed region hands the reaper at most one zero-row slice per
    device set of its output, and none where every leaf is already
    complete, read on the operator's thread after `exec.<op>` closed:
    the span says the live leaves it saw, the slices it dispatched,
    and whether none was needed."""
    events, session = _collect(two_files)
    query_id = session.history.events[-1].query_id
    derived = [e for e in events if e.name == "exec.sentinels"]
    plan_threads = {e.tid for e in events
                    if e.name.startswith("exec.") and "op" in e.attrs}
    assert derived
    assert sum(e.attrs["leaves"] for e in derived) > 0
    for e in derived:
        assert e.tid in plan_threads and e.attrs["query_id"] == query_id
        assert e.attrs["metric"]
        # one device: one slice bounds every leaf of the region
        assert 0 <= e.attrs["sentinels"] <= min(1, e.attrs["leaves"])
        assert e.attrs["ready"] is (e.attrs["sentinels"] == 0)
        timed = [x for x in events if x.tid == e.tid and "op" in x.attrs
                 and x.name.startswith("exec.") and x.end_ns <= e.ts_ns]
        assert timed, "no exec.<op> span closed before its sentinels"


def test_with_the_tracer_off_the_same_collect_records_nothing(two_files):
    events, _ = _collect(two_files, traced=False)
    assert not trace.is_enabled() and events == []


# -- program names ------------------------------------------------------ #

class _Scaler:
    def __init__(self, by):
        self.by = by

    def times(self, x):
        return x * self.by

    def __call__(self, x):
        return x * self.by


def _plain(x):
    return x + 1


def _module_name(prog, *args) -> str:
    # the ledger's wrapper keeps the jitted callable as __wrapped__
    text = prog.__wrapped__.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("make,op,key,name", [
    (lambda: _plain, "TpuProjectExec", ("proj", 1),
     "jit_tpu__TpuProjectExec__proj"),
    (lambda: functools.partial(lambda by, x: x * by, 3),
     "TpuFilterExec", ("flt", 1), "jit_tpu__TpuFilterExec__flt"),
    (lambda: _Scaler(2).times, "TpuSortExec", ("sort", 1),
     "jit_tpu__TpuSortExec__sort"),
    (lambda: _Scaler(2), "TpuSortExec", ("sort-merge", 1),
     "jit_tpu__TpuSortExec__sort_merge"),
    # a site that passes no op, a key without a leading string, and
    # characters no module name takes
    (lambda: lambda x: x, None, ("rangepid", 1),
     "jit_tpu__none__rangepid"),
    (lambda: lambda x: x, "Tpu Exec[2]", (7, "layers-test"),
     "jit_tpu__Tpu_Exec_2___prog"),
], ids=["function", "partial", "bound-method", "callable-object",
        "no-op", "unsafe-characters"])
def test_a_program_from_cached_jit_is_named_for_its_exec_and_key(
        make, op, key, name):
    key = key + ("test_trace_layers",)
    x = jnp.arange(4)
    prog = jit_cache.cached_jit(key, make, op=op)
    assert _module_name(prog, x) == name
    want = make()(x)
    assert (prog(x) == want).all()
    # the same key again: the same program under the same name, and
    # the second make_fn is never asked
    again = jit_cache.cached_jit(
        key, lambda: pytest.fail("a cache hit built a program"), op=op)
    assert again is prog and _module_name(again, x) == name
    # the function handed in keeps its own name: one may serve two keys
    assert _plain.__name__ == "_plain"


def test_unkeyed_fused_pipelines_use_the_same_rule():
    named = jit_cache.named_program(_plain, "TpuProjectExec", "unkeyed")
    assert named.__name__ == "tpu__TpuProjectExec__unkeyed"
    assert named(1) == 2 and named.__wrapped__ is _plain


def test_a_restored_programs_fallback_compile_gets_the_name(monkeypatch):
    """`persist.RestoredProgram` compiles an unseen signature through
    the make_fn it was handed: cached_jit hands it the naming one."""
    from spark_rapids_tpu import persist

    handed = []

    class Store:
        def load_programs(self, key, conf_fp):
            return {"some-signature": object()}

    class Recorder:
        def __init__(self, key, exported, make_fn, jit_kwargs, store,
                     conf_fp):
            handed.append(make_fn)

    monkeypatch.setattr(persist, "active", lambda conf=None: Store())
    monkeypatch.setattr(persist, "RestoredProgram", Recorder)
    key = ("restored", "test_trace_layers")
    try:
        jit_cache.cached_jit(key, lambda: _plain, op="TpuProjectExec")
    finally:
        jit_cache._CACHE.pop(key, None)
    (make_fn,) = handed
    fn = make_fn()
    assert fn.__name__ == "tpu__TpuProjectExec__restored"
    import jax

    text = jax.jit(fn).lower(jnp.arange(4)).as_text()
    assert "module @jit_tpu__TpuProjectExec__restored " in text


# -- bench._stage_breakdown --------------------------------------------- #

def test_stage_breakdown_reads_spans_and_patches_nothing(two_files):
    import bench
    import spark_rapids_tpu.io.scan as scan_mod
    import spark_rapids_tpu.plan.planner as planner_mod
    from spark_rapids_tpu.io import fastpar

    before = (fastpar.read_file, scan_mod.ParquetScanExec._upload,
              planner_mod.to_arrow)
    seen = []
    df = TpuSession().read_parquet(*two_files).group_by(col("k")).agg(
        (sum_(col("v")), "s"))
    real = df.collect

    def collect(**kw):
        # while the collect runs the engine's functions are themselves
        seen.append((fastpar.read_file, scan_mod.ParquetScanExec._upload,
                     planner_mod.to_arrow))
        return real(**kw)

    df.collect = collect
    out = bench._stage_breakdown(df, "q")
    assert seen == [before]
    assert set(out) == {"q_stage_host_decode_s", "q_stage_wire_upload_s",
                        "q_stage_final_fetch_s", "q_stage_other_s"}
    assert out["q_stage_host_decode_s"] > 0
    assert out["q_stage_wire_upload_s"] > 0
    assert out["q_stage_final_fetch_s"] > 0
    # the tracer is left as it was found
    assert not trace.is_enabled()
    assert not hasattr(bench, "_StageTaps")
