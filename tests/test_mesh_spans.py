"""The host's work between a mesh's stage programs, as the tracer and
the counters say it (docs/observability.md): `parallel/spmd.py` opens
ONE `mesh.stack`, `mesh.shrink` or `mesh.launch` span a call, every
fetch at a stage boundary goes through `pipeline.device_read` (the
`stage.mesh.counts` / `stage.mesh.drain` counters, tracer on or off),
and `placement.place_piece` counts the bytes it moves chip to chip.
A stage stacks its input once, at its entry, and cuts pieces once, at
its exit: between two of its programs stands `spmd.restage`, one
program under a `mesh.shrink` span that says `path="program"`.
What the benchmark's `idle_mesh_*`, `mesh_host_s`, `mesh_syncs` and
`mesh_d2d_bytes` readers read, on the 8-virtual-device mesh."""

import dataclasses

import jax
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.execs.collective  # noqa: F401  (registers confs)
from spark_rapids_tpu import trace
from spark_rapids_tpu.parallel import placement
from spark_rapids_tpu.parallel.pipeline import stage_snapshot
from spark_rapids_tpu.session import TpuSession, col, sum_

N_DEV = 8
BROADCAST_KEY = "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes"


@pytest.fixture
def session():
    s = TpuSession()
    s.conf.set(BROADCAST_KEY, -1)
    s.enable_collective_shuffle(N_DEV)
    yield s
    s.disable_collective_shuffle()


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _table(rows: int, seed: int, name: str = "v") -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.permutation(4 * rows)[:rows].astype(np.int64),
                     name: rng.integers(0, 9, rows).astype(np.int64)})


def _agg(s):
    return s.create_dataframe(_table(1500, 3)).group_by(col("k")).agg(
        (sum_(col("v")), "s"))


def _join(s):
    # distinct keys on both sides: no stream row matches twice, so the
    # probe's capacity guess holds and its program is launched once
    return s.create_dataframe(_table(1200, 5, "lv")).join(
        s.create_dataframe(_table(300, 7, "rv")), on="k", how="inner")


def _sort(s):
    return s.create_dataframe(_table(1500, 11)).order_by(col("k"))


def _window(s):
    from spark_rapids_tpu.exprs.window import Window, rank

    t = _table(1500, 13)
    return s.create_dataframe(t).select(
        col("k"), col("v"),
        rank().over(Window.partition_by("v").order_by("k")).alias("r"))


@dataclasses.dataclass(frozen=True)
class Stage:
    """One collective stage of one round and one bucket, and what its
    driver (`_materialize`) is known to do on the host."""

    query: object
    op: str
    programs: tuple  # the stage programs it launches, once each call
    stacks: int  # its entries: a side of the join has its own
    shrinks: int  # boundaries (a program or skipped) and the exit's cut
    fetches: int


STAGES = {
    # stack, update, counts, boundary (every key is distinct, so the
    # partials stand at the input's capacity already: no program),
    # exchange + merge, counts, boundary, the buckets end to end (one:
    # no program), tail, counts, unstack
    "agg": Stage(_agg, "TpuCollectiveHashAggregateExec",
                 ("spmdrestage", "spmdtail", "spmdupdate", "spmdxchg"),
                 1, 4, 3),
    # a side: stack, count, fetch, route, boundary by the same counts;
    # the build side's fold; the probe, its totals, its unstack
    "join": Stage(_join, "TpuCollectiveHashJoinExec",
                  ("spmdjoin", "spmdrestage", "spmdrestage",
                   "spmdroutecount", "spmdroutecount", "spmdtail",
                   "spmdxchg", "spmdxchg"), 2, 3, 4),
    # stack, route, counts, boundary, tail, counts, unstack
    "sort": Stage(_sort, "TpuCollectiveSortExec",
                  ("spmdrestage", "spmdsortroute", "spmdtail"), 1, 2, 2),
    # stack, count, fetch, route, boundary by the same counts, tail,
    # unstack by them too
    "window": Stage(_window, "TpuCollectiveWindowExec",
                    ("spmdrestage", "spmdroutecount", "spmdtail",
                     "spmdwinroute"), 1, 2, 1),
}


def _fetches() -> int:
    return stage_snapshot().get("mesh.counts", {}).get("readbacks", 0)


@pytest.mark.parametrize("name", sorted(STAGES))
def test_a_stage_names_its_host_work_one_span_a_call(session, name):
    stage = STAGES[name]
    df = stage.query(session)
    moved = placement.stats()
    trace.enable()
    rows = df.collect(engine="tpu").num_rows
    events = trace.snapshot()
    trace.disable()
    assert rows
    moved = {k: v - moved[k] for k, v in placement.stats().items()}
    mine = [e for e in events if e.attrs.get("op") == stage.op]

    def named(span: str) -> list:
        return [e.attrs for e in mine if e.name == span]

    launches = named("mesh.launch")
    assert sorted(a["program"] for a in launches) == list(stage.programs)
    assert all(a["devices"] == N_DEV and a["rounds"] == 1
               for a in launches)
    stacks, shrinks = named("mesh.stack"), named("mesh.shrink")
    assert (len(stacks), len(shrinks)) == (stage.stacks, stage.shrinks)
    for a in stacks:
        assert (a["rounds"], a["shards"]) == (1, N_DEV)
        assert a["leaves"] >= 4 and 0 <= a["repadded"] <= N_DEV
        assert a["capacity"] >= 1
        # every leaf of every shard was placed once, one way or another
        assert a["host_uploads"] + a["device_born"] + a["d2d_transfers"] \
            == a["leaves"] * N_DEV
    # what the spans say crossed chips is what placement counted
    for key in ("d2d_bytes", "d2d_transfers", "device_born"):
        assert sum(a[key] for a in stacks) == moved[key]
    assert moved["d2d_bytes"] > 0
    for a in shrinks:
        assert a["pieces"] >= 1 and a["leaves"] >= 4
        assert 0 <= a["rows"] <= a["pieces"] * a["capacity"]
    # a boundary that ran a program, and no other, launched a restage
    ran = [a for a in shrinks
           if a["path"] == "program" and not a["skipped"]]
    assert len(ran) == stage.programs.count("spmdrestage")
    assert all(a["to_capacity"] < a["capacity"] for a in ran)
    # from an entry's stack to the stage's exit (or the other side's
    # entry) the host stacks nothing and cuts no piece: what stands
    # before the last launch is a boundary, what follows it the exit
    runs, run = [], None
    for e in sorted(mine, key=lambda e: e.ts_ns):
        if e.name == "mesh.stack":
            run = []
            runs.append(run)
        elif e.name in ("mesh.shrink", "mesh.launch"):
            run.append(e)
    assert len(runs) == stage.stacks
    for run in runs:
        last = max(i for i, e in enumerate(run)
                   if e.name == "mesh.launch")
        cuts = [(i < last, e.attrs["path"]) for i, e in enumerate(run)
                if e.name == "mesh.shrink"]
        assert all(path == ("program" if inside else "pieces")
                   for inside, path in cuts), cuts
    syncs = [a for a in named("pipe.readback")
             if a["tag"] == "mesh.counts"]
    assert len(syncs) == stage.fetches
    # the older spans of the stage stand, and say whose they are too
    if name != "sort":
        assert named(f"collective.{name}.exchange")


@pytest.mark.parametrize("name", sorted(STAGES))
def test_with_the_tracer_off_the_fetches_are_still_counted(session, name):
    stage = STAGES[name]
    df = stage.query(session)
    before = _fetches()
    assert df.collect(engine="tpu").num_rows
    assert _fetches() - before == stage.fetches
    assert trace.snapshot() == []
    # and with it on, the counter moves by the same number
    trace.enable()
    before = _fetches()
    stage.query(session).collect(engine="tpu")
    assert _fetches() - before == stage.fetches


def test_the_drain_reads_a_device_count_through_device_read():
    """`_shard_rounds` needs every child batch's row count on the host
    to deal it to a shard: a count still on the device is one
    `mesh.drain` readback, a host int is free."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.execs.collective import _CollectiveBase
    from spark_rapids_tpu.parallel.mesh import make_mesh

    schema = T.Schema([T.Field("k", T.LONG)])
    host = [ColumnarBatch.from_numpy(
        {"k": np.arange(n, dtype=np.int64)}, schema) for n in (5, 9, 3)]

    class Child:
        num_partitions = 1

        def __init__(self, batches):
            self.schema, self.batches = schema, batches

        def execute_partition(self, p):
            yield from self.batches

    def drained(batches) -> tuple:
        exec_ = _CollectiveBase(Child(batches))
        exec_.mesh = make_mesh(N_DEV)
        exec_._init_stage(None)
        before = stage_snapshot().get("mesh.drain", {}).get("readbacks", 0)
        rounds = list(exec_._shard_rounds(exec_.children[0]))
        return (stage_snapshot().get("mesh.drain", {}).get("readbacks", 0)
                - before,
                sorted(b.num_rows for shards in rounds for b in shards))

    assert drained(host) == (0, [0] * (N_DEV - 3) + [3, 5, 9])
    on_device = [b.with_device_num_rows() for b in host]
    trace.enable()
    assert drained(on_device) == (3, [0] * (N_DEV - 3) + [3, 5, 9])
    assert [e.attrs["tag"] for e in trace.snapshot()
            if e.name == "pipe.readback"] == ["mesh.drain"] * 3


def test_placement_counts_the_bytes_it_moves_chip_to_chip():
    devs = jax.devices()
    x = jax.device_put(np.arange(1000, dtype=np.int64), devs[0])
    y = jax.device_put(np.zeros((3, 7), np.float32), devs[2])
    placement.reset_stats()
    try:
        assert placement.place_piece(x, devs[0]) is x  # born there
        placement.place_piece(np.ones(16), devs[1])  # from the host
        st = placement.stats()
        assert (st["device_born"], st["host_uploads"]) == (1, 1)
        assert (st["d2d_transfers"], st["d2d_bytes"]) == (0, 0)
        moved = placement.place_piece(x, devs[1])
        assert moved.devices() == {devs[1]}
        placement.place_piece(y, devs[0])
        st = placement.stats()
        assert st["d2d_transfers"] == 2
        assert st["d2d_bytes"] == x.nbytes + y.nbytes == 8000 + 84
    finally:
        placement.reset_stats()
