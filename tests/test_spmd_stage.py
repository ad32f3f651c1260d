"""SPMD whole-stage execution tests (docs/spmd.md): a collective query
stage lowers to O(1) partitioned pjit programs over the 8-virtual-device
mesh — global sharded inputs (NamedSharding end-to-end), exchange rounds
as an in-program lax.scan, host syncs deferred to stage exit — with
results identical to the one-device answer of the same session
(`shuffle.transport = local`), plus the `_CollectiveBase._shard_rounds`
round-staging contracts the stage input rides on."""

import ast
import dataclasses
import inspect
import pathlib

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.execs.collective  # noqa: F401  (register confs
# before any conf snapshot — they are lazily registered, like fusion's)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.session import TpuSession, col, count, sum_

N_DEV = 8

ROUND_KEY = "spark.rapids.tpu.shuffle.collective.roundRows"
TRANSPORT_KEY = "spark.rapids.tpu.shuffle.transport"
BUCKET_KEY = "spark.rapids.tpu.shuffle.collective.spmd.bucketRounds"
BATCH_KEY = "spark.rapids.tpu.sql.batchSizeRows"


@pytest.fixture
def collective_session():
    s = TpuSession()
    s.enable_collective_shuffle(N_DEV)
    yield s
    s.disable_collective_shuffle()


@pytest.fixture
def conf_sandbox():
    """Snapshot/restore the confs these tests tweak."""
    conf = get_conf()
    keys = (ROUND_KEY, BUCKET_KEY, BATCH_KEY,
            "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes")
    old = {k: conf.get(k) for k in keys}
    yield conf
    for k, v in old.items():
        conf.set(k, v)


# ------------------------------------------------------------------ #
# _shard_rounds round-staging contracts
# ------------------------------------------------------------------ #


class _FakeChild:
    """Minimal child exec for driving _shard_rounds directly."""

    def __init__(self, schema: T.Schema, batches):
        self.schema = schema
        self._batches = list(batches)
        self.num_partitions = 1

    def execute_partition(self, p):
        assert p == 0
        yield from self._batches


def _int_schema():
    return T.Schema([T.Field("k", T.LONG), T.Field("v", T.LONG)])


def _batch(n_rows: int, seed: int = 0) -> ColumnarBatch:
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_numpy(
        {"k": rng.integers(0, 100, n_rows).astype(np.int64),
         "v": rng.integers(0, 100, n_rows).astype(np.int64)},
        _int_schema())


def _collective_base(mesh):
    from spark_rapids_tpu.execs.collective import _CollectiveBase

    schema = _int_schema()
    child = _FakeChild(schema, [])
    exec_ = _CollectiveBase(child)
    exec_.mesh = mesh
    exec_._init_stage(None)
    return exec_


@pytest.fixture
def mesh8():
    from spark_rapids_tpu.parallel.mesh import make_mesh

    return make_mesh(N_DEV)


def test_shard_rounds_least_loaded_balancing(mesh8, conf_sandbox):
    """Skewed batch sizes spread by LEAST-LOADED shard, not round
    robin: after a 900-row batch lands on one shard, the next batches
    fill the other shards before that one sees more rows."""
    exec_ = _collective_base(mesh8)
    conf_sandbox.set(ROUND_KEY, 1 << 20)  # one round
    batches = [_batch(900, seed=1)] + [_batch(100, seed=2 + i)
                                       for i in range(14)]
    child = _FakeChild(_int_schema(), batches)
    rounds = list(exec_._shard_rounds(child))
    assert len(rounds) == 1
    rows = [b.concrete_num_rows() for b in rounds[0]]
    assert sum(rows) == 900 + 14 * 100
    # the skewed batch's shard received nothing further: its load is
    # exactly 900, and every other shard got two 100-row batches
    assert sorted(rows) == [200] * 7 + [900]


def test_shard_rounds_always_yields_empties(mesh8):
    """An empty child still yields ONE round of schema-correct empty
    shard batches, so downstream stage programs emit schema-correct
    empty output."""
    exec_ = _collective_base(mesh8)
    child = _FakeChild(_int_schema(), [])
    rounds = list(exec_._shard_rounds(child))
    assert len(rounds) == 1
    assert len(rounds[0]) == N_DEV
    for b in rounds[0]:
        assert b.concrete_num_rows() == 0
        assert b.schema == _int_schema()


def test_shard_rounds_budget_boundary(mesh8, conf_sandbox):
    """A round closes exactly when SOME shard reaches the row budget
    (COLLECTIVE_ROUND_ROWS): one budget-sized batch per round when
    batches match the budget, and a trailing partial round flushes at
    end of input."""
    exec_ = _collective_base(mesh8)
    conf_sandbox.set(ROUND_KEY, 500)
    # 3 batches of exactly 500 -> each fills one shard to the budget
    # and closes a round; a final 10-row batch flushes as round 4
    child = _FakeChild(_int_schema(),
                       [_batch(500, seed=i) for i in range(3)]
                       + [_batch(10, seed=99)])
    rounds = list(exec_._shard_rounds(child))
    assert len(rounds) == 4
    for r in rounds[:3]:
        per_shard = [b.concrete_num_rows() for b in r]
        assert max(per_shard) == 500
        assert sum(per_shard) == 500
    assert sum(b.concrete_num_rows() for b in rounds[3]) == 10
    # one row under the budget does NOT close a round mid-stream
    conf_sandbox.set(ROUND_KEY, 501)
    child = _FakeChild(_int_schema(), [_batch(500, seed=5)])
    rounds = list(exec_._shard_rounds(child))
    assert len(rounds) == 1


def test_pad_rounds_pow2(mesh8):
    from spark_rapids_tpu.parallel import spmd as S

    schema = _int_schema()
    one = [[_batch(4)] * N_DEV]
    assert len(S.pad_rounds_pow2(list(one), schema, N_DEV)) == 1
    three = [[_batch(4)] * N_DEV] * 3
    padded = S.pad_rounds_pow2(list(three), schema, N_DEV)
    assert len(padded) == 4
    assert all(b.concrete_num_rows() == 0 for b in padded[-1])


# ------------------------------------------------------------------ #
# Global sharded input assembly
# ------------------------------------------------------------------ #


def test_shard_stack_rounds_is_global_and_sharded(mesh8):
    import jax
    from jax.sharding import PartitionSpec as P

    from spark_rapids_tpu.parallel import spmd as S

    rounds = [[_batch(16, seed=r * N_DEV + d) for d in range(N_DEV)]
              for r in range(2)]
    xs = S.shard_stack_rounds(rounds, mesh8)
    leaf = xs.columns[0].data
    assert leaf.shape[:2] == (2, N_DEV)
    assert leaf.sharding.spec == P(None, "data")
    assert leaf.sharding.mesh.shape["data"] == N_DEV
    # shard d's slice lives on mesh device d, not one host-stacked blob
    devices = {s.index[1].start: s.device
               for s in leaf.addressable_shards}
    assert len(devices) == N_DEV
    assert devices[0] != devices[1]
    counts = np.asarray(jax.device_get(xs.num_rows))
    assert counts.shape == (2, N_DEV)
    assert counts.sum() == 2 * N_DEV * 16


# ------------------------------------------------------------------ #
# The mid-stage boundary: one program from a stage program's stacked
# output to the next one's stacked input
# ------------------------------------------------------------------ #

RESTAGE_DEV = 4
_KVS = T.Schema([T.Field("k", T.LONG), T.Field("v", T.DOUBLE),
                 T.Field("s", T.STRING)])


@dataclasses.dataclass(frozen=True)
class Restage:
    """A boundary's input: a `(rounds, capacity)` and a `(R, n)` count
    array a part (a part is what one bucket's program returned), the
    string column's width, and whether a program has to run."""

    parts: tuple  # ((rounds, capacity), counts) a part
    width: int = 8
    runs: bool = True


def _counts(*rounds) -> np.ndarray:
    return np.asarray(rounds, np.int32)


RESTAGES = {
    "one round": Restage((((1, 64), _counts([5, 9, 3, 7])),)),
    "three rounds padded to four": Restage(
        (((3, 32), _counts([1, 2, 3, 4], [9, 0, 2, 1], [4, 4, 4, 4])),)),
    "two buckets of unlike capacities": Restage(
        (((2, 64), _counts([20, 3, 1, 0], [7, 7, 7, 7])),
         ((1, 16), _counts([2, 16, 0, 5])))),
    "a string column's width after pad_width": Restage(
        (((2, 32), _counts([3, 1, 2, 2], [0, 8, 1, 1])),), width=5),
    "a shard with no rows": Restage(
        (((2, 64), _counts([6, 0, 11, 2], [1, 0, 3, 9])),)),
    "counts that pad back to the input's capacity": Restage(
        (((2, 16), _counts([12, 3, 0, 1], [2, 2, 9, 4])),), runs=False),
}


def _stacked(mesh, shape: tuple, counts: np.ndarray, width: int,
             seed: int) -> tuple:
    """A stage program's output as the boundary finds it: every leaf
    one `(R, n, capacity, ...)` array under `rounds_sharding`, rows
    past a piece's count left as the program left them (not zero).
    Returns the batch and its leaves on the host."""
    import jax

    from spark_rapids_tpu.columnar.column import Column, StringColumn
    from spark_rapids_tpu.parallel import spmd as S

    rng = np.random.default_rng(seed)
    lead = (shape[0], RESTAGE_DEV, shape[1])
    host = [rng.integers(-99, 99, lead).astype(np.int64),
            rng.random(lead) < 0.9,
            rng.random(lead),
            rng.random(lead) < 0.9,
            rng.integers(97, 123, lead + (width,)).astype(np.uint8),
            rng.integers(0, width + 1, lead).astype(np.int32),
            rng.random(lead) < 0.9]
    sharding = S.rounds_sharding(mesh)
    k, kv, v, vv, chars, lens, sv, rows = [
        jax.device_put(x, sharding) for x in host + [counts]]
    batch = ColumnarBatch(
        [Column(k, kv, T.LONG), Column(v, vv, T.DOUBLE),
         StringColumn(chars, lens, sv)], rows, _KVS)
    return batch, host


def _the_old_path(parts: list, width: int) -> list:
    """What `shard_stack_rounds(pad_rounds_pow2(shrink_rounds(...)))`
    made of the same input, as shapes: every (round, shard) piece cut
    to `pad_capacity` of its rows (never grown), rounds of empty
    batches up to a power of two, all unified to the largest capacity
    and the `pad_width` bucket of the widest string."""
    from spark_rapids_tpu.columnar.column import (
        MIN_CAPACITY,
        pad_capacity,
        pad_width,
    )

    caps = [min(cap, max(MIN_CAPACITY, pad_capacity(int(rows))))
            for (_, cap), counts in parts for rows in counts.flat]
    rounds = sum(r for (r, _), _ in parts)
    r2 = 1 << (rounds - 1).bit_length() if rounds > 1 else 1
    lead = (r2, RESTAGE_DEV, max(caps))
    return [(lead, np.int64), (lead, np.bool_), (lead, np.float64),
            (lead, np.bool_), (lead + (pad_width(width),), np.uint8),
            (lead, np.int32), (lead, np.bool_)]


@pytest.mark.parametrize("name", sorted(RESTAGES))
def test_restage_is_the_old_cut_and_stack_in_one_program(name):
    """`spmd.restage` against what it replaced (a `take_piece` and a
    `shrink_to_capacity` a leaf a piece, then `unify_batches`' pads, a
    `jnp.stack` a leaf a device and `place_piece`): leaf for leaf the
    same shapes and dtypes, so the next stage program keeps its key;
    the live rows bit-equal; shard d's slice on mesh device d; ONE
    `spmdrestage` launch inside ONE `mesh.shrink` span, and no program
    at all where the output would have the input's shapes."""
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.parallel import spmd as S
    from spark_rapids_tpu.parallel.mesh import make_mesh

    case = RESTAGES[name]
    mesh = make_mesh(RESTAGE_DEV)
    made = [_stacked(mesh, shape, counts, case.width, seed)
            for seed, (shape, counts) in enumerate(case.parts)]
    counts = [c for _, c in case.parts]
    trace.enable()
    trace.clear()
    try:
        out = S.restage([(b, c) for (b, _), c in zip(made, counts)],
                        mesh, op="Test")
        events = trace.snapshot()
    finally:
        trace.disable()
        trace.clear()

    want = _the_old_path(list(case.parts), case.width)
    got = S._leaves(out)
    assert [(x.shape, x.dtype) for x in got] == want
    rows = np.asarray(out.num_rows)
    assert rows.dtype == np.int32 and rows.shape == want[0][0][:2]
    assert (rows[:sum(len(c) for c in counts)]
            == np.concatenate(counts)).all()
    assert not rows[sum(len(c) for c in counts):].any()
    # live rows, bit for bit, part after part on the rounds axis
    r0 = 0
    for (_, host), part_counts in zip(made, counts):
        for (r, d), n_rows in np.ndenumerate(part_counts):
            for x, h in zip(got, host):
                live = np.asarray(x)[r0 + r, d, :n_rows]
                if h.ndim == 4:  # the characters, zeros past the width
                    assert not live[:, case.width:].any()
                    live = live[:, :case.width]
                assert (live == h[r, d, :n_rows]).all()
        r0 += len(part_counts)
    # nothing left its chip: shard d's slice is on mesh device d
    devs = list(mesh.devices.flat)
    for x in got + [out.num_rows]:
        assert {s.index[1].start: s.device
                for s in x.addressable_shards} == dict(enumerate(devs))

    launches = [e.attrs for e in events if e.name == "mesh.launch"]
    cut, = [e.attrs for e in events if e.name == "mesh.shrink"]
    assert cut["path"] == "program"
    assert cut["skipped"] is not case.runs
    assert cut["pieces"] == RESTAGE_DEV * sum(len(c) for c in counts)
    assert cut["rows"] == sum(int(c.sum()) for c in counts)
    assert cut["capacity"] == max(cap for (_, cap), _ in case.parts)
    assert cut["to_capacity"] == want[0][0][2] and cut["leaves"] == 7
    if case.runs:
        assert [a["program"] for a in launches] == ["spmdrestage"]
        assert launches[0]["rounds"] == want[0][0][0]
        assert launches[0]["op"] == "Test"
    else:
        assert not launches and out is made[0][0]


def test_mesh_key_identity(mesh8):
    from spark_rapids_tpu.parallel.mesh import make_mesh, mesh_key

    assert mesh_key(mesh8) == mesh_key(make_mesh(N_DEV))
    assert mesh_key(mesh8) != mesh_key(make_mesh(4))


def test_cached_jit_shardings_fold_into_key(mesh8):
    from spark_rapids_tpu.execs import jit_cache
    from spark_rapids_tpu.parallel import spmd as S

    key = ("spmdtestkey", 1)
    plain = jit_cache.cached_jit(key, lambda: (lambda x: x))
    sharded = jit_cache.cached_jit(
        key, lambda: (lambda x: x),
        in_shardings=(S.rounds_sharding(mesh8),),
        out_shardings=S.rounds_sharding(mesh8))
    assert plain is not sharded
    again = jit_cache.cached_jit(
        key, lambda: (lambda x: x),
        in_shardings=(S.rounds_sharding(mesh8),),
        out_shardings=S.rounds_sharding(mesh8))
    assert sharded is again


def test_choose_bounds_dynamic_matches_static():
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.range_partition import (
        choose_bounds,
        choose_bounds_dynamic,
    )
    from spark_rapids_tpu.ops.sort import SortOrder

    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1000, 96).astype(np.int64)
    schema = T.Schema([T.Field("k", T.LONG)])
    samples = ColumnarBatch.from_numpy({"k": vals}, schema)
    orders = [SortOrder(0)]
    static = choose_bounds(samples, orders, 8, 96).to_pydict()["k"]
    dyn = choose_bounds_dynamic(
        samples, orders, 8).to_pydict()["k"]
    assert dyn == static
    # and with a TRACED num_rows (the in-program form)
    traced = ColumnarBatch(samples.columns,
                           jnp.asarray(96, jnp.int32), schema)
    dyn2 = choose_bounds_dynamic(traced, orders, 8).to_pydict()["k"]
    assert dyn2 == static


# ------------------------------------------------------------------ #
# Whole-stage answers: the collective stage vs one device
# ------------------------------------------------------------------ #


def _canon(table: pa.Table) -> list:
    d = table.to_pydict()
    cols = sorted(d)
    # NULL keys sort last instead of failing the comparison
    return sorted(zip(*[d[c] for c in cols]),
                  key=lambda row: tuple((x is None, x) for x in row)
                  ) if cols else []


def _collect_both(session, make_df, conf):
    """(the collective answer, the one-device answer) of one query in
    one session: the second collect plans under `shuffle.transport =
    local`, so no collective exec and no mesh program is in it."""
    from spark_rapids_tpu.plan.planner import plan_query

    def run(collective: bool) -> pa.Table:
        df = make_df(session)
        plan = plan_query(df._plan, session.conf)[0].tree_string()
        assert ("TpuCollective" in plan) == collective, plan
        return df.collect(engine="tpu")

    mesh_answer = run(True)
    conf.set(TRANSPORT_KEY, "local")
    try:
        return mesh_answer, run(False)
    finally:
        conf.set(TRANSPORT_KEY, "collective")


def _assert_same_result(session, make_df, conf):
    mesh_answer, single = map(_canon, _collect_both(session, make_df,
                                                    conf))
    assert mesh_answer == single
    return mesh_answer


def test_spmd_agg_identical_to_one_device(collective_session,
                                          conf_sandbox):
    rng = np.random.default_rng(11)
    t = pa.table({"k": rng.integers(0, 40, 3000).astype(np.int64),
                  "v": rng.integers(0, 100, 3000).astype(np.int64)})
    conf_sandbox.set(ROUND_KEY, 256)
    conf_sandbox.set(BATCH_KEY, 128)

    def q(s):
        return (s.create_dataframe(t).group_by(col("k"))
                .agg((sum_(col("v")), "s"), (count(col("v")), "c")))

    rows = _assert_same_result(collective_session, q, conf_sandbox)
    wd = t.group_by("k").aggregate(
        [("v", "sum"), ("v", "count")]).to_pydict()
    # rows are (c, k, s) tuples (columns sorted by name)
    want = sorted(zip(wd["v_count"], wd["k"], wd["v_sum"]))
    assert rows == want


@pytest.mark.parametrize("how", [
    "inner",
    # the other types compile their own programs on the mesh and on
    # one device — covered, but in the slow tier to keep tier-1's
    # wall bounded
    pytest.param("left_anti", marks=pytest.mark.slow),
    pytest.param("left_outer", marks=pytest.mark.slow),
    pytest.param("left_semi", marks=pytest.mark.slow),
])
def test_spmd_join_identical_to_one_device(collective_session,
                                           conf_sandbox, how):
    rng = np.random.default_rng(13)
    lt = pa.table({"k": rng.integers(0, 30, 1200).astype(np.int64),
                   "lv": rng.integers(0, 9, 1200).astype(np.int64)})
    rt = pa.table({"k": rng.integers(0, 45, 300).astype(np.int64),
                   "rv": rng.integers(0, 9, 300).astype(np.int64)})
    conf_sandbox.set(
        "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes", -1)
    conf_sandbox.set(ROUND_KEY, 200)
    conf_sandbox.set(BATCH_KEY, 128)

    def q(s):
        return s.create_dataframe(lt).join(
            s.create_dataframe(rt), on="k", how=how)

    _assert_same_result(collective_session, q, conf_sandbox)


def test_spmd_sort_identical_to_one_device(collective_session,
                                           conf_sandbox):
    rng = np.random.default_rng(17)
    t = pa.table({"k": rng.integers(0, 10_000, 2500).astype(np.int64),
                  "v": np.arange(2500, dtype=np.int64)})
    conf_sandbox.set(ROUND_KEY, 300)
    conf_sandbox.set(BATCH_KEY, 128)

    # k repeats, so v decides between equal keys: the total order
    # (k, v) is unique, whichever shard and round a row went through
    def q(s):
        return s.create_dataframe(t).order_by(col("k"), col("v"))

    def rows(table):
        d = table.to_pydict()
        return list(zip(d["k"], d["v"]))

    mesh_answer, single = map(rows, _collect_both(
        collective_session, q, conf_sandbox))
    assert mesh_answer == rows(t.sort_by([("k", "ascending"),
                                          ("v", "ascending")]))
    assert mesh_answer == single


def test_spmd_empty_input_stages(collective_session, conf_sandbox):
    conf_sandbox.set(
        "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes", -1)
    empty = pa.table({"k": pa.array([], pa.int64()),
                      "v": pa.array([], pa.int64())})
    s = collective_session
    agg = (s.create_dataframe(empty).group_by(col("k"))
           .agg((sum_(col("v")), "s"))).collect(engine="tpu")
    assert agg.num_rows == 0
    srt = s.create_dataframe(empty).order_by(col("k")) \
        .collect(engine="tpu")
    assert srt.num_rows == 0
    j = s.create_dataframe(empty).join(
        s.create_dataframe(empty), on="k", how="inner") \
        .collect(engine="tpu")
    assert j.num_rows == 0


# ------------------------------------------------------------------ #
# THE acceptance test: O(1) partitioned programs per stage
# ------------------------------------------------------------------ #


def _collective_programs(snap: dict) -> dict:
    return {k: v for k, v in snap.items()
            if v["tag"].startswith("spmd")}


def _budget_table(distinct: bool = False) -> pa.Table:
    rng = np.random.default_rng(23)
    k = rng.permutation(8192) if distinct else rng.integers(0, 64, 8192)
    return pa.table({"k": k.astype(np.int64),
                     "v": rng.integers(0, 100, 8192).astype(np.int64)})


def _budget_query(session, conf, t: pa.Table):
    """The dispatch-budget query: a round closes when one shard hits
    the budget; with least-loaded filling that is ~8 shards x 128 rows
    = 1024 rows per round -> 8192 rows = ~8 rounds of input, one full
    bucket (and the trailing round's bucket, if any)."""
    conf.set(ROUND_KEY, 128)
    conf.set(BATCH_KEY, 64)
    conf.set(BUCKET_KEY, 8)
    return (session.create_dataframe(t).group_by(col("k"))
            .agg((sum_(col("v")), "s")))


def _agg_node(exec_):
    return next(nd for nd in exec_._walk()
                if type(nd).__name__ == "TpuCollectiveHashAggregateExec")


def _traced_collect(exec_, span: str = "collective.agg.exchange"):
    """collect_exec under the tracer: (answer, the attrs of the stage's
    `span`s, one dict a dispatch of its exchange program)."""
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.plan.planner import collect_exec

    trace.enable()
    trace.clear()
    try:
        got = collect_exec(exec_)
        spans = [e.attrs for e in trace.snapshot() if e.name == span]
    finally:
        trace.disable()
        trace.clear()
    return got, spans


def test_spmd_stage_dispatch_budget(collective_session, conf_sandbox):
    """Many exchange rounds, O(1) program dispatches: with the round
    budget forced tiny (8+ rounds' worth of input), the warm agg stage
    still executes as four programs a bucket (update, exchange and the
    boundary after each) plus the buckets' rounds end to end and one
    fold — the rounds run as an in-program scan, not a Python loop of
    dispatches — and the ledger attributes the partitioned programs
    with their mesh width and in-program round counts."""
    from spark_rapids_tpu.plan.planner import collect_exec, plan_query
    from spark_rapids_tpu.trace import ledger

    t = _budget_table()
    df = _budget_query(collective_session, conf_sandbox, t)
    exec_, _ = plan_query(df._plan, collective_session.conf)
    assert "stage=spmd" in exec_.tree_string()

    ledger.enable()
    ledger.reset_stats()
    try:
        got = collect_exec(exec_)
        ledger.LEDGER.flush(timeout=10.0)
        snap = _collective_programs(ledger.snapshot())
        dispatches = sum(p["dispatches"] for p in snap.values())
        rounds = _agg_node(exec_).metrics["collectiveRounds"].value
        buckets = -(-rounds // 8)
        assert rounds >= 8, rounds
        # stage budget: an update and an exchange program a bucket,
        # a boundary program after each, one more to put several
        # buckets' rounds end to end, and one fold — never one
        # dispatch per round
        assert dispatches == 4 * buckets + (buckets > 1) + 1, snap
        assert {p["tag"] for p in snap.values()} == {
            "spmdupdate", "spmdxchg", "spmdrestage", "spmdtail"}, snap
        assert all(p["devices"] == N_DEV for p in snap.values()), snap
        scan_rounds = max(p["rounds"] for p in snap.values())
        assert scan_rounds >= 8, snap  # rounds folded INTO a program
    finally:
        ledger.disable()
        ledger.reset_stats()
    want = t.group_by("k").aggregate([("v", "sum")])
    assert _canon(got) == _canon(want)


def test_spmd_agg_exchange_sized_to_counted_partials(collective_session,
                                                     conf_sandbox):
    """The exchange program of the aggregate stage runs at the capacity
    of the largest COUNTED partial, not at the input round's: the
    `collective.agg.exchange` span says what it was sized to, and
    `collectivePartialRows` is the partial rows the shuffle carried —
    the distinct keys of every (round, shard) input, summed."""
    from spark_rapids_tpu.columnar.column import pad_capacity
    from spark_rapids_tpu.plan.planner import plan_query

    t = _budget_table()
    df = _budget_query(collective_session, conf_sandbox, t)
    # what the map side must count, from the same round staging
    twin, _ = plan_query(df._plan, collective_session.conf)
    node = _agg_node(twin)
    per_shard = [len(set(b.to_pydict()["k"]))
                 for shards in node._shard_rounds(node.children[0])
                 for b in shards]

    exec_, _ = plan_query(df._plan, collective_session.conf)
    got, spans = _traced_collect(exec_)
    assert spans and sum(s["rounds"] for s in spans) >= 8, spans
    for s in spans:
        assert s["capacity"] == pad_capacity(s["partial_rows"]), s
        assert s["capacity"] < s["input_capacity"], s
    assert max(s["partial_rows"] for s in spans) == max(per_shard)
    metrics = _agg_node(exec_).metrics
    assert metrics["collectivePartialRows"].value == sum(per_shard)
    want = t.group_by("k").aggregate([("v", "sum")])
    assert _canon(got) == _canon(want)


def test_spmd_agg_exchange_stands_aside_for_distinct_keys(
        collective_session, conf_sandbox):
    """Every key distinct: the partials are as many as the rows, the
    counted capacity IS the input's bucket and the exchange runs at
    the size it always did — no shape test, no knob, same answer."""
    from spark_rapids_tpu.plan.planner import plan_query

    t = _budget_table(distinct=True)
    df = _budget_query(collective_session, conf_sandbox, t)
    exec_, _ = plan_query(df._plan, collective_session.conf)
    got, spans = _traced_collect(exec_)
    assert spans
    full = [s for s in spans if s["rounds"] >= 8]
    assert full, spans
    for s in full:
        assert s["capacity"] == s["input_capacity"], s
    metrics = _agg_node(exec_).metrics
    assert metrics["collectivePartialRows"].value == t.num_rows
    want = t.group_by("k").aggregate([("v", "sum")])
    assert _canon(got) == _canon(want)


def test_spmd_agg_string_keys_with_nulls_digest(collective_session,
                                                conf_sandbox):
    """String group keys with NULLs, two rounds a bucket, double sums
    (whose digest moves with the ORDER of a sum's terms): the counted
    exchange keeps sender order and the merge sorts by key, so two
    collective collects are identical to the bit; one device adds the
    same terms in another order, so its sums agree to 1e-12 relative
    and its keys and counts exactly."""
    rng = np.random.default_rng(31)
    words = np.array(["", "a", "bb", "Ünï", "delta-long-key", "zz"])
    k1 = [None if x == 6 else str(words[x])
          for x in rng.integers(0, 7, 2000)]
    k2 = [None if x == 3 else "NYR"[x]
          for x in rng.integers(0, 4, 2000)]
    t = pa.table({"k1": pa.array(k1, pa.string()),
                  "k2": pa.array(k2, pa.string()),
                  "v": rng.random(2000) * 1e6})
    conf_sandbox.set(ROUND_KEY, 64)
    conf_sandbox.set(BATCH_KEY, 64)
    conf_sandbox.set(BUCKET_KEY, 2)

    def q(s):
        return (s.create_dataframe(t).group_by(col("k1"), col("k2"))
                .agg((sum_(col("v")), "s"), (count(col("v")), "c")))

    # columns sorted by name: (c, k1, k2, s)
    rows, single = map(_canon, _collect_both(
        collective_session, q, conf_sandbox))
    assert rows == _canon(q(collective_session).collect(engine="tpu"))
    assert [r[:3] for r in rows] == [r[:3] for r in single]
    np.testing.assert_allclose([r[3] for r in rows],
                               [r[3] for r in single], rtol=1e-12, atol=0)
    groups = {(a, b) for a, b in zip(k1, k2)}
    assert {(r[1], r[2]) for r in rows} == groups
    assert sum(r[0] for r in rows) == 2000


# ------------------------------------------------------------------ #
# The map-side update groups by dictionary code, as on one chip
# ------------------------------------------------------------------ #

#: rows of one batch, and of a round where one shard is loaded
CODED_ROWS = 256
_RAN, _OF = tuple("RAN"), tuple("OF")
#: twenty-two words, in another order than any other batch's
_MANY = tuple(f"w{i:02d}" for i in range(20))[::-1] + ("a", "c")


@dataclasses.dataclass(frozen=True)
class CodedAgg:
    """A group-by over keys that arrive dictionary coded: per batch of
    `CODED_ROWS`, the values each key column draws from (numbers make
    an int64 dictionary column).  One batch is one round, one loaded
    shard beside three empty ones, unless `spread`: then the four
    batches of a round land one a shard.  `ks`: the `dict_len` of each
    key the update program must group by, the bucket's largest."""

    batches: tuple
    ks: tuple
    nulls: float = 0.0
    spread: bool = False
    bucket: int = 8

    def table(self) -> pa.Table:
        rng = np.random.default_rng(53)
        parts = []
        for draw in self.batches:
            cols = {}
            for name, values in draw.items():
                picked = [None if rng.random() < self.nulls else
                          values[i] for i in rng.integers(
                              0, len(values), CODED_ROWS)]
                if isinstance(values[0], int):
                    cols[name] = pa.array(picked,
                                          pa.int64()).dictionary_encode()
                else:
                    cols[name] = pa.array(picked, pa.string())
            cols["v"] = pa.array(rng.random(CODED_ROWS) * 1e3)
            parts.append(pa.table(cols))
        return pa.concat_tables(parts)


CODED_AGGS = {
    "two string keys": CodedAgg(({"a": _RAN, "b": _OF},) * 4, (8, 8),
                                spread=True),
    "one numeric dictionary key": CodedAgg(
        ({"n": (7, -3, 1 << 40, 12, 0)},) * 2, (8,)),
    "NULL keys": CodedAgg(({"a": _RAN, "b": _OF},) * 4, (8, 8),
                          nulls=0.2, spread=True),
    "three of four shards empty": CodedAgg(({"a": _RAN, "b": _OF},),
                                           (8, 8)),
    "two rounds whose dictionaries differ in size and order": CodedAgg(
        ({"a": ("c", "a", "b"), "b": _OF},
         {"a": _MANY, "b": ("O", "F")}), (32, 8), bucket=2),
    "an empty round pad_rounds_pow2 adds": CodedAgg(
        ({"a": _RAN, "b": _OF},) * 3, (8, 8)),
}


def _coded_conf(conf, rows: int, spread: bool, bucket: int) -> None:
    conf.set(BATCH_KEY, rows)
    conf.set(ROUND_KEY, 4 * rows if spread else rows)
    conf.set(BUCKET_KEY, bucket)


def _stacks_and_codes(df, monkeypatch):
    """Collect `df` on the mesh with every program traced anew: (the
    answer, the attrs of its `mesh.stack` spans, the `ks` of each
    coded group-by the traces took, the columns of every stage input
    `_stack_rounds` built)."""
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.execs import jit_cache
    from spark_rapids_tpu.ops import groupby as G
    from spark_rapids_tpu.parallel import spmd as S

    coded, stacked = [], []
    coded_groupby, stack_rounds = G._coded_groupby, S._stack_rounds

    def noting_coded(batch, key_ordinals, ks, *args):
        coded.append(tuple(ks))
        return coded_groupby(batch, key_ordinals, ks, *args)

    def noting_stacks(*args):
        out = stack_rounds(*args)
        stacked.append(out.columns)
        return out

    monkeypatch.setattr(G, "_coded_groupby", noting_coded)
    monkeypatch.setattr(S, "_stack_rounds", noting_stacks)
    jit_cache.clear()
    trace.enable()
    trace.clear()
    try:
        got = df.collect(engine="tpu")
        stacks = [e.attrs for e in trace.snapshot()
                  if e.name == "mesh.stack"]
    finally:
        trace.disable()
        trace.clear()
    return got, stacks, coded, stacked


@pytest.mark.parametrize("name", sorted(CODED_AGGS))
def test_spmd_update_groups_by_dictionary_code(mesh4_session,
                                               conf_sandbox,
                                               monkeypatch, name):
    """The stage entry keeps the grouping keys' dictionary sidecars and
    the update program takes the coded group-by, at the bucket's
    largest `dict_len` of each key, with every (round, shard) indexing
    its own dictionary: the CPU engine's answer, one `mesh.stack`
    whose `coded_keys` counts the keys, every sidecar leaf placed."""
    from tests.differential import assert_tables_equal

    case = CODED_AGGS[name]
    _coded_conf(conf_sandbox, CODED_ROWS, case.spread, case.bucket)
    keys = [k for k in case.batches[0]]
    df = (mesh4_session.create_dataframe(case.table())
          .group_by(*[col(k) for k in keys])
          .agg((sum_(col("v")), "s"), (count(col("v")), "c")))
    got, stacks, coded, _ = _stacks_and_codes(df, monkeypatch)
    assert coded == [case.ks]  # the update's trace; merges sort
    assert len(stacks) == 1
    (stack,) = stacks
    assert stack["coded_keys"] == len(keys)
    assert stack["host_uploads"] + stack["device_born"] \
        + stack["d2d_transfers"] == stack["leaves"] * JOIN_DEV
    assert_tables_equal(got, df.collect(engine="cpu"), approx_float=True)


def _many_words(prefix: str) -> tuple:
    return tuple(f"{prefix}{i}" for i in range(60))


def _rollup(session, t):
    return session.create_dataframe(t).rollup("a", "b").agg(
        (sum_(col("v")), "s"))


def _past_the_domain(session, t):
    return session.create_dataframe(t).group_by(
        col("a"), col("b"), col("c")).agg((sum_(col("v")), "s"))


#: name -> (the query, the batches of its table)
CODED_BYPASSES = {
    # three keys of 60 words: (64 + 1)^3 groups past MAX_CODED_DOMAIN
    "a domain past MAX_CODED_DOMAIN": (_past_the_domain, (
        {"a": _many_words("a"), "b": _many_words("b"),
         "c": _many_words("c")},)),
    "a rollup": (_rollup, ({"a": _RAN, "b": _OF},) * 2),
}


@pytest.mark.parametrize("name", sorted(CODED_BYPASSES))
def test_spmd_coded_update_bypassed(mesh4_session, conf_sandbox,
                                    monkeypatch, name):
    """Where the update could not group by code, the stage entry stacks
    what it always did: no column keeps a sidecar, every `mesh.stack`
    reads `coded_keys` 0 and counts only the data leaves, and no
    program takes the coded group-by."""
    from spark_rapids_tpu.parallel import spmd as S
    from tests.differential import assert_tables_equal

    query, batches = CODED_BYPASSES[name]
    t = CodedAgg(batches, ()).table()
    _coded_conf(conf_sandbox, CODED_ROWS, False, 8)
    df = query(mesh4_session, t)
    got, stacks, coded, stacked = _stacks_and_codes(df, monkeypatch)
    assert coded == []
    assert stacks and [s["coded_keys"] for s in stacks] == [0] * len(stacks)
    assert len(stacked) == len(stacks)
    for s, cols in zip(stacks, stacked):
        assert all(getattr(c, "codes", None) is None for c in cols)
        leaves = S._leaves(ColumnarBatch(cols, 0, None))
        assert s["leaves"] == len(leaves)
    assert_tables_equal(got, df.collect(engine="cpu"), approx_float=True)


# ------------------------------------------------------------------ #
# The join stage: both sides leave through counted send slots
# ------------------------------------------------------------------ #

JOIN_DEV = 4
BROADCAST_KEY = "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes"


@pytest.fixture
def mesh4_session():
    s = TpuSession()
    s.enable_collective_shuffle(JOIN_DEV)
    yield s
    s.disable_collective_shuffle()


def _join_node(exec_):
    return next(nd for nd in exec_._walk()
                if type(nd).__name__ == "TpuCollectiveHashJoinExec")


def _join_tables(same_key: bool = False) -> tuple:
    """2,048 stream rows against 512 build rows with distinct keys (no
    stream row matches twice, so the probe's guess never overflows);
    `same_key`: 512 against 16, every key of both sides 7."""
    rng = np.random.default_rng(41)
    if same_key:
        lk, rk = np.full(512, 7), np.full(16, 7)
    else:
        lk, rk = rng.integers(0, 3000, 2048), rng.permutation(3000)[:512]
    return (pa.table({"k": lk.astype(np.int64),
                      "lv": np.arange(len(lk), dtype=np.int64)}),
            pa.table({"k": rk.astype(np.int64),
                      "rv": np.arange(len(rk), dtype=np.int64)}))


def _join_query(session, conf, lt: pa.Table, rt: pa.Table,
                batch_rows: int, how: str = "inner"):
    """A shuffled join whose rounds are one batch each: a round closes
    once one shard holds `roundRows`, so with the batch as large every
    round is one loaded shard beside three empty ones (what the
    four-chip cells run), four rounds a stream bucket."""
    conf.set(BROADCAST_KEY, -1)
    conf.set(ROUND_KEY, batch_rows)
    conf.set(BATCH_KEY, batch_rows)
    conf.set(BUCKET_KEY, 4)
    return session.create_dataframe(lt).join(
        session.create_dataframe(rt), on="k", how=how)


def _one_device(df, conf) -> pa.Table:
    conf.set(TRANSPORT_KEY, "local")
    try:
        return df.collect(engine="tpu")
    finally:
        conf.set(TRANSPORT_KEY, "collective")


def _counted_slots(node) -> dict:
    """side -> the slot capacity each of its exchanges must leave at,
    reckoned on the host: the side's own `_shard_rounds` staging cut
    into the stage's buckets, every staged batch's partition ids, the
    largest (round, source, destination) count of a bucket padded."""
    from spark_rapids_tpu.columnar.column import pad_capacity

    def largest(rounds, route) -> int:
        worst = 0
        for shards in rounds:
            for b in shards:
                rows = b.concrete_num_rows()
                pid = np.asarray(route(b))[:rows]
                worst = max(worst, int(np.bincount(
                    pid, minlength=JOIN_DEV).max()))
        return pad_capacity(worst)

    build = list(node._shard_rounds(node.children[1]))
    stream = list(node._shard_rounds(node.children[0]))
    step = node.bucket_rounds
    return {"build": [largest(build, node._route_build)],
            "stream": [largest(stream[i:i + step], node._route_stream)
                       for i in range(0, len(stream), step)]}


def test_spmd_join_exchange_sized_to_counted_slots(mesh4_session,
                                                   conf_sandbox):
    """Both sides of the join stage leave through send slots of the
    capacity the largest COUNTED (source, destination) rows pad to, not
    of the input's: the `collective.join.exchange` span says both, and
    `collectiveBytes` is what the all_to_alls were sized to carry."""
    from spark_rapids_tpu.plan.planner import plan_query

    lt, rt = _join_tables()
    df = _join_query(mesh4_session, conf_sandbox, lt, rt, 256)
    twin, _ = plan_query(df._plan, mesh4_session.conf)
    want_slots = _counted_slots(_join_node(twin))

    exec_, _ = plan_query(df._plan, mesh4_session.conf)
    got, spans = _traced_collect(exec_, "collective.join.exchange")
    by_side = {side: [s for s in spans if s["side"] == side]
               for side in ("build", "stream")}
    assert len(by_side["build"]) == 1 and len(by_side["stream"]) == 2
    for side, held in by_side.items():
        assert [s["capacity"] for s in held] == want_slots[side], held
        for s in held:
            assert s["capacity"] < s["input_capacity"] == 256, s
            assert s["row_bytes"] == 18, s  # two int64 and validity
    metrics = _join_node(exec_).metrics
    assert metrics["collectiveBytes"].value == sum(
        s["rounds"] * JOIN_DEV * JOIN_DEV * s["capacity"] * s["row_bytes"]
        for s in spans)
    assert metrics["collectiveRows"].value == lt.num_rows + rt.num_rows
    assert sum(s["rows"] for s in spans) == lt.num_rows + rt.num_rows
    assert metrics["buildRows"].value == rt.num_rows
    assert got.num_rows > 0
    assert _canon(got) == _canon(_one_device(df, conf_sandbox))


def test_spmd_join_exchange_stands_aside_for_one_destination(
        mesh4_session, conf_sandbox):
    """Every key equal: a round's rows all leave for one destination,
    the counted slot IS the input's capacity and the exchange runs at
    the size it always did — no shape test, no knob, same answer."""
    from spark_rapids_tpu.plan.planner import plan_query

    lt, rt = _join_tables(same_key=True)
    df = _join_query(mesh4_session, conf_sandbox, lt, rt, 128)
    exec_, _ = plan_query(df._plan, mesh4_session.conf)
    got, spans = _traced_collect(exec_, "collective.join.exchange")
    assert {s["side"] for s in spans} == {"build", "stream"}, spans
    for s in spans:
        if s["side"] == "stream":
            assert s["capacity"] == s["input_capacity"] == 128, s
        else:  # 16 rows of a batch of 16
            assert s["capacity"] == s["input_capacity"] == 16, s
    assert got.num_rows == lt.num_rows * rt.num_rows
    assert _canon(got) == _canon(_one_device(df, conf_sandbox))


@pytest.mark.parametrize("how", ["inner", "left_outer", "left_semi",
                                 "left_anti"])
def test_spmd_join_null_keys_and_strings_digest(mesh4_session,
                                                conf_sandbox, how):
    """NULL keys on both sides (they match nothing, and the outer and
    anti joins keep the stream's), a string column a side, repeated
    keys, two rounds a bucket: the counted exchange keeps sender order,
    so two collective collects are identical row for row, and equal to
    the one-device and the CPU engine's answers as sets of rows."""
    rng = np.random.default_rng(43)
    words = np.array(["", "a", "Ünï", "delta-long-value", "zz"])

    def keys(n, hi):
        return pa.array([None if x == hi else x
                         for x in rng.integers(0, hi + 1, n).tolist()],
                        pa.int64())

    lt = pa.table({"k": keys(600, 40),
                   "ls": pa.array(words[rng.integers(0, 5, 600)].tolist(),
                                  pa.string()),
                   "lv": np.arange(600, dtype=np.int64)})
    rt = pa.table({"rk": keys(90, 60),
                   "rs": pa.array([None if x == 4 else str(words[x])
                                   for x in rng.integers(0, 5, 90)],
                                  pa.string())})
    conf_sandbox.set(BROADCAST_KEY, -1)
    conf_sandbox.set(ROUND_KEY, 64)
    conf_sandbox.set(BATCH_KEY, 64)
    conf_sandbox.set(BUCKET_KEY, 2)

    def q(s):
        return s.create_dataframe(lt).join(
            s.create_dataframe(rt), left_on=[col("k")],
            right_on=[col("rk")], how=how)

    mesh_answer, single = _collect_both(mesh4_session, q, conf_sandbox)
    again = q(mesh4_session).collect(engine="tpu")
    assert mesh_answer.to_pydict() == again.to_pydict()
    assert _canon(mesh_answer) == _canon(single)
    assert _canon(mesh_answer) == _canon(
        q(mesh4_session).collect(engine="cpu"))
    null_keys = sum(k is None for k in lt["k"].to_pylist())
    assert null_keys > 0
    kept = sum(k is None for k in mesh_answer["k"].to_pylist())
    assert kept == (null_keys if how in ("left_outer", "left_anti")
                    else 0)


def test_spmd_join_stage_dispatch_budget(mesh4_session, conf_sandbox):
    """Eight stream rounds, O(1) dispatches a side and bucket: a
    count, a route and a boundary program each, the build side's fold
    and a probe a bucket — the rounds run inside the programs' scans."""
    from spark_rapids_tpu.plan.planner import collect_exec, plan_query
    from spark_rapids_tpu.trace import ledger

    lt, rt = _join_tables()
    df = _join_query(mesh4_session, conf_sandbox, lt, rt, 256)
    exec_, _ = plan_query(df._plan, mesh4_session.conf)
    assert "stage=spmd(bucket=4)" in exec_.tree_string()

    ledger.enable()
    ledger.reset_stats()
    try:
        got = collect_exec(exec_)
        ledger.LEDGER.flush(timeout=10.0)
        snap = _collective_programs(ledger.snapshot())
        rounds = _join_node(exec_).metrics["collectiveRounds"].value
        by_tag: dict = {}
        for p in snap.values():
            by_tag[p["tag"]] = by_tag.get(p["tag"], 0) + p["dispatches"]
        # 2 build rounds, 8 stream rounds in 2 buckets
        assert rounds == 10, rounds
        assert by_tag == {"spmdroutecount": 3, "spmdxchg": 3,
                          "spmdrestage": 3, "spmdtail": 1,
                          "spmdjoin": 2}, snap
        assert all(p["devices"] == JOIN_DEV for p in snap.values()), snap
        assert max(p["rounds"] for p in snap.values()) == 4, snap
    finally:
        ledger.disable()
        ledger.reset_stats()
    assert _canon(got) == _canon(_one_device(df, conf_sandbox))


def test_spmd_explain_shows_stage_decision(collective_session,
                                           conf_sandbox):
    """The stage shape is decided by the planner seam at plan time and
    is visible in the plan report (and therefore the event log)."""
    from spark_rapids_tpu.plan.planner import plan_query

    t = pa.table({"k": pa.array([1, 2], pa.int64()),
                  "v": pa.array([3, 4], pa.int64())})
    df = (collective_session.create_dataframe(t).group_by(col("k"))
          .agg((sum_(col("v")), "s")))
    conf_sandbox.set(BUCKET_KEY, 4)
    exec_, _ = plan_query(df._plan, collective_session.conf)
    assert "stage=spmd(bucket=4)" in exec_.tree_string()
    # conf flips AFTER planning do not change the planned stage shape
    conf_sandbox.set(BUCKET_KEY, 2)
    assert "stage=spmd(bucket=4)" in exec_.tree_string()
    assert _agg_node(exec_).bucket_rounds == 4


# ------------------------------------------------------------------ #
# One executor, one compile door
# ------------------------------------------------------------------ #


def test_mesh_programs_compile_through_cached_jit_only():
    """No module of the collective tier calls `jax.jit` itself: every
    mesh program goes through `spmd._stage_jit` -> `cached_jit`, which
    gives it its `jit_tpu__<op>__<tag>` name, its cache key, its ledger
    entry and the one collective dispatch gate."""
    import spark_rapids_tpu

    root = pathlib.Path(spark_rapids_tpu.__file__).parent
    paths = sorted((root / "parallel").glob("*.py")) + [
        root / "execs" / "collective.py"]
    assert len(paths) > 5, paths
    direct = []
    for path in paths:
        tree = ast.parse(path.read_text(), str(path))
        jit_names = {a.asname or a.name for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom) and n.module == "jax"
                     for a in n.names if a.name in ("jit", "pjit")}
        for n in ast.walk(tree):
            if isinstance(n, ast.Attribute) and n.attr in ("jit", "pjit") \
                    and isinstance(n.value, ast.Name) \
                    and n.value.id == "jax":
                direct.append(f"{path.name}:{n.lineno}")
            elif isinstance(n, ast.Name) and n.id in jit_names:
                direct.append(f"{path.name}:{n.lineno}")
    assert not direct, direct


def test_collective_stage_has_one_executor_and_no_switch():
    """The per-round host loop and the key that chose it are gone: the
    key is in no documented conf, and the three execs take no `spmd`
    argument (what is left to pin at plan time is `bucket_rounds`)."""
    from spark_rapids_tpu.execs import collective as C
    from spark_rapids_tpu.tools.gen_docs import configs_md

    doc = configs_md()
    assert BUCKET_KEY.replace("bucketRounds", "enabled") not in doc
    assert BUCKET_KEY in doc and ROUND_KEY in doc
    for cls in (C.TpuCollectiveHashAggregateExec,
                C.TpuCollectiveHashJoinExec, C.TpuCollectiveSortExec):
        params = inspect.signature(cls.__init__).parameters
        assert "spmd" not in params, cls.__name__
        assert "bucket_rounds" in params, cls.__name__
        assert not hasattr(cls, "_materialize_spmd"), cls.__name__
