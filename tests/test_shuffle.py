"""Partitioned execution + shuffle exchange tests (mirrors the
reference's GpuPartitioningSuite + shuffle suites + hash_aggregate_test
multi-partition paths)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import BATCH_SIZE_ROWS, get_conf
from spark_rapids_tpu.execs.basic import TpuBatchSourceExec
from spark_rapids_tpu.execs.exchange import (
    SHUFFLE_PARTITIONS,
    TpuShuffleExchangeExec,
)
from spark_rapids_tpu.exprs.base import ColumnReference as C
from spark_rapids_tpu.exprs.hashing import partition_ids
from spark_rapids_tpu.ops.partition import (
    HashPartitioning,
    RoundRobinPartitioning,
    SinglePartitioning,
    split_batch,
)
from spark_rapids_tpu.session import TpuSession, avg, col, count_star, sum_

from differential import assert_tpu_cpu_equal, gen_table

SCHEMA = T.Schema([T.Field("k", T.LONG), T.Field("v", T.LONG)])


@pytest.fixture
def small_batches():
    conf = get_conf()
    old = conf.get(BATCH_SIZE_ROWS)
    conf.set(BATCH_SIZE_ROWS.key, 50)
    yield
    conf.set(BATCH_SIZE_ROWS.key, old)


def make_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_numpy(
        {"k": rng.integers(0, 30, n).astype(np.int64),
         "v": rng.integers(0, 100, n).astype(np.int64)}, SCHEMA)


def test_split_batch_partitions_rows():
    b = make_batch(100, 1)
    pids = partition_ids([b.columns[0]], b.capacity, 5)
    parts = split_batch(b, pids, 5)
    want = b.to_pydict()
    got_rows = []
    pid_np = np.asarray(pids)[:100]
    for p, sub in parts:
        d = sub.to_pydict()
        for k, v in zip(d["k"], d["v"]):
            got_rows.append((k, v))
        # every row in partition p must hash there
        for k in d["k"]:
            kb = ColumnarBatch.from_numpy(
                {"k": np.array([k], np.int64)},
                T.Schema([T.Field("k", T.LONG)]))
            assert int(np.asarray(partition_ids(
                [kb.columns[0]], kb.capacity, 5))[0]) == p
    assert sorted(got_rows) == sorted(zip(want["k"], want["v"]))


def test_exchange_roundtrip_preserves_rows():
    batches = [make_batch(60, s) for s in range(3)]
    src = TpuBatchSourceExec(batches, SCHEMA)
    ex = TpuShuffleExchangeExec(HashPartitioning([C("k")], 4), src)
    assert ex.num_partitions == 4
    got = []
    for p in range(4):
        for b in ex.execute_partition(p):
            d = b.to_pydict()
            got.extend(zip(d["k"], d["v"]))
    want = []
    for b in batches:
        d = b.to_pydict()
        want.extend(zip(d["k"], d["v"]))
    assert sorted(got) == sorted(want)


def test_roundrobin_balances():
    batches = [make_batch(64, 7)]
    src = TpuBatchSourceExec(batches, SCHEMA)
    ex = TpuShuffleExchangeExec(RoundRobinPartitioning(4), src)
    sizes = []
    for p in range(4):
        n = sum(b.concrete_num_rows() for b in ex.execute_partition(p))
        sizes.append(n)
    assert sum(sizes) == 64
    assert max(sizes) - min(sizes) <= 1


def test_single_partitioning():
    src = TpuBatchSourceExec([make_batch(30, 8)], SCHEMA)
    ex = TpuShuffleExchangeExec(SinglePartitioning(), src)
    assert ex.num_partitions == 1
    n = sum(b.concrete_num_rows() for b in ex.execute())
    assert n == 30


@pytest.mark.slow
def test_multipartition_groupby_via_shuffle(small_batches):
    """Forces scan -> partial agg -> hash exchange -> final agg."""
    spark = TpuSession()
    t = gen_table({"k": "smallint64", "v": "int64"}, 500, seed=40)
    q = spark.create_dataframe(t).group_by("k").agg(
        (sum_("v"), "s"), (count_star(), "n"), (avg("v"), "a"))
    # the physical plan really is partial/exchange/final
    from spark_rapids_tpu.plan.planner import plan_query

    exec_, _ = plan_query(q._plan)
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec

    assert isinstance(exec_, TpuHashAggregateExec) and exec_.mode == "final"
    assert isinstance(exec_.children[0], TpuShuffleExchangeExec)
    assert_tpu_cpu_equal(q, approx_float=True)


def test_multipartition_grand_aggregate(small_batches):
    spark = TpuSession()
    t = gen_table({"k": "smallint64", "v": "int64"}, 400, seed=41)
    q = spark.create_dataframe(t).agg((sum_("v"), "s"), (count_star(), "n"))
    assert_tpu_cpu_equal(q)


@pytest.mark.slow
def test_multipartition_full_query(small_batches):
    """scan+filter+join+groupby+sort across many partitions."""
    spark = TpuSession()
    t = gen_table({"k": "smallint64", "v": "int64"}, 600, seed=42)
    d = spark.create_dataframe(
        pa.table({"dk": pa.array(range(12), pa.int64()),
                  "nm": pa.array([f"g{i}" for i in range(12)])}))
    from spark_rapids_tpu.exprs.base import lit

    q = (spark.create_dataframe(t)
         .where(col("v") > lit(10))
         .join(d, left_on=["k"], right_on=["dk"], how="inner")
         .group_by("nm").agg((sum_("v"), "s"))
         .order_by("nm"))
    assert_tpu_cpu_equal(q, ignore_order=False)


@pytest.mark.slow
def test_multipartition_parquet(small_batches, tmp_path):
    import pyarrow.parquet as pq

    spark = TpuSession()
    paths = []
    for i in range(3):
        t = gen_table({"a": "int64", "s": "string"}, 120, seed=50 + i)
        p = str(tmp_path / f"part-{i}.parquet")
        pq.write_table(t, p, row_group_size=40)
        paths.append(p)
    q = spark.read_parquet(*paths).group_by("s").agg((count_star(), "n"))
    assert_tpu_cpu_equal(q)


# -- the map side's cut: one cached program a non-empty partition ------- #

CUT_SCHEMA = T.Schema([
    T.Field("k", T.LONG), T.Field("d", T.DOUBLE), T.Field("s", T.STRING),
    T.Field("st", T.StructType([T.Field("a", T.INT), T.Field("b", T.STRING)])),
])


def _cut_batch(n: int, cap: int, seed: int) -> ColumnarBatch:
    """`n` live rows at capacity `cap`: a long key, a DOUBLE with NULLs,
    a string with a dictionary sidecar and a struct of two children;
    padding rows hold values too, so a cut that reads the wrong padding
    rows shows."""
    import dataclasses

    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import Column, StructColumn

    rng = np.random.default_rng(seed)
    words = ["x", "yy", "zzz", "wwww"]
    codes = rng.integers(0, len(words), cap).astype(np.int32)
    base = ColumnarBatch.from_numpy(
        {"k": rng.integers(-50, 50, cap).astype(np.int64),
         "d": rng.normal(size=cap),
         "s": np.array([words[c] for c in codes], dtype=object)},
        T.Schema(CUT_SCHEMA.fields[:3]),
        validity={"d": rng.random(cap) > 0.3}, capacity=cap)
    k, d, s = base.columns
    dictionary = T.Schema([T.Field("w", T.STRING)])
    dict_col = ColumnarBatch.from_numpy(
        {"w": np.array(words, dtype=object)}, dictionary).columns[0]
    s = dataclasses.replace(
        s, codes=jnp.asarray(codes), dict_chars=dict_col.chars,
        dict_lens=dict_col.lengths, dict_len=16)
    child_a = Column(jnp.asarray(rng.integers(0, 9, cap).astype(np.int32)),
                     jnp.asarray(rng.random(cap) > 0.2), T.INT)
    st = StructColumn((child_a, s), jnp.asarray(rng.random(cap) > 0.1),
                      CUT_SCHEMA.fields[3].dtype)
    return ColumnarBatch([k, d, s, st], n, CUT_SCHEMA).with_device_num_rows()


def _cut_as_before(grouped: ColumnarBatch, counts) -> list:
    """The cut as it was: each partition gathered at the grouped batch's
    whole capacity, its validity masked past the count, the empty ones
    dropped, then each shrunk to the capacity bucket of its count."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import pad_capacity

    cap = grouped.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    out = []
    for p, cnt in enumerate(int(c) for c in counts):
        sub = grouped.gather(jnp.clip(idx + int(offsets[p]), 0, cap - 1),
                             cnt)
        cols = [c.with_validity(c.validity & (idx < cnt))
                for c in sub.columns]
        if cnt:
            out.append((p, ColumnarBatch(cols, cnt, grouped.schema)
                        .shrink_to_capacity(pad_capacity(cnt))))
    return out


def _leaves(batch: ColumnarBatch) -> list:
    import jax

    leaves, tree = jax.tree_util.tree_flatten(batch.columns)
    return [str(tree)] + [(a.shape, a.dtype.name, np.asarray(a).tobytes())
                          for a in leaves]


def _crafted(counts):
    """Partition ids that give exactly `counts`, shuffled."""
    def pids(batch):
        import jax.numpy as jnp

        ids = np.repeat(np.arange(len(counts)), counts)
        np.random.default_rng(7).shuffle(ids)
        return jnp.asarray(np.pad(ids, (0, batch.capacity - len(ids))),
                           jnp.int32)
    return pids


def _range_pids(batch):
    from spark_rapids_tpu.execs.sort import SortKey
    from spark_rapids_tpu.ops.partition import RangePartitioning

    part = RangePartitioning([SortKey(C("k"))], 8).bind(batch.schema)
    bounds = ColumnarBatch.from_numpy(
        {"__rk0": np.array([-40, -25, -10, 0, 5, 20, 30], np.int64)},
        T.Schema([T.Field("__rk0", T.LONG)]))
    return part.partition_ids_with_bounds(batch, bounds)


@pytest.mark.parametrize("rows,cap,pids,policy", [
    pytest.param(200, 256, lambda b: HashPartitioning([C("k")], 8).bind(
        CUT_SCHEMA).partition_ids(b), "pow2", id="hash"),
    pytest.param(256, 256, lambda b: RoundRobinPartitioning(8, 3)
                 .partition_ids(b), "pow2", id="round-robin-full-batch"),
    pytest.param(180, 256, _range_pids, "pow2", id="range"),
    pytest.param(100, 128, _crafted([0, 0, 0, 100, 0, 0, 0, 0]), "pow2",
                 id="every-row-in-one-partition"),
    pytest.param(60, 64, _crafted([1, 0, 30, 1, 0, 0, 27, 1]), "pow2",
                 id="empty-and-one-row-partitions"),
    pytest.param(64, 64, _crafted([16, 8, 0, 32, 0, 0, 0, 8]), "pow2",
                 id="partitions-at-a-bucket-size"),
    pytest.param(64, 64, _crafted([40, 0, 0, 0, 0, 0, 0, 24]), "pow2",
                 id="last-partition-past-the-capacity"),
    pytest.param(90, 96, _crafted([20, 5, 10, 0, 40, 3, 6, 6]), "pow2x3",
                 id="pow2x3"),
])
def test_cut_equals_the_whole_capacity_gather(rows, cap, pids, policy):
    """Every leaf of every non-empty partition, padding rows included,
    is what a gather at the whole capacity, the mask and the shrink
    gave; an empty partition has no batch."""
    from spark_rapids_tpu.columnar.column import CAPACITY_POLICY
    from spark_rapids_tpu.ops.partition import (
        split_batch_dispatch,
        split_batch_finish,
    )

    conf = get_conf()
    old = conf.get(CAPACITY_POLICY)
    conf.set(CAPACITY_POLICY.key, policy)
    try:
        batch = _cut_batch(rows, cap, seed=rows + cap)
        grouped, counts = split_batch_dispatch(batch, pids(batch), 8)
        counts = np.asarray(counts)
        got = split_batch_finish(grouped, counts)
        want = _cut_as_before(grouped, counts)
        assert [p for p, _ in got] == [p for p, _ in want] \
            == [p for p in range(8) if counts[p]]
        for (_, g), (_, w) in zip(got, want):
            assert g.num_rows == w.num_rows
            assert g.capacity == w.capacity
            assert _leaves(g) == _leaves(w)
    finally:
        conf.set(CAPACITY_POLICY.key, old)


def test_cut_launches_one_program_a_non_empty_partition(monkeypatch):
    """A batch with k non-empty partitions launches k cut programs, and
    a second batch whose partitions pad to the same capacities, at
    other offsets and counts, compiles none."""
    import jax
    from jax._src import monitoring

    from spark_rapids_tpu.execs import jit_cache
    from spark_rapids_tpu.ops.partition import (
        split_batch_dispatch,
        split_batch_finish,
    )

    launches = []
    real = jit_cache.cached_jit

    def counting(key, make_fn, **kw):
        fn = real(key, make_fn, **kw)
        if key[0] != "cut":
            return fn

        def launch(*args):
            launches.append(key[-1])
            return fn(*args)
        return launch

    monkeypatch.setattr(jit_cache, "cached_jit", counting)
    compiled = []

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(name)

    def cut(counts, seed):
        batch = _cut_batch(sum(counts), 128, seed)
        grouped, dev_counts = split_batch_dispatch(
            batch, _crafted(counts)(batch), 8)
        host = np.asarray(dev_counts)
        jax.block_until_ready(grouped)
        assert host.tolist() == counts
        before = len(compiled)
        out = split_batch_finish(grouped, host, op="TpuShuffleExchangeExec")
        jax.block_until_ready([b.columns for _, b in out])
        return out, len(compiled) - before

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first, _ = cut([30, 0, 9, 0, 0, 1, 40, 0], seed=1)
        assert [p for p, _ in first] == [0, 2, 5, 6]
        assert launches == [32, 16, 8, 64]
        del launches[:]
        # 0 rows where 9 were, and the same four capacities elsewhere
        second, new = cut([0, 17, 0, 25, 5, 0, 60, 0], seed=2)
        assert [b.capacity for _, b in second] == [32, 32, 8, 64]
        assert launches == [32, 32, 8, 64]
        assert new == 0
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
