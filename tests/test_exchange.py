"""Collective exchange tests on an 8-virtual-device CPU mesh (the model
for testing the distributed path without a pod — mirrors the reference's
in-process mock-transport shuffle suites, SURVEY.md §4.3)."""

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exprs.hashing import partition_ids
from spark_rapids_tpu.ops.groupby import AggSpec, groupby_aggregate
from spark_rapids_tpu.parallel import make_mesh
from spark_rapids_tpu.parallel import spmd as S
from spark_rapids_tpu.parallel.exchange import exchange_shard
from spark_rapids_tpu.parallel.mesh import DATA_AXIS

N_DEV = 8


def exchange_one_round(mesh, shards, tag, pre=None, post=None):
    """`exchange_shard` on key 0 (with `pre`/`post` fused in the body)
    as a one-round exchange stage program; the shards' outputs."""

    def body(b):
        b = pre(b) if pre else b
        b = exchange_shard(b, [0], N_DEV, DATA_AXIS)
        return post(b) if post else b

    step = S.make_exchange_scan_stage(mesh, ("test_exchange", tag),
                                      body, 1)
    out = step(S.shard_stack_rounds([shards], mesh))
    return [got[0] if got else ColumnarBatch.empty(out.schema)
            for got in S.unstack_round_stage(out)]


def make_shards(schema, n_rows_per_shard, seed=0):
    rng = np.random.default_rng(seed)
    shards = []
    for _ in range(N_DEV):
        data = {
            "k": rng.integers(0, 20, n_rows_per_shard).astype(np.int64),
            "v": rng.integers(0, 100, n_rows_per_shard).astype(np.int64),
        }
        shards.append(ColumnarBatch.from_numpy(data, schema, capacity=32))
    return shards


def test_exchange_routes_rows_to_hash_owner():
    mesh = make_mesh(N_DEV)
    schema = T.Schema([T.Field("k", T.LONG), T.Field("v", T.LONG)])
    shards = make_shards(schema, 20)
    outs = exchange_one_round(mesh, shards, "route")

    # every input row lands on exactly the device that owns its hash bucket
    all_in = []
    for s in shards:
        d = s.to_pydict()
        all_in.extend(zip(d["k"], d["v"]))
    all_out = []
    for dev, o in enumerate(outs):
        d = o.to_pydict()
        for k, v in zip(d["k"], d["v"]):
            kcol = ColumnarBatch.from_numpy(
                {"k": np.array([k])}, T.Schema([T.Field("k", T.LONG)]))
            want_dev = int(np.asarray(
                partition_ids([kcol.columns[0]], kcol.capacity, N_DEV))[0])
            assert want_dev == dev, f"row k={k} on wrong device"
            all_out.append((k, v))
    assert sorted(all_in) == sorted(all_out)


def test_exchange_with_fused_partial_and_merge_agg():
    """Map-side partial agg -> exchange -> reduce-side merge, all one
    program: the TPU analog of the reference's partial/final aggregate
    around a shuffle (aggregate.scala modes)."""
    mesh = make_mesh(N_DEV)
    schema = T.Schema([T.Field("k", T.LONG), T.Field("v", T.LONG)])
    partial_schema = T.Schema([T.Field("k", T.LONG), T.Field("s", T.LONG)])
    shards = make_shards(schema, 24, seed=3)

    def pre(b):
        return groupby_aggregate(b, [0], [AggSpec("sum", 1)], partial_schema)

    def post(b):
        return groupby_aggregate(b, [0], [AggSpec("sum", 1)], partial_schema)

    outs = exchange_one_round(mesh, shards, "fused", pre=pre, post=post)

    got = {}
    for o in outs:
        d = o.to_pydict()
        for k, s in zip(d["k"], d["s"]):
            assert k not in got, "key owned by two devices"
            got[k] = s
    want = {}
    for sh in shards:
        d = sh.to_pydict()
        for k, v in zip(d["k"], d["v"]):
            want[k] = want.get(k, 0) + v
    assert got == want
