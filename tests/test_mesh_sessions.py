"""Serving sessions on a mesh, under the default conf.

- the SPMD x serving digest-identity storm: four concurrent sessions
  x three templates (agg / join / sort) on the virtual 8-device mesh,
  every result bit-identical (canonical row-sorted digest) to the
  serial single-device run;
- a cancellation storm ON the mesh whose unwinds leave every process
  residency gauge exactly at baseline (conftest.leak_check);
- mesh re-keying: a new mesh changes parallel/mesh.mesh_cache_suffix
  and therefore every prepared-plan template key under an UNCHANGED
  conf fingerprint, and no mesh keeps the suffix empty;
- placement classification unit coverage (place_piece / adopt_batch:
  host vs control vs device-born vs d2d) and the SRC016 choke point.
"""

from __future__ import annotations

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf, get_conf, set_conf
from spark_rapids_tpu.parallel import make_mesh
from spark_rapids_tpu.parallel import placement
from spark_rapids_tpu.parallel.mesh import (
    active_mesh,
    mesh_cache_suffix,
    mesh_key,
    set_active_mesh,
)
from spark_rapids_tpu.serving import scheduler as scheduler_mod
from spark_rapids_tpu.session import TpuSession, col, count_star, sum_
from spark_rapids_tpu.shuffle.transport import SHUFFLE_TRANSPORT

ROUND_ROWS = "spark.rapids.tpu.shuffle.collective.roundRows"


@pytest.fixture(autouse=True)
def _isolate_mesh():
    """Active mesh, scheduler ring and serving context are process
    state — every test leaves them as found (conf restore is
    conftest._isolate_conf's job)."""
    from spark_rapids_tpu.serving import clear_serving_context

    prev = active_mesh()
    scheduler_mod.reset()
    clear_serving_context()
    yield
    set_active_mesh(prev)
    scheduler_mod.reset()
    clear_serving_context()


def _canon_digest(tbl) -> str:
    import __graft_entry__ as graft

    return graft._canon_digest(tbl)


def _tables(rows: int = 2048, seed: int = 3):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": rng.integers(0, 256, rows).astype(np.int64),
        "v": rng.integers(0, 1000, rows).astype(np.int64),
    })
    dim = pa.table({
        "k": np.arange(256, dtype=np.int64),
        "w": np.arange(256, dtype=np.int64) * 3,
    })
    sort_t = pa.table({
        "k": rng.permutation(rows).astype(np.int64),
        "v": np.arange(rows, dtype=np.int64),
    })
    return fact, dim, sort_t


def _templates(session, fact, dim, sort_t):
    return [
        ("agg", session.create_dataframe(fact)
         .group_by(col("k"))
         .agg((sum_(col("v")), "sv"), (count_star(), "n"))),
        ("join", session.create_dataframe(fact)
         .join(session.create_dataframe(dim), on="k", how="inner")),
        ("sort", session.create_dataframe(sort_t).order_by(col("k"))),
    ]


def _mesh_conf(rows: int) -> TpuConf:
    over = dict(get_conf()._values)
    over.update({
        SHUFFLE_TRANSPORT.key: "collective",
        ROUND_ROWS: max(256, rows // 8),
        "spark.rapids.tpu.sql.batchSizeRows": max(256, rows // 8),
        "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes": -1,
    })
    return TpuConf(over)


def _serial_digests(fact, dim, sort_t) -> dict:
    conf = TpuConf(dict(get_conf()._values))
    conf.set(SHUFFLE_TRANSPORT.key, "local")
    conf.set("spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes",
             -1)
    set_conf(conf)
    s0 = TpuSession(conf)
    return {name: _canon_digest(df.collect(engine="tpu"))
            for name, df in _templates(s0, fact, dim, sort_t)}


# ------------------------------------------------------------------ #
# Placement classification (the device-born contract's unit layer)
# ------------------------------------------------------------------ #


def test_place_piece_classification():
    """place_piece classifies every move: host-born numpy counts
    host_uploads (or control_uploads under control=True), an exactly
    placed jax.Array is a zero-copy device_born adoption, and an
    array on ANOTHER device is a d2d transfer."""
    import jax

    devs = jax.devices()
    placement.reset_stats()
    a = placement.place_piece(np.arange(8), devs[0])
    assert placement.stats()["host_uploads"] == 1
    placement.place_piece(np.arange(4), devs[0], control=True)
    st = placement.stats()
    assert st["host_uploads"] == 1 and st["control_uploads"] == 1
    b = placement.place_piece(a, devs[0])
    assert b is a  # exactly placed: returned unchanged
    assert placement.stats()["device_born"] == 1
    c = placement.place_piece(a, devs[1])
    assert c.devices() == {devs[1]}
    st = placement.stats()
    assert st["d2d_transfers"] == 1
    placement.reset_stats()
    assert all(v == 0 for v in placement.stats().values())


def test_adopt_batch_idempotent_and_counted():
    """adopt_batch commits every column leaf onto the shard's device;
    already-resident leaves are untouched (idempotent, zero adoptions
    on the second call) and num_rows stays a host int."""
    import jax

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    devs = jax.devices()
    schema = T.Schema([T.Field("x", T.LONG)])
    batch = ColumnarBatch.from_numpy(
        {"x": np.arange(16, dtype=np.int64)}, schema, capacity=16)
    placement.reset_stats()
    moved = placement.adopt_batch(batch, devs[1])
    n_moved = placement.stats()["adoptions"]
    assert n_moved >= 1
    again = placement.adopt_batch(moved, devs[1])
    assert placement.stats()["adoptions"] == n_moved  # idempotent
    assert isinstance(again.num_rows, int)
    np.testing.assert_array_equal(
        np.asarray(again.columns[0].data), np.arange(16))


def test_src016_choke_point_is_clean():
    """The in-tree execs//parallel/ layers carry ZERO raw
    jax.device_put calls (SRC016): placement.py is the only mover."""
    from spark_rapids_tpu.lint.source_rules import check_sources

    hits = [d for d in check_sources() if d.rule == "SRC016"]
    assert hits == [], hits


# ------------------------------------------------------------------ #
# Mesh admission + cache re-keying
# ------------------------------------------------------------------ #


def test_mesh_cache_suffix_keys_on_mesh_shape():
    """A new mesh changes mesh_cache_suffix (and so every mesh-keyed
    cache key); no active mesh keeps the suffix empty."""
    set_active_mesh(None)
    assert mesh_cache_suffix() == ""
    m8 = make_mesh(8)
    set_active_mesh(m8)
    sfx8 = mesh_cache_suffix()
    assert sfx8.startswith("|mesh:") and len(sfx8) == len("|mesh:") + 12
    m4 = make_mesh(4)
    set_active_mesh(m4)
    sfx4 = mesh_cache_suffix()
    assert sfx4.startswith("|mesh:") and sfx4 != sfx8
    assert mesh_key(m4) != mesh_key(m8)
    # back to 8: the suffix is a pure function of the mesh identity
    set_active_mesh(m8)
    assert mesh_cache_suffix() == sfx8
    set_active_mesh(None)
    assert mesh_cache_suffix() == ""


def test_template_key_rekeys_on_mesh_shape_change():
    """The prepared-plan template key folds the mesh identity: same
    plan, same conf -> different key on another mesh (stale
    partitioned entries can never serve it), the same key again when
    the original mesh returns, and a third key with no mesh."""
    from spark_rapids_tpu.serving.plan_cache import template_key

    conf = get_conf()
    session = TpuSession(conf)
    fact, _dim, _sort = _tables(rows=64)
    df = (session.create_dataframe(fact)
          .group_by(col("k")).agg((sum_(col("v")), "sv")))
    set_active_mesh(make_mesh(8))
    k8 = template_key(df._plan, conf)
    set_active_mesh(make_mesh(4))
    k4 = template_key(df._plan, conf)
    assert k8 != k4
    set_active_mesh(make_mesh(8))
    assert template_key(df._plan, conf) == k8
    set_active_mesh(None)
    koff = template_key(df._plan, conf)
    assert koff not in (k8, k4)
    assert template_key(df._plan, conf) == koff


# ------------------------------------------------------------------ #
# SPMD x serving digest identity (the storm-shaped acceptance test)
# ------------------------------------------------------------------ #


def test_spmd_serving_digest_identity_four_sessions():
    """Four concurrent sessions x three templates on the virtual
    8-device mesh: every result (warm and repeat) hashes bit-identical
    to the serial single-device reference, and the measured repeats compile nothing new."""
    from spark_rapids_tpu.execs.jit_cache import cache_stats

    fact, dim, sort_t = _tables(rows=2048)
    digests = _serial_digests(fact, dim, sort_t)
    set_active_mesh(make_mesh(8))
    n_sessions = 4
    errors: list = []
    mismatches: list = []
    lock = threading.Lock()
    warm_done = threading.Barrier(n_sessions + 1)
    go = threading.Event()

    def run(i: int) -> None:
        pqs = {}
        try:
            conf = _mesh_conf(rows=2048)
            set_conf(conf)
            session = TpuSession(conf, tenant=f"t{i % 2}")
            for name, df in _templates(session, fact, dim, sort_t):
                pqs[name] = session.prepare(df)
            for name, pq in pqs.items():
                if _canon_digest(pq.execute()) != digests[name]:
                    with lock:
                        mismatches.append((i, name, "warm"))
        except BaseException as e:  # noqa: BLE001 — reported below
            with lock:
                errors.append((i, repr(e)))
            pqs = {}
        finally:
            warm_done.wait()
        if not pqs:
            return
        go.wait()
        try:
            for name, pq in pqs.items():
                if _canon_digest(pq.execute()) != digests[name]:
                    with lock:
                        mismatches.append((i, name, "repeat"))
        except BaseException as e:  # noqa: BLE001 — reported below
            with lock:
                errors.append((i, repr(e)))

    threads = [threading.Thread(target=run, args=(i,),
                                name=f"mesh-serve-{i}")
               for i in range(n_sessions)]
    for t in threads:
        t.start()
    warm_done.wait()
    jit0 = cache_stats()
    go.set()
    for t in threads:
        t.join()
    jit1 = cache_stats()
    assert not errors, errors
    assert not mismatches, mismatches
    assert jit1["misses"] == jit0["misses"], (jit0, jit1)


# ------------------------------------------------------------------ #
# Cancellation storm on the mesh: unwinds leave no residency
# ------------------------------------------------------------------ #


def test_mesh_cancellation_storm_leaves_no_residency(leak_check):
    """session.cancel() fired mid-flight against executions on the
    mesh: every surviving result stays digest-gated, cancelled
    ones unwind cleanly, and the process residency gauges (permits,
    store bytes, stage threads, scan shares — conftest.leak_check)
    return EXACTLY to baseline."""
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.serving import cancel as C

    C.reset()
    TpuSemaphore.reset()
    fact, dim, sort_t = _tables(rows=2048)
    digests = _serial_digests(fact, dim, sort_t)
    set_active_mesh(make_mesh(8))
    conf = _mesh_conf(rows=2048)
    set_conf(conf)
    session = TpuSession(conf, tenant="storm")
    pqs = {name: session.prepare(df)
           for name, df in _templates(session, fact, dim, sort_t)}
    for name, pq in pqs.items():  # warm: compile the program set
        assert _canon_digest(pq.execute()) == digests[name]
    survived = cancelled = 0
    for round_i in range(4):
        for name, pq in pqs.items():
            canceller = threading.Timer(0.005 * (round_i + 1),
                                        session.cancel)
            canceller.start()
            try:
                r = pq.execute()
                assert _canon_digest(r) == digests[name], name
                survived += 1
            except C.QueryCancelled:
                cancelled += 1
            finally:
                canceller.cancel()
                canceller.join()
    # the storm must have produced BOTH outcomes being meaningful is
    # timing-dependent; what is load-bearing is that every execution
    # either survived digest-gated or unwound cleanly
    assert survived + cancelled == 12
    C.reset()
    TpuSemaphore.reset()
