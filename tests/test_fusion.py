"""Whole-stage program fusion + buffer donation (docs/fusion.md).

The contract under test:

- FUSED-CHAIN PARITY: scan->filter->project->agg chains compiled into
  single XLA programs answer bit-for-bit like the unfused engine
  (`spark.rapids.tpu.sql.fusion.enabled=false`), across encoded and
  plain batches, ANSI on/off, and null-heavy data;
- DONATION IDENTITY: `fusion.donation.enabled` is a pure HBM
  optimization — digests identical on/off, and the consumed-state
  bookkeeping (EncodedBatch.consumed, SpillableBatch.mark_consumed)
  never lets a donated buffer be re-parked, re-split or re-spilled,
  including under a --chaos-style exec.batch fault inside the
  split-retry ladder;
- JIT-KEY STABILITY: identical collects mint no new programs
  (re-key stability), and the warm pass of the q1-shaped smoke stays
  within the conf dispatch budget with zero jit misses — THE
  warm-dispatch-budget acceptance test
  (tools/bench_smoke.run_fusion_smoke, wired into tier-1 here).
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.eventlog import table_digest
from spark_rapids_tpu.exprs.base import lit
from spark_rapids_tpu.session import TpuSession, col, count_star, sum_

FUSION_KEY = "spark.rapids.tpu.sql.fusion.enabled"
DONATE_KEY = "spark.rapids.tpu.sql.fusion.donation.enabled"


def _quiet_conf(conf, batch_rows=2048):
    """Deterministic dispatch accounting: pipeline + speculation off,
    small batches so streams actually stream."""
    conf.set("spark.rapids.tpu.sql.pipeline.enabled", False)
    conf.set("spark.rapids.tpu.sql.speculation.enabled", False)
    conf.set("spark.rapids.tpu.sql.batchSizeRows", batch_rows)
    conf.set("spark.rapids.tpu.sql.shuffle.partitions", 1)


def _write_lineitem(d, n=8192, null_heavy=False):
    rng = np.random.default_rng(0xF0510)
    ship = rng.integers(8766, 10957, n).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.int64)
    key = rng.integers(0, 6, n).astype(np.int64)
    cols = {
        "l_shipdate": ship,
        "l_key": key,
        "l_quantity": qty,
        "l_price": rng.integers(900, 105000, n).astype(np.int64),
    }
    t = pa.table(cols)
    if null_heavy:
        mask = rng.random(n) < 0.6
        arrs = dict(cols)
        arrs["l_quantity"] = pa.array(
            [None if m else int(v) for m, v in zip(mask, qty)],
            type=pa.int64())
        t = pa.table(arrs)
    p = os.path.join(d, "li.parquet")
    pq.write_table(t, p, row_group_size=max(n // 4, 1))
    return p


def _q(session, path):
    """scan -> filter -> project -> agg: the whole-stage chain."""
    return (session.read_parquet(path)
            .where(col("l_shipdate") <= lit(10471))
            .select(col("l_key"),
                    (col("l_quantity") * lit(2)).alias("q2"),
                    col("l_price"))
            .group_by(col("l_key"))
            .agg((sum_(col("q2")), "sq"),
                 (sum_(col("l_price")), "sp"),
                 (count_star(), "n"))
            .order_by(col("l_key")))


def _collect_digest(path, **conf_over):
    conf = get_conf()
    _quiet_conf(conf)
    for k, v in conf_over.items():
        conf.set(k, v)
    return table_digest(_q(TpuSession(), path).collect(engine="tpu"))


# ------------------------------------------------------------------ #
# fused-chain parity across shapes
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("null_heavy", [False, True],
                         ids=["dense", "null-heavy"])
def test_fusion_on_off_digest_identity(tmp_path, null_heavy):
    """Encoded scan batches through the fused decode+filter+project+
    update program answer exactly like the unfused per-exec engine."""
    p = _write_lineitem(str(tmp_path), null_heavy=null_heavy)
    on = _collect_digest(p, **{FUSION_KEY: True})
    off = _collect_digest(p, **{FUSION_KEY: False})
    assert on == off


def test_fusion_parity_plain_batches():
    """In-memory (non-parquet) sources feed PLAIN device batches into
    the same chain — parity must hold without the wire decode."""
    conf = get_conf()
    _quiet_conf(conf, batch_rows=512)
    rng = np.random.default_rng(7)
    n = 2048
    t = pa.table({
        "k": rng.integers(0, 5, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64),
    })

    def run():
        s = TpuSession()
        return table_digest(
            (s.create_dataframe(t)
             .where(col("v") > lit(10))
             .select(col("k"), (col("v") + lit(1)).alias("v1"))
             .group_by(col("k")).agg((sum_(col("v1")), "sv"),
                                     (count_star(), "n"))
             .order_by(col("k"))).collect(engine="tpu"))

    conf.set(FUSION_KEY, True)
    on = run()
    conf.set(FUSION_KEY, False)
    assert on == run()


def test_fusion_parity_ansi(tmp_path):
    """ANSI mode blocks the agg absorption (error polling needs its
    own driver) but the standalone chains still fuse — results and
    ANSI error behavior must match the unfused engine."""
    p = _write_lineitem(str(tmp_path))
    ansi = "spark.rapids.tpu.sql.ansi.enabled"
    on = _collect_digest(p, **{FUSION_KEY: True, ansi: True})
    off = _collect_digest(p, **{FUSION_KEY: False, ansi: True})
    assert on == off


def test_donation_digest_identity(tmp_path):
    """Donation is a pure HBM optimization: digests identical with
    fusion.donation.enabled on and off."""
    p = _write_lineitem(str(tmp_path))
    base = _collect_digest(p, **{FUSION_KEY: True, DONATE_KEY: False})
    donated = _collect_digest(p, **{FUSION_KEY: True,
                                    DONATE_KEY: True})
    assert base == donated


# ------------------------------------------------------------------ #
# consumed-state bookkeeping
# ------------------------------------------------------------------ #


def test_encoded_batch_consumed_state():
    """A consumed wire batch refuses decode_now/bisection and the
    memoized output resumes re-runs (run_consuming)."""
    from spark_rapids_tpu.columnar.transfer import (
        ConsumedBatchError,
        EncodedBatch,
        encode_batch,
        run_consuming,
    )
    from spark_rapids_tpu.execs.retry import _batch_rows, is_retryable

    t = pa.table({"a": np.arange(64, dtype=np.int64)})
    from spark_rapids_tpu import types as T

    schema = T.Schema([T.Field("a", T.LONG, True)])
    eb = encode_batch(list(t.columns), schema, 64)
    assert isinstance(eb, EncodedBatch) and not eb.consumed

    calls = []

    def fake_program(b):
        calls.append(b)
        return "OUT"

    assert run_consuming(fake_program, eb) == "OUT"
    assert eb.consumed
    # re-run RESUMES from the memoized output, no re-execution
    assert run_consuming(fake_program, eb) == "OUT"
    assert len(calls) == 1
    # the ladder refuses to size/split a consumed batch...
    assert _batch_rows(eb) is None
    # ...and an eager decode fails fast, non-retryably
    with pytest.raises(ConsumedBatchError) as ei:
        eb.decode_now()
    assert not is_retryable(ei.value)


def test_run_consuming_program_death_is_fatal():
    """A donated program dying mid-execution leaves the input gone and
    nothing memoized: the re-run must fail fast (non-retryable), not
    burn the spill/split ladder on freed HBM."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.transfer import (
        ConsumedBatchError,
        encode_batch,
        run_consuming,
    )
    from spark_rapids_tpu.execs.retry import is_retryable

    t = pa.table({"a": np.arange(16, dtype=np.int64)})
    schema = T.Schema([T.Field("a", T.LONG, True)])
    eb = encode_batch(list(t.columns), schema, 16)

    def dying(b):
        raise RuntimeError("RESOURCE_EXHAUSTED: boom")

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        run_consuming(dying, eb)
    assert eb.consumed and eb.donated_out is None
    with pytest.raises(ConsumedBatchError) as ei:
        run_consuming(dying, eb)
    assert not is_retryable(ei.value)


def test_cached_jit_donate_spec_validation():
    """Malformed donate= specs fail loud AT the chokepoint — in
    particular donate=True (bool IS int in Python) must not silently
    normalize to argnum 1 and donate the wrong buffer."""
    from spark_rapids_tpu.execs.jit_cache import _validate_donate

    assert _validate_donate((0,)) == (0,)
    assert _validate_donate(0) == (0,)
    assert _validate_donate(()) == ()
    for bad in (True, (True,), (-1,), (0, 0), ("0",)):
        with pytest.raises(TypeError):
            _validate_donate(bad)


def test_spillable_batch_mark_consumed():
    """mark_consumed un-registers WITHOUT deleting: the store can
    never spill (use-after-free) a donated buffer, rollback sweeps
    (unpin/close) become no-ops, and get() fails fast."""
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.columnar.transfer import ConsumedBatchError
    from spark_rapids_tpu.memory.store import BufferStore

    store = BufferStore(device_budget=1 << 30, host_budget=1 << 30)
    data = jnp.arange(8, dtype=jnp.int64)
    valid = jnp.ones(8, jnp.bool_)
    b = ColumnarBatch([Column(data, valid, T.LONG)], 8,
                      T.Schema([T.Field("a", T.LONG, False)]))
    h = store.register(b)
    used = store.device_used
    assert used > 0
    h.mark_consumed()
    assert h.consumed
    assert store.device_used == 0  # un-registered, accounting settled
    assert store.spill_all_unpinned() == 0  # nothing left to spill
    h.unpin()  # rollback-sweep no-ops
    h.close()
    h.mark_consumed()  # idempotent
    with pytest.raises(ConsumedBatchError):
        h.get()
    # every handle surface fails TYPED on a consumed buffer (a raw
    # KeyError would dodge the retry classifier's fail-fast contract)
    with pytest.raises(ConsumedBatchError):
        h.get_host()
    with pytest.raises(ConsumedBatchError):
        _ = h.tier
    with pytest.raises(ConsumedBatchError):
        _ = h.nbytes
    # the donated arrays themselves are untouched (XLA owns them now;
    # on CPU donation is a no-op so they are simply still alive)
    assert int(data.sum()) == 28
    store.close()


def test_spilled_donated_memo_repair_and_fail_fast():
    """A donated update output registered UNPINNED with the spill
    store may be spilled by pressure — the spill deletes the device
    arrays the memoized `donated_out` references (the store restores
    into a NEW batch object, never the memo).  Two contracts: (1) a
    rollback about to drop the registration repairs the memo through
    the handle, so the re-run's resume hands downstream a LIVE batch
    with the same data; (2) a dead memo with no surviving copy fails
    fast with ConsumedBatchError, not an opaque deleted-array crash
    deep in the next merge."""
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.columnar.transfer import (
        ConsumedBatchError,
        EncodedBatch,
        memo_is_dead,
        repair_donated_memo,
        run_consuming,
    )
    from spark_rapids_tpu.execs.retry import is_retryable
    from spark_rapids_tpu.memory.store import BufferStore

    schema = T.Schema([T.Field("a", T.LONG, False)])

    def _part():
        return ColumnarBatch(
            [Column(jnp.arange(8, dtype=jnp.int64),
                    jnp.ones(8, jnp.bool_), T.LONG)], 8, schema)

    store = BufferStore(device_budget=1 << 30, host_budget=1 << 30)
    eb = EncodedBatch([], ("p",), schema, num_rows=8)
    out = _part()
    assert run_consuming(lambda _: out, eb) is out
    h = store.register(out)  # retire's unpinned registration
    assert not memo_is_dead(eb.donated_out)
    assert repair_donated_memo(eb, h) is False  # live memo: no-op
    assert store.spill_all_unpinned() == 1  # pressure strikes
    assert memo_is_dead(eb.donated_out)
    # (1) rollback repair: restore through the handle, re-memoize
    assert repair_donated_memo(eb, h) is True
    assert not memo_is_dead(eb.donated_out)
    resumed = run_consuming(lambda _: None, eb)
    assert resumed is eb.donated_out
    assert [int(x) for x in
            np.asarray(resumed.columns[0].data)] == list(range(8))
    # the rollback sweep then drops the registration: the restored
    # arrays survive (store.remove never deletes device buffers)
    h.close()
    assert not memo_is_dead(eb.donated_out)
    # (2) dead memo, host copy already dropped: fail fast, typed,
    # non-retryable — never hand freed buffers downstream
    eb2 = EncodedBatch([], ("p",), schema, num_rows=8)
    out2 = _part()
    run_consuming(lambda _: out2, eb2)
    h2 = store.register(out2)
    assert store.spill_all_unpinned() == 1
    h2.close()
    with pytest.raises(ConsumedBatchError, match="spilled") as ei:
        run_consuming(lambda _: None, eb2)
    assert not is_retryable(ei.value)
    store.close()


# ------------------------------------------------------------------ #
# split-retry under chaos with donation on
# ------------------------------------------------------------------ #


def test_chaos_exec_batch_with_donation(tmp_path):
    """THE donated-ladder acceptance: an exec.batch fault firing
    inside the fused+donated scan->agg unit must recover to a
    bit-identical answer without ever touching a consumed buffer —
    pre-consumption faults bisect the intact batch, post-consumption
    re-runs resume from the memoized update output."""
    from spark_rapids_tpu.robustness import faults

    p = _write_lineitem(str(tmp_path))
    clean = _collect_digest(p, **{FUSION_KEY: True, DONATE_KEY: True})
    conf = get_conf()
    conf.set(DONATE_KEY, True)
    try:
        # nth=1,times=2: the FIRST ladder unit eats two faults — the
        # first re-run happens with the dispatch-side update already
        # consumed (memoized-resume path), the second drives the
        # bisection decision against a consumed batch (must skip the
        # split, not decode freed buffers)
        faults.install("exec.batch:nth=1,times=2", forced=True)
        chaotic = table_digest(
            _q(TpuSession(), p).collect(engine="tpu"))
    finally:
        faults.disarm()
    assert chaotic == clean

    # and a fault schedule that also bisects an INTACT batch
    # (initial_error path: dispatch-time failure before consumption)
    try:
        faults.install("exec.batch:nth=2,times=2", forced=True)
        chaotic2 = table_digest(
            _q(TpuSession(), p).collect(engine="tpu"))
    finally:
        faults.disarm()
    assert chaotic2 == clean


# ------------------------------------------------------------------ #
# jit-key bucketing stability
# ------------------------------------------------------------------ #


def test_rekey_stability_identical_collects(tmp_path):
    """Two identical collects mint ZERO new compiled programs: the
    program census (per-tag distinct-program counts) is unchanged and
    the second collect has no jit-cache misses — per-batch offsets,
    live counts and dictionary cardinalities must ride as runtime
    args / bucketed aux, never in the keys."""
    from spark_rapids_tpu.execs import jit_cache

    p = _write_lineitem(str(tmp_path))
    conf = get_conf()
    _quiet_conf(conf)
    conf.set(FUSION_KEY, True)
    session = TpuSession()
    _q(session, p).collect(engine="tpu")
    census0 = jit_cache.program_census()
    j0 = jit_cache.cache_stats()
    r = _q(session, p).collect(engine="tpu")
    j1 = jit_cache.cache_stats()
    census1 = jit_cache.program_census()
    assert j1["misses"] - j0["misses"] == 0, (
        f"identical collect re-compiled: census {census0} -> "
        f"{census1}")
    assert census1 == census0
    assert r.num_rows > 0


def test_capacity_buckets_share_programs(tmp_path):
    """Different row counts in the same capacity bucket share one
    compiled program; a different bucket compiles, a repeat of the
    first bucket hits (capacity bucketing = the jax shape key)."""
    from spark_rapids_tpu.execs import jit_cache

    conf = get_conf()
    _quiet_conf(conf, batch_rows=1 << 20)
    conf.set(FUSION_KEY, True)
    rng = np.random.default_rng(3)

    def run(n):
        t = pa.table({
            "k": rng.integers(0, 4, n).astype(np.int64),
            "v": rng.integers(0, 9, n).astype(np.int64),
        })
        s = TpuSession()
        return (s.create_dataframe(t)
                .where(col("v") > lit(2))
                .group_by(col("k")).agg((sum_(col("v")), "sv"))
                .order_by(col("k"))).collect(engine="tpu")

    run(1000)  # capacity bucket 1024
    j0 = jit_cache.cache_stats()
    run(900)  # same bucket: different live count, same programs
    j1 = jit_cache.cache_stats()
    assert j1["misses"] == j0["misses"], \
        "same capacity bucket re-compiled"


# ------------------------------------------------------------------ #
# the fusion smoke: dispatch budget + savings, wired into tier-1
# ------------------------------------------------------------------ #


def test_fusion_smoke():
    """THE warm-dispatch-budget acceptance test: the q1-shaped smoke's
    warm pass has 0 jit misses, strictly fewer ledger dispatches than
    the unfused baseline, digest equality across fusion/donation
    on/off, and a warm dispatch count within the conf budget."""
    from spark_rapids_tpu.tools.bench_smoke import run_fusion_smoke

    out = run_fusion_smoke()
    assert out["fusion_warm_jit_misses"] == 0
    assert out["fusion_warm_dispatches"] \
        < out["fusion_unfused_dispatches"]
    assert out["fusion_chains"] >= 1
    assert out["fusion_saved_dispatches"] >= 1
    # the budget gate ran inside the smoke; re-assert the headroom
    # here so budget regressions name this test
    from spark_rapids_tpu.execs.base import warm_dispatch_budget

    assert out["fusion_warm_dispatches"] <= warm_dispatch_budget()


def test_warm_dispatch_budget_gate_trips():
    """The budget gate has teeth: an absurdly tight budget makes the
    smoke fail with the budget assertion (not some other error)."""
    conf = get_conf()
    conf.set("spark.rapids.tpu.sql.fusion.warmDispatchBudget", 1)
    from spark_rapids_tpu.tools.bench_smoke import run_fusion_smoke

    with pytest.raises(AssertionError, match="warmDispatchBudget"):
        run_fusion_smoke()


def test_warm_budget_zero_disables_gate():
    """warmDispatchBudget=0 is the documented escape hatch ('0
    disables the gate'): the smoke's warm pass pays at least one
    launch, which a literal budget of 0 would refuse, and the smoke
    passes."""
    conf = get_conf()
    conf.set("spark.rapids.tpu.sql.fusion.warmDispatchBudget", 0)
    from spark_rapids_tpu.tools.bench_smoke import run_fusion_smoke

    out = run_fusion_smoke()
    assert out["fusion_warm_dispatches"] >= 1


# ------------------------------------------------------------------ #
# explain() integration
# ------------------------------------------------------------------ #


def test_explain_fusion_section(tmp_path):
    """explain() gains a "Fusion:" section naming the fused chains;
    with fusion disabled it says so instead."""
    p = _write_lineitem(str(tmp_path))
    conf = get_conf()
    _quiet_conf(conf)
    conf.set(FUSION_KEY, True)
    s = TpuSession()
    text = _q(s, p).explain()
    assert "Fusion:" in text
    assert "one program" in text
    conf.set(FUSION_KEY, False)
    text_off = _q(TpuSession(), p).explain()
    assert "Fusion:" in text_off and "disabled" in text_off


def test_explain_fusion_donation_annotated(tmp_path):
    p = _write_lineitem(str(tmp_path))
    conf = get_conf()
    _quiet_conf(conf)
    conf.set(FUSION_KEY, True)
    conf.set(DONATE_KEY, True)
    text = _q(TpuSession(), p).explain()
    assert "inputs donated" in text
