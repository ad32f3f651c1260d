"""TPC-DS q97 as the benchmark's cell `tpcds-sf10-channels.q97` runs it:
`benchmarks/queries/q97.py:build` through `TpuSession` against the same
file's plain reference, and what the query leans on that no other cell
does: a FULL OUTER join on a composite key with NULLs on both sides,
whose build side several stream batches probe; a DISTINCT whose
partials do not collapse when merged; a full outer join that stays off
the broadcast path whatever its sides' sizes."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import _tpcds, catalog_sales, date_dim, store_sales
from benchmarks.harness import check, spec
from benchmarks.queries import q97
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.execs.basic import TpuBatchSourceExec
from spark_rapids_tpu.execs.join import (
    TpuBroadcastHashJoinExec,
    TpuShuffledHashJoinExec,
)
from spark_rapids_tpu.exprs.base import ColumnReference as C

SEEDS = [97, 3400000097, 5]
# the rehearsal's cut: each table's first file at a sixteenth of its rows
ROWS = {"store_sales": 960_000 // spec.REHEARSAL_CUT,
        "catalog_sales": 480_000 // spec.REHEARSAL_CUT,
        "date_dim": _tpcds.DAYS // spec.REHEARSAL_CUT}
GENERATORS = {"store_sales": store_sales, "catalog_sales": catalog_sales,
              "date_dim": date_dim}


def _tables(seed: int, work) -> tuple:
    """The three tables' files and the reference's answer."""
    cols = {name: gen.generate(seed, 0, ROWS[name])
            for name, gen in GENERATORS.items() if name != "store_sales"}
    # `store_sales.generate` as it is, but for the item domain, which
    # `sales_draws` bound when it was defined (the `crowded` fixture)
    cols["store_sales"] = _tpcds.sales_draws(
        seed, 0, ROWS["store_sales"], _tpcds.ITEMS)
    paths = {}
    for name, gen in GENERATORS.items():
        paths[name] = str(work / f"{name}.parquet")
        pq.write_table(gen.to_arrow(cols[name], seed, 0), paths[name])
    side = {role: cols[role] for role in q97.COLUMNS if role != q97.DRIVER}
    return paths, q97.combine([q97.partial(cols[q97.DRIVER], side)])


def _collect(paths: dict, engine=None) -> pa.Table:
    from spark_rapids_tpu.session import TpuSession

    session = TpuSession()
    frames = {role: session.read_parquet(paths[role], columns=columns)
              for role, columns in q97.COLUMNS.items()}
    return q97.build(session, frames).collect(engine=engine)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_engine_answers_as_the_reference(tmp_path, seed):
    """At the rehearsal's cut and the specification's domains: 5.1e10
    possible pairs, so nearly every sale is a pair of its own and the
    channels share none or nearly none."""
    paths, want = _tables(seed, tmp_path)
    assert want.schema.names == q97.ANSWER and want.num_rows == 1
    store, catalog, both = (want[name][0].as_py() for name in q97.ANSWER)
    assert store > 9_000 and catalog > 4_500 and both < 10
    why, _ = check.compare(_collect(paths), want, q97.ORDERED)
    assert why is None, why


@pytest.fixture
def crowded(monkeypatch):
    """Key domains small enough that the channels share pairs, a pair
    repeats within a channel, and (NULL, item) stands on both sides."""
    monkeypatch.setattr(_tpcds, "CUSTOMERS", 400)
    monkeypatch.setattr(_tpcds, "ITEMS", 100)
    monkeypatch.setattr(catalog_sales, "NULL_PCT", 900)


@pytest.mark.parametrize("engine", [None, "cpu"])
def test_crowded_channels_count_as_the_reference(tmp_path, crowded, engine):
    """Both engines, where every count is large.  A pair without a
    customer is a group and a joined row and counts in none of the
    three: matching NULL keys would move `store_and_catalog`."""
    paths, want = _tables(SEEDS[0], tmp_path)
    store, catalog, both = (want[name][0].as_py() for name in q97.ANSWER)
    assert min(store, catalog, both) > 100
    sales = pq.read_table(paths["store_sales"])
    billed = pq.read_table(paths["catalog_sales"])
    assert sales["ss_customer_sk"].null_count > 100
    assert billed["cs_bill_customer_sk"].null_count > 100
    why, _ = check.compare(_collect(paths, engine), want, q97.ORDERED)
    assert why is None, why


def test_the_reference_counts_pairs_not_sales():
    """The plain reference against a loop over Python sets, file by
    file as `datagen` hands it the driver table."""
    dates = {"d_date_sk": np.arange(10, 20),
             "d_month_seq": np.r_[[1199] * 2, [1200] * 6, [1212] * 2]}
    # (date, customer, item); -1 is NULL; dates 12..17 are in the year
    sold = [[(12, 1, 1), (13, 1, 1), (14, 2, 1), (11, 3, 3), (15, -1, 7),
             (-1, 4, 4)],
            [(16, 1, 1), (17, 5, 5), (12, -1, 7), (12, 6, 6)]]
    billed = [(12, 1, 1), (12, 5, 9), (17, -1, 7), (13, 6, 6), (19, 2, 1),
              (14, 6, 6)]
    side = {"date_dim": dates, "catalog_sales": {
        name: np.array([row[at] for row in billed])
        for at, name in enumerate(q97.COLUMNS["catalog_sales"])}}
    partials = [q97.partial({
        name: np.array([row[at] for row in rows])
        for at, name in enumerate(q97.COLUMNS["store_sales"])}, side)
        for rows in sold]
    got = q97.combine(partials).to_pylist()[0]
    # store pairs with a customer: (1,1), (2,1), (5,5), (6,6); catalog's:
    # (1,1), (5,9), (6,6); (NULL, 7) on both sides matches nothing
    assert got == {"store_only": 2, "catalog_only": 1,
                   "store_and_catalog": 2}


# -- the full outer join's rows ------------------------------------------ #

L_SCHEMA = T.Schema([T.Field("lc", T.LONG), T.Field("li", T.LONG)])
R_SCHEMA = T.Schema([T.Field("rc", T.LONG), T.Field("ri", T.LONG)])


def _source(schema, keys: np.ndarray, n_batches: int):
    """`keys`: (n, 2), -1 for NULL."""
    batches = []
    for chunk in np.array_split(keys, n_batches):
        names = [f.name for f in schema.fields]
        batches.append(ColumnarBatch.from_numpy(
            {n: np.maximum(chunk[:, at], 0) for at, n in enumerate(names)},
            schema, {n: chunk[:, at] >= 0 for at, n in enumerate(names)}))
    return TpuBatchSourceExec(batches, schema)


def _rows(exec_) -> list:
    out = []
    for b in exec_.execute():
        d = b.to_pydict()
        out += list(zip(*(d[n] for n in d)))
    return sorted(out, key=lambda t: tuple((x is None, x) for x in t))


def _numpy_full_outer(left: np.ndarray, right: np.ndarray) -> tuple:
    """Full outer join of two (n, 2) key arrays on both columns, -1
    NULL: a row with a NULL in either key column matches nothing.
    Returns the rows, sorted, and how many right rows found no match."""
    def null(a):
        return (a < 0).any(axis=1)

    def packed(a):
        return a[:, 0] * 1_000_003 + a[:, 1]

    def spelt(a):
        return [tuple(None if v < 0 else int(v) for v in row) for row in a]

    lp, rp = packed(left), packed(right)
    out = []
    for at in np.flatnonzero(~null(left)):
        hits = np.flatnonzero((rp == lp[at]) & ~null(right))
        out += [spelt(left[at:at + 1])[0] + spelt(right[h:h + 1])[0]
                for h in hits]
    lone_l = null(left) | ~np.isin(lp, rp[~null(right)])
    lone_r = null(right) | ~np.isin(rp, lp[~null(left)])
    out += [row + (None, None) for row in spelt(left[lone_l])]
    out += [(None, None) + row for row in spelt(right[lone_r])]
    return (sorted(out, key=lambda t: tuple((x is None, x) for x in t)),
            int(lone_r.sum()))


@pytest.mark.parametrize("stream_batches", [1, 5])
def test_the_full_outer_joins_rows_are_numpys(stream_batches):
    """The OUTPUT ROWS as a multiset: NULL keys on both sides (either
    column), keys on one side only, keys on both, repeated keys, and a
    build side that five stream batches probe, each matching another
    part of it, so that `matched_b` has to be OR-ed over them."""
    rng = np.random.default_rng(97)

    def side(n):
        keys = np.stack([rng.integers(1, 30, n), rng.integers(1, 12, n)], 1)
        keys[rng.random(n) < 0.08, 0] = -1
        keys[rng.random(n) < 0.04, 1] = -1
        return keys

    left, right = side(700), side(400)
    right[:40, 0] += 100  # build rows that no stream row matches
    join = TpuShuffledHashJoinExec(
        [C("lc"), C("li")], [C("rc"), C("ri")], "full_outer",
        _source(L_SCHEMA, left, stream_batches), _source(R_SCHEMA, right, 2))
    got = _rows(join)
    want, lone_right = _numpy_full_outer(left, right)
    assert got == want and lone_right >= 40
    assert any(r[0] is None and r[1] is not None and r[2] is None
               for r in got)  # a (NULL, item) stream row, unmatched
    assert any(r[:2] == (None, None) and r[2] is None for r in got)
    assert join.metrics["probeBatches"].value == stream_batches
    assert join.metrics["streamRows"].value == len(left)
    assert join.metrics["unmatchedBuildRows"].value == lone_right


def test_a_small_full_outer_join_stays_off_the_broadcast_path():
    """Both sides far under the 10 MiB threshold, as the catalog side
    of the smallest cut is."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.plan.planner import plan_query
    from spark_rapids_tpu.session import TpuSession, col

    session = TpuSession()
    a = session.create_dataframe(pa.table(
        {"c": pa.array([1, 2, None, 4], pa.int64()),
         "i": pa.array([1, 1, 1, 2], pa.int64())}))
    b = session.create_dataframe(pa.table(
        {"bc": pa.array([1, None, 9], pa.int64()),
         "bi": pa.array([1, 1, 9], pa.int64())}))
    df = a.join(b, how="full_outer", left_on=[col("c"), col("i")],
                right_on=[col("bc"), col("bi")])
    exec_, _ = plan_query(df._plan, get_conf())
    kinds = {type(e) for e in exec_._walk()}
    assert TpuShuffledHashJoinExec in kinds
    assert TpuBroadcastHashJoinExec not in kinds
    got = sorted(df.collect().to_pylist(), key=str)
    assert got == sorted(df.collect(engine="cpu").to_pylist(), key=str)
    assert len(got) == 6  # one match; 3 left alone, 2 right alone


# -- a DISTINCT whose partials do not collapse ---------------------------- #

def _merge_spans(run) -> tuple:
    """What `run()` returns, and the attributes of the `agg.merge`
    spans it recorded."""
    from spark_rapids_tpu import trace

    trace.enable()
    trace.clear()
    try:
        out = run()
        return out, [s.attrs for s in trace.snapshot()
                     if s.name == "agg.merge"]
    finally:
        trace.disable()
        trace.clear()


@pytest.mark.parametrize("n_batches", [4, 12])
def test_a_distinct_of_near_unique_pairs_is_np_unique(n_batches):
    """`goal_rows` well under a batch, so the re-merge of what is
    pending fires at every batch after the first; the keys are
    near-unique and hold (NULL, item), so no merge collapses anything.
    The groups equal `np.unique`'s, and every `agg.merge` span says how
    many partials it took."""
    rng = np.random.default_rng(n_batches)
    per = 300
    keys = np.stack([rng.integers(1, 50_000, per * n_batches),
                     rng.integers(1, 1_000, per * n_batches)], 1)
    keys[rng.random(len(keys)) < 0.05, 0] = -1
    keys[:per // 2] = keys[per:per + per // 2]  # some pairs do repeat
    agg = TpuHashAggregateExec([C("lc"), C("li")], [],
                               _source(L_SCHEMA, keys, n_batches),
                               goal_rows=per // 2)
    got, merges = _merge_spans(lambda: _rows(agg))
    want = sorted({tuple(None if v < 0 else int(v) for v in row)
                   for row in keys},
                  key=lambda t: tuple((x is None, x) for x in t))
    assert got == want and len(want) > 0.8 * len(keys)
    assert agg.metrics["numMerges"].value > 1
    assert len(merges) > 1
    assert all(m["pending"] >= 2 for m in merges[:-1])


# -- tasks of unlike sizes under one join --------------------------------- #

def test_a_joins_unlike_batches_in_another_order_compile_nothing():
    """A scan split into tasks of 10 files and 5 hands one join stream
    batches of two capacities.  Each batch's expansion is sized by its
    own counted pairs, so no bucket moves with the order the tasks ran
    in: a second pass over the same batches in another order compiles
    nothing new."""
    from spark_rapids_tpu.execs.jit_cache import cache_stats

    rng = np.random.default_rng(5)
    big = np.stack([rng.integers(1, 40, 900), rng.integers(1, 5, 900)], 1)
    small = np.stack([rng.integers(1, 40, 100), rng.integers(1, 5, 100)], 1)
    right = np.stack([np.repeat(np.arange(1, 40), 4),
                      np.tile(np.arange(1, 5), 39)], 1)

    def join(order):
        """The stream side: the tasks' batches in the order given."""
        stream = [b for keys in order
                  for b in _source(L_SCHEMA, keys, 1).execute()]
        return TpuShuffledHashJoinExec(
            [C("lc"), C("li")], [C("rc"), C("ri")], "inner",
            TpuBatchSourceExec(stream, L_SCHEMA),
            _source(R_SCHEMA, right, 1))

    ex = join([big, small, big, small])
    first = _rows(ex)
    assert len(first) == 2 * (len(big) + len(small))
    assert ex.metrics["expandRows"].value == len(first)
    assert ex.metrics["expandCapacityRows"].value == 2 * (1024 + 128)
    before = cache_stats()["misses"]
    assert _rows(join([small, small, big, big])) == first
    assert cache_stats()["misses"] == before


def test_a_concat_of_other_counts_is_the_same_programs():
    """The pieces an exchange hands its reduce side have another row
    count in every partition, round and seed: `concat_batches` takes
    no shape from a count, so pieces of the same capacities and other
    counts compile nothing again, and the rows past the last are zero
    and not valid."""
    import jax.monitoring

    from spark_rapids_tpu.columnar.batch import concat_batches

    def piece(n: int) -> ColumnarBatch:
        keys = np.stack([np.arange(1, n + 1), np.full(n, -1)], 1)
        return next(iter(_source(L_SCHEMA, keys, 1).execute()))

    def packed(counts) -> tuple:
        out = concat_batches([piece(n) for n in counts])
        assert out.num_rows == sum(counts)
        return (np.asarray(out.columns[0].data),
                np.asarray(out.columns[0].validity),
                np.asarray(out.columns[1].validity))

    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiled.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    packed((100, 70, 90))  # capacities 128, 128, 128 into 512
    before = len(compiled)
    data, valid, none_valid = packed((65, 128, 110))
    assert len(compiled) == before
    want = np.concatenate([np.arange(1, 66), np.arange(1, 129),
                           np.arange(1, 111), np.zeros(512 - 303, int)])
    assert data.tolist() == want.tolist()
    assert valid.tolist() == (want > 0).tolist()
    assert not none_valid.any()


# -- spans and counters --------------------------------------------------- #

def _outer_join(stream_batches: int = 2):
    rng = np.random.default_rng(3)
    left = np.stack([rng.integers(1, 30, 200), rng.integers(1, 9, 200)], 1)
    right = np.stack([rng.integers(20, 60, 120), rng.integers(1, 9, 120)], 1)
    return TpuShuffledHashJoinExec(
        [C("lc"), C("li")], [C("rc"), C("ri")], "full_outer",
        _source(L_SCHEMA, left, stream_batches),
        _source(R_SCHEMA, right, 1))


def test_the_outer_joins_spans_say_what_the_readers_need():
    """Traced: `join.unmatched` encloses the program that emits the
    unmatched build rows and its one readback (so the chip's wait for
    it has a name and `host_syncs` counts it); `join.build` and the
    probe's `exec.<op>` span say the join's type."""
    from spark_rapids_tpu import trace
    from spark_rapids_tpu.parallel.pipeline import stage_snapshot

    def readbacks():
        return stage_snapshot().get("join.unmatched", {}).get("readbacks", 0)

    before = readbacks()
    trace.enable()
    trace.clear()
    try:
        join = _outer_join()
        rows = _rows(join)
        spans = trace.snapshot()
    finally:
        trace.disable()
        trace.clear()
    assert readbacks() == before + 1
    unmatched = [s for s in spans if s.name == "join.unmatched"]
    assert len(unmatched) == 1
    attrs = unmatched[0].attrs
    assert attrs["join_type"] == "full_outer"
    assert attrs["build_capacity"] >= 120
    lone = sum(1 for r in rows if r[0] is None and r[1] is None)
    assert attrs["rows"] == lone == join.metrics["unmatchedBuildRows"].value
    assert lone > 0
    inside = [s for s in spans if s.name == "pipe.readback"
              and s.attrs.get("tag") == "join.unmatched"]
    assert len(inside) == 1
    assert unmatched[0].ts_ns <= inside[0].ts_ns \
        and inside[0].end_ns <= unmatched[0].end_ns
    probes = [s for s in spans if s.name == f"exec.{join.name}"
              and "capacity" in s.attrs]
    assert len(probes) == 2
    assert {s.attrs["join_type"] for s in probes} == {"full_outer"}
    built = [s for s in spans if s.name == "join.build"]
    assert [s.attrs["join_type"] for s in built] == ["full_outer"]


def test_an_untraced_outer_join_records_nothing():
    from spark_rapids_tpu import trace

    assert not trace.is_enabled()
    trace.clear()
    join = _outer_join(1)
    assert len(_rows(join)) > 0
    assert trace.snapshot() == []
    assert join.metrics["streamRows"].value == 200
