"""TPC-DS q67's operators through the collective tier, on a virtual
mesh of four CPU devices and at small sizes, each against
`collect(engine="cpu")`: a ROLLUP taken inside the collective
aggregate's map side (and the same rows from a materialised Expand and
from the one-chip rollup path), ranking windows over a collective
window stage, a string-keyed collective join with NULL build keys, and
the whole q67 shape with its executed plan asserted operator by
operator."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.execs.collective  # noqa: F401  (registers confs)
from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.execs.base import _fusion_conf
from spark_rapids_tpu.exprs.base import lit
from spark_rapids_tpu.exprs.window import (
    Window,
    dense_rank,
    rank,
    row_number,
)
from spark_rapids_tpu.session import TpuSession, col, sum_
from tests.differential import assert_tables_equal

N_DEV = 4

ROUND_KEY = "spark.rapids.tpu.shuffle.collective.roundRows"
BATCH_KEY = "spark.rapids.tpu.sql.batchSizeRows"
BROADCAST_KEY = "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes"
TASK_KEY = "spark.rapids.tpu.sql.scan.taskTargetBytes"


@pytest.fixture
def mesh_session():
    s = TpuSession()
    s.enable_collective_shuffle(N_DEV)
    yield s
    s.disable_collective_shuffle()


@pytest.fixture
def conf():
    """The process's conf, with the keys these tests set put back."""
    c = get_conf()
    keys = (ROUND_KEY, BATCH_KEY, BROADCAST_KEY, TASK_KEY,
            _fusion_conf().key)
    old = {k: c.get(k) for k in keys}
    yield c
    for k, v in old.items():
        c.set(k, v)


def _nodes(session) -> list:
    """The last executed plan's operators, root first (the history
    event's snapshots: `desc`, `metrics`, `children`)."""
    held, todo = [], [session.history.events[-1].root]
    while todo:
        node = todo.pop(0)
        held.append(node)
        todo = node.children + todo
    return held


def _descriptions(session) -> list:
    return [node.desc for node in _nodes(session)]


def _executed(session) -> list:
    """The operators' first words."""
    return [desc.split(" ", 1)[0] for desc in _descriptions(session)]


# ------------------------------------------------------------------ #
# ROLLUP under the collective aggregate
# ------------------------------------------------------------------ #

def _sales(n: int, seed: int, null_share: float = 0.1) -> pa.Table:
    """Three grouping keys, one a string, each with NULLs of its own
    beside the ones the rollup writes, and whole-number values so that
    every order of adding gives the same sum."""
    rng = np.random.default_rng(seed)

    def nulled(values, kind):
        hide = rng.random(n) < null_share
        return pa.array([None if h else v
                         for h, v in zip(hide, values.tolist())], kind)

    return pa.table({
        "cat": nulled(np.array(["Books", "Music", "Shoes", "Home & garden"]
                               )[rng.integers(0, 4, n)], pa.string()),
        "year": nulled(rng.integers(1998, 2003, n), pa.int32()),
        "store": nulled(rng.integers(1, 7, n), pa.int64()),
        "qty": pa.array(rng.integers(1, 50, n), pa.int64()),
        "price": pa.array(rng.integers(1, 300, n).astype(np.float64)),
    })


def _rollup_frame(session, table: pa.Table):
    return (session.create_dataframe(table)
            .rollup("cat", "year", "store")
            .agg((sum_(col("qty")), "q"), (sum_(col("price")), "p")))


ROLLUP_DATA = {
    # NULL keys in the data beside the rollup's own NULLs
    "null_keys": dict(n=3000, null_share=0.15, round_rows=None),
    # seven rows: at least one shard of four holds none
    "empty_shard": dict(n=7, null_share=0.3, round_rows=None),
    # batches of 512 rows and a round budget of 512: six rounds
    "rounds": dict(n=3000, null_share=0.05, round_rows=512),
}


@pytest.mark.parametrize("data", sorted(ROLLUP_DATA))
@pytest.mark.parametrize("path", ["rollup", "expand"])
def test_rollup_under_collective_aggregate(mesh_session, conf, path, data):
    """The collective aggregate over a ROLLUP's Expand: taken as the
    levels of one sort (the default) and, with fusion off, from the
    materialised Expand; both give the CPU engine's rows, and the
    one-chip rollup path's."""
    shape = ROLLUP_DATA[data]
    table = _sales(shape["n"], seed=67, null_share=shape["null_share"])
    if shape["round_rows"]:
        conf.set(ROUND_KEY, shape["round_rows"])
        conf.set(BATCH_KEY, shape["round_rows"])
    if path == "expand":
        conf.set(_fusion_conf().key, False)
    frame = _rollup_frame(mesh_session, table)
    got = frame.collect(engine="tpu")
    plan = _descriptions(mesh_session)
    assert any(d.startswith("TpuCollectiveHashAggregateExec")
               for d in plan), plan
    expand = [d for d in plan if d.startswith("TpuExpandExec")]
    assert len(expand) == 1, plan
    assert ("taken as rollup levels" in expand[0]) == (path == "rollup")
    assert_tables_equal(got, frame.collect(engine="cpu"))
    if shape["round_rows"]:
        agg = next(n for n in _nodes(mesh_session)
                   if n.desc.startswith("TpuCollectiveHashAggregateExec"))
        assert agg.metrics["collectiveRounds"] > 1, agg.metrics
    # the one-chip rollup path, no mesh: the same rows
    mesh_session.disable_collective_shuffle()
    conf.set(_fusion_conf().key, True)
    one_chip = _rollup_frame(mesh_session, table)
    assert_tables_equal(got, one_chip.collect(engine="tpu"))
    assert any("taken as rollup levels" in d
               for d in _descriptions(mesh_session))


def test_rollup_counts_its_exchange(mesh_session):
    """The stage's counters: the absorbed Expand ticks the rows it was
    handed (not rows x levels), the aggregate its groups and the bytes
    its all_to_all was sized to carry."""
    table = _sales(2000, seed=5)
    got = _rollup_frame(mesh_session, table).collect(engine="tpu")
    by_name = {n.desc.split(" ", 1)[0]: n for n in _nodes(mesh_session)}
    assert by_name["TpuExpandExec"].metrics["numOutputRows"] == 2000
    agg = by_name["TpuCollectiveHashAggregateExec"].metrics
    assert agg["collectiveRows"] == got.num_rows
    assert agg["collectivePartialRows"] >= got.num_rows
    assert agg["collectiveBytes"] > 0


# ------------------------------------------------------------------ #
# ranking windows over the collective window stage
# ------------------------------------------------------------------ #

def _scores(kind: str, seed: int = 11) -> pa.Table:
    rng = np.random.default_rng(seed)
    if kind == "few_partitions":
        # two partitions and a NULL one over four shards: a shard at
        # least receives nothing; ties in `s` within each
        n = 600
        part = [None if r < 0.2 else ("a" if r < 0.6 else "b")
                for r in rng.random(n)]
    else:
        # one partition holds nine rows in ten
        n = 2000
        part = ["big" if r < 0.9 else f"p{int(r * 1000) % 7}"
                for r in rng.random(n)]
    return pa.table({
        "part": pa.array(part, pa.string()),
        "s": pa.array(rng.integers(0, 40, n).astype(np.float64)),
        "id": pa.array(np.arange(n), pa.int64()),
    })


RANKING = {"rank": rank, "dense_rank": dense_rank,
           "row_number": row_number}


@pytest.mark.parametrize("data", ["few_partitions", "one_large"])
@pytest.mark.parametrize("fn", sorted(RANKING))
def test_ranking_over_collective_window(mesh_session, fn, data):
    table = _scores(data)
    by = Window.partition_by("part")
    # rank and dense_rank over ties; row_number needs a total order
    spec = by.order_by("s", "id", desc=True) if fn == "row_number" \
        else by.order_by("s", desc=True)
    frame = (mesh_session.create_dataframe(table)
             .select(col("part"), col("s"), col("id"),
                     RANKING[fn]().over(spec).alias("r")))
    got = frame.collect(engine="tpu")
    held = _executed(mesh_session)
    assert "TpuCollectiveWindowExec" in held, held
    assert "TpuWindowExec" not in held \
        and "TpuShuffleExchangeExec" not in held, held
    assert_tables_equal(got, frame.collect(engine="cpu"))
    window = next(n for n in _nodes(mesh_session)
                  if n.desc.startswith("TpuCollectiveWindowExec"))
    assert window.metrics["collectiveRows"] == table.num_rows
    assert window.metrics["numOutputRows"] == table.num_rows


def test_window_without_a_mesh_keeps_the_local_exchange(conf, tmp_path):
    """No mesh, several partitions: the window's exchange is the local
    tier's, as before."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.plan.planner import plan_query

    table = _scores("few_partitions")
    paths = []
    for i in range(3):
        paths.append(str(tmp_path / f"s{i}.parquet"))
        pq.write_table(table.slice(i * 200, 200), paths[-1])
    conf.set(TASK_KEY, 1024)  # a scan task a file: three partitions
    session = TpuSession()
    frame = session.read_parquet(*paths).select(
        col("part"), col("s"),
        rank().over(Window.partition_by("part").order_by("s")).alias("r"))
    tree = plan_query(frame._plan, session.conf)[0].tree_string()
    assert "TpuShuffleExchangeExec" in tree \
        and "TpuCollectiveWindowExec" not in tree, tree


def test_concat_of_batches_on_two_devices_names_them():
    """Until a plan can reach it no other way, the concat says what it
    was handed instead of JAX's device mismatch."""
    import jax

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import (
        ColumnarBatch,
        concat_batches,
    )

    schema = T.Schema([T.Field("v", T.LONG)])
    devs = jax.devices()[:2]
    parts = [jax.device_put(ColumnarBatch.from_numpy(
        {"v": np.arange(5, dtype=np.int64)}, schema), d) for d in devs]
    with pytest.raises(ValueError) as raised:
        concat_batches(parts, op="TpuWindowExec")
    said = str(raised.value)
    assert "TpuWindowExec" in said
    assert str(devs[0]) in said and str(devs[1]) in said


# ------------------------------------------------------------------ #
# string-keyed collective join, NULL build keys
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("how", ["inner", "left_outer"])
def test_string_keyed_collective_join_with_null_build_keys(
        mesh_session, conf, how):
    conf.set(BROADCAST_KEY, -1)
    rng = np.random.default_rng(3)
    names = [f"item-{i:03d}-{'x' * (i % 9)}" for i in range(60)]
    left = pa.table({
        "name": pa.array([None if r < 0.1 else names[int(r * 59)]
                          for r in rng.random(800)], pa.string()),
        "lv": pa.array(np.arange(800), pa.int64()),
    })
    right = pa.table({
        # every fifth build key NULL: it matches nothing, a NULL
        # stream key neither
        "name": pa.array([None if i % 5 == 0 else n
                          for i, n in enumerate(names[:45])], pa.string()),
        "rv": pa.array(np.arange(45) * 10, pa.int64()),
    })
    frame = (mesh_session.create_dataframe(left).join(
        mesh_session.create_dataframe(right), how=how,
        left_on=[col("name")], right_on=[col("name")]))
    got = frame.collect(engine="tpu")
    assert "TpuCollectiveHashJoinExec" in _executed(mesh_session)
    assert_tables_equal(got, frame.collect(engine="cpu"))
    join = next(n for n in _nodes(mesh_session)
                if n.desc.startswith("TpuCollectiveHashJoinExec"))
    assert join.metrics["collectiveRows"] == 845
    assert join.metrics["collectiveBytes"] > 0


# ------------------------------------------------------------------ #
# the whole q67 shape
# ------------------------------------------------------------------ #

def _q67_tables(seed: int = 67) -> dict:
    rng = np.random.default_rng(seed)
    n_items, n_stores, n_days, n_sales = 400, 12, 90, 6000
    cats = np.array(["Books", "Home", "Music", "Shoes", "Sports"])
    items = pa.table({
        "i_item_sk": pa.array(np.arange(1, n_items + 1), pa.int64()),
        "i_category": pa.array(
            [None if i % 41 == 0 else cats[i % 5]
             for i in range(n_items)], pa.string()),
        "i_class": pa.array([f"class-{i % 13}" for i in range(n_items)],
                            pa.string()),
        "i_product_name": pa.array(
            [f"product{'n' * (i % 7)}{i}" for i in range(n_items)],
            pa.string()),
        # what makes item too large to broadcast beside the other two
        "i_desc": pa.array(["d" * 100] * n_items, pa.string()),
    })
    stores = pa.table({
        "s_store_sk": pa.array(np.arange(1, n_stores + 1), pa.int64()),
        "s_store_id": pa.array([f"AAAAAAAA{i // 2:08d}"
                                for i in range(n_stores)], pa.string()),
    })
    days = pa.table({
        "d_date_sk": pa.array(np.arange(1000, 1000 + n_days), pa.int64()),
        "d_month_seq": pa.array(1199 + np.arange(n_days) // 30, pa.int32()),
        "d_moy": pa.array(1 + np.arange(n_days) // 30 % 12, pa.int32()),
    })

    def key(low, high):
        hide = rng.random(n_sales) < 0.05
        return pa.array([None if h else int(v) for h, v in
                         zip(hide, rng.integers(low, high, n_sales))],
                        pa.int64())

    sales = pa.table({
        "ss_sold_date_sk": key(1000, 1000 + n_days),
        "ss_item_sk": key(1, n_items + 1),
        "ss_store_sk": key(1, n_stores + 1),
        "ss_quantity": pa.array(rng.integers(1, 100, n_sales), pa.int32()),
        "ss_sales_price": pa.array(
            rng.integers(1, 200, n_sales).astype(np.float64)),
    })
    return {"sales": sales, "items": items, "stores": stores, "days": days}


def test_q67_shape_plan_operator_by_operator(mesh_session, conf):
    """Three joins (two broadcast, item over the threshold), ROLLUP,
    rank within the category, the filter on it, ORDER BY, LIMIT: the
    executed plan holds the two broadcast joins and one each of the
    four collective operators, no local exchange, no Expand that ran,
    and no CPU fallback."""
    from spark_rapids_tpu.execs.retry import retry_stats
    from spark_rapids_tpu.exprs.predicates import Coalesce

    t = _q67_tables()
    conf.set(BROADCAST_KEY, 16 << 10)  # stores, days under; items over
    s = mesh_session
    months = s.create_dataframe(t["days"]).where(
        (col("d_month_seq") >= lit(1200)) & (col("d_month_seq")
                                            <= lit(1201)))
    joined = (s.create_dataframe(t["sales"])
              .join(months, left_on=[col("ss_sold_date_sk")],
                    right_on=[col("d_date_sk")])
              .join(s.create_dataframe(t["stores"]),
                    left_on=[col("ss_store_sk")],
                    right_on=[col("s_store_sk")])
              .join(s.create_dataframe(t["items"]),
                    left_on=[col("ss_item_sk")],
                    right_on=[col("i_item_sk")]))
    keys = ["i_category", "i_class", "i_product_name", "d_moy",
            "s_store_id"]
    summed = joined.rollup(*keys).agg(
        (sum_(Coalesce(col("ss_sales_price") * col("ss_quantity"),
                       lit(0))), "sumsales"))
    columns = [col(k) for k in keys] + [col("sumsales")]
    by_category = Window.partition_by("i_category").order_by(
        "sumsales", desc=True)
    frame = (summed.select(*columns, rank().over(by_category).alias("rk"))
             .where(col("rk") <= lit(20))
             .order_by(*columns, col("rk")).limit(50))
    fallbacks = retry_stats()["cpu_fallbacks"]
    got = frame.collect(engine="tpu")
    assert retry_stats()["cpu_fallbacks"] == fallbacks
    held = _executed(mesh_session)
    # root first: the limit, the distributed sort, the filter with the
    # projection it absorbed, the window, the aggregate over the
    # Expand it took, then the joins
    assert held[:2] == ["TpuCollectLimitExec", "TpuCollectiveSortExec"], held
    below = [op for op in held if op.startswith("TpuCollective")
             or op in ("TpuExpandExec", "TpuBroadcastHashJoinExec")]
    assert below == ["TpuCollectiveSortExec", "TpuCollectiveWindowExec",
                     "TpuCollectiveHashAggregateExec", "TpuExpandExec",
                     "TpuCollectiveHashJoinExec",
                     "TpuBroadcastHashJoinExec",
                     "TpuBroadcastHashJoinExec"], held
    assert "TpuShuffleExchangeExec" not in held \
        and "TpuWindowExec" not in held \
        and "TpuShuffledHashJoinExec" not in held, held
    assert any("taken as rollup levels" in d
               for d in _descriptions(mesh_session))
    explain = mesh_session.history.events[-1].explain
    assert "[degraded to CPU engine" not in explain
    assert_tables_equal(got, frame.collect(engine="cpu"),
                        ignore_order=False)
