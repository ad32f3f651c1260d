"""TPC-DS q16 as the benchmark's cell `tpcds-sf10-returns.q16` runs it:
`benchmarks/queries/q16.py:build` through `TpuSession` against the same
files' plain reference, and what the query leans on that no other cell
does: an EXISTS whose correlation has a `<>` beside the equality (a
conditional `left_semi` join), a NOT EXISTS (a `left_anti` join) and
`count(DISTINCT)` beside two sums.  Three broken programs read unequal:
the condition dropped, `<>` read as `=`, NOT EXISTS read as EXISTS."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.generators import (
    _tpcds,
    call_center,
    catalog_returns,
    catalog_sales,
    customer_address,
    date_dim,
)
from benchmarks.harness import check, engine
from benchmarks.queries import q16

SEEDS = [16, 3400000016, 5]
GENERATORS = {"catalog_sales": catalog_sales,
              "catalog_returns": catalog_returns,
              "customer_address": customer_address,
              "call_center": call_center, "date_dim": date_dim}
FILES = 2  # of each fact table: an order and its returns stay in one


@pytest.fixture
def crowded(monkeypatch):
    """Sales of the first quarter of 2002, so a third ship in the
    window; 2,000 addresses, half of them in Georgia; two warehouses,
    so that one order in eight or so ships from one alone.  Returns
    (rows of each table's file, the files of each fact table)."""
    monkeypatch.setattr(_tpcds, "SALES_FIRST_DAY", 11_688)  # 2002-01-01
    monkeypatch.setattr(_tpcds, "SALES_LAST_DAY", 11_777)
    monkeypatch.setattr(_tpcds, "ADDRESSES", 2_000)
    weights = np.full(len(customer_address.STATES), 0.5 / 50)
    weights[list(customer_address.STATES).index("GA")] = 0.5
    monkeypatch.setattr(customer_address, "STATE_WEIGHTS", weights)
    monkeypatch.setattr(catalog_sales, "WAREHOUSES", 2)
    return {"catalog_sales": 9_000, "catalog_returns": 900,
            "customer_address": 2_000, "call_center": 24,
            "date_dim": _tpcds.DAYS}


def _tables(seed: int, work, rows: dict) -> tuple:
    """Each table's files, and the reference's partials: a file of
    `catalog_sales` at a time, the side tables whole."""
    paths, cols = {}, {}
    for name, gen in GENERATORS.items():
        files = FILES if name.startswith("catalog_") else 1
        cols[name] = [gen.generate(seed, i, rows[name]) for i in range(files)]
        paths[name] = []
        for i, c in enumerate(cols[name]):
            paths[name].append(str(work / f"{name}-{i}.parquet"))
            pq.write_table(gen.to_arrow(c, seed, i), paths[name][-1])
    side = {role: {key: np.concatenate([c[key] for c in cols[role]])
                   for key in q16.COLUMNS[role]}
            for role in q16.COLUMNS if role != q16.DRIVER}
    return paths, [q16.partial(c, side) for c in cols[q16.DRIVER]]


def _frames(session, paths: dict) -> dict:
    return {role: session.read_parquet(*paths[role], columns=columns)
            for role, columns in q16.COLUMNS.items()}


def _collect(paths: dict, engine_=None) -> pa.Table:
    from spark_rapids_tpu.session import TpuSession

    session = TpuSession()
    return q16.build(session, _frames(session, paths)).collect(
        engine=engine_)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_engine_answers_as_the_reference(tmp_path, crowded, seed):
    """Both engines; some hundred orders pass, a third of the lines that
    pass the filters are in a returned order, and NULL warehouses and
    one-warehouse orders both stand in the EXISTS's way."""
    paths, partials = _tables(seed, tmp_path, crowded)
    want = q16.combine(partials)
    assert want.schema.names == list(q16.ANSWER) and want.num_rows == 1
    assert 50 < want[q16.ANSWER[0]][0].as_py() < 1_000
    for engine_ in (None, "cpu"):
        why, _ = check.compare(_collect(paths, engine_), want, q16.ORDERED)
        assert why is None, (engine_, why)


def _broken(monkeypatch, case: str) -> None:
    """Rewrite the subquery joins as `case` says, in every build."""
    from spark_rapids_tpu import session as engine_session

    join = engine_session.DataFrame.join

    def broken(self, other, *args, how="inner", condition=None, **kw):
        if how == "left_semi" and case == "condition_dropped":
            condition = None
        elif how == "left_semi" and case == "ne_read_as_eq":
            condition = condition.child  # Not(EqualTo(a, b)) -> a = b
        elif how == "left_anti" and case == "not_exists_as_exists":
            how = "left_semi"
        return join(self, other, *args, how=how, condition=condition, **kw)

    monkeypatch.setattr(engine_session.DataFrame, "join", broken)


@pytest.mark.parametrize("case", ["condition_dropped", "ne_read_as_eq",
                                  "not_exists_as_exists"])
def test_a_broken_program_reads_unequal(tmp_path, crowded, monkeypatch,
                                        case):
    """The comparison that decides `correct` tells each apart from the
    reference: a line with a NULL warehouse or of a one-warehouse order
    is in no EXISTS, and a returned order in no NOT EXISTS."""
    paths, partials = _tables(SEEDS[0], tmp_path, crowded)
    want = q16.combine(partials)
    _broken(monkeypatch, case)
    # past the look at the plan, which refuses two of them before a round
    monkeypatch.setattr(q16, "_refuse_a_plan_without_its_subquery_joins",
                        lambda plan: None)
    why, _ = check.compare(_collect(paths), want, q16.ORDERED)
    assert why is not None, case


def test_the_reference_by_hand():
    """Three orders in one file: order 1 ships from warehouses 1 and 2
    and a NULL one, order 2 from warehouse 3 alone, order 3 from 1 and
    2 but is returned; every line passes the filters."""
    day = q16.FIRST_DAY + _tpcds.EPOCH_SK
    cols = {
        "cs_order_number": np.array([1, 1, 1, 2, 2, 3, 3]),
        "cs_warehouse_sk": np.array([1, 2, -1, 3, 3, 1, 2]),
        "cs_ship_date_sk": np.full(7, day),
        "cs_ship_addr_sk": np.full(7, 5),
        "cs_call_center_sk": np.full(7, 7),
        "cs_ext_ship_cost": np.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0]),
        "cs_net_profit": np.array([1.0, np.nan, 1.0, 1.0, 1.0, 1.0, 1.0]),
    }
    side = {
        "date_dim": {"d_date_sk": np.array([day, day + 100]),
                     "d_date": np.array([q16.FIRST_DAY, q16.FIRST_DAY + 100],
                                        np.int32)},
        "customer_address": {
            "ca_address_sk": np.array([5, 6]),
            "ca_state": np.array([list(customer_address.STATES).index("GA"),
                                  -1], np.int32)},
        "call_center": {"cc_call_center_sk": np.array([7, 8]),
                        "cc_county": np.array([0, 1], np.int32)},
        "catalog_returns": {"cr_order_number": np.array([3, 9])},
    }
    part = q16.partial(cols, side)
    # order 1's lines of warehouses 1 and 2 pass; its NULL one does not
    assert part[0].tolist() == [1]
    answer = q16.combine([part, part]).to_pylist()
    assert answer == [{"order count": 2, "total shipping cost": 6.0,
                       "total net profit": 2.0}]
    # a line that fails a filter, and a sum with no value, is NULL
    cols["cs_call_center_sk"] = np.full(7, 8)
    assert q16.combine([q16.partial(cols, side)]).to_pylist() == [
        {"order count": 0, "total shipping cost": None,
         "total net profit": None}]


def test_the_plan_runs_on_the_device(tmp_path, crowded):
    """Every operator on the device; the EXISTS a conditional semi join
    and the NOT EXISTS an anti join that ran their pairs there."""
    from spark_rapids_tpu.session import TpuSession

    paths, _ = _tables(SEEDS[0], tmp_path, crowded)
    session = TpuSession()
    q16.build(session, _frames(session, paths)).collect()
    event = session.history.events[-1]
    assert engine.off_device(event.explain) == []
    assert "[degraded to CPU engine" not in event.explain
    held, todo = [], [event.root]
    while todo:
        node = todo.pop()
        held.append(node)
        todo += node.children
    semi = [n for n in held if "left_semi" in n.desc]
    anti = [n for n in held if "left_anti" in n.desc]
    assert len(semi) == len(anti) == 1
    assert "cond=Not(EqualTo(cs_warehouse_sk, cs2_warehouse_sk))" \
        in semi[0].desc
    assert semi[0].metrics["condPairs"] > semi[0].metrics["streamRows"] > 0
    assert semi[0].metrics["buildRows"] == FILES * crowded["catalog_sales"]
    assert anti[0].metrics["buildRows"] == FILES * crowded[
        "catalog_returns"]


def test_a_program_that_cannot_run_it_is_refused(tmp_path, crowded,
                                                 monkeypatch):
    """Before a round: a program without the distinct rewrite beside a
    sum, or whose plan would run the conditional semi join on the CPU
    engine, exits with the benchmark's refusal and runs nothing."""
    from spark_rapids_tpu import session as engine_session
    from spark_rapids_tpu.execs import join as engine_join

    paths, _ = _tables(SEEDS[0], tmp_path, crowded)

    def refused(*_a, **_kw):
        raise ValueError("mixing count_distinct with other aggregates is "
                         "not supported yet")

    with monkeypatch.context() as m:
        m.setattr(engine_session.GroupedData, "_agg_distinct", refused)
        with pytest.raises(SystemExit, match="benchmarks.run REFUSED: .*"
                           "count_distinct"):
            _collect(paths)
    with monkeypatch.context() as m:
        m.setattr(engine_join, "CONDITIONAL_TYPES", ("inner",))
        with pytest.raises(SystemExit, match="benchmarks.run REFUSED: .*"
                           "off the device"):
            _collect(paths)
    # a plan whose EXISTS has no condition, or whose NOT EXISTS is no
    # anti join, is refused by what the plan says of its joins
    for case, what in (("condition_dropped", "EXISTS"),
                       ("not_exists_as_exists", "NOT EXISTS")):
        with monkeypatch.context() as m:
            _broken(m, case)
            with pytest.raises(SystemExit, match="benchmarks.run "
                               f"REFUSED: .*does not run the {what} "):
                _collect(paths)
