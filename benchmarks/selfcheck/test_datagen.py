"""The seeded generators, and each query's plain reference against the
CPU engine at the rehearsal size."""

import hashlib
import os

import pyarrow.parquet as pq
import pytest

from benchmarks.harness import check, datagen, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def _digest(paths: dict) -> dict:
    """Per file, a hash of the decoded columns (Parquet's own bytes
    carry a writer version; the rows are what the seed fixes)."""
    out = {}
    for table, files in paths.items():
        for p in files:
            h = hashlib.sha256()
            for col in pq.read_table(p).columns:
                for chunk in col.chunks:
                    for buf in chunk.buffers():
                        if buf is not None:
                            h.update(buf)
            out[os.path.basename(p)] = h.hexdigest()
    return out


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    """(cell, seed) -> Data, made once."""
    made = {}

    def get(cell_name: str, seed: int):
        if (cell_name, seed) not in made:
            cell = spec.load_cell(cell_name, rehearse=True)
            work = tmp_path_factory.mktemp("data")
            made[cell_name, seed] = (cell, datagen.generate(
                cell, seed, str(work)))
        return made[cell_name, seed]
    return get


def test_same_seed_same_bytes_other_seed_other_bytes(generated,
                                                     tmp_path):
    cell, first = generated(CELLS[0], 11)
    again = datagen.generate(cell, 11, str(tmp_path / "again"))
    other = datagen.generate(cell, 12, str(tmp_path / "other"))
    assert _digest(first.paths) == _digest(again.paths)
    assert all(a != b for a, b in zip(_digest(first.paths).values(),
                                      _digest(other.paths).values()))
    for a, b in zip(first.expected, again.expected):
        assert a.equals(b)


def test_files_are_independent_of_the_file_count():
    """File i depends on (seed, i) alone: a cell that reads fewer files
    reads the same ones."""
    import numpy as np

    from benchmarks.generators import lineitem

    a, b = lineitem.generate(5, 0, 1024), lineitem.generate(5, 0, 1024)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    c = lineitem.generate(5, 1, 1024)
    assert not np.array_equal(a["l_partkey"], c["l_partkey"])
    assert c["l_orderkey"].min() > a["l_orderkey"].max()


def test_the_tables_have_the_specifications_shapes():
    """Clauses 1.4.1 and 4.2.3: every column, lines clustered under
    their order, dates and flags that follow from the order's date."""
    import numpy as np

    from benchmarks.generators import _tpch, lineitem, orders

    rows = 1 << 16
    li, od = lineitem.generate(9, 2, rows), orders.generate(9, 2, rows // 4)
    file = lineitem.to_arrow(li, 9, 2)
    assert file.num_rows == rows and file.schema.names == list(
        lineitem.COLUMN_BYTES) and len(file.schema.names) == 16
    assert orders.to_arrow(od, 9, 2).schema.names == list(
        orders.COLUMN_BYTES) and len(orders.COLUMN_BYTES) == 9
    import pyarrow.compute as pc

    lengths = pc.utf8_length(file["l_comment"]).to_numpy()
    assert lengths.min() >= 10 and lengths.max() <= 43
    # 1 to 7 lines an order, four on average, numbered from 1, together
    lines = np.bincount(li["order_of"])
    assert lines.min() == 1 and lines.max() == 7 and lines.sum() == rows
    assert np.array_equal(lines, od["lines"])
    assert np.all(np.diff(li["l_orderkey"]) >= 0)
    assert np.array_equal(np.unique(li["l_orderkey"]), od["o_orderkey"])
    assert np.all((od["o_orderkey"] - 1) % 32 < 8)
    assert np.array_equal(li["l_linenumber"][np.cumsum(lines) - 1], lines)
    assert np.all(od["o_custkey"] % 3 != 0)
    # dates hang on the order's date; flags on the dates
    placed = od["o_orderdate"][li["order_of"]]
    after = li["l_shipdate"] - placed
    assert after.min() == 1 and after.max() == 121
    assert placed.min() >= _tpch.STARTDATE
    assert placed.max() <= _tpch.ENDDATE - 151
    late = li["l_receiptdate"] > _tpch.CURRENTDATE
    assert np.all(_tpch.RETURNFLAGS[li["l_returnflag"]][late] == "N")
    assert set(_tpch.RETURNFLAGS[li["l_returnflag"]][~late]) == {"A", "R"}
    assert np.array_equal(_tpch.LINESTATUSES[li["l_linestatus"]] == "O",
                          li["l_shipdate"] > _tpch.CURRENTDATE)
    # q1 sees the four groups dbgen's data has, N/F the rare one
    groups = np.bincount(li["l_returnflag"] * 2 + li["l_linestatus"])
    assert np.flatnonzero(groups).tolist() == [0, 2, 3, 4]
    assert groups[2] < groups[0] / 10
    assert 0.97 < np.mean(li["l_shipdate"] <= 10471) < 0.995
    # an order is F or O as all its lines are, else P, and totals them
    open_lines = np.bincount(li["order_of"], li["l_linestatus"])
    assert np.all((od["o_orderstatus"] == 1) == (open_lines == lines))
    assert 0 < np.mean(od["o_orderstatus"] == 2) < 0.05
    cents = np.round(li["l_extendedprice"] * (1 + li["l_tax"])
                     * (1 - li["l_discount"]) * 100)
    assert np.allclose(od["o_totalprice"],
                       np.bincount(li["order_of"], cents) / 100)


@pytest.mark.parametrize("cell_name", CELLS)
def test_expected_answers_equal_the_cpu_engine(generated, cell_name):
    """The plain reference (numpy, per file, combined) against
    `collect(engine="cpu")`, the program's own oracle, on the same
    files."""
    from spark_rapids_tpu.session import TpuSession

    cell, data = generated(cell_name, 7)
    session = TpuSession()
    for step, want in zip(cell.round, data.expected):
        query = spec.module("queries", step.query)
        frames = {role: session.read_parquet(*data.paths[t.name],
                                             columns=query.COLUMNS[role])
                  for role, t in step.tables}
        got = query.build(session, frames).collect(engine="cpu")
        assert want.num_rows > 0
        assert check.difference(got, want, query.ORDERED) is None, \
            step.query


def test_a_wrong_answer_is_told():
    import pyarrow as pa

    want = pa.table({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]})
    assert check.difference(want, want, True) is None
    shuffled = want.take([2, 0, 1])
    assert check.difference(shuffled, want, False) is None
    assert "k row 0" in check.difference(shuffled, want, True)
    off = pa.table({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0 + 1e-5]})
    assert "v row 2" in check.difference(off, want, True)
    near = pa.table({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0 + 1e-9]})
    assert check.difference(near, want, True) is None
    assert "rows" in check.difference(want.slice(0, 2), want, True)
    wide = pa.table({"k": pa.array([1, 2, 3], pa.int32()),
                     "v": [1.0, 2.0, 3.0]})
    assert check.difference(wide, want, True) is None
