"""TPC-H q6 is a real query: keep it covered by CI (tiny scale) and
assert the compile cache makes repeat collects trace-free."""

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.exprs.base import lit
from spark_rapids_tpu.session import TpuSession, col, sum_
from tests.differential import assert_tpu_cpu_equal


def _q6(session, paths):
    ship, disc = col("l_shipdate"), col("l_discount")
    return session.read_parquet(*paths).where(
        (ship >= lit(8766)) & (ship < lit(9131)) & (disc >= lit(0.05))
        & (disc <= lit(0.07)) & (col("l_quantity") < lit(24.0))).agg(
        (sum_(col("l_extendedprice") * disc), "revenue"))


def _tiny_lineitem(tmp_path, n=1000, files=2):
    import numpy as np

    rng = np.random.default_rng(7)
    paths = []
    for i in range(files):
        t = pa.table({
            "l_quantity": rng.integers(1, 51, n).astype(np.float64),
            "l_extendedprice": rng.uniform(900, 105000, n),
            "l_discount": rng.integers(0, 11, n) / 100.0,
            "l_shipdate": rng.integers(8766, 10957, n).astype(np.int32),
        })
        p = str(tmp_path / f"li-{i}.parquet")
        pq.write_table(t, p)
        paths.append(p)
    return paths


def test_bench_q6_differential(tmp_path):
    paths = _tiny_lineitem(tmp_path)
    df = _q6(TpuSession(), paths)
    assert_tpu_cpu_equal(df, approx_float=True)


def test_repeat_collect_reuses_compiled_programs(tmp_path):
    from spark_rapids_tpu.execs import jit_cache

    paths = _tiny_lineitem(tmp_path)
    session = TpuSession()
    df = _q6(session, paths)
    df.collect(engine="tpu")
    size_after_first = jit_cache.cache_size()
    df2 = _q6(session, paths)  # fresh plan, same structure
    df2.collect(engine="tpu")
    assert jit_cache.cache_size() == size_after_first, (
        "second identical query created new jit wrappers — the global "
        "compile cache is not keying structurally")
