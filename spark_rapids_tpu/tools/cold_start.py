"""Cold-process measurement harness for the warm-start cache.

A process restart is the one cost the in-process caches cannot see:
every jit wrapper, prepared plan and cached result dies with the
process, and the next process re-pays trace + XLA compile for the
whole working set (docs/warm_start.md).  This module is the measured
unit for that cost — ONE fresh process executing the fusion-smoke
query (the same q1-shaped scan->filter->agg fixture
tools/bench_smoke.run_fusion_smoke gates on) against a given persist
directory, reporting wall time, result digest, jit miss/compile
counts, ledger dispatch count and the persist.* counter snapshot as
one JSON line on stdout.

``tools/bench_smoke.run_warm_start_smoke`` (tier-1) forks it: one
populate-and-prime pass, then a measured child asserting ZERO compiles
and a digest bit-identical to the in-process run.

Run: python -m spark_rapids_tpu.tools.cold_start --data DIR \\
         [--persist DIR]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

#: fixture constants — shared with run_fusion_smoke's shape so the
#: warm-start numbers describe the same program population the fusion
#: gates describe
FIXTURE_SEED = 0xF05E
FIXTURE_ROWS = 1 << 14


def make_fixture(dir_: str) -> str:
    """Write the fusion-smoke parquet fixture (4 row groups) into
    `dir_` and return its path.  Deterministic: every process seeds
    the same rng, so parent and children agree on content digests."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(FIXTURE_SEED)
    n = FIXTURE_ROWS
    t = pa.table({
        "l_shipdate": rng.integers(8766, 10957, n).astype(np.int32),
        "l_key": rng.integers(0, 4, n).astype(np.int64),
        "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        "l_price": rng.integers(900, 105000, n).astype(np.int64),
    })
    path = os.path.join(dir_, "li.parquet")
    pq.write_table(t, path, row_group_size=n // 4)
    return path


def run_once(data_dir: str,
             persist_dir: Optional[str] = None) -> dict:
    """Execute the fixture query once in THIS process and return the
    measurement record.  With `persist_dir` set, persistence is
    enabled against it BEFORE any compile (so the XLA compilation
    cache attaches in time) and the background writer is drained
    before returning (so a later process sees every entry).

    wall_ms times session construction + collect only — the portion
    a restart re-pays per query; interpreter/jax import time is paid
    before this function runs and is the same for warm and empty."""
    from spark_rapids_tpu import persist as P
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.execs.base import _budget_conf, _fusion_conf
    from spark_rapids_tpu.execs.jit_cache import cache_stats
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import (
        TpuSession,
        col,
        count_star,
        sum_,
    )
    from spark_rapids_tpu.trace import ledger

    _fusion_conf()
    _budget_conf()
    conf = get_conf()
    n = FIXTURE_ROWS
    # pinned like run_fusion_smoke: deterministic dispatch pattern,
    # 4 row groups -> 4 wire batches, fused chain on
    conf.set("spark.rapids.tpu.sql.pipeline.enabled", False)
    conf.set("spark.rapids.tpu.sql.speculation.enabled", False)
    conf.set("spark.rapids.tpu.sql.batchSizeRows", n // 4)
    conf.set("spark.rapids.tpu.sql.shuffle.partitions", 1)
    conf.set("spark.rapids.tpu.sql.fusion.enabled", True)
    conf.set("spark.rapids.tpu.sql.fusion.donation.enabled", False)
    if persist_dir is not None:
        conf.set("spark.rapids.tpu.persist.enabled", True)
        conf.set("spark.rapids.tpu.persist.dir", persist_dir)
        # activate NOW, before the first compile: the XLA persistent
        # compilation cache only captures compiles that happen after
        # jax_compilation_cache_dir is set
        P.active()
    ledger.enable()

    path = os.path.join(data_dir, "li.parquet")
    t0 = time.perf_counter()
    session = TpuSession()
    r = (session.read_parquet(path)
         .where(col("l_shipdate") <= lit(10471))
         .group_by(col("l_key"))
         .agg((sum_(col("l_quantity")), "sum_qty"),
              (sum_(col("l_price")), "sum_price"),
              (count_star(), "n"))
         .order_by(col("l_key"))
         .collect(engine="tpu"))
    wall_ms = (time.perf_counter() - t0) * 1e3

    ledger.LEDGER.flush(timeout=30.0)
    summary = ledger.summarize(ledger.snapshot())
    jc = cache_stats()
    if persist_dir is not None:
        P.flush(timeout=30.0)
    from spark_rapids_tpu.memory.device_manager import device_fields

    return {
        **device_fields(),
        "wall_ms": round(wall_ms, 3),
        "digest": table_digest(r),
        "rows": r.num_rows,
        "jit_misses": jc["misses"],
        "compiles": jc["compiles"],
        "dispatches": summary["totals"]["dispatches"],
        "persist": P.stats(),
    }


def run_subprocess(data_dir: str, persist_dir: Optional[str] = None,
                   timeout: float = 300.0) -> dict:
    """Fork one fresh interpreter running this module's CLI and parse
    its JSON record.  The child inherits the environment (so a
    JAX_PLATFORMS pin applies) with the repo root prepended to
    PYTHONPATH."""
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "spark_rapids_tpu.tools.cold_start",
           "--data", data_dir]
    if persist_dir is not None:
        cmd += ["--persist", persist_dir]
    proc = subprocess.run(cmd, env=env, capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"cold-start child failed ({proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    # the record is the LAST stdout line (backends may chat above it)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1])


def main(argv: Optional[list] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    data_dir = persist_dir = None
    i = 0
    while i < len(args):
        if args[i] == "--data" and i + 1 < len(args):
            data_dir = args[i + 1]
            i += 2
        elif args[i] == "--persist" and i + 1 < len(args):
            persist_dir = args[i + 1]
            i += 2
        else:
            print(f"unknown arg: {args[i]}", file=sys.stderr)
            return 2
    if not data_dir:
        print("usage: python -m spark_rapids_tpu.tools.cold_start "
              "--data DIR [--persist DIR]", file=sys.stderr)
        return 2
    if not os.path.exists(os.path.join(data_dir, "li.parquet")):
        make_fixture(data_dir)
    print(json.dumps(run_once(data_dir, persist_dir)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
