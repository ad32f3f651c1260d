"""chip_smoke.py: the quickest proof that the query path still runs on the chip.

`python chip_smoke.py` answers TPC-H q6, q1, a q3-shaped join and a
TPC-DS q67-shaped window query at SF10 scale (BASELINE.md config #1's
deployment, with the columns bench.py's generators draw) through the
entry points a user calls — `TpuSession.read_parquet(...)...collect()`,
the planner, `execs/` — under the shipped default conf, on one TPU.
Every result is compared with the CPU engine's at the same full size.
It exits non-zero, with the reason and without a result line, when:

- JAX did not hand back a TPU (`jax.devices()[0].platform`);
- a query degraded to the CPU engine, or its plan has an operator
  that is not on the device;
- the Pallas string-hash kernel did not compile and run on the chip,
  or differs from the jnp path on the same device array;
- the spill store's budget was not derived from the chip's HBM limit,
  or the native host codec was not built;
- the second process found none of the first one's programs in the
  persistent compilation cache;
- any phase raised.  No phase is wrapped in an `except`.

One process owns a chip, so this parent imports neither jax nor the
engine: it runs the query set in a child and, after that child has
exited, once more in a second child that must find the first one's
compiled programs in the cache.  The walls in the record are smoke
timings of single runs, compilation included where it says "cold";
they are not benchmark numbers.

Standard output is two lines of JSON.  The first is the record: sizes,
`reduced`, per-query rows, walls and compiles, the fallback counters.
The last is `{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}` with exactly those keys, the device as JAX reports it.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

#: lineitem: 60 files x 2^20 rows = 62.9M (the spec's SF10 lineitem is
#: 59.99M), keyed into SF10's 15M orders; store_sales 28.8M for q67
LINEITEM_FILES = 60
ORDERS_ROWS = 15_000_000
STORE_SALES_ROWS = 28_800_000
STORE_SALES_FILES = 30
#: q3 runs on tables of its own at SF1 (see REDUCED): lineitem 6 files
#: x 2^20 rows keyed into 1.5M orders
Q3_LINEITEM_FILES = 6
Q3_ORDERS_ROWS = 1_500_000
#: the four-chip phase reads this many of the SF10 lineitem files and
#: the SF10 orders, which only it needs
FOUR_CHIP_LINEITEM_FILES = 16
#: every cut of scale made to fit the time limit, as it is printed in
#: the record
REDUCED = [{
    "query": "q3",
    "ran": "TPC-H SF1: lineitem 6 x 2^20 rows joined to 1.5M orders "
           "(bench.py's default fixture is 2 x 2^20 and 2^20)",
    "full": "SF10: lineitem 62.9M rows joined to 15M orders",
    "why": "at SF10 on a v5e q3 answered equal to the CPU engine but "
           "took 638 s cold (5,151 XLA compiles) and 290 s warm: the "
           "join re-sorts its 5M-row build side under every stream "
           "batch (ROADMAP S1)",
}]
#: the q1/q6 columns of lineitem, cached in HBM for the cached pass
CACHED_COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                  "l_shipdate", "l_returnflag", "l_linestatus"]
#: the smoke has 1200 s in all, compilation included: a child still
#: running this long after the start is stopped and the run fails
DEADLINE_S = 1150
#: relative tolerance for double aggregates: bench._check_rows' and the
#: q6 gate's.  Keys, counts and integer columns compare exactly.
REL_TOL = 1e-6

_T0 = time.perf_counter()


def _say(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _fail(reason: str):
    sys.exit(f"chip_smoke FAILED: {reason}")


# -- the parent: no jax, no engine ------------------------------------ #


def main() -> None:
    if sys.argv[1:2] == ["child"]:
        # how the parent below re-enters this file; not a user option
        child(int(sys.argv[2]), sys.argv[3])
        return
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        first = _run_child(1, work)
        second = _run_child(2, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    c1, c2 = first["compile"], second["compile"]
    if c2["persistent_cache_hits"] <= 0:
        _fail("the second process hit nothing in the persistent "
              f"compilation cache at {first['compile_cache_dir']}: {c2}")
    if not c2["backend_compile_s"] < c1["backend_compile_s"]:
        _fail("the second process compiled for "
              f"{c2['backend_compile_s']} s, the first for "
              f"{c1['backend_compile_s']} s: the cache saved nothing")
    if second.pop("device") != first["device"]:
        _fail("the second process found another device than the first: "
              f"{first['device']}")
    record = dict(first)
    record["second_process"] = second
    record["reduced"] = REDUCED
    record["walls_are"] = "smoke timings of single runs, not benchmark " \
                          "numbers"
    record["total_s"] = round(time.perf_counter() - _T0, 1)
    print(json.dumps({"record": record}), flush=True)
    print(json.dumps({"ok": True, "device": first["device"]}), flush=True)


def _run_child(which: int, work: str) -> dict:
    """Run the query set in a fresh process that owns the chip; its
    last stdout line is its record.  A child that fails, fails the
    smoke with the same code."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "child", str(which),
         work], stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(
            timeout=max(1.0, DEADLINE_S - (time.perf_counter() - _T0)))
    except subprocess.TimeoutExpired:
        # SIGTERM first: a process killed outright while it holds the
        # chip can leave the chip unanswering for whoever comes next
        proc.terminate()
        try:
            proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
        _fail(f"process {which} had not finished {DEADLINE_S} s after "
              "the start")
    if proc.returncode != 0:
        sys.stderr.write(out)
        sys.exit(proc.returncode)
    return json.loads(out.strip().splitlines()[-1])


# -- a child: owns the chip ------------------------------------------- #


def _require_tpu():
    """The device JAX returned, checked: not JAX_PLATFORMS, which says
    what was asked for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"JAX found no TPU: jax.devices()[0] is platform "
              f"{devs[0].platform!r}, kind {devs[0].device_kind!r}")
    return devs


class _CompileCounters:
    """Seconds in XLA's backend compile and persistent-cache traffic,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.backend_compile_s = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs
            self.backend_compiles += 1

    def snapshot(self) -> dict:
        from spark_rapids_tpu.execs.jit_cache import cache_stats

        return {"programs_compiled": cache_stats()["compiles"],
                "backend_compiles": self.backend_compiles,
                "backend_compile_s": round(self.backend_compile_s, 3),
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


def _file_rows(paths) -> int:
    import pyarrow.parquet as pq

    return sum(pq.read_metadata(p).num_rows for p in paths)


def _assert_on_device(name: str, explain_text: str) -> None:
    """Every operator of the plan carries the `*` mark (planner.py:
    `!` marks one that runs on the CPU engine, with its reason)."""
    plan = []
    for line in explain_text.splitlines():
        if line and not line[0].isspace() and line[0] not in "*!":
            break  # the report's sections (Pipeline:, Fusion:, ...)
        plan.append(line)
    off = [ln for ln in plan if ln.strip() and not
           ln.lstrip().startswith("* ")]
    if off or not plan:
        _fail(f"{name}: operators not on the device:\n"
              + "\n".join(off or [explain_text]))


def _assert_equal(name: str, got, want, ordered: bool) -> None:
    """Keys, counts and integer columns exactly; doubles within
    REL_TOL.  Unordered results are sorted by their non-double
    columns first."""
    import pyarrow as pa

    if got.schema.names != want.schema.names:
        _fail(f"{name}: columns {got.schema.names} != "
              f"{want.schema.names}")
    if got.num_rows != want.num_rows:
        _fail(f"{name}: {got.num_rows} rows, the CPU engine has "
              f"{want.num_rows}")
    exact = [f.name for f in want.schema
             if not pa.types.is_floating(f.type)]
    if not ordered and exact:
        keys = [(k, "ascending") for k in exact]
        got, want = got.sort_by(keys), want.sort_by(keys)
    for f in want.schema:
        g = got.column(f.name).to_pylist()
        w = want.column(f.name).to_pylist()
        if f.name in exact:
            if g != w:
                bad = next(i for i, (a, b) in enumerate(zip(g, w))
                           if a != b)
                _fail(f"{name}.{f.name} row {bad}: {g[bad]!r} != "
                      f"{w[bad]!r} (exact column)")
            continue
        for i, (a, b) in enumerate(zip(g, w)):
            if (a is None) != (b is None) or (
                    b is not None
                    and not abs(a - b) <= REL_TOL * max(1.0, abs(b))):
                _fail(f"{name}.{f.name} row {i}: {a!r} vs CPU {b!r}")


def _make_data(work: str, four_chips: bool) -> dict:
    """TPC-H/TPC-DS-shaped tables from bench.py's seeded generators."""
    import bench

    dirs = {n: os.path.join(work, n)
            for n in ("lineitem", "store_sales", "q3", "orders")}
    for d in dirs.values():
        os.makedirs(d)
    data = {
        "lineitem": bench.make_lineitem(
            dirs["lineitem"], n_files=LINEITEM_FILES, with_q1_cols=True,
            with_orderkey=True, n_orders=ORDERS_ROWS),
        "store_sales": bench.make_store_sales(
            dirs["store_sales"], n_rows=STORE_SALES_ROWS,
            n_files=STORE_SALES_FILES),
        "q3_lineitem": bench.make_lineitem(
            dirs["q3"], n_files=Q3_LINEITEM_FILES, with_orderkey=True,
            n_orders=Q3_ORDERS_ROWS),
        "q3_orders": bench.make_orders(dirs["q3"],
                                       n_orders=Q3_ORDERS_ROWS),
    }
    if four_chips:
        data["orders"] = bench.make_orders(dirs["orders"],
                                           n_orders=ORDERS_ROWS)
    return data


def _queries(session, data: dict) -> dict:
    """name -> (DataFrame, input paths, result is ordered)."""
    import bench

    li, ss = data["lineitem"], data["store_sales"]
    q3_li, q3_orders = data["q3_lineitem"], data["q3_orders"]
    return {
        "q6": (bench.q6_dataframe(session, li), li, False),
        "q1": (bench.q1_dataframe(session, li), li, False),
        "q3": (bench.q3_dataframe(session, q3_li, q3_orders),
               q3_li + [q3_orders], True),
        "q67": (bench.q67_dataframe(session, ss), ss, True),
    }


def _timed_collect(df):
    t0 = time.perf_counter()
    out = df.collect(engine="tpu")
    return out, round(time.perf_counter() - t0, 3)


def _run_queries(session, data: dict, work: str, which: int,
                 cc: _CompileCounters) -> dict:
    """The first child collects each query cold, then warm, and checks
    both against the CPU engine at full size (outside the timing); it
    leaves the CPU tables behind for the second child, which collects
    each query once and checks it against them."""
    import pyarrow.parquet as pq

    out: dict = {}
    for name, (df, paths, ordered) in _queries(session, data).items():
        _assert_on_device(name, df.explain())
        c0 = cc.snapshot()
        got, cold_s = _timed_collect(df)
        rec = {"rows_in": _file_rows(paths), "rows_out": got.num_rows,
               "cold_s": cold_s, "cold_compile": cc.since(c0)}
        _say(f"{name}: cold {cold_s} s, {rec['cold_compile']}")
        oracle = os.path.join(work, f"cpu_{name}.parquet")
        if which == 1:
            c1 = cc.snapshot()
            again, rec["warm_s"] = _timed_collect(df)
            rec["warm_compile"] = cc.since(c1)
            t0 = time.perf_counter()
            want = df.collect(engine="cpu")
            rec["cpu_engine_s"] = round(time.perf_counter() - t0, 3)
            pq.write_table(want, oracle)
            _assert_equal(name + " (warm)", again, want, ordered)
            _say(f"{name}: warm {rec['warm_s']} s, CPU engine "
                 f"{rec['cpu_engine_s']} s")
        else:
            want = pq.read_table(oracle)
        _assert_equal(name, got, want, ordered)
        out[name] = rec
    return out


def _run_cached(session, data: dict, work: str, device) -> dict:
    """q6 and q1 once more over read_parquet(...).cache(): the q1/q6
    columns of all 62.9M lineitem rows live in HBM."""
    import bench
    import pyarrow.parquet as pq

    from spark_rapids_tpu.memory import get_store

    lineitem = session.read_parquet(
        *data["lineitem"], columns=CACHED_COLUMNS).cache()
    q6, q1 = bench.q6_over(lineitem), bench.q1_over(lineitem)
    out: dict = {}
    try:
        _assert_on_device("q6 (cached)", q6.explain())
        got6, out["q6_fill_s"] = _timed_collect(q6)  # fills the cache
        stats = get_store().spill_stats()
        rows = _file_rows(data["lineitem"])
        # the least the cached columns can take: four doubles and the
        # int32 date per row, before validity and the two flag columns
        floor = rows * (4 * 8 + 4)
        out.update(rows=rows, cached_device_bytes=stats["device_used"],
                   cached_bytes_floor=floor)
        if stats["device_used"] < floor:
            _fail(f"the cache holds {stats['device_used']} device bytes; "
                  f"{rows} rows of the q1/q6 columns need {floor}")
        _assert_on_device("q1 (cached)", q1.explain())
        got1, out["q1_s"] = _timed_collect(q1)
        again6, out["q6_s"] = _timed_collect(q6)
        mem = device.memory_stats()
        out["peak_bytes_in_use"] = mem["peak_bytes_in_use"]
        out["bytes_in_use"] = mem["bytes_in_use"]
        if mem["peak_bytes_in_use"] < stats["device_used"]:
            _fail(f"peak_bytes_in_use {mem['peak_bytes_in_use']} is "
                  f"below the {stats['device_used']} bytes the store "
                  "says are cached on the device")
        spilled = get_store().spill_stats()["spilled_device_to_host"]
        if spilled:
            _fail(f"{spilled} cached bytes spilled to the host")
    finally:
        lineitem.unpersist()
    want6 = pq.read_table(os.path.join(work, "cpu_q6.parquet"))
    want1 = pq.read_table(os.path.join(work, "cpu_q1.parquet"))
    _assert_equal("q6 (cache fill)", got6, want6, False)
    _assert_equal("q6 (cached)", again6, want6, False)
    _assert_equal("q1 (cached)", got1, want1, False)
    _say(f"cached pass: {out}")
    return out


def _run_pallas(session, data: dict) -> dict:
    """The Pallas string-hash kernel, twice: directly against the jnp
    path on the same device arrays, and through the planner as
    hash(l_returnflag, l_linestatus) over every lineitem row."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.exprs.hashing import (
        Murmur3Hash,
        hash_string_bytes_jnp,
    )
    from spark_rapids_tpu.ops import pallas_kernels as pk
    from spark_rapids_tpu.session import col, count_star, max_, min_

    # -- the kernel against the jnp path, same device arrays.  Widths
    # on both sides of a 4-byte block, a one-byte column like q1's
    # flags, the widest the kernel takes, and a row count that is not
    # a block multiple (the pad-to-block branch)
    rng = np.random.default_rng(21)
    jnp_path = jax.jit(hash_string_bytes_jnp)
    shapes = [(1 << 20, 1), (1 << 20, 8), (1 << 20, 20),
              (3 * pk._BLOCK_N // 2, 12), (1 << 16, pk._MAX_WIDTH)]
    for n, width in shapes:
        lengths = rng.integers(0, width + 1, n, dtype=np.int32)
        chars = rng.integers(0, 256, (n, width), dtype=np.uint8)
        chars[np.arange(width)[None, :] >= lengths[:, None]] = 0
        d_chars, d_lengths = jnp.asarray(chars), jnp.asarray(lengths)
        seeds = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
        fast = pk.maybe_pallas_hash_string(d_chars, d_lengths, seeds)
        if fast is None:
            _fail(f"the Pallas kernel was not routed to for a ({n}, "
                  f"{width}) string column on {jax.default_backend()}")
        ref = jnp_path(d_chars, d_lengths, seeds)
        if not np.array_equal(np.asarray(fast), np.asarray(ref)):
            _fail(f"the Pallas kernel differs from the jnp path on a "
                  f"({n}, {width}) string column")
    _say(f"pallas kernel bit-equal to the jnp path on {shapes}")

    # -- through the planner.  The CPU engine hashes strings row by row
    # in Python, so it is given the six distinct keys to hash and the
    # full table to count: every row of a group must carry that hash.
    routed0 = pk.routed_count()
    flag, status = col("l_returnflag"), col("l_linestatus")
    hashed = session.read_parquet(*data["lineitem"]).select(
        flag, status, Murmur3Hash(flag, status).alias("h"))
    df = hashed.group_by(flag, status).agg(
        (min_(col("h")), "h_min"), (max_(col("h")), "h_max"),
        (count_star(), "n"))
    _assert_on_device("hash(l_returnflag, l_linestatus)", df.explain())
    got, wall_s = _timed_collect(df)
    routed = pk.routed_count() - routed0
    if routed <= 0:
        _fail("no program of the hash query was traced through the "
              "Pallas kernel")
    counts = session.read_parquet(*data["lineitem"]).group_by(
        flag, status).agg((count_star(), "n")).collect(engine="cpu")
    keys = counts.select(["l_returnflag", "l_linestatus"])
    key_hash = session.create_dataframe(keys).select(
        flag, status, Murmur3Hash(flag, status).alias("h")).collect(
        engine="cpu")
    want = pa.table({
        "l_returnflag": counts["l_returnflag"],
        "l_linestatus": counts["l_linestatus"],
        "h_min": key_hash["h"], "h_max": key_hash["h"],
        "n": counts["n"]})
    _assert_equal("hash(l_returnflag, l_linestatus)", got,
                  want.cast(got.schema), False)
    return {"kernel_shapes_bit_equal": shapes, "query_rows_in":
            _file_rows(data["lineitem"]), "query_rows_out": got.num_rows,
            "query_s": wall_s, "programs_routed_to_kernel": routed}


def _run_four_chips(session, data: dict, devs) -> dict:
    """The collective shuffle tier on four chips, through the planner:
    a group-by, a shuffled join and an ORDER BY over the same lineitem
    and orders, each drained shard by shard and compared with what one
    chip answered.  Rows, bytes and the devices holding them are
    recorded per shard, so "everything on device 0" shows."""
    import jax
    import pyarrow as pa

    from spark_rapids_tpu.columnar.arrow import to_arrow
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.plan.planner import plan_query
    from spark_rapids_tpu.session import col, count_star, sum_

    li = data["lineitem"][:FOUR_CHIP_LINEITEM_FILES]
    broadcast = "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes"

    def queries():
        lineitem = session.read_parquet(*li)
        orders = session.read_parquet(data["orders"]).where(
            col("o_orderdate") < lit(8800))
        return {
            "group_by": (lineitem.group_by(col("l_shipdate")).agg(
                (sum_(col("l_quantity")), "qty"), (count_star(), "n")),
                "TpuCollectiveHashAggregateExec", False),
            "join": (lineitem.join(
                orders, left_on=[col("l_orderkey")],
                right_on=[col("o_orderkey")]).select(
                col("l_orderkey"), col("o_orderdate"),
                col("l_shipdate"), col("l_quantity")),
                "TpuCollectiveHashJoinExec", False),
            "order_by": (orders.order_by(col("o_orderdate"),
                                         col("o_orderkey")),
                         "TpuCollectiveSortExec", True),
        }

    # one chip first: the answers the four have to reproduce
    old_broadcast = session.conf.get(broadcast)
    session.conf.set(broadcast, -1)  # a shuffled join, not a broadcast
    single = {name: df.collect(engine="tpu")
              for name, (df, _, _) in queries().items()}
    mesh = session.enable_collective_shuffle(4)
    mesh_ids = [int(d.id) for d in mesh.devices.flat]
    out: dict = {"mesh_device_ids": mesh_ids,
                 "lineitem_rows": _file_rows(li),
                 "orders_rows": _file_rows([data["orders"]])}
    try:
        for name, (df, exec_name, ordered) in queries().items():
            exec_, meta = plan_query(df._plan, session.conf)
            _assert_on_device(f"four chips {name}", meta.explain())
            if exec_name not in exec_.tree_string():
                _fail(f"four chips {name}: the planner did not lower to "
                      f"{exec_name}:\n{exec_.tree_string()}")
            t0 = time.perf_counter()
            tables, shards = [], []
            try:
                for p in range(exec_.num_partitions):
                    rows = nbytes = 0
                    on: set = set()
                    for b in exec_.execute_partition(p):
                        leaves = [x for x in jax.tree_util.tree_leaves(b)
                                  if isinstance(x, jax.Array)]
                        nbytes += sum(x.nbytes for x in leaves)
                        for x in leaves:
                            on |= {int(d.id) for d in x.devices()}
                        t = to_arrow(b)
                        rows += t.num_rows
                        tables.append(t)
                    shards.append({"rows": rows, "bytes": nbytes,
                                   "device_ids": sorted(on)})
            finally:
                exec_.close()
            wall_s = round(time.perf_counter() - t0, 3)
            for p, sh in enumerate(shards):
                if sh["rows"] <= 0 or sh["device_ids"] != [mesh_ids[p]]:
                    _fail(f"four chips {name}: shard {p} should hold "
                          f"rows on device {mesh_ids[p]} alone: {shards}")
            got = pa.concat_tables(tables)
            _assert_equal(f"four chips {name}", got,
                          single[name].cast(got.schema), ordered)
            out[name] = {"rows_out": got.num_rows, "wall_s": wall_s,
                         "per_device": shards}
            _say(f"four chips {name}: {out[name]}")
    finally:
        session.disable_collective_shuffle()
        session.conf.set(broadcast, old_broadcast)
    out["peak_bytes_in_use_per_device"] = [
        d.memory_stats()["peak_bytes_in_use"] for d in devs[:4]]
    return out


def child(which: int, work: str) -> None:
    from importlib import metadata

    import jax
    import jaxlib

    import spark_rapids_tpu
    from spark_rapids_tpu import native
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.execs.retry import retry_stats
    from spark_rapids_tpu.memory import device_manager, get_store
    from spark_rapids_tpu.session import TpuSession

    devs = _require_tpu()
    device = devs[0]
    cc = _CompileCounters()
    _say(f"process {which}: {len(devs)} x {device.device_kind}")

    # -- start-up state, each with its own way of failing ------------- #
    bytes_limit = device.memory_stats()["bytes_limit"]
    fraction = get_conf().get(device_manager.MEMORY_FRACTION)
    budget = get_store().device_budget
    if budget != int(bytes_limit * fraction):
        _fail(f"the spill store's device budget {budget} is not "
              f"{fraction} of the chip's bytes_limit {bytes_limit}")
    if native.load() is None:
        _fail("the native host codec is not loaded (no g++?): scans "
              "would decode through the slow path")
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir != spark_rapids_tpu.compile_cache_dir():
        _fail(f"the compile cache is at {cache_dir}, not at "
              f"{spark_rapids_tpu.compile_cache_dir()}")
    record = {
        "device": {"platform": device.platform,
                   "kind": device.device_kind, "count": len(devs)},
        "hbm_bytes_limit": bytes_limit,
        "store_device_budget": budget,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "native_codec": "built",
    }

    if which == 1:
        t0 = time.perf_counter()
        data = _make_data(work, four_chips=len(devs) >= 4)
        with open(os.path.join(work, "data.json"), "w") as f:
            json.dump(data, f)
        record["datagen_s"] = round(time.perf_counter() - t0, 1)
        _say(f"data generated in {record['datagen_s']} s")
    else:
        with open(os.path.join(work, "data.json")) as f:
            data = json.load(f)

    session = TpuSession()
    record["queries"] = _run_queries(session, data, work, which, cc)
    if which == 1:
        record["cached"] = _run_cached(session, data, work, device)
        record["pallas"] = _run_pallas(session, data)
        # four chips run the collective tier too; one chip cannot,
        # and says so: never a pass
        record["four_chips"] = _run_four_chips(session, data, devs) \
            if len(devs) >= 4 else "not_run"
    record["compile"] = cc.snapshot()

    # -- nothing was answered by the CPU engine ------------------------ #
    record["retry"] = retry_stats()
    if record["retry"]["cpu_fallbacks"]:
        _fail(f"{record['retry']['cpu_fallbacks']} queries degraded to "
              "the CPU engine")
    for ev in session.history.events:
        if "[degraded to CPU engine" in ev.explain:
            _fail(f"query {ev.query_id} degraded to the CPU engine:\n"
                  + ev.explain)
    store = get_store().spill_stats()
    record["spilled_device_to_host"] = store["spilled_device_to_host"]
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
