"""Benchmark driver: TPC-H q6 + q1 + a q3-shaped join, end-to-end
through the framework, one chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} —
headline = q6 (BASELINE.md config #1); q1 (config #2's shape: grouped
8-aggregate over string keys), q3 (config #3's shape: two-table hash
join -> grouped aggregate -> top-k) and q67 (config #4's shape:
grouped aggregate -> rank window -> rank filter -> sort) ride as
q1_*/q3_*/q67_* fields.

Unlike a kernel microbenchmark, this measures the REAL query path:
`TpuSession.read_parquet -> ... -> collect`, which includes the host
Parquet decode, plan tagging, wire encode + H2D upload, the fused jitted
programs, and the D2H result materialization.  Every timed iteration is
a full collect() (the returned Arrow table forces a sync, so no
async-dispatch artifact).

`vs_baseline` is measured IN-RUN: the same logical plan executed by the
CPU reference engine (pyarrow compute — the "CPU Spark" stand-in this
repo uses for differential testing), same files, same process.

Attribution fields (so round-over-round deltas are explainable):
- per-config min/median/max seconds (link weather varies ~100x between
  runs; a median alone cannot distinguish regression from weather);
- a link probe (scalar-fetch round-trip + upload bandwidth) taken right
  before timing;
- a q6 stage breakdown: host decode / wire encode+upload / the final
  fetch (which inlines the remaining device execution wait);
- per-query `q*_host_sync_count` (blocking device->host readbacks per
  collect — the number speculative output sizing drives to zero) and
  `q{1,3,67}_speculation_hit_rate` (fraction of speculative dispatches
  whose predicted capacity covered the true count), so the sync
  elimination is visible in the perf trajectory;
- `q3_rf_*` runtime-filter attribution (pruned rows, build ms, pruned
  row groups per collect) plus `q3_upload_rows` vs
  `q3_upload_rows_no_rf` — the probe-side wire-shrink runtime join
  filters buy (docs/runtime_filters.md);
- `q6_warm_*` / `q1_warm_*` + `hbm_roofline_fraction_warm`: a second
  pass against df.cache()-materialized DEVICE-resident batches, so
  actual device throughput is measured with the H2D wire out of the
  loop;
- per-query DEVICE-LEDGER attribution (trace/ledger.py,
  docs/device_ledger.md): `q*_device_busy_ms` (attributed device time
  per collect, vs the wall-clock numbers' host+wire+dispatch
  residual), `q*_roofline_attributed` (XLA-cost-model bytes over
  settled device time against the HBM peak — the honest counterpart
  of the coarse `hbm_roofline_fraction` quotients, same constant via
  trace/ledger.roofline_fraction), `q*_dispatches`/`q*_programs`
  (launch counts + distinct compiled programs: the ROADMAP #2
  fusion/bucketing scoreboard), `q*_live_capacity_ratio` (live rows
  over padded capacity across the window's dispatches — the occupancy
  scoreboard, docs/occupancy.md) and `q*_top_program` (+`_share`).
  Batch coalescing is ON by default for rounds (`--no-coalesce`
  reverts; results are bit-identical either way);
- `q*_fusion_chains` / `q*_fused_dispatch_savings` (docs/fusion.md):
  whole-stage fusion attribution per collect — chains the planner
  fused into single programs and the program launches those fused
  executions did not pay; the warm passes are additionally GATED by
  `spark.rapids.tpu.sql.fusion.warmDispatchBudget` (warm dispatches
  over budget, or any warm jit miss, fails the round — ROADMAP #2's
  dispatch-soup diagnosis as a regression gate).  Buffer donation is
  ON by default for rounds (`--no-donation` reverts);
- `q{1,3,6,67}_retry_splits` / `_spills_under_pressure` /
  `_recovered_faults` (reset per query like the pipeline/speculation
  counters): recovery activity in the timed window.  On a clean run
  all three are 0; under `--chaos` — which arms the deterministic
  fault schedule below for every query (robustness/faults.py,
  docs/robustness.md) — they record what the recovery ladder absorbed,
  so BENCH_r06+ measures recovery OVERHEAD, not just happy-path speed
  (the correctness gates still run, so a chaos round that survives is
  a chaos round that answered exactly);
- a persistent EVENT LOG per round (on by default; `--no-eventlog` to
  opt out, `--eventlog DIR` / $BENCH_EVENTLOG_DIR to place it): every
  collect's plan, settled operator metrics and counter deltas, so
  rounds are diffable offline via
  `python -m spark_rapids_tpu.tools.history report` instead of
  hand-diffing these JSON fields (docs/eventlog.md); the file path
  rides in the output as `eventlog`.

- `q{1,3,6}_upload_bytes_wire` / `_upload_bytes_raw` / `_upload_ratio`
  (+ `link_upload_mb_s_effective`): bytes actually crossing the H2D
  link over the tapped batched-upload counter, wire compression
  as-configured vs forced off — the multiplier the wire-codec
  subsystem (docs/wire_compression.md) buys on the H2D link.
  Compression is ON by default for bench rounds
  (`--no-wire-compression` reverts to the raw wire; the correctness
  gates run either way).

`bench.py --scale-rows N` switches to the SCALING-CURVE round
(ROADMAP #1): q6 at N rows (~63M = SF10 lineitem) and q1 at
max(N // 3, 20M) rows with the full per-stage attribution, proving
the codec + OOC machinery under real pressure.

`bench.py --multichip N` switches to the MULTICHIP round (docs/spmd.md,
ROADMAP #3): the collective tier's agg/join/sort phases on the virtual
N-device CPU mesh — per-phase wall, exchange rounds, partitioned
program counts, ledger dispatches/device time, per-device wall — plus
the milestone comparison: single-device vs SPMD whole-stage walls,
bit-identical canonical digests, and
`speedup_vs_single_device`.  Known-noise XLA:CPU AOT stderr is
filtered out of the captured `tail`, so MULTICHIP_r*.json carries only
signal.

`bench.py --sessions N [--tenants K]` switches to the SERVING bench
(docs/serving.md): N concurrent sessions across K tenants drive
deterministic golden templates through admission control and the
prepared-plan cache, emitting `serving_qps`, `serving_p50_ms` /
`serving_p99_ms`, `admission_wait_p99_ms` and `plan_cache_hit_rate`,
with a bit-for-bit digest gate against serial execution and a
repeat-template pass asserting hit rate 1.0 with zero plan/tag/lower
spans and zero jit-cache misses.  Cross-tenant work sharing
(docs/work_sharing.md) is ON by default: the round runs the whole
concurrent pass twice — sharing off then on — and emits the A/B
(`serving_qps_sharing_{on,off}`, `shared_scan_dedup_ratio`,
`result_cache_hit_rate`, tapped upload-byte totals); `--no-sharing`
opts out, `--chaos` arms the deterministic fault schedule in both
arms, `--store-budget N` shrinks the spill-store budgets so cached
results take the host->disk spill/restore path mid-round.
`--cancel-rate P` (0..1) arms the CANCELLATION STORM on the measured
window: each repeat execution is perturbed with probability P
(seeded per session) — half get a mid-flight session.cancel(), half
a short per-query deadline — and one extra POISON tenant crash-loops
into the circuit breaker (serving.breaker.failureThreshold).  The
round then emits `cancelled_count` / `deadline_exceeded_count` /
`breaker_trips` / `quarantined_count`, every SURVIVING query's
digest stays bit-identical to serial, and the post-phase residency
gauges (semaphore permits, stage threads, in-flight scan shares,
admission queue) are asserted back at baseline — a cancelled query
is an outcome, not a leak (docs/robustness.md).

Every --sessions measured window additionally runs under the runtime
lock-order tracker (robustness/lock_tracker.py, docs/concurrency.md):
the phase asserts ZERO lock-order cycles across the storm's
interleavings and emits `lock_acquisitions` /
`lock_contention_waits` / `max_lock_hold_ms` — observed registry-mutex
contention, the HC014 health surface measured rather than inferred.

Every --sessions measured window also runs SCRAPED: the live ops
plane (spark_rapids_tpu/obs/, docs/ops_plane.md) is forced on and a
scraper thread hammers /metrics concurrently with the repeat pass.
The phase asserts every monotone eventlog counter only ever moves
forward across successive scrapes, and — because the serial reference
digests were computed with the plane off — the existing digest gate
doubles as the zero-impact proof: obs on vs off is bit-identical.
The round emits `obs_scrapes` / `obs_scrape_monotone`.

`bench.py --cold-start N` switches to the COLD-START bench
(docs/warm_start.md): after two unmeasured populate/prime children
fill one persist directory, N fresh subprocesses run the fusion-smoke
query against the WARM directory and N against EMPTY ones, emitting
`warm_cold_p50_ms` / `warm_cold_p99_ms` / `warm_cold_jit_misses` /
`warm_persist_hit_rate` and the `empty_*` mirror, a bit-identical
digest gate across every child, and `cold_p50_speedup` — the wall a
process restart re-pays with and without the warm-start cache.
"""

import json
import os
import statistics
import sys
import tempfile
import time

ROWS_PER_FILE = 1 << 20
N_FILES = 6  # ~6.3M rows ~ TPC-H SF1 lineitem
ROW_BYTES = 8 * 3 + 4  # three float64 columns + one int32 date
TPU_ITERS = 5
CPU_ITERS = 3


def _roofline(rows_per_s: float):
    """Coarse roofline fraction of a rows/s figure.  The formula AND
    the HBM-bandwidth table (DEVICE_PEAKS, keyed by device_kind) live
    in trace/ledger.py — one definition shared by this coarse
    quotient, the warm-pass variant and the ledger's per-program
    attribution, so the three can never drift.  None on a device the
    table does not hold."""
    from spark_rapids_tpu.trace.ledger import roofline_fraction

    frac = roofline_fraction(rows_per_s * ROW_BYTES)
    return round(frac, 4) if frac is not None else None

#: --chaos schedule, re-armed (fresh counters, so the nth-call policies
#: re-fire) at every per-query counter reset: one device-alloc OOM
#: early, one upload fault, one compile fault, one stage fault, and a
#: two-deep batch fault that drives the ladder past the spill rung into
#: an actual bisection.
CHAOS_SPEC = ("alloc.device:nth=2;transfer.upload:nth=2;"
              "jit.compile:nth=1;pipeline.stage:nth=2;"
              "exec.batch:nth=3,times=2")
_CHAOS = False


def make_lineitem(dirpath: str, n_files: int = N_FILES,
                  with_q1_cols: bool = False,
                  with_orderkey: bool = False,
                  n_orders: int = 1 << 20):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(42)
    paths = []
    for i in range(n_files):
        cols = {
            "l_quantity": rng.integers(1, 51, ROWS_PER_FILE).astype(
                np.float64),
            # TPC-H spec: l_extendedprice is a 2-decimal money value
            "l_extendedprice": np.round(
                rng.uniform(900, 105000, ROWS_PER_FILE), 2),
            "l_discount": rng.integers(0, 11, ROWS_PER_FILE) / 100.0,
            "l_shipdate": rng.integers(8766, 10957, ROWS_PER_FILE).astype(
                np.int32),
        }
        if with_q1_cols:
            cols["l_tax"] = rng.integers(0, 9, ROWS_PER_FILE) / 100.0
            cols["l_returnflag"] = np.array(["A", "N", "R"])[
                rng.integers(0, 3, ROWS_PER_FILE)]
            cols["l_linestatus"] = np.array(["F", "O"])[
                rng.integers(0, 2, ROWS_PER_FILE)]
        if with_orderkey:
            cols["l_orderkey"] = rng.integers(
                0, n_orders, ROWS_PER_FILE).astype(np.int64)
        t = pa.table(cols)
        p = os.path.join(dirpath, f"lineitem-{i}.parquet")
        pq.write_table(t, p, row_group_size=ROWS_PER_FILE)
        paths.append(p)
    return paths


def make_orders(dirpath: str, n_orders: int = 1 << 20):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(7)
    t = pa.table({
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_orderdate": rng.integers(8766, 10957, n_orders).astype(
            np.int32),
        "o_shippriority": rng.integers(0, 5, n_orders).astype(np.int32),
    })
    p = os.path.join(dirpath, "orders.parquet")
    pq.write_table(t, p, row_group_size=n_orders)
    return p


def q6_dataframe(session, paths):
    return q6_over(session.read_parquet(*paths))


def q6_over(lineitem):
    """TPC-H q6 over any lineitem frame (a scan, or a cache()d one)."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import col, sum_

    ship, disc, qty = col("l_shipdate"), col("l_discount"), col("l_quantity")
    price = col("l_extendedprice")
    cond = ((ship >= lit(8766)) & (ship < lit(9131))
            & (disc >= lit(0.05)) & (disc <= lit(0.07))
            & (qty < lit(24.0)))
    return (lineitem
            .where(cond)
            .agg((sum_(price * disc), "revenue")))


def q1_dataframe(session, paths):
    return q1_over(session.read_parquet(*paths))


def q1_over(lineitem):
    """TPC-H q1 over any lineitem frame (a scan, or a cache()d one)."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import avg, col, count_star, sum_

    qty, price = col("l_quantity"), col("l_extendedprice")
    disc, tax = col("l_discount"), col("l_tax")
    return (lineitem
            .where(col("l_shipdate") <= lit(10471))
            .group_by(col("l_returnflag"), col("l_linestatus"))
            .agg((sum_(qty), "sum_qty"),
                 (sum_(price), "sum_base_price"),
                 (sum_(price * (lit(1.0) - disc)), "sum_disc_price"),
                 (sum_(price * (lit(1.0) - disc) * (lit(1.0) + tax)),
                  "sum_charge"),
                 (avg(qty), "avg_qty"),
                 (avg(price), "avg_price"),
                 (avg(disc), "avg_disc"),
                 (count_star(), "count_order")))


def q3_dataframe(session, li_paths, orders_path):
    """TPC-H q3 shape on two tables: lineitem JOIN orders on orderkey,
    date filters on both sides, revenue per order, top-10 by revenue
    (exchange + shuffled hash join + high-cardinality group-by +
    sort/limit — BASELINE config #3's moving parts)."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import col, sum_

    li = (session.read_parquet(*li_paths)
          .where(col("l_shipdate") > lit(9500)))
    orders = (session.read_parquet(orders_path)
              .where(col("o_orderdate") < lit(9500)))
    joined = li.join(orders, left_on=[col("l_orderkey")],
                     right_on=[col("o_orderkey")])
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (joined
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg((sum_(rev), "revenue"))
            .order_by(col("revenue"), desc=True)
            .limit(10))


def make_store_sales(dirpath: str, n_rows: int = 1 << 21,
                     n_files: int = 2):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(67)
    per = n_rows // n_files
    paths = []
    for i in range(n_files):
        t = pa.table({
            "ss_store_sk": rng.integers(1, 9, per),
            "ss_item_sk": rng.integers(1, 2000, per),
            "ss_quantity": rng.integers(1, 20, per).astype(np.float64),
            "ss_sales_price": np.round(rng.uniform(1, 300, per), 2),
        })
        p = os.path.join(dirpath, f"ss-{i}.parquet")
        pq.write_table(t, p, row_group_size=per)
        paths.append(p)
    return paths


def q67_dataframe(session, paths):
    """TPC-DS q67 shape: grouped aggregate -> rank window partitioned
    by store -> rank filter -> ordered output (BASELINE config #4's
    sort + window moving parts)."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.exprs.window import Window, rank
    from spark_rapids_tpu.session import col, sum_

    agg = (session.read_parquet(*paths)
           .group_by(col("ss_store_sk"), col("ss_item_sk"))
           .agg((sum_(col("ss_sales_price") * col("ss_quantity")),
                 "sumsales")))
    spec = Window.partition_by("ss_store_sk").order_by(
        "sumsales", desc=True)
    ranked = agg.select(col("ss_store_sk"), col("ss_item_sk"),
                        col("sumsales"),
                        rank().over(spec).alias("rk"))
    return (ranked.where(col("rk") <= lit(10))
            .order_by(col("ss_store_sk"), col("rk"), col("ss_item_sk")))


def _time_collect(df, engine: str, iters: int):
    """([seconds per full collect...], last result)."""
    times = []
    result = None
    for _ in range(iters):
        t0 = time.perf_counter()
        result = df.collect(engine=engine)
        times.append(time.perf_counter() - t0)
    return times, result


def _stats(times, prefix: str) -> dict:
    return {
        f"{prefix}_s_min": round(min(times), 4),
        f"{prefix}_s_median": round(statistics.median(times), 4),
        f"{prefix}_s_max": round(max(times), 4),
    }


def _link_probe() -> dict:
    """Scalar-fetch round trips + one 8MB upload: the weather report.
    Taken AFTER the first result fetch, i.e. in the same degraded client
    mode the timed queries run in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rtts = []
    x = jnp.asarray(1.0)
    for _ in range(5):
        t0 = time.perf_counter()
        float(jax.device_get(x + 1.0))
        rtts.append(time.perf_counter() - t0)
    a = np.random.default_rng(0).random(1 << 20)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(a))
    up = time.perf_counter() - t0
    return {
        "link_rtt_ms_median": round(statistics.median(rtts) * 1e3, 1),
        "link_upload_mb_s": round(8.0 / max(up, 1e-9), 1),
    }


def _stage_breakdown(df, prefix: str) -> dict:
    """One traced collect: where does an iteration of this query go?
    host_decode / wire_upload / final_fetch are the seconds inside the
    engine's own spans (`scan.decode.file`, `wire.encode` +
    `wire.put`, `query.fetch.batch`: docs/observability.md), summed
    over the threads that recorded them; `other` is the residual —
    with the software pipeline on, stages OVERLAP and decode runs on a
    pool, so the residual approximates the non-overlapped
    compute+dispatch and the four fields can sum past the total.  The
    final-fetch figure inlines the wait for any device execution still
    in flight (dispatch is async) — if the residual is dominated by
    fetch at near-zero decode/wire time, the bottleneck is the link,
    not the engine."""
    from spark_rapids_tpu import trace

    was_on = trace.is_enabled()
    trace.enable()
    try:
        t0_ns = time.perf_counter_ns()
        df.collect(engine="tpu")
        total = (time.perf_counter_ns() - t0_ns) / 1e9
        spans = [e for e in trace.snapshot() if e.ts_ns >= t0_ns]
    finally:
        if not was_on:
            trace.disable()

    def inside(*names: str) -> float:
        return sum(e.dur_ns for e in spans if e.name in names) / 1e9

    host_s = inside("scan.decode.file")
    wire_s = inside("wire.encode", "wire.put")
    fetch_s = inside("query.fetch.batch")
    return {
        f"{prefix}_stage_host_decode_s": round(host_s, 4),
        f"{prefix}_stage_wire_upload_s": round(wire_s, 4),
        f"{prefix}_stage_final_fetch_s": round(fetch_s, 4),
        f"{prefix}_stage_other_s": round(
            max(0.0, total - host_s - wire_s - fetch_s), 4),
    }


def _pipeline_occupancy(prefix: str = "pipeline") -> dict:
    """Aggregate the software pipeline's stage counters
    (parallel.pipeline.stage_snapshot) into one occupancy figure:
    item-weighted mean of each stage's queue-occupancy fraction.  ~1.0
    means producers stay ahead of consumers (the pipeline is full);
    ~0.0 means stages run starved/serial.  Per-stage detail rides as a
    sub-object so round-over-round deltas are attributable.

    Counters are RESET between benchmark configs
    (parallel.pipeline.reset_stage_counters), so each
    `{q}_pipeline_occupancy` reflects that query alone instead of
    accumulating across q6/q1/q3/q67."""
    from spark_rapids_tpu.parallel.pipeline import stage_snapshot

    snap = stage_snapshot()
    weighted = 0.0
    items = 0
    for s in snap.values():
        if s["items"]:
            weighted += s["occupancy_fraction"] * s["items"]
            items += s["items"]
    return {
        f"{prefix}_occupancy": round(weighted / items, 3)
        if items else 0.0,
        f"{prefix}_stages": snap,
    }


def reset_all_counters() -> None:
    """THE per-query counter reset: every process-global stat surface
    the q*_ attribution fields read — pipeline stage counters,
    speculation, runtime filters, retry ladder, device ledger, fusion
    chains, upload taps and the fault schedule — zeroed in ONE place
    so a new counter surface cannot be forgotten at one of the call
    sites (the warm-window choreography used to re-list them
    per site)."""
    from spark_rapids_tpu.columnar.transfer import reset_upload_stats
    from spark_rapids_tpu.execs.base import reset_fusion_stats
    from spark_rapids_tpu.execs.retry import reset_retry_stats
    from spark_rapids_tpu.parallel.pipeline import reset_stage_counters
    from spark_rapids_tpu.parallel.speculation import reset_stats
    from spark_rapids_tpu.plan import runtime_filter
    from spark_rapids_tpu.robustness import faults
    from spark_rapids_tpu.trace import ledger

    reset_stage_counters()
    reset_stats()  # per-query speculation hit rates, same discipline
    runtime_filter.reset_stats()  # per-query pruned-row counts too
    reset_retry_stats()  # per-query split/spill-retry attribution
    ledger.reset_stats()  # per-query program/roofline attribution
    reset_fusion_stats()  # per-query fused-chain/savings attribution
    reset_upload_stats()  # per-query H2D byte taps
    if _CHAOS:
        # fresh schedule per query: counters zero, nth policies re-fire
        faults.install(CHAOS_SPEC, forced=True)
    else:
        faults.reset_stats()


def _reset_ledger() -> None:
    """Zero ONLY the device ledger (warm passes call this so their
    attribution covers the warm runs alone, without the side effects
    of the full counter reset — which re-arms the --chaos schedule)."""
    from spark_rapids_tpu.trace import ledger

    ledger.reset_stats()


def _robustness_fields(prefix: str, spilled_before: int = 0) -> dict:
    """Recovery activity in the timed window (reset per query by
    reset_all_counters): ladder bisections, device->host bytes
    spilled under pressure, and recovered injected faults (nonzero
    only under --chaos)."""
    from spark_rapids_tpu.execs.retry import retry_stats
    from spark_rapids_tpu.memory import get_store
    from spark_rapids_tpu.robustness import faults

    st = retry_stats()
    return {
        f"{prefix}_retry_splits": st["splits"],
        f"{prefix}_spills_under_pressure":
            get_store().spilled_device_to_host - spilled_before,
        f"{prefix}_recovered_faults": faults.recovered_total(),
    }


def _spilled_now() -> int:
    from spark_rapids_tpu.memory import get_store

    return get_store().spilled_device_to_host


def _sync_spec_fields(prefix: str, iters: int,
                      with_hit_rate: bool = True) -> dict:
    """Host-sync + speculation attribution for the timed window:

    - `{prefix}_host_sync_count`: BLOCKING device->host readbacks per
      collect (stage-counter `readbacks`, which speculative sizing's
      async harvest does not tick) — the number the speculation layer
      exists to drive to zero; on a ~100ms-RTT link each unit is a
      stalled link round trip on the critical path;
    - `{prefix}_speculation_hit_rate`: fraction of speculative
      dispatches whose predicted capacity covered the true count
      (sized-output queries only — a grand aggregate never sizes)."""
    from spark_rapids_tpu.parallel import speculation
    from spark_rapids_tpu.parallel.pipeline import stage_snapshot

    snap = stage_snapshot()
    syncs = sum(s["readbacks"] for s in snap.values())
    out = {f"{prefix}_host_sync_count": round(syncs / max(iters, 1), 2)}
    if with_hit_rate:
        out[f"{prefix}_speculation_hit_rate"] = speculation.hit_rate()
        st = speculation.stats()
        out[f"{prefix}_speculation_overflows"] = sum(
            s["overflows"] for s in st.values())
        # adaptive kill-switch verdict for the window: tags whose
        # rolling hit rate fell below speculation.adaptive.minHitRate
        # and were auto-disabled (0 with the default threshold off)
        out[f"{prefix}_speculation_disabled"] = len(
            speculation.disabled_tags())
    return out


def _ledger_fields(prefix: str, iters: int) -> dict:
    """Per-query device-ledger attribution for the timed window (the
    ledger is reset per query by reset_all_counters, so the
    cumulative snapshot IS the window):

    - `{prefix}_device_busy_ms`: attributed device time per collect —
      summed dispatch-to-completion wall of every program the window
      dispatched (the DEVICE share of the coarse wall-clock numbers
      above; the gap is host decode/wire/dispatch overhead);
    - `{prefix}_roofline_attributed`: device-time-weighted roofline
      fraction from XLA's cost model (bytes accessed x dispatches /
      device time / HBM peak) — the honest per-program counterpart of
      the coarse `hbm_roofline_fraction`;
    - `{prefix}_dispatches` / `{prefix}_programs`: launch count per
      collect and distinct compiled programs in the window (the
      fusion/bucketing scoreboard of ROADMAP #2);
    - `{prefix}_live_capacity_ratio`: live rows over padded capacity
      across every dispatch in the window — the occupancy scoreboard
      (1.0 = every program ran full; docs/occupancy.md);
    - `{prefix}_top_program` (+ `_share`): where the device time went.
    """
    from spark_rapids_tpu.trace import ledger

    ledger.LEDGER.flush(timeout=10.0)
    s = ledger.summarize(ledger.snapshot())
    t = s["totals"]
    per = max(iters, 1)
    out = {
        f"{prefix}_device_busy_ms": round(t["device_ms"] / per, 2),
        f"{prefix}_dispatches": round(t["dispatches"] / per, 1),
        f"{prefix}_programs": t["programs"],
        f"{prefix}_roofline_attributed": t["roofline"],
    }
    if t.get("live_capacity_ratio") is not None:
        out[f"{prefix}_live_capacity_ratio"] = t["live_capacity_ratio"]
    top = t.get("top") or []
    if top:
        out[f"{prefix}_top_program"] = top[0]["key"]
        out[f"{prefix}_top_program_share"] = top[0]["share"]
    return out


def _fusion_fields(prefix: str, iters: int) -> dict:
    """Whole-stage fusion attribution for the timed window (reset per
    query by reset_all_counters; docs/fusion.md):

    - `{prefix}_fusion_chains`: fused chain programs planned per
      collect (the planner's _plan_fusion count — agrees with
      explain()'s "Fusion:" section by construction);
    - `{prefix}_fused_dispatch_savings`: program launches the fused
      executions did NOT pay per collect vs the unfused engine
      (chain length - 1 per execution, +1 when the wire decode rode
      inside) — the BENCH_r06+ scoreboard for ROADMAP #2's
      dispatch-soup diagnosis."""
    from spark_rapids_tpu.execs.base import fusion_stats

    st = fusion_stats()
    per = max(iters, 1)
    return {
        f"{prefix}_fusion_chains": round(st["chains"] / per, 1),
        f"{prefix}_fused_dispatch_savings": round(
            st["saved_dispatches"] / per, 1),
    }


def _assert_warm_budget(prefix: str, fields: dict) -> None:
    """The dispatch-budget regression GATE (ROADMAP #2): a warm
    (compile-cache-hot) milestone query must pay at most
    spark.rapids.tpu.sql.fusion.warmDispatchBudget program launches
    per collect and compile NOTHING — un-fusing a chain or
    destabilizing a jit key fails the round here instead of drifting
    in the diagnostics."""
    from spark_rapids_tpu.execs.base import warm_dispatch_budget

    budget = warm_dispatch_budget()
    if budget > 0:
        # budget 0 disables BOTH halves of the gate (the conf's
        # documented escape hatch for environments where warm
        # recompiles are expected, e.g. backend bring-up)
        misses = fields.get(f"{prefix}_jit_misses")
        assert misses == 0, (
            f"{prefix}: warm pass re-compiled {misses} program(s) — "
            "jit keys are unstable across identical collects")
        d = fields.get(f"{prefix}_dispatches")
        assert d is not None and d <= budget, (
            f"{prefix}: warm dispatch count {d} exceeds the budget "
            f"{budget} (spark.rapids.tpu.sql.fusion."
            f"warmDispatchBudget)")


def _wire_fields(df, prefix: str) -> dict:
    """Wire-compression attribution: bytes actually crossing the H2D
    link (the tapped batched-upload counter) with the codec subsystem
    as-configured vs forced off — `{prefix}_upload_ratio` is the
    multiplier the codecs buy on the H2D link
    (docs/wire_compression.md)."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.tools.bench_smoke import count_upload_bytes

    key = "spark.rapids.tpu.sql.wireCompression.enabled"
    conf = get_conf()
    old = conf.get(key)
    try:
        # AS-CONFIGURED first (matches the timed windows — under
        # --no-wire-compression this honestly reports ratio 1.0
        # instead of attributing bytes the measured run never shipped)
        on_bytes = count_upload_bytes(df)
        conf.set(key, False)
        off_bytes = count_upload_bytes(df)
    finally:
        conf.set(key, old)
    return {
        f"{prefix}_upload_bytes_wire": on_bytes,
        f"{prefix}_upload_bytes_raw": off_bytes,
        f"{prefix}_upload_ratio": round(off_bytes / max(on_bytes, 1),
                                        3),
    }


def _rf_fields(df, iters: int) -> dict:
    """q3 runtime-filter attribution: pruned rows + build cost over the
    timed window (per collect), plus uploaded-row counts with filters
    on vs off — the wire-shrink the filters buy, measured."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.plan import runtime_filter
    from spark_rapids_tpu.tools.bench_smoke import count_upload_rows

    st = runtime_filter.stats()
    per = max(iters, 1)
    out = {
        "q3_rf_pruned_rows": round(st["pruned_rows"] / per, 1),
        "q3_rf_build_ms": round(st["build_ms"] / per, 2),
        "q3_rf_row_groups_pruned": round(
            st["row_groups_pruned"] / per, 1),
    }
    key = "spark.rapids.tpu.sql.runtimeFilter.enabled"
    conf = get_conf()
    old = conf.get(key)
    try:
        conf.set(key, True)
        out["q3_upload_rows"] = count_upload_rows(df)
        conf.set(key, False)
        out["q3_upload_rows_no_rf"] = count_upload_rows(df)
    finally:
        conf.set(key, old)
    return out


def _bench_warm(df, prefix: str, n_rows: int, iters: int = 3) -> dict:
    """Warm device-resident pass: `df` reads a df.cache()-materialized
    subtree, so timed collects run against batches already in HBM — the
    first measurement of actual DEVICE throughput, with the H2D wire
    out of the loop.  Caller collects once to fill
    the cache before timing.  `{prefix}_jit_misses` (compiles inside
    the warm window — budgeted to 0 by _assert_warm_budget) rides
    along for the dispatch-budget gate."""
    from spark_rapids_tpu.execs.jit_cache import cache_stats

    j0 = cache_stats()
    times, _r = _time_collect(df, "tpu", iters)
    j1 = cache_stats()
    t = statistics.median(times)
    rows_per_s = n_rows / t
    out = {
        f"{prefix}_s_median": round(t, 4),
        f"{prefix}_s_min": round(min(times), 4),
        f"{prefix}_s_max": round(max(times), 4),
        f"{prefix}_rows_per_s": round(rows_per_s, 1),
        f"{prefix}_jit_misses": j1["misses"] - j0["misses"],
    }
    return out


def _check_rows(tpu_tbl, cpu_tbl, float_from: int, key_cols: int):
    got = sorted(zip(*tpu_tbl.to_pydict().values()))
    want = sorted(zip(*cpu_tbl.to_pydict().values()))
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert g[:key_cols] == w[:key_cols], (g[:key_cols], w[:key_cols])
        for gv, wv in zip(g[float_from:], w[float_from:]):
            assert abs(gv - wv) <= 1e-6 * max(1.0, abs(wv)), (gv, wv)


def _bench_q1(session, d: str) -> dict:
    """BASELINE config #2's SHAPE (grouped 8-aggregate q1) at a scale
    the bench host generates in seconds; full SF100 needs a real
    cluster-sized host.  Exchange width 1: on a single chip the
    8-way hash exchange is pure dispatch overhead."""
    from spark_rapids_tpu.config import get_conf

    conf = get_conf()
    key = "spark.rapids.tpu.sql.shuffle.partitions"
    old_sp = conf.get(key)
    conf.set(key, 1)
    try:
        q1_files = make_lineitem(os.path.join(d, "q1"), n_files=2,
                                 with_q1_cols=True)
        df = q1_dataframe(session, q1_files)
        df.collect(engine="tpu")  # warmup
        reset_all_counters()  # per-query occupancy
        sp0 = _spilled_now()
        tpu_ts, tpu_r = _time_collect(df, "tpu", 3)
        # occupancy + sync/speculation counters read BEFORE the tapped
        # breakdown collect, so they reflect only the timed runs
        occ = _pipeline_occupancy("q1_pipeline")
        occ.update(_sync_spec_fields("q1", 3))
        occ.update(_robustness_fields("q1", sp0))
        occ.update(_ledger_fields("q1", 3))
        occ.update(_fusion_fields("q1", 3))
        occ.update(_wire_fields(df, "q1"))
        cpu_ts, cpu_r = _time_collect(df, "cpu", 2)
        breakdown = _stage_breakdown(df, "q1")
        breakdown.update(occ)
        # warm device-resident pass: cache the scan output, re-run the
        # aggregate against HBM-resident batches (no H2D in the loop)
        from spark_rapids_tpu.session import avg, col, count_star, sum_
        from spark_rapids_tpu.exprs.base import lit

        cached = session.read_parquet(*q1_files).cache()
        qty, price = col("l_quantity"), col("l_extendedprice")
        disc, tax = col("l_discount"), col("l_tax")
        warm_df = (cached.where(col("l_shipdate") <= lit(10471))
                   .group_by(col("l_returnflag"), col("l_linestatus"))
                   .agg((sum_(qty), "sum_qty"),
                        (sum_(price), "sum_base_price"),
                        (avg(disc), "avg_disc"),
                        (count_star(), "count_order")))
        try:
            warm_df.collect(engine="tpu")  # fills the cache slot
            # ledger-ONLY reset: the full counter reset would re-arm
            # the --chaos fault schedule inside the warm timed loop,
            # perturbing the steady-state numbers this pass exists for
            _reset_ledger()
            breakdown.update(_bench_warm(warm_df, "q1_warm",
                                         ROWS_PER_FILE * 2))
            # q1's own coarse roofline for the warm window (ISSUE 17
            # acceptance metric; q6's equivalent is the headline
            # hbm_roofline_fraction_warm)
            breakdown["q1_hbm_roofline_fraction_warm"] = _roofline(
                breakdown["q1_warm_rows_per_s"])
            breakdown.update(_ledger_fields("q1_warm", 3))
            # the dispatch-budget regression gate: warm q1 must stay
            # under the conf budget and compile nothing
            _assert_warm_budget("q1_warm", breakdown)
        finally:
            cached.unpersist()
    finally:
        conf.set(key, old_sp)
    _check_rows(tpu_r, cpu_r, float_from=2, key_cols=2)
    tpu_t = statistics.median(tpu_ts)
    cpu_t = statistics.median(cpu_ts)
    out = {
        "q1_tpu_s_per_query": round(tpu_t, 4),
        "q1_cpu_s_per_query": round(cpu_t, 4),
        "q1_vs_cpu": round(cpu_t / tpu_t, 3),
        "q1_rows": ROWS_PER_FILE * 2,
    }
    out.update(_stats(tpu_ts, "q1_tpu"))
    out.update(breakdown)
    return out


def _bench_q3(session, d: str) -> dict:
    """BASELINE config #3's shape: two-table shuffled hash join ->
    grouped aggregate -> top-k, correctness-gated against the CPU
    engine."""
    q3dir = os.path.join(d, "q3")
    os.makedirs(q3dir, exist_ok=True)
    li = make_lineitem(q3dir, n_files=2, with_orderkey=True)
    orders = make_orders(q3dir)
    df = q3_dataframe(session, li, orders)
    df.collect(engine="tpu")  # warmup
    reset_all_counters()  # per-query occupancy
    sp0 = _spilled_now()
    tpu_ts, tpu_r = _time_collect(df, "tpu", 3)
    occ = _pipeline_occupancy("q3_pipeline")  # timed runs only
    occ.update(_sync_spec_fields("q3", 3))
    occ.update(_robustness_fields("q3", sp0))
    occ.update(_ledger_fields("q3", 3))
    occ.update(_fusion_fields("q3", 3))
    # runtime-filter attribution for the timed window + the on/off
    # uploaded-row delta (the wire-shrink the filters buy)
    occ.update(_rf_fields(df, 3))
    occ.update(_wire_fields(df, "q3"))
    cpu_ts, cpu_r = _time_collect(df, "cpu", 2)
    # top-k by float revenue: compare the revenue VALUES (ties may order
    # differently) and the grouped rows' exactness via set inclusion
    got = sorted(tpu_r.to_pydict()["revenue"], reverse=True)
    want = sorted(cpu_r.to_pydict()["revenue"], reverse=True)
    assert len(got) == len(want) == 10, (len(got), len(want))
    for gv, wv in zip(got, want):
        assert abs(gv - wv) <= 1e-6 * max(1.0, abs(wv)), (gv, wv)
    tpu_t = statistics.median(tpu_ts)
    cpu_t = statistics.median(cpu_ts)
    out = {
        "q3_tpu_s_per_query": round(tpu_t, 4),
        "q3_cpu_s_per_query": round(cpu_t, 4),
        "q3_vs_cpu": round(cpu_t / tpu_t, 3),
        "q3_rows": ROWS_PER_FILE * 2 + (1 << 20),
    }
    out.update(_stats(tpu_ts, "q3_tpu"))
    out.update(_stage_breakdown(df, "q3"))
    out.update(occ)
    return out


def _bench_q67(session, d: str) -> dict:
    """BASELINE config #4's shape: grouped aggregate under a ranking
    window under a rank filter under a global sort, correctness-gated
    against the CPU engine."""
    q67dir = os.path.join(d, "q67")
    os.makedirs(q67dir, exist_ok=True)
    paths = make_store_sales(q67dir)
    df = q67_dataframe(session, paths)
    df.collect(engine="tpu")  # warmup
    reset_all_counters()  # per-query occupancy
    sp0 = _spilled_now()
    tpu_ts, tpu_r = _time_collect(df, "tpu", 3)
    occ = _pipeline_occupancy("q67_pipeline")  # timed runs only
    occ.update(_sync_spec_fields("q67", 3))
    occ.update(_robustness_fields("q67", sp0))
    occ.update(_ledger_fields("q67", 3))
    occ.update(_fusion_fields("q67", 3))
    cpu_ts, cpu_r = _time_collect(df, "cpu", 2)
    got = list(zip(*tpu_r.to_pydict().values()))
    want = list(zip(*cpu_r.to_pydict().values()))
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[3] == w[3], (g, w)  # store, rank
        assert abs(g[2] - w[2]) <= 1e-6 * max(1.0, abs(w[2])), (g, w)
    tpu_t = statistics.median(tpu_ts)
    cpu_t = statistics.median(cpu_ts)
    out = {
        "q67_tpu_s_per_query": round(tpu_t, 4),
        "q67_cpu_s_per_query": round(cpu_t, 4),
        "q67_vs_cpu": round(cpu_t / tpu_t, 3),
        "q67_rows": 1 << 21,
    }
    out.update(_stats(tpu_ts, "q67_tpu"))
    out.update(occ)
    return out


def _serving_queries(session, li_paths, orders_path):
    """The serving bench's golden templates.  Every one is
    DETERMINISTIC to the bit: aggregates are exact (sums of
    integer-valued doubles far below 2^53, counts, min/max) and output
    order is pinned by ORDER BY — so the concurrent-vs-serial digest
    gate can demand bit-for-bit equality, which thread-timing-dependent
    float aggregation order could not honor."""
    from spark_rapids_tpu.exprs.base import lit
    from spark_rapids_tpu.session import (
        col,
        count_star,
        max_,
        min_,
        sum_,
    )

    qty = col("l_quantity")
    qa = (session.read_parquet(*li_paths)
          .where(col("l_shipdate") <= lit(10471))
          .group_by(col("l_returnflag"), col("l_linestatus"))
          .agg((sum_(qty), "sum_qty"), (count_star(), "n"),
               (min_(col("l_shipdate")), "d0"),
               (max_(col("l_shipdate")), "d1"))
          .order_by(col("l_returnflag"), col("l_linestatus")))
    li = (session.read_parquet(*li_paths)
          .where(col("l_shipdate") > lit(9500)))
    orders = (session.read_parquet(orders_path)
              .where(col("o_orderdate") < lit(9500)))
    qb = (li.join(orders, left_on=[col("l_orderkey")],
                  right_on=[col("o_orderkey")])
          .group_by(col("o_shippriority"))
          .agg((sum_(qty), "sum_qty"), (count_star(), "n"))
          .order_by(col("o_shippriority")))
    qc = (session.read_parquet(*li_paths)
          .agg((count_star(), "n"),
               (min_(col("l_shipdate")), "d0"),
               (max_(col("l_shipdate")), "d1")))
    return [("qa", qa), ("qb", qb), ("qc", qc)]


def _serving_phase(n_sessions: int, n_tenants: int, li, orders,
                   digests: dict, conf_factory, sharing: bool,
                   cancel_rate: float = 0.0) -> dict:
    """One full concurrent serving pass (warm + measured repeat) with
    cross-tenant sharing on or off: the A/B unit of the serving bench.
    Resets the scheduler/plan-cache/work-share/upload counters at
    phase start, runs every session's warm pass, arms the measured
    window at the barrier, and returns the phase's latency set plus
    every counter surface (docs/work_sharing.md).

    ``cancel_rate`` > 0 arms the cancellation storm on the measured
    window: each repeat execution is perturbed with probability P
    (seeded per session; half mid-flight session.cancel(), half a
    short per-query deadline) and one extra POISON tenant crash-loops
    into its circuit breaker concurrently — surviving digests stay
    gated, and the post-phase residency gauges are asserted back at
    baseline (docs/robustness.md)."""
    import random as _random
    import threading

    from spark_rapids_tpu import trace as _trace
    from spark_rapids_tpu.columnar.transfer import (
        reset_upload_stats,
        upload_stats,
    )
    from spark_rapids_tpu.config import set_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.execs.jit_cache import cache_stats
    from spark_rapids_tpu.robustness import faults
    from spark_rapids_tpu.robustness import lock_tracker as _locks
    from spark_rapids_tpu.serving import cancel as _cancel
    from spark_rapids_tpu.serving import plan_cache as _plan_cache
    from spark_rapids_tpu.serving import scheduler as _scheduler
    from spark_rapids_tpu.serving import work_share as _ws
    from spark_rapids_tpu.session import TpuSession

    repeat_iters = 3
    _scheduler.reset()
    _plan_cache.reset_stats()
    _ws.reset()
    _cancel.reset()
    reset_upload_stats()
    if _CHAOS:
        # fresh deterministic schedule per phase so the nth-call
        # policies fire in BOTH the sharing-off and sharing-on arms
        faults.install(CHAOS_SPEC, forced=True)
    lat_lock = threading.Lock()
    latencies: list = []
    mismatches: list = []
    prepared: list = []  # (session, {name: PreparedQuery})
    # the main thread is a barrier party: it arms the measured
    # window's instrumentation strictly AFTER every warm pass and
    # strictly BEFORE any repeat execution
    warm_done = threading.Barrier(n_sessions + 1)
    go_repeat = threading.Event()
    DEADLINE_KEY = "spark.rapids.tpu.serving.deadlineMs"

    def run_session(i: int) -> None:
        pqs = {}
        conf = None
        session = None
        try:
            conf = conf_factory(sharing=sharing)
            set_conf(conf)
            session = TpuSession(conf, tenant=f"t{i % n_tenants}")
            for name, df in _serving_queries(session, li, orders):
                pqs[name] = session.prepare(df)
            with lat_lock:
                prepared.append((session, pqs))
            # warm pass: every template once (prepare already
            # lowered; this compiles + validates), digest-gated
            for name, pq in pqs.items():
                r = pq.execute()
                if table_digest(r) != digests[name]:
                    with lat_lock:
                        mismatches.append((i, name, "warm"))
        except BaseException as e:  # noqa: BLE001 — reported below
            with lat_lock:
                mismatches.append((i, "session-error", repr(e)))
            pqs = {}
        finally:
            # ALWAYS reach the barrier: a dead party would leave
            # the main thread blocked in warm_done.wait() forever
            # instead of failing with the recorded error
            warm_done.wait()
        if not pqs:
            return
        go_repeat.wait()
        # measured REPEAT pass: pure cache hits, timed.  Under the
        # storm, a seeded per-session RNG perturbs executions; the
        # digest gate applies to every execution that SURVIVES.  The
        # deadline value is FIXED per session (and restored to the
        # constructed conf's explicit 0.0): the serving deadline is
        # conf-fingerprint-keyed like every conf, so each session pays
        # at most ONE plan-cache re-key per template (its single
        # deadline fingerprint) for the whole window — bounded below
        # by the scoped purity assert
        rng = _random.Random(9000 + i)
        dl_ms = round(rng.uniform(2.0, 20.0), 2)
        try:
            for _ in range(repeat_iters):
                for name, pq in pqs.items():
                    mode = None
                    if cancel_rate > 0:
                        roll = rng.random()
                        if roll < cancel_rate / 2:
                            mode = "deadline"
                        elif roll < cancel_rate:
                            mode = "cancel"
                    canceller = None
                    if mode == "deadline":
                        conf.set(DEADLINE_KEY, dl_ms)
                    elif mode == "cancel":
                        canceller = threading.Timer(
                            rng.uniform(0.0, 0.02), session.cancel)
                        canceller.start()
                    try:
                        t0 = time.perf_counter()
                        r = pq.execute()
                        dt = time.perf_counter() - t0
                        if table_digest(r) != digests[name]:
                            with lat_lock:
                                mismatches.append((i, name, "repeat"))
                        if mode is None:
                            # only unperturbed executions are latency
                            # samples — a shed query's 2ms would skew
                            # p50 optimistically
                            with lat_lock:
                                latencies.append(dt)
                    except _cancel.QueryCancelled:
                        pass  # counted process-wide by cancel.stats()
                    finally:
                        if mode == "deadline":
                            conf.set(DEADLINE_KEY, 0.0)
                        if canceller is not None:
                            # fired or defused, then joined: a late
                            # cancel must not bleed into the next
                            # execution's token
                            canceller.cancel()
                            canceller.join()
        except BaseException as e:  # noqa: BLE001 — reported below
            with lat_lock:
                mismatches.append((i, "repeat-error", repr(e)))

    poison_report: dict = {}

    def run_poison() -> None:
        """The crash-looping tenant: a prepared scan whose backing
        file is deleted, executed repeatedly under a 3-failure
        breaker — quarantine must engage within failureThreshold
        queries while the real tenants keep serving."""
        from spark_rapids_tpu.serving.cancel import TenantQuarantined

        conf = conf_factory(sharing=False)
        conf.set("spark.rapids.tpu.serving.breaker.failureThreshold",
                 3)
        conf.set("spark.rapids.tpu.serving.breaker.cooldownMs",
                 60_000.0)
        set_conf(conf)
        session = TpuSession(conf, tenant="poison")
        pdir = tempfile.mkdtemp(prefix="poison_")
        ppath = os.path.join(pdir, "p.parquet")
        import pyarrow as pa
        import pyarrow.parquet as pq_

        pq_.write_table(pa.table({"x": [1, 2, 3]}), ppath)
        df = session.read_parquet(ppath)
        os.remove(ppath)  # every execution now dies in the scan
        failures = quarantined = 0
        for _ in range(10):
            try:
                df.collect(engine="tpu")
            except TenantQuarantined:
                quarantined += 1
            except Exception:  # noqa: BLE001 — the poison crash
                failures += 1
        poison_report.update(
            {"failures": failures, "quarantined": quarantined})

    threads = [threading.Thread(target=run_session, args=(i,),
                                name=f"serve-bench-{i}")
               for i in range(n_sessions)]
    for t in threads:
        t.start()
    warm_done.wait()
    # measured-window instrumentation, armed while every session
    # sits at go_repeat: plan-cache stats reset (repeats must show
    # hit rate 1.0), jit snapshot (zero misses on hits), tracer on
    # (zero query.plan/tag/lower spans on hits), work-share window
    # snapshot (repeats with sharing on must be pure result-cache
    # hits)
    _plan_cache.reset_stats()
    _scheduler.reset()  # fresh wait ring for the measured window
    # runtime lock-order tracker over the measured window: the N-way
    # repeat pass (and the cancellation storm's unwinds) is the most
    # contended interleaving the engine sees — a cycle here is a
    # deadlock a production fleet would eventually hit
    _locks.install(forced=True)
    jit0 = cache_stats()
    ws0 = _ws.stats()
    cancel0 = _cancel.stats()
    poison_thread = None
    if cancel_rate > 0:
        poison_thread = threading.Thread(target=run_poison,
                                         name="serve-bench-poison")
    # scrape-under-storm (docs/ops_plane.md): the ops plane is forced
    # on and a scraper hammers /metrics CONCURRENTLY with the measured
    # window.  Every monotone eventlog counter must never step
    # backwards across successive scrapes, and the digest gate below
    # doubles as the zero-impact proof — the serial reference digests
    # were computed with the plane off, so obs on vs off stays
    # bit-identical by the same assert
    from spark_rapids_tpu import obs as _obs
    from spark_rapids_tpu.eventlog import MONOTONIC_COUNTERS
    from spark_rapids_tpu.obs import metrics as _om

    obs_owned = not _obs.is_enabled()
    if obs_owned:
        _obs.start(port=0)  # forced: sessions' sync_conf can't stop it
    scrape_stop = threading.Event()
    scrape_report = {"scrapes": 0, "violations": [], "errors": 0}

    def run_scraper() -> None:
        import urllib.request

        base = f"http://127.0.0.1:{_obs.plane().port}"
        mono = tuple(MONOTONIC_COUNTERS)
        prev: dict = {}
        while True:
            try:
                body = urllib.request.urlopen(
                    base + "/metrics", timeout=5).read().decode()
                parsed = _om.parse_openmetrics(body)
                for key in mono:
                    v = _om.scrape_value(
                        parsed, _om.counter_metric_name(key))
                    if v is None:
                        continue
                    if key in prev and v < prev[key]:
                        scrape_report["violations"].append(
                            (key, prev[key], v))
                    prev[key] = v
                scrape_report["scrapes"] += 1
            except Exception:  # noqa: BLE001 — scrape, don't perturb
                scrape_report["errors"] += 1
            if scrape_stop.wait(0.02):
                return

    scraper = threading.Thread(target=run_scraper,
                               name="serve-bench-scraper")
    _trace.clear()
    _trace.enable()
    wall0 = time.perf_counter()
    go_repeat.set()
    scraper.start()
    if poison_thread is not None:
        poison_thread.start()
    for t in threads:
        t.join()
    if poison_thread is not None:
        poison_thread.join()
    wall = time.perf_counter() - wall0
    scrape_stop.set()
    scraper.join()
    if obs_owned:
        _obs.stop()
    assert scrape_report["scrapes"] >= 1, \
        "the storm scraper never completed a scrape"
    assert not scrape_report["violations"], (
        "monotone counter stepped backwards under concurrent "
        f"scraping: {scrape_report['violations']}")
    _trace.disable()
    spans = _trace.snapshot()
    _trace.clear()
    lock_agg = _locks.aggregate_stats()
    lock_graph = _locks.order_graph()
    _locks.disarm()
    assert lock_agg["cycles"] == 0, (
        f"lock-order cycle under the serving storm: {lock_graph}")
    jit1 = cache_stats()
    pc = _plan_cache.stats()
    sched = _scheduler.scheduler_stats()
    ws1 = _ws.stats()
    up = upload_stats()

    # -- streaming gate: stream == collect, to the bit ---------- #
    stream_ok = False
    if prepared and not mismatches:
        import pyarrow as pa

        _s_last, pqs_last = prepared[-1]
        batches = list(pqs_last["qa"].execute_stream())
        stream_tbl = pa.Table.from_batches(batches)
        stream_ok = table_digest(stream_tbl) == digests["qa"]

    # event logs hold every query before the dir is reported
    for session, _p in prepared:
        if session.event_log_path is not None:
            _ = session.history.events

    assert not mismatches, (
        f"serving results diverged from serial digests "
        f"(sharing={sharing}): {mismatches}")
    assert stream_ok, "streamed result digest != collect digest"
    plan_spans = sum(1 for e in spans
                     if e.name in ("query.plan", "query.tag",
                                   "query.lower"))
    n_execs = len(latencies)
    latencies.sort()

    def q(p: float) -> float:
        return latencies[min(n_execs - 1,
                             int(round(p * (n_execs - 1))))]

    cancel1 = _cancel.stats()
    storm = {k: cancel1[k] - cancel0[k] for k in cancel1}
    if cancel_rate > 0:
        # the storm must actually have shed something, quarantine must
        # have engaged within the failure threshold, and the unwinds
        # must leave NO residency behind: permits free, no live stage
        # threads, no in-flight scan shares, empty admission queue —
        # a cancelled query is an outcome, not a leak
        assert storm["cancelled"] + storm["deadline_exceeded"] >= 1, \
            storm
        assert poison_report.get("quarantined", 0) >= 1, poison_report
        assert poison_report.get("failures", 99) <= 3, poison_report
        from spark_rapids_tpu.trace.telemetry import sample_now

        gauges = sample_now()
        for g in ("semaphore.in_use", "pipeline.stage_threads",
                  "scan.inflight", "admission.running",
                  "admission.waiting"):
            assert gauges[g] == 0, (g, gauges)
    window = ws1["result_hits"] - ws0["result_hits"] \
        + ws1["result_misses"] - ws0["result_misses"]
    hits = ws1["result_hits"] - ws0["result_hits"]
    return {
        "qps": round(n_execs / wall, 2),
        "p50_ms": round(q(0.50) * 1e3, 1),
        "p99_ms": round(q(0.99) * 1e3, 1),
        "n_execs": n_execs,
        "sched": sched,
        "pc": pc,
        # the storm's plan-cache purity bound: each session's fixed
        # deadline fingerprint re-keys each of its prepared templates
        # at most once (set(0.0) restores the constructed conf's
        # explicit base fingerprint)
        "pc_miss_bound": sum(len(p) for _s, p in prepared),
        "plan_spans": plan_spans,
        "jit_misses": jit1["misses"] - jit0["misses"],
        # per-PHASE device-work evidence (warm + repeat): decoded
        # rows/units and tapped H2D wire bytes — the sub-linearity
        # story is these staying ~flat in sessions with sharing on
        "scan_rows_decoded": ws1["scan_rows_decoded"],
        "scan_units_decoded": ws1["scan_units_decoded"],
        "scan_units_shared": ws1["scan_units_shared"],
        "scan_subscribes": ws1["scan_subscribes"],
        "upload_bytes": up["wire_bytes"],
        # measured-WINDOW result-cache verdict: hit rate over the
        # repeat pass alone
        "result_cache_window_hits": hits,
        "result_cache_hit_rate":
            round(hits / window, 3) if window else 0.0,
        "result_inserts": ws1["result_inserts"],
        # cancellation-storm outcome counters (zero without
        # --cancel-rate): the serving tier's blast-radius story
        "cancelled_count": storm["cancelled"],
        "deadline_exceeded_count": storm["deadline_exceeded"],
        "breaker_trips": storm["breaker_trips"],
        "quarantined_count": storm["quarantined"],
        # measured-window lock health (runtime tracker, armed for the
        # repeat pass): real contention on the engine's registry
        # mutexes and the longest single hold — the HC014 surface,
        # observed under the storm instead of inferred
        "lock_acquisitions": lock_agg["acquisitions"],
        "lock_contention_waits": lock_agg["contention_waits"],
        "max_lock_hold_ms": lock_agg["max_hold_ms"],
        "admission_shed": sched.get("shed", 0),
        "poison": poison_report or None,
        # scrape-under-storm outcome: /metrics scrapes completed
        # concurrently with this measured window (monotonicity and
        # the digest gates asserted above)
        "obs_scrapes": scrape_report["scrapes"],
        "obs_scrape_errors": scrape_report["errors"],
    }


def _bench_serving(n_sessions: int, n_tenants: int) -> dict:
    """The multi-session serving bench (bench.py --sessions N
    [--tenants K]): N concurrent sessions across K tenants drive the
    deterministic golden templates through the serving tier — admission
    control + prepared-plan cache + cross-tenant work sharing +
    per-session event logs — and the output makes 'heavy traffic' a
    measured claim:

    - serving_qps, serving_p50_ms / serving_p99_ms over the measured
      window (all sessions, all templates);
    - admission_wait_p99_ms from the scheduler's wait ring;
    - plan_cache_hit_rate over the REPEAT-template pass, asserted 1.0,
      with serving_repeat_plan_spans (query.plan/tag/lower spans seen
      during that pass — asserted 0: hits skip lowering entirely) and
      serving_repeat_jit_misses (asserted 0: cached trees re-use their
      compiled programs);
    - the sharing A/B (docs/work_sharing.md): the whole concurrent
      pass runs TWICE, sharing off then on (skip the on-arm with
      --no-sharing), emitting serving_qps_sharing_{on,off},
      shared_scan_dedup_ratio (decoded rows off/on, tapped counter),
      result_cache_hit_rate (repeat window, asserted 1.0 with sharing
      on) and the upload-byte totals proving device work scales
      sub-linearly in sessions;
    - a bit-for-bit digest gate: every concurrent result in BOTH arms
      must hash identical to the serial sharing-off run's, and one
      streamed fetch must hash identical to its collect — under
      --chaos too (the deterministic fault schedule re-arms per arm).
    """
    from spark_rapids_tpu.config import TpuConf, set_conf
    from spark_rapids_tpu.eventlog import table_digest
    from spark_rapids_tpu.robustness import faults
    from spark_rapids_tpu.serving import work_share as _ws
    from spark_rapids_tpu.session import TpuSession

    sharing_on = "--no-sharing" not in sys.argv[1:]
    max_concurrent = max(1, min(2, n_sessions))
    store_budget = _int_flag("--store-budget")
    cancel_rate = _float_flag("--cancel-rate")
    if not 0.0 <= cancel_rate <= 1.0:
        raise SystemExit("bench.py: --cancel-rate takes 0..1")
    ev_dir = None
    if "--no-eventlog" not in sys.argv[1:]:
        ev_dir = _eventlog_dir()

    def _conf(extra=None, sharing=False) -> TpuConf:
        over = {
            "spark.rapids.tpu.serving.maxConcurrent": max_concurrent,
            "spark.rapids.tpu.serving.queueDepth": 4 * n_sessions + 8,
            # admission slots must not outnumber device permits, or the
            # scheduler clamp makes maxConcurrent a dead knob here
            "spark.rapids.tpu.sql.concurrentTpuTasks":
                max(2, max_concurrent),
            "spark.rapids.tpu.serving.sharing.enabled": sharing,
        }
        if store_budget:
            # --store-budget N: shrink the spill-store budgets so
            # cached shared results are forced through the host->disk
            # spill/restore path during the bench itself
            over["spark.rapids.tpu.memory.hbm.budgetBytes"] = \
                store_budget
            over["spark.rapids.tpu.memory.host.spillStorageSize"] = \
                store_budget
        if ev_dir is not None:
            over["spark.rapids.tpu.eventLog.enabled"] = True
            over["spark.rapids.tpu.eventLog.dir"] = ev_dir
        over.update(extra or {})
        return TpuConf(over)

    if store_budget:
        # the store snapshots budgets at construction: start fresh so
        # the serving sessions' shrunken budgets actually apply
        from spark_rapids_tpu.memory.store import reset_store

        reset_store()

    with tempfile.TemporaryDirectory(prefix="serve_bench_") as d:
        li = make_lineitem(d, n_files=2, with_q1_cols=True,
                           with_orderkey=True)
        orders = make_orders(d)

        # -- serial reference: digests + latency baseline (sharing
        # off, fault-free — THE ground truth both arms must match) -- #
        serial_conf = _conf(
            {"spark.rapids.tpu.serving.maxConcurrent": 0})
        set_conf(serial_conf)
        s0 = TpuSession(serial_conf)
        digests = {}
        serial_ts = []
        for name, df in _serving_queries(s0, li, orders):
            df.collect(engine="tpu")  # warm compile caches
            t0 = time.perf_counter()
            r = df.collect(engine="tpu")
            serial_ts.append(time.perf_counter() - t0)
            digests[name] = table_digest(r)

        try:
            off = _serving_phase(n_sessions, n_tenants, li, orders,
                                 digests, _conf, sharing=False,
                                 cancel_rate=cancel_rate)
            on = None
            if sharing_on:
                on = _serving_phase(n_sessions, n_tenants, li, orders,
                                    digests, _conf, sharing=True,
                                    cancel_rate=cancel_rate)
        finally:
            if _CHAOS:
                faults.disarm()
            _ws.reset()

    # headline fields come from the DEFAULT posture (sharing on unless
    # --no-sharing): the serving round measures the fleet as shipped
    head = on if on is not None else off
    out = {
        "metric": "serving_bench",
        "value": head["qps"],
        "unit": "qps",
        "serving_sessions": n_sessions,
        "serving_tenants": n_tenants,
        "serving_max_concurrent": max_concurrent,
        "serving_sharing": bool(on is not None),
        "serving_qps": head["qps"],
        "serving_p50_ms": head["p50_ms"],
        "serving_p99_ms": head["p99_ms"],
        "serving_executions": head["n_execs"],
        "serial_p50_ms": round(
            statistics.median(serial_ts) * 1e3, 1),
        "admission_wait_p99_ms": head["sched"]["wait_p99_ms"],
        "admission_total_wait_ms": head["sched"]["total_wait_ms"],
        "admitted": head["sched"]["admitted"],
        "rejected": head["sched"]["rejected"],
        "admission_coalesced": head["sched"]["coalesced"],
        "plan_cache_hit_rate": head["pc"]["hit_rate"],
        "plan_cache_hits": head["pc"]["hits"],
        "plan_cache_misses": head["pc"]["misses"],
        "serving_repeat_plan_spans": head["plan_spans"],
        "serving_repeat_jit_misses": head["jit_misses"],
        "serving_qps_sharing_off": off["qps"],
        "serving_upload_bytes_sharing_off": off["upload_bytes"],
        "serving_scan_rows_decoded_sharing_off":
            off["scan_rows_decoded"],
        "digests_match": True,
        "stream_matches_collect": True,
        # cancellation-storm counters (the headline phase's; zero
        # without --cancel-rate — docs/robustness.md)
        "cancelled_count": head["cancelled_count"],
        "deadline_exceeded_count": head["deadline_exceeded_count"],
        "breaker_trips": head["breaker_trips"],
        "quarantined_count": head["quarantined_count"],
        "admission_shed": head["admission_shed"],
        # lock-tracker surface (tracker armed for every measured
        # window; the phase already asserted zero cycles)
        "lock_acquisitions": head["lock_acquisitions"],
        "lock_contention_waits": head["lock_contention_waits"],
        "max_lock_hold_ms": head["max_lock_hold_ms"],
        # scrape-under-storm (docs/ops_plane.md): concurrent /metrics
        # scrapes over the measured window, monotone counters and the
        # obs-on digests bit-identical to the obs-off serial reference
        # — both asserted inside the phase
        "obs_scrapes": head["obs_scrapes"],
        "obs_scrape_monotone": True,
    }
    if cancel_rate > 0:
        out["cancel_rate"] = cancel_rate
        out["poison"] = head["poison"]
        if on is not None:
            # the off arm's storm outcome too: its ~N×-slower
            # executions absorb mid-flight cancels the on arm's
            # near-instant result-cache hits outrun (a completed
            # query always wins the cooperative race)
            for k in ("cancelled_count", "deadline_exceeded_count",
                      "breaker_trips", "quarantined_count"):
                out[f"{k}_sharing_off"] = off[k]
    if _CHAOS:
        out["chaos"] = CHAOS_SPEC
    if store_budget:
        out["store_budget_bytes"] = store_budget
    if on is not None:
        out.update({
            "serving_qps_sharing_on": on["qps"],
            "serving_upload_bytes_sharing_on": on["upload_bytes"],
            "serving_scan_rows_decoded_sharing_on":
                on["scan_rows_decoded"],
            "shared_scan_dedup_ratio": round(
                off["scan_rows_decoded"]
                / max(1, on["scan_rows_decoded"]), 2),
            "result_cache_hit_rate": on["result_cache_hit_rate"],
            "result_cache_window_hits":
                on["result_cache_window_hits"],
            "scan_units_shared": on["scan_units_shared"],
            "scan_subscribes": on["scan_subscribes"],
        })
    if ev_dir is not None:
        out["eventlog"] = ev_dir
    # the acceptance contract, enforced where it is measured: repeats
    # are pure hits that lowered nothing and compiled nothing — and
    # with sharing on, pure RESULT-cache hits that out-run and
    # out-dedup the sharing-off arm.  Under the storm the deadline
    # conf re-keys the plan cache (conf-fingerprint keying, by
    # design): each session pays at most ONE miss PER TEMPLATE — its
    # single fixed deadline fingerprint — so the purity gate becomes
    # that bound; programs are structural, so zero jit misses holds
    # regardless
    for phase in (off,) if on is None else (off, on):
        if cancel_rate > 0:
            assert phase["pc"]["misses"] <= phase["pc_miss_bound"], \
                (phase["pc"], phase["pc_miss_bound"])
        else:
            assert phase["pc"]["hit_rate"] == 1.0, phase["pc"]
            assert phase["plan_spans"] == 0, phase["plan_spans"]
        assert phase["jit_misses"] == 0, phase
    if on is not None:
        if cancel_rate == 0:
            assert on["result_cache_hit_rate"] == 1.0, on
            assert off["scan_rows_decoded"] >= \
                2 * max(1, on["scan_rows_decoded"]), (off, on)
            assert on["qps"] > off["qps"], (on["qps"], off["qps"])
        else:
            # under the storm both arms shed a seeded fraction of
            # their executions, deadline-fingerprint executions
            # bypass the result cache, and a shed query never offers
            # its result back — so the exact purity/2x/qps gates are
            # no longer stable claims.  Sharing must still ENGAGE:
            # hits present, strictly less device work than the off
            # arm (decoded rows AND upload bytes)
            assert on["result_cache_window_hits"] >= 1, on
            assert off["scan_rows_decoded"] > \
                on["scan_rows_decoded"], (off, on)
        assert off["upload_bytes"] > on["upload_bytes"], (off, on)
    return out


def _bench_scaled(scale_rows: int) -> dict:
    """The scaling-curve round (ROADMAP #1: bench scale was ~SF1
    against milestones specced SF10+): `bench.py --scale-rows N` runs
    q6 at N rows (~63M = SF10 lineitem) and q1 at max(N // 3, 20M)
    rows, each with the full per-stage attribution — stage breakdown,
    blocking syncs, spills under pressure, device-ledger programs and
    the wire-compression on/off byte delta — so BENCH_r06+ can prove
    the codec + OOC machinery under real pressure instead of unit
    tests.  Correctness stays gated against the CPU engine (one
    reference iteration; a fast wrong answer at scale is still not a
    benchmark)."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.session import TpuSession

    n_files6 = max(1, -(-scale_rows // ROWS_PER_FILE))
    q1_rows = max(scale_rows // 3, 20 * 10**6)
    n_files1 = max(1, -(-q1_rows // ROWS_PER_FILE))
    out: dict = {
        "metric": "scaling_curve",
        "value": scale_rows,
        "unit": "rows",
        "scale_rows": scale_rows,
        "q6_scaled_rows": n_files6 * ROWS_PER_FILE,
        "q1_scaled_rows": n_files1 * ROWS_PER_FILE,
    }
    conf = get_conf()
    conf.set("spark.rapids.tpu.trace.ledger.enabled", True)
    session = TpuSession()
    with tempfile.TemporaryDirectory(prefix="qscale_") as d:
        paths = make_lineitem(d, n_files=n_files6)
        df = q6_dataframe(session, paths)
        df.collect(engine="tpu")  # warmup
        link = _link_probe()
        reset_all_counters()
        sp0 = _spilled_now()
        tpu_ts, tpu_r = _time_collect(df, "tpu", 3)
        occ = _pipeline_occupancy("q6_scaled_pipeline")
        occ.update(_sync_spec_fields("q6_scaled", 3,
                                     with_hit_rate=False))
        occ.update(_robustness_fields("q6_scaled", sp0))
        occ.update(_ledger_fields("q6_scaled", 3))
        occ.update(_fusion_fields("q6_scaled", 3))
        occ.update(_wire_fields(df, "q6_scaled"))
        occ.update(_stage_breakdown(df, "q6_scaled"))
        cpu_ts, cpu_r = _time_collect(df, "cpu", 1)
        got = tpu_r.to_pydict()["revenue"][0]
        want = cpu_r.to_pydict()["revenue"][0]
        assert abs(got - want) <= 1e-6 * max(1.0, abs(want)), (got, want)
        tpu_t = statistics.median(tpu_ts)
        out.update(_stats(tpu_ts, "q6_scaled_tpu"))
        out.update({
            "q6_scaled_tpu_s_per_query": round(tpu_t, 4),
            "q6_scaled_cpu_s_per_query": round(cpu_ts[0], 4),
            "q6_scaled_vs_cpu": round(cpu_ts[0] / tpu_t, 3),
            "q6_scaled_rows_per_s": round(
                n_files6 * ROWS_PER_FILE / tpu_t, 1),
        })
        out.update(occ)
        out.update(link)

        # q1 at >= 20M rows: the grouped 8-aggregate under the same
        # exchange-width-1 discipline as the plain round
        key = "spark.rapids.tpu.sql.shuffle.partitions"
        old_sp = conf.get(key)
        conf.set(key, 1)
        try:
            os.makedirs(os.path.join(d, "q1"), exist_ok=True)
            q1_files = make_lineitem(os.path.join(d, "q1"),
                                     n_files=n_files1,
                                     with_q1_cols=True)
            df1 = q1_dataframe(session, q1_files)
            df1.collect(engine="tpu")  # warmup
            reset_all_counters()
            sp0 = _spilled_now()
            tpu_ts, tpu_r = _time_collect(df1, "tpu", 3)
            occ = _pipeline_occupancy("q1_scaled_pipeline")
            occ.update(_sync_spec_fields("q1_scaled", 3))
            occ.update(_robustness_fields("q1_scaled", sp0))
            occ.update(_ledger_fields("q1_scaled", 3))
            occ.update(_fusion_fields("q1_scaled", 3))
            occ.update(_wire_fields(df1, "q1_scaled"))
            occ.update(_stage_breakdown(df1, "q1_scaled"))
            cpu_ts, cpu_r = _time_collect(df1, "cpu", 1)
            _check_rows(tpu_r, cpu_r, float_from=2, key_cols=2)
            tpu_t = statistics.median(tpu_ts)
            out.update(_stats(tpu_ts, "q1_scaled_tpu"))
            out.update({
                "q1_scaled_tpu_s_per_query": round(tpu_t, 4),
                "q1_scaled_cpu_s_per_query": round(cpu_ts[0], 4),
                "q1_scaled_vs_cpu": round(cpu_ts[0] / tpu_t, 3),
            })
            out.update(occ)
        finally:
            conf.set(key, old_sp)
    return out


def _eventlog_dir() -> str:
    """Where this round's event log lands: --eventlog DIR, else
    $BENCH_EVENTLOG_DIR, else ./bench_eventlog.  On by default so
    every BENCH round is self-documenting — the per-query records
    (plan, settled operator metrics, counter deltas) reload via
    `python -m spark_rapids_tpu.tools.history report` for cross-round
    regression triage (docs/eventlog.md); --no-eventlog opts out."""
    argv = sys.argv[1:]
    if "--eventlog" in argv:
        i = argv.index("--eventlog")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            # silently falling back would write the round's log
            # somewhere the operator didn't ask for
            raise SystemExit(
                "bench.py: --eventlog requires a directory operand")
        return argv[i + 1]
    return os.environ.get("BENCH_EVENTLOG_DIR", "bench_eventlog")


def _flag_operand(name: str, conv):
    """Parse `name VALUE` from argv through `conv` (int/float);
    absent flag -> conv's zero, malformed operand -> SystemExit."""
    argv = sys.argv[1:]
    if name not in argv:
        return conv(0)
    i = argv.index(name)
    try:
        return conv(argv[i + 1])
    except (IndexError, ValueError):
        raise SystemExit(
            f"bench.py: {name} requires a {conv.__name__} operand")


def _int_flag(name: str) -> int:
    return _flag_operand(name, int)


def _float_flag(name: str) -> float:
    return _flag_operand(name, float)


def _bench_cold_start(n: int) -> dict:
    """bench.py --cold-start N: the restart-cost artifact
    (docs/warm_start.md).  Two unmeasured children populate + prime
    one persist directory, then N measured WARM children run against
    it and N EMPTY children against fresh directories — cold wall
    p50/p99, jit misses, compile counts and persist hit rate both
    ways, a digest gate across every child, and the p50 speedup the
    warm-start cache buys a restarted fleet."""
    from spark_rapids_tpu.tools import cold_start as cs

    data = tempfile.mkdtemp(prefix="tpu-coldstart-data-")
    warm_dir = tempfile.mkdtemp(prefix="tpu-coldstart-warm-")
    cs.make_fixture(data)
    for _ in range(2):  # populate the program store, prime XLA cache
        first = cs.run_subprocess(data, warm_dir)
        if first["platform"] != "tpu":
            # this parent stays off jax (a chip belongs to one
            # process), so the first child says what the device is
            raise SystemExit(
                "bench.py: the cold-start child found no TPU (platform "
                f"{first['platform']!r}); the single-chip modes do not "
                "run on another platform")
    warm = [cs.run_subprocess(data, warm_dir) for _ in range(n)]
    empty = [cs.run_subprocess(
        data, tempfile.mkdtemp(prefix="tpu-coldstart-empty-"))
        for _ in range(n)]

    def fold(runs, label):
        walls = sorted(r["wall_ms"] for r in runs)
        return {
            f"{label}_cold_p50_ms": round(
                statistics.median(walls), 3),
            f"{label}_cold_p99_ms": round(
                walls[min(len(walls) - 1,
                          int(0.99 * len(walls)))], 3),
            f"{label}_cold_jit_misses": max(
                r["jit_misses"] for r in runs),
            f"{label}_compiles": max(r["compiles"] for r in runs),
            f"{label}_persist_hit_rate": min(
                r["persist"]["hit_rate"] for r in runs),
        }

    digests = {r["digest"] for r in warm} | {r["digest"] for r in empty}
    out = {"metric": "cold_start_bench", "children": n,
           "digest_ok": len(digests) == 1}
    out.update({k: first[k] for k in
                ("platform", "device_kind", "device_count")})
    out.update(fold(warm, "warm"))
    out.update(fold(empty, "empty"))
    if out["warm_cold_p50_ms"]:
        out["cold_p50_speedup"] = round(
            out["empty_cold_p50_ms"] / out["warm_cold_p50_ms"], 2)
    return out


def _bench_multichip(n_devices: int) -> dict:
    """The MULTICHIP round: run dryrun_multichip on the virtual
    N-device CPU mesh with stderr captured at the fd level (XLA's AOT
    warnings are C-level glog lines Python redirection cannot see),
    then fold the bench fields + a noise-FILTERED tail into one
    artifact dict — the MULTICHIP_r*.json shape, now carrying signal
    instead of machine-feature spam."""
    import tempfile

    import __graft_entry__ as graft

    saved_fd = os.dup(2)
    tmp = tempfile.TemporaryFile(mode="w+b")
    ok = True
    err = None
    bench: dict = {"metric": "multichip_bench", "n_devices": n_devices}
    try:
        os.dup2(tmp.fileno(), 2)
        try:
            bench = graft.dryrun_multichip(n_devices)
        except Exception as e:
            # a failed gate still emits the artifact: rc=1 plus the
            # captured (filtered) stderr IS the diagnostic
            ok = False
            err = f"{type(e).__name__}: {e}"
            import traceback

            traceback.print_exc()  # lands in the captured tail
    finally:
        os.dup2(saved_fd, 2)
        os.close(saved_fd)
        tmp.seek(0)
        tail = tmp.read().decode(errors="replace")[-65536:]
        tmp.close()
    out = dict(bench)
    out.update({
        "n_devices": n_devices,
        "rc": 0 if ok else 1,
        "ok": ok,
        "skipped": False,
        "tail": graft.filter_stderr_noise(tail)[-4000:],
    })
    if err is not None:
        out["error"] = err
    return out


def _bench_mesh_serving(n_devices: int, n_sessions: int) -> dict:
    """bench.py --multichip N --sessions K: pod-scale serving — K
    concurrent sessions drive the milestone templates (agg / join /
    sort) through the serving tier ON an N-device virtual mesh with
    mesh-resident execution enabled (docs/pod_serving.md).  Emits
    `serving_qps_per_chip` and asserts the tentpole's contracts where
    they are measured:

    - every concurrent result hashes bit-identical (canonical digest)
      to the SERIAL SINGLE-DEVICE reference;
    - `serving.mesh.enabled=false` on the same mesh is asserted
      bit-for-bit identical too (the flag-off path is untouched);
    - steady state is device-born: the measured window's tapped
      `placement.host_uploads` counter is asserted ZERO (control-plane
      uploads tallied separately);
    - repeats are pure plan-cache hits (rate 1.0) that compile nothing
      (zero jit-cache misses).
    """
    import threading

    from spark_rapids_tpu.platform import pin_cpu_platform

    cpu_devs = pin_cpu_platform(n_devices)

    import __graft_entry__ as graft
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.config import TpuConf, set_conf
    from spark_rapids_tpu.execs.jit_cache import cache_stats
    from spark_rapids_tpu.parallel import make_mesh
    from spark_rapids_tpu.parallel import placement as _placement
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    from spark_rapids_tpu.serving import plan_cache as _plan_cache
    from spark_rapids_tpu.serving import scheduler as _scheduler
    from spark_rapids_tpu.session import TpuSession, col, count, sum_
    from spark_rapids_tpu.shuffle.transport import SHUFFLE_TRANSPORT

    mesh = make_mesh(n_devices, devices=cpu_devs)
    rows = int(os.environ.get("MESH_SERVING_ROWS", 1 << 14))
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": rng.integers(0, 1024, rows).astype(np.int64),
        "v": rng.integers(0, 1000, rows).astype(np.int64),
    })
    dim = pa.table({
        "k": np.arange(1024, dtype=np.int64),
        "w": np.arange(1024, dtype=np.int64) * 3,
    })
    sort_t = pa.table({
        "k": rng.permutation(rows).astype(np.int64),
        "v": np.arange(rows, dtype=np.int64),
    })

    def templates(s):
        return [
            ("agg", s.create_dataframe(fact)
             .group_by(col("k"))
             .agg((sum_(col("v")), "s"), (count(col("v")), "c"))),
            ("join", s.create_dataframe(fact)
             .join(s.create_dataframe(dim), on="k", how="inner")),
            ("sort", s.create_dataframe(sort_t).order_by(col("k"))),
        ]

    def _conf(transport: str, mesh_serving: bool) -> TpuConf:
        return TpuConf({
            SHUFFLE_TRANSPORT.key: transport,
            "spark.rapids.tpu.shuffle.collective.roundRows":
                max(1024, rows // (n_devices * 4)),
            "spark.rapids.tpu.sql.batchSizeRows":
                max(512, rows // (n_devices * 8)),
            "spark.rapids.tpu.sql.autoBroadcastJoinThresholdBytes": -1,
            "spark.rapids.tpu.serving.mesh.enabled": mesh_serving,
            "spark.rapids.tpu.serving.maxConcurrent": 2,
            "spark.rapids.tpu.sql.concurrentTpuTasks": 2,
            "spark.rapids.tpu.serving.sharing.enabled": False,
        })

    set_active_mesh(mesh)
    out: dict = {"metric": "mesh_serving_bench",
                 "n_devices": n_devices,
                 "serving_sessions": n_sessions, "rows": rows}
    try:
        # -- serial single-device reference (the ground truth) ------ #
        serial_conf = _conf("local", False)
        serial_conf.set("spark.rapids.tpu.serving.maxConcurrent", 0)
        set_conf(serial_conf)
        s0 = TpuSession(serial_conf)
        digests = {}
        for name, df in templates(s0):
            df.collect(engine="tpu")  # warm
            digests[name] = graft._canon_digest(df.collect(engine="tpu"))

        # -- flag-off gate: collective SPMD on the mesh with
        # serving.mesh.enabled=false must be bit-for-bit the
        # pre-mesh-serving engine (every gated path dormant) -------- #
        off_conf = _conf("collective", False)
        set_conf(off_conf)
        s_off = TpuSession(off_conf)
        for name, df in templates(s_off):
            got = graft._canon_digest(df.collect(engine="tpu"))
            assert got == digests[name], \
                f"mesh.enabled=false diverged on {name}"
        out["mesh_off_identical"] = True

        # -- mesh-resident serving phase ---------------------------- #
        repeat_iters = 3
        _scheduler.reset()
        lock = threading.Lock()
        latencies: list = []
        mismatches: list = []
        warm_done = threading.Barrier(n_sessions + 1)
        go = threading.Event()

        def run_session(i: int) -> None:
            pqs = {}
            try:
                conf = _conf("collective", True)
                set_conf(conf)
                session = TpuSession(conf, tenant=f"t{i % 2}")
                for name, df in templates(session):
                    pqs[name] = session.prepare(df)
                for name, pq in pqs.items():
                    if graft._canon_digest(pq.execute()) \
                            != digests[name]:
                        with lock:
                            mismatches.append((i, name, "warm"))
            except BaseException as e:  # noqa: BLE001 — reported below
                with lock:
                    mismatches.append((i, "session-error", repr(e)))
                pqs = {}
            finally:
                warm_done.wait()
            if not pqs:
                return
            go.wait()
            try:
                for _ in range(repeat_iters):
                    for name, pq in pqs.items():
                        t0 = time.perf_counter()
                        r = pq.execute()
                        dt = time.perf_counter() - t0
                        if graft._canon_digest(r) != digests[name]:
                            with lock:
                                mismatches.append((i, name, "repeat"))
                        with lock:
                            latencies.append(dt)
            except BaseException as e:  # noqa: BLE001 — reported below
                with lock:
                    mismatches.append((i, "repeat-error", repr(e)))

        threads = [threading.Thread(target=run_session, args=(i,),
                                    name=f"mesh-serve-{i}")
                   for i in range(n_sessions)]
        for t in threads:
            t.start()
        warm_done.wait()
        # measured window armed strictly after every warm pass:
        # repeats must be pure plan-cache hits that compile nothing
        # and upload nothing on the data plane
        _plan_cache.reset_stats()
        _scheduler.reset()
        _placement.reset_stats()
        jit0 = cache_stats()
        wall0 = time.perf_counter()
        go.set()
        for t in threads:
            t.join()
        wall = time.perf_counter() - wall0
        assert not mismatches, (
            f"mesh serving diverged from the serial single-device "
            f"digests: {mismatches}")
        jit1 = cache_stats()
        pc = _plan_cache.stats()
        pl = _placement.stats()
        n_execs = len(latencies)
        latencies.sort()
        qps = n_execs / wall if wall else 0.0
        out.update({
            "serving_executions": n_execs,
            "serving_qps": round(qps, 2),
            "serving_qps_per_chip": round(qps / n_devices, 3),
            "serving_p50_ms": round(
                latencies[n_execs // 2] * 1e3, 1) if n_execs else 0.0,
            "plan_cache_hit_rate": pc["hit_rate"],
            "serving_repeat_jit_misses":
                jit1["misses"] - jit0["misses"],
            "placement_host_uploads": pl["host_uploads"],
            "placement_control_uploads": pl["control_uploads"],
            "placement_device_born": pl["device_born"],
            "placement_d2d_transfers": pl["d2d_transfers"],
            "placement_adoptions": pl["adoptions"],
            "digests_match": True,
        })
        assert pc["hit_rate"] == 1.0, pc
        assert out["serving_repeat_jit_misses"] == 0, (jit0, jit1)
        # the device-born contract, measured where it bites: the
        # steady-state window moved ZERO data-plane bytes host->device
        # through stage assembly
        assert pl["host_uploads"] == 0, pl
        out["ok"] = True
    finally:
        set_active_mesh(None)
    return out


def _require_tpu() -> dict:
    """The single-chip modes measure the chip: refuse any other
    platform instead of timing XLA:CPU under a chip's name."""
    from spark_rapids_tpu.memory.device_manager import device_fields

    dev = device_fields()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU (platform {dev['platform']!r}, "
            f"kind {dev['device_kind']!r}); the single-chip modes do "
            "not run on another platform")
    return dev


def main() -> None:
    global _CHAOS
    multichip = _int_flag("--multichip")
    if multichip:
        # multichip mode FIRST: it must pin the virtual CPU platform
        # before any backend initialization below touches jax
        from spark_rapids_tpu.memory.device_manager import device_fields

        sessions = _int_flag("--sessions")
        # pod-scale serving when K sessions are asked for: they run on
        # the N-device mesh with mesh-resident execution
        # (docs/pod_serving.md)
        out = _bench_mesh_serving(multichip, sessions) if sessions \
            else _bench_multichip(multichip)
        out.update(device_fields())
        print(json.dumps(out))
        if not out.get("ok", True):
            raise SystemExit(1)
        return
    if "--chaos" in sys.argv[1:]:
        # chaos mode (parsed ahead of the mode dispatch so the serving
        # round honors it too): every query below runs under the
        # deterministic fault schedule — the correctness gates stay
        # on, so what gets measured is the cost of RECOVERING, not a
        # different answer
        _CHAOS = True
    sessions = _int_flag("--sessions")
    if sessions:
        # serving mode: the multi-session concurrency bench ONLY (the
        # single-session q6/q1/q3/q67 rounds are the plain invocation)
        tenants = _int_flag("--tenants") or min(2, sessions)
        dev = _require_tpu()
        out = _bench_serving(sessions, tenants)
        out.update(dev)
        print(json.dumps(out))
        return
    cold = _int_flag("--cold-start")
    if cold:
        # cold-start mode: fresh subprocesses only — this parent
        # process must not touch jax before forking the children (a
        # chip belongs to one process); each child names its device
        print(json.dumps(_bench_cold_start(cold)))
        return
    # wire compression rides every bench round by default (the lever
    # for the upload-bound milestones; correctness gates stay on, and
    # the per-query _wire_fields still measure the on/off byte delta);
    # --no-wire-compression reverts to the raw wire
    if "--no-wire-compression" not in sys.argv[1:]:
        from spark_rapids_tpu.config import get_conf as _gc

        _gc().set("spark.rapids.tpu.sql.wireCompression.enabled", True)
    # buffer donation rides bench rounds by default (the fused
    # scan->agg programs reuse the wire components' HBM;
    # docs/fusion.md) — `--no-donation` reverts; the digest-gated
    # correctness checks run either way
    if "--no-donation" not in sys.argv[1:]:
        from spark_rapids_tpu.config import get_conf as _gc

        _gc().set("spark.rapids.tpu.sql.fusion.donation.enabled", True)
    # batch coalescing rides bench rounds by default (dense programs
    # under fused chains / joins / aggregates; docs/occupancy.md) —
    # `--no-coalesce` reverts; results are bit-identical either way
    # (coalescing only re-buckets rows) and the digest gates run anyway
    if "--no-coalesce" not in sys.argv[1:]:
        from spark_rapids_tpu.config import get_conf as _gc

        _gc().set("spark.rapids.tpu.sql.coalesce.enabled", True)
    dev = _require_tpu()
    scale = _int_flag("--scale-rows")
    if scale:
        # scaling-curve mode ONLY (ROADMAP #1): q6 at N rows, q1 at
        # >= 20M, full per-stage attribution, CPU-gated
        out = _bench_scaled(scale)
        out.update(dev)
        print(json.dumps(out))
        return
    n_rows = ROWS_PER_FILE * N_FILES
    with tempfile.TemporaryDirectory(prefix="q6bench_") as d:
        paths = make_lineitem(d)
        os.makedirs(os.path.join(d, "q1"), exist_ok=True)

        from spark_rapids_tpu.config import get_conf
        from spark_rapids_tpu.session import TpuSession

        ev_dir = None
        if "--no-eventlog" not in sys.argv[1:]:
            ev_dir = _eventlog_dir()
            get_conf().set("spark.rapids.tpu.eventLog.enabled", True)
            get_conf().set("spark.rapids.tpu.eventLog.dir", ev_dir)
        # device-ledger attribution rides every round: per-query
        # q*_device_busy_ms / q*_roofline_attributed / top-program
        # fields, and the event log's per-query `programs` section
        # (docs/device_ledger.md); per-dispatch cost is one counter
        # bump, settlement is off the timed path
        get_conf().set("spark.rapids.tpu.trace.ledger.enabled", True)
        session = TpuSession()
        df = q6_dataframe(session, paths)

        df.collect(engine="tpu")  # warmup: compile cache, page cache
        link = _link_probe()
        reset_all_counters()  # q6 occupancy = timed runs only
        sp0 = _spilled_now()
        tpu_ts, tpu_result = _time_collect(df, "tpu", TPU_ITERS)
        cpu_ts, cpu_result = _time_collect(df, "cpu", CPU_ITERS)
        tpu_t = statistics.median(tpu_ts)
        cpu_t = statistics.median(cpu_ts)

        # correctness gate: a fast wrong answer is not a benchmark
        got = tpu_result.to_pydict()["revenue"][0]
        want = cpu_result.to_pydict()["revenue"][0]
        assert abs(got - want) <= 1e-6 * max(1.0, abs(want)), (got, want)

        # headline occupancy is q6's own (counters reset per config),
        # read BEFORE the tapped breakdown collect
        occ = _pipeline_occupancy("pipeline")
        # q6 is a grand aggregate: its partials carry static counts, so
        # there is nothing to speculate — host_sync_count only
        occ.update(_sync_spec_fields("q6", TPU_ITERS,
                                     with_hit_rate=False))
        occ.update(_robustness_fields("q6", sp0))
        occ.update(_ledger_fields("q6", TPU_ITERS))
        occ.update(_fusion_fields("q6", TPU_ITERS))
        occ.update(_wire_fields(df, "q6"))
        breakdown = _stage_breakdown(df, "q6")
        breakdown.update(occ)
        # effective upload bandwidth: raw (uncompressed-equivalent)
        # bytes over the wall the wire stage actually spent moving the
        # compressed form — the codec's multiplier applied to the
        # physical link's weather-of-the-day figure
        wire_s = breakdown.get("q6_stage_wire_upload_s", 0.0)
        if wire_s > 0:
            breakdown["link_upload_mb_s_effective"] = round(
                occ["q6_upload_bytes_raw"] / wire_s / 1e6, 1)

        # warm device-resident q6: the same filter+aggregate against a
        # df.cache()-materialized scan — batches already in HBM, so
        # this measures DEVICE throughput instead of the wire; the
        # roofline fraction rides along
        cached = session.read_parquet(*paths).cache()
        warm_df = q6_over(cached)
        try:
            warm_df.collect(engine="tpu")  # fills the cache slot
            # ledger-ONLY reset (see _bench_q1: the full reset would
            # re-arm the --chaos schedule inside the warm loop)
            _reset_ledger()
            warm = _bench_warm(warm_df, "q6_warm", n_rows)
            warm["hbm_roofline_fraction_warm"] = _roofline(
                warm["q6_warm_rows_per_s"])
            # the ATTRIBUTED counterpart: per-program device time +
            # cost-model roofline for the warm window — the number
            # ROADMAP #2's fusion/donation work moves
            warm.update(_ledger_fields("q6_warm", 3))
            # the dispatch-budget regression gate: warm q6 must stay
            # under the conf budget and compile nothing
            _assert_warm_budget("q6_warm", warm)
        finally:
            cached.unpersist()
        breakdown.update(warm)

        extra = _bench_q1(session, d)
        extra.update(_bench_q3(session, d))
        extra.update(_bench_q67(session, d))

    rows_per_s = n_rows / tpu_t
    bytes_per_s = rows_per_s * ROW_BYTES
    cpu_rows_per_s = n_rows / cpu_t
    out = {
        "metric": "tpch_q6_e2e_throughput",
        "value": round(rows_per_s, 1),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_s / cpu_rows_per_s, 3),
        "rows": n_rows,
        "tpu_s_per_query": round(tpu_t, 4),
        "cpu_s_per_query": round(cpu_t, 4),
        "bytes_per_s": round(bytes_per_s, 1),
        "hbm_roofline_fraction": _roofline(rows_per_s),
    }
    out.update(dev)
    out.update(_stats(tpu_ts, "q6_tpu"))
    out.update(link)
    out.update(breakdown)
    out.update(extra)
    if _CHAOS:
        from spark_rapids_tpu.robustness import faults

        out["chaos"] = CHAOS_SPEC
        faults.disarm()
    if session.event_log_path is not None:
        # reading events drains the snapshot worker: the log holds
        # every query of this round before we report its path
        _ = session.history.events
        out["eventlog"] = session.event_log_path
    print(json.dumps(out))


if __name__ == "__main__":
    main()
