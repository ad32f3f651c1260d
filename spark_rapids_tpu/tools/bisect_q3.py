"""q3 regression bisect: A/B the suspect layers.

BENCH_r06 ran q3 at 0.117x vs CPU where the round before it ran
0.248x (neither record names its device).  The layers that
landed between the rounds (fusion + buffer donation in PR11) each
ship a kill switch, so the regression is bisectable by CONF, not by
checkout (the SPMD stage execution of PR14 had one too, until PR 30
deleted the path it fell back to): every arm below re-runs the exact
bench.py q3 shape (same fixture generator, same timed-iteration
protocol, wire compression + device ledger + event log on, matching
the committed rounds) in a FRESH subprocess (no shared jit cache —
each arm pays its own compiles, exactly like a bench round) with one
suspect toggled off.

Run:  python -m spark_rapids_tpu.tools.bisect_q3 [out.json]

Writes a committed artifact (BISECT_q3_r07.json by default): per-arm
timings + dispatch/ledger fields, the wall-clock delta of each arm
against the r06 baseline arm, and the `tools/history compare` matrix
across the per-arm event logs (per-query and per-operator deltas, the
CompareApplications analog).  The arm set also includes the r07
mitigation config (batch coalescing on, docs/occupancy.md) so the
artifact shows the regression AND the shipped answer side by side.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_DONATE = "spark.rapids.tpu.sql.fusion.donation.enabled"
_FUSION = "spark.rapids.tpu.sql.fusion.enabled"
_SPEC = "spark.rapids.tpu.sql.speculation.enabled"
_RF = "spark.rapids.tpu.sql.runtimeFilter.enabled"
_COALESCE = "spark.rapids.tpu.sql.coalesce.enabled"

#: each arm = the r06 bench config with ONE suspect toggled (plus the
#: r05-equivalent "all suspects off" floor and the r07 mitigation).
ARMS = [
    ("r06_base", {_DONATE: True}),
    ("no_donation", {_DONATE: False}),
    ("no_fusion", {_DONATE: True, _FUSION: False}),
    ("no_fusion_no_donation", {_DONATE: False, _FUSION: False}),
    ("no_speculation", {_DONATE: True, _SPEC: False}),
    ("no_runtime_filter", {_DONATE: True, _RF: False}),
    ("r07_coalesce", {_DONATE: True, _COALESCE: True}),
]


def run_arm(fixture_dir: str, ev_dir: str, overrides: dict) -> dict:
    """Child-process body: one bench-equivalent q3 round under the
    arm's conf.  Digest-gated against the CPU engine like bench.py's
    _bench_q3 (a fast wrong answer is not a data point)."""
    sys.path.insert(0, REPO)
    import bench

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.session import TpuSession

    conf = get_conf()
    conf.set("spark.rapids.tpu.sql.wireCompression.enabled", True)
    conf.set("spark.rapids.tpu.trace.ledger.enabled", True)
    conf.set("spark.rapids.tpu.eventLog.enabled", True)
    conf.set("spark.rapids.tpu.eventLog.dir", ev_dir)
    for k, v in overrides.items():
        conf.set(k, v)

    session = TpuSession()
    li = [os.path.join(fixture_dir, f"lineitem-{i}.parquet")
          for i in range(2)]
    orders = os.path.join(fixture_dir, "orders.parquet")
    df = bench.q3_dataframe(session, li, orders)

    df.collect(engine="tpu")  # warmup: compile + page cache
    bench.reset_all_counters()
    tpu_ts, tpu_r = bench._time_collect(df, "tpu", 3)
    out = {"q3_tpu_s_median": round(statistics.median(tpu_ts), 4)}
    from spark_rapids_tpu.memory.device_manager import device_fields

    out.update(device_fields())
    out.update(bench._stats(tpu_ts, "q3_tpu"))
    out.update(bench._ledger_fields("q3", 3))
    out.update(bench._fusion_fields("q3", 3))
    out.update(bench._rf_fields(df, 3))
    out.update(bench._stage_breakdown(df, "q3"))
    cpu_ts, cpu_r = bench._time_collect(df, "cpu", 2)
    got = sorted(tpu_r.to_pydict()["revenue"], reverse=True)
    want = sorted(cpu_r.to_pydict()["revenue"], reverse=True)
    assert len(got) == len(want) == 10, (len(got), len(want))
    for gv, wv in zip(got, want):
        assert abs(gv - wv) <= 1e-6 * max(1.0, abs(wv)), (gv, wv)
    cpu_t = statistics.median(cpu_ts)
    out["q3_cpu_s_per_query"] = round(cpu_t, 4)
    out["q3_vs_cpu"] = round(cpu_t / out["q3_tpu_s_median"], 3)
    return out


def _make_fixture(d: str) -> None:
    sys.path.insert(0, REPO)
    import bench

    bench.make_lineitem(d, n_files=2, with_orderkey=True)
    bench.make_orders(d)


def _compare_md(ev_dirs: dict) -> str:
    """history compare across the per-arm event logs (baseline first)."""
    from spark_rapids_tpu.tools import history

    apps = []
    for label, d in ev_dirs.items():
        logs = sorted(os.path.join(d, f) for f in os.listdir(d))
        if not logs:
            continue
        # label the app by ARM (compare renders basenames)
        named = os.path.join(d, f"{label}.jsonl")
        os.rename(logs[0], named)
        apps.append(history.load_application(named))
    if len(apps) < 2:
        return "(compare skipped: <2 event logs)"
    return history.render_compare_md(history.compare_applications(
        apps, history.DEFAULT_REGRESSION_THRESHOLD))


def main(out_path: str = "BISECT_q3_r07.json") -> int:
    results: dict = {"protocol": {
        "fixture": "bench.make_lineitem(n_files=2, with_orderkey) + "
                   "make_orders (q3_rows=3145728), warmup + median of "
                   "3 timed tpu collects, cpu median of 2, fresh "
                   "subprocess per arm",
        "arms": {label: ov for label, ov in ARMS},
    }, "arms": {}}
    tmp = tempfile.mkdtemp(prefix="q3bisect_")
    fixture = os.path.join(tmp, "fixture")
    os.makedirs(fixture)
    _make_fixture(fixture)
    ev_dirs = {}
    failed: list = []
    for label, overrides in ARMS:
        ev_dir = os.path.join(tmp, f"ev_{label}")
        os.makedirs(ev_dir)
        ev_dirs[label] = ev_dir
        child = (
            "import json,sys; sys.path.insert(0, %r); "
            "from spark_rapids_tpu.tools.bisect_q3 import run_arm; "
            "print('ARM_RESULT ' + json.dumps(run_arm(%r, %r, "
            "json.loads(sys.argv[1]))))"
            % (REPO, fixture, ev_dir))
        # each arm's child owns the device in turn (this parent
        # stays off jax) and runs on whatever platform the
        # environment gives it — its record names it
        proc = subprocess.run(
            [sys.executable, "-c", child, json.dumps(overrides)],
            capture_output=True, text=True, cwd=REPO)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("ARM_RESULT ")), None)
        if line is None:
            results["arms"][label] = {
                "error": (proc.stderr or proc.stdout)[-2000:]}
            print(f"{label}: FAILED", file=sys.stderr)
            failed.append(label)
            continue
        results["arms"][label] = json.loads(line[len("ARM_RESULT "):])
        print(f"{label}: q3_tpu_s_median="
              f"{results['arms'][label]['q3_tpu_s_median']} "
              f"vs_cpu={results['arms'][label]['q3_vs_cpu']}")
    base = results["arms"].get("r06_base", {}).get("q3_tpu_s_median")
    if base:
        results["delta_vs_r06_base"] = {
            label: round(base / a["q3_tpu_s_median"], 3)
            for label, a in results["arms"].items()
            if a.get("q3_tpu_s_median")}
    results["history_compare_md"] = _compare_md(ev_dirs)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    print(f"wrote {out_path}")
    if failed:
        print(f"arms failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else
                  "BISECT_q3_r07.json"))
