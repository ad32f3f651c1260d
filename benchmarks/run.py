"""One run of one benchmark cell, as the driver starts it:

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1>

One process, in this order: (1) before JAX or the engine is imported,
make the cell's tables and each query's expected answer from the seed
(`harness/datagen.py`); (2) import the engine and require a TPU and
the cell's chip count; (3) fill the cache where the traffic reads
resident tables; (4) warm up by whole rounds until one brings no new
program into the process; (5) run rounds for `--seconds`: none starts
after the time is up, the one in flight finishes and counts; (6) check
every answer, reduce, print.  The last line of standard output is the
contract's object; the record of the run is the line before it and a
file under `benchmarks/out/<cell>/`.  What decided `correct` stands
last in that object, under `compared`, each number beside its limit,
and again as the last lines of standard error.

`--trace 1` turns the engine's tracer on (the one conf key the
benchmark sets, in that run alone) and takes a `jax.profiler` trace
of whole rounds; the per-layer metrics come from that run.

`--rehearse` is the builder's: every table becomes its first file at a
sixteenth of the rows and the run takes whatever JAX finds.  Its line names that
platform and says `"rehearsal": true`; it is not a measurement.

This file names no cell, query or metric: it finds them through
`harness/spec.py` by the names in `BENCHMARK.json`.
"""

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from benchmarks.harness import datagen, spec  # noqa: E402

#: a traced window is whole rounds until this many seconds are up: one
#: round of the longest cell, a few of the others, and a trace of a
#: size that comes back
TRACE_SECONDS = 10.0


def _metrics_line(entries: tuple, values: dict) -> dict:
    """The line's `metrics`: the values the cell's entries name, each
    with the unit BENCHMARK.json gives it."""
    units = {m["name"]: m["unit"] for m in entries}
    return {n: {"value": v, "unit": units[n]} for n, v in values.items()
            if n in units}


def measure(cell: spec.Cell, data: datagen.Data, args, out_dir: str):
    """Steps (2) to (6).  Returns the contract's line and the record."""
    from importlib import metadata

    import jax

    # compiles under a second are most of what a query runs; without
    # this the cache refuses them and every run compiles them again.
    # Set before the engine is imported; the engine's own default stays.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import spark_rapids_tpu
    from benchmarks.harness import engine, reduce
    from spark_rapids_tpu import trace as engine_trace

    devs = engine.require_devices(cell.chips, args.rehearse)
    runner = engine.Runner(cell, data, devs, bool(args.trace))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    record = {
        "cell": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rehearsal": args.rehearse, "device": device,
        "versions": {"jax": jax.__version__,
                     "jaxlib": metadata.version("jaxlib"),
                     "libtpu": metadata.version("libtpu")},
        "compile_cache_dir": spark_rapids_tpu.compile_cache_dir(),
        "datagen_s": data.seconds,
        "ready_s": time.perf_counter() - T0,
    }
    try:
        if cell.resident:
            record["cache"] = runner.fill_cache()
            engine.say(f"cache filled: {record['cache']}")
        warmup = []
        for i in range(engine.MAX_WARMUP_ROUNDS):
            done = runner.run_round()
            runner.check(done)
            warmup.append(done)
            c = done.counters
            engine.say(f"warm-up round {i}: {done.wall_s:.3f} s, "
                       f"{c['backend_compiles']} programs new to the "
                       f"process, {c['backend_compile_s']:.1f} s in the "
                       f"backend, {c['persistent_cache_hits']} from the "
                       "persistent cache")
            if not done.counters["backend_compiles"]:
                break
        setup_s = time.perf_counter() - T0

        seconds, trace_dir, marker_ns = args.seconds, None, None
        if args.trace:
            seconds = min(seconds, TRACE_SECONDS)
            trace_dir = os.path.join(out_dir, f"trace-seed{args.seed}")
            marker_ns = _start_trace(trace_dir)
        rounds = []
        window_ends = time.perf_counter() + seconds
        while True:
            rounds.append(runner.run_round())
            if time.perf_counter() >= window_ends:
                break
        if args.trace:
            jax.profiler.stop_trace()
        for done in rounds:
            runner.check(done)

        run = reduce.Run(cell, warmup, rounds, setup_s,
                         device["kind"], runner.memory_peak_bytes())
        device["memory_peak_bytes"] = run.memory_peak_bytes
        collects = [c for r in warmup + rounds for c in r.collects]
        failures = [f"{c.query} round {c.round}: "
                    f"{c.failure or c.plan_fault}"
                    for c in collects if c.failure or c.plan_fault]
        line = {"correct": not failures, "attempted": len(collects),
                "failed": len(failures), "device": device}
        record["chip_peak_bytes"] = runner.memory_peaks()
        if args.rehearse:
            line["rehearsal"] = True
        if args.trace:
            lo, hi = rounds[0].t0_ns, rounds[-1].t1_ns
            run.spans = [s for s in engine_trace.snapshot()
                         if lo <= s.ts_ns <= hi]
            record["trace_file"] = _read_trace(run, trace_dir, marker_ns)
            if run.trace is not None and run.trace.chips:
                if cell.chips > 1:
                    try:
                        record["chip_busy_s"] = reduce.chips_at_work(
                            run, cell.chips)
                    except ValueError as e:
                        raise engine.Refused(str(e))
                device["busy_s"] = run.busy_s()
                device["window_s"] = run.window_s()
                line["breakdown"] = reduce.breakdown(run, marker_ns)
            elif not args.rehearse:
                raise engine.Refused(
                    f"no device plane in the trace under {trace_dir}")
            line["metrics"] = _metrics_line(cell.per_layer,
                                            reduce.per_layer(run))
        else:
            line["metrics"] = _metrics_line(cell.end_to_end,
                                            reduce.end_to_end(run))
        # what decided `correct`, last in the line and on standard error
        line["compared"] = reduce.compared(collects)
        record.update(
            setup_s=setup_s, failures=failures[:20],
            warmup=[_round_record(r) for r in warmup],
            window=[_round_record(r) for r in rounds],
            compile_total=runner.compiles.snapshot(),
            input_rows_per_round=cell.input_rows(),
            line=line)
    finally:
        runner.close()
    return line, record


def _start_trace(trace_dir: str) -> int:
    """Start the profiler, the host's own events and the device's and
    no Python frames, and open the marker that ties the engine
    tracer's clock to the trace's.  Returns the marker's reading."""
    import jax

    from benchmarks.harness import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    marker_ns = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
        pass
    return marker_ns


def _read_trace(run, trace_dir: str, marker_ns: int):
    """Load the profiler's trace into `run` and leave the engine's
    spans beside it, for whoever reads the run by hand.  Returns the
    trace's path, None where the profiler wrote none."""
    from benchmarks.harness import trace_reduce

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        return None
    run.trace = trace_reduce.load(found[0])
    with open(os.path.join(trace_dir, "engine_spans.json"), "w") as f:
        json.dump({"marker_perf_ns": marker_ns, "spans": [
            [s.name, s.ts_ns, s.dur_ns, s.thread_name]
            for s in run.spans]}, f)
    return found[0]


def _round_record(done) -> dict:
    return {"round": done.index, "wall_s": done.wall_s,
            "queries": {c.query: c.wall_s for c in done.collects},
            # what moved: a counter that stood still is left out
            "counters": {k: v for k, v in done.counters.items() if v}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, args.rehearse)
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        raise SystemExit("benchmarks.run REFUSED: no spark_rapids_tpu beside "
                         "benchmarks/, so no system to measure")
    out_dir = str(spec.PACKAGE / "out" / cell.name)
    os.makedirs(out_dir, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="benchmarks-")
    try:
        data = datagen.generate(cell, args.seed, workdir)
        line, record = measure(cell, data, args, out_dir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    with open(os.path.join(
            out_dir, f"run-seed{args.seed}-trace{args.trace}.json"),
            "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"record": record}), flush=True)
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"[bench] compared {name}: {c['value']!r}, limit "
              f"{c['limit']!r}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
