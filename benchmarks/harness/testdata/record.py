"""Records the small trace that `selfcheck/test_trace_reduce.py` checks
the reduction against.  Run on the chip, once, by the PR that changes
what a trace must show:

    python3 -m benchmarks.harness.testdata.record

Two "rounds", each of two annotated "collects": a chain of matrix
products, then 30 ms in which the host sleeps inside a span of its
own and the chip idles, then a sort.  Writes `small.xplane.pb` and
`small.json` (the marker's clock reading and the host spans, as the
engine's tracer would give them) beside this file.

    python3 -m benchmarks.harness.testdata.record x4

on a host of four chips records `small_x4.xplane.pb` and
`small_x4.json` instead: two rounds of one program over a 1-D mesh of
the four, in which every chip squares its rows, exchanges them by
`all_to_all`, sums them by `psum` and gathers eight of them by
`all_gather` (which the compiler folds into the `all-reduce`: the trace
holds an `all-to-all` and an `all-reduce` a round on every chip).
The JSON keeps what `layer_metrics/collective_s.py` and
`busy_skew.py` read from that trace, and the names of the lines and of
the collective operations it holds.  The committed `small_x4.json` was
written again off the chip, by `describe_x4` over the committed trace,
after the reader's pattern was rewritten to match opcodes (on the chip
it had found `%all-reduce.1` alone and read 1.86e-6): it pins the
reader to itself and no more.  The independent answer is in
`selfcheck/test_mesh_cell.py`, worked out by hand from the trace's raw
event times.
"""

import glob
import json
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SLEEP_S = 0.03


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace_reduce

    @jax.jit
    def fixture_products(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def fixture_sort(x):
        return jnp.sort(x)

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.arange(1 << 20, dtype=jnp.float32)[::-1]
    fixture_products(a).block_until_ready()
    fixture_sort(b).block_until_ready()

    spans = []
    work = tempfile.mkdtemp(prefix="record-")
    try:
        _trace_to(work)
        marker_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
            pass
        for r in range(2):
            with jax.profiler.TraceAnnotation(f"bench.round {r}"):
                t0 = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(
                        f"bench.collect products round {r}"):
                    fixture_products(a).block_until_ready()
                    s0 = time.perf_counter_ns()
                    time.sleep(SLEEP_S)
                    spans.append({"name": "fixture.sleep", "ts_ns": s0,
                                  "dur_ns": time.perf_counter_ns() - s0})
                with jax.profiler.TraceAnnotation(
                        f"bench.collect sort round {r}"):
                    fixture_sort(b).block_until_ready()
                spans.append({"name": "fixture.round", "ts_ns": t0,
                              "dur_ns": time.perf_counter_ns() - t0})
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(work, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        shutil.copy(found[0], os.path.join(HERE, "small.xplane.pb"))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    dev = jax.devices()[0]
    with open(os.path.join(HERE, "small.json"), "w") as f:
        json.dump({"marker_perf_ns": marker_ns, "spans": spans,
                   "sleep_s": SLEEP_S, "rounds": 2,
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "jax": jax.__version__}, f, indent=1)
    print(os.path.getsize(os.path.join(HERE, "small.xplane.pb")),
          "bytes of trace")


def _trace_to(work: str) -> None:
    """Start the profiler as the benchmark does, without the HLO."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(work, profiler_options=options)


def describe_x4(path: str, device: dict, jax_version: str) -> None:
    """Write `small_x4.json` from the recorded trace: what it holds,
    and what the two readers of the layer read from it (so the file
    follows the readers: run it again, on or off the chip, when they
    change)."""
    import types

    from jax.profiler import ProfileData

    from benchmarks.harness import trace_reduce
    from benchmarks.layer_metrics import busy_skew, collective_s

    trace = trace_reduce.load(path)
    run = types.SimpleNamespace(trace=trace, rounds=[None, None])
    lines = {plane.name: [ln.name for ln in plane.lines]
             for plane in ProfileData.from_file(path).planes
             if trace_reduce.DEVICE_PLANE.match(plane.name)}
    per_chip = [sorted(name.split(" = ")[0]
                       for name in chip.op_names + chip.async_names
                       if collective_s.COLLECTIVE.search(name))
                for chip in trace.chips]
    with open(os.path.join(HERE, "small_x4.json"), "w") as f:
        json.dump({"rounds": 2, "chips": [c.index for c in trace.chips],
                   "device": device, "jax": jax_version, "lines": lines,
                   "collectives_per_chip": per_chip,
                   "chip_busy_s": trace_reduce.chips_busy_s(trace),
                   "collective_s": collective_s.reduce(run),
                   "busy_skew": busy_skew.reduce(run)}, f, indent=1)


def main_x4() -> None:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks.harness import trace_reduce

    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise SystemExit(f"four chips wanted, JAX found {len(devs)}")
    mesh = Mesh(devs, ("data",))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def fixture_exchange(x):
        x = x * x
        x = jax.lax.all_to_all(x, "data", 0, 0, tiled=True)
        total = jax.lax.psum(jnp.sum(x), "data")
        whole = jax.lax.all_gather(x[:8], "data", tiled=True)
        return x + total + jnp.sum(whole)

    rows = 1 << 16
    a = jax.device_put(jnp.arange(4 * rows, dtype=jnp.float32) / rows,
                       NamedSharding(mesh, P("data")))
    fixture_exchange(a).block_until_ready()

    work = tempfile.mkdtemp(prefix="record-")
    try:
        _trace_to(work)
        with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
            pass
        for r in range(2):
            with jax.profiler.TraceAnnotation(f"bench.round {r}"):
                fixture_exchange(a).block_until_ready()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(work, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        path = os.path.join(HERE, "small_x4.xplane.pb")
        shutil.copy(found[0], path)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    describe_x4(path, {"platform": devs[0].platform,
                       "kind": devs[0].device_kind}, jax.__version__)
    print(os.path.getsize(path), "bytes of trace")


if __name__ == "__main__":
    import sys

    if sys.argv[1:] == ["x4"]:
        main_x4()
    else:
        main()
