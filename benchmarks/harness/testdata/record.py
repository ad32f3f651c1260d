"""Records the small trace that `selfcheck/test_trace_reduce.py` checks
the reduction against.  Run on the chip, once, by the PR that changes
what a trace must show:

    python3 -m benchmarks.harness.testdata.record

Two "rounds", each of two annotated "collects": a chain of matrix
products, then 30 ms in which the host sleeps inside a span of its
own and the chip idles, then a sort.  Writes `small.xplane.pb` and
`small.json` (the marker's clock reading and the host spans, as the
engine's tracer would give them) beside this file.
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
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(work, profiler_options=options)
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


if __name__ == "__main__":
    main()
