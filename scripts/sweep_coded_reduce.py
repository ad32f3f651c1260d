"""The sweep behind `ops/groupby.py:MAX_MASKED_CELLS`: time the coded
group-by's two reductions (masked sum, `segment_sum`) on the chip.

    python scripts/sweep_coded_reduce.py [--rows N] [--m 11 ...] [--K 9 ...]

One JSON line per (dtype, K, m, form): milliseconds a call (median of
three timed loops, each ended by `block_until_ready`), the first call's
seconds (trace + compile + run), and the largest relative difference
between the two forms.  Refuses anything but a TPU: a time from XLA:CPU
is not a speed (PERF.md).  The table it printed for PR 26 is in PERF.md
section 6.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    reps = int(max(3, min(50, 1.0 / max(time.perf_counter() - t0, 1e-4))))
    loops = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        loops.append((time.perf_counter() - t0) / reps)
    return first_s, float(np.median(loops)), np.asarray(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--m", type=int, nargs="+", default=[11])
    ap.add_argument("--K", type=int, nargs="+",
                    default=[9, 81, 289, 1089, 4225])
    ap.add_argument("--dtype", nargs="+", default=["float64", "int64"])
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import spark_rapids_tpu  # noqa: F401  (turns jax_enable_x64 on)
    from spark_rapids_tpu.ops.groupby import _segment_sums

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refusing to time on {dev.platform}", file=sys.stderr)
        return 1
    rng = np.random.default_rng(26)
    for dtype in a.dtype:
        for m in a.m:
            if dtype == "float64":
                cols = [jnp.asarray(rng.random(a.rows) * 1e5)
                        for _ in range(m)]
            else:
                cols = [jnp.asarray(rng.integers(-2**40, 2**40, a.rows))
                        for _ in range(m)]
            for K in a.K:
                # K itself marks a dead row, as in _coded_groupby
                seg = jnp.asarray(
                    rng.integers(0, K + 1, a.rows).astype(np.int32))
                outs = {}
                for masked in (False, True):
                    fn = jax.jit(lambda s, c, K=K, masked=masked:
                                 _segment_sums(c, s, K, masked))
                    first_s, ms, outs[masked] = _timed(fn, (seg, cols))
                    rec = {"device_kind": dev.device_kind, "rows": a.rows,
                           "dtype": dtype, "K": K, "m": m,
                           "form": "masked" if masked else "scatter",
                           "ms": ms * 1e3, "first_s": first_s}
                    if masked:
                        ref = outs[False].astype(np.float64)
                        rec["rel_diff"] = float(np.max(
                            np.abs(outs[True] - outs[False])
                            / np.maximum(np.abs(ref), 1.0)))
                    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
