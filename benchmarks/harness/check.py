"""The comparison that decides `correct`.

Keys, counts and integer columns compare exactly; double aggregates
within REL_TOL.  A DOUBLE on a v5e is a pair of float32 (about 49 bits;
ROADMAP R10) and the engine sums in another order than the reference,
so sums over millions of rows agree to about 1e-12 and not to the last
bit.  REL_TOL stands between two readings (PERF.md section 6, PR 27).
The lower: the widest gap of sound runs on the chip, 9.9e-13 over ten
seeds of the four-chip cell, 2.8e-15 in `.join`.  The upper: the
smallest that the control reads, the plain reference in float32 put in
the program's place (`selfcheck/_f32_control.py`, at the listed sizes,
three seeds a cell): 1.2e-8 where its sums are accumulated in float32,
2.9e-8 where its answer is only stored in float32.  Until PR 27 the
tolerance was `chip_smoke.REL_TOL`, 1e-6, which every one of those
controls passed.
"""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

REL_TOL = 1e-9


def difference(got: pa.Table, want: pa.Table, ordered: bool):
    """None when `got` answers as `want` does, else the reason."""
    return compare(got, want, ordered)[0]


def compare(got: pa.Table, want: pa.Table, ordered: bool) -> tuple:
    """(None when `got` answers as `want` does, else the reason; the
    widest gap of a double from the expected one, as a share of the
    larger of 1 and the expected one's size, which REL_TOL limits:
    None where the answers differ before their doubles are compared)."""
    if got.schema.names != want.schema.names:
        return f"columns {got.schema.names} != {want.schema.names}", None
    if got.num_rows != want.num_rows:
        return f"{got.num_rows} rows, expected {want.num_rows}", None
    exact = [f.name for f in want.schema
             if not pa.types.is_floating(f.type)]
    inexact = [n for n in want.schema.names if n not in exact]
    if not ordered:
        # by the exact columns first: group keys are unique, and where
        # they are not (a join's rows) the doubles break the tie
        keys = [(n, "ascending") for n in exact + inexact]
        got, want = got.sort_by(keys), want.sort_by(keys)
    for name in exact:
        w = want.column(name).combine_chunks()
        g = got.column(name).combine_chunks()
        if g.type != w.type:
            try:
                g = g.cast(w.type)
            except pa.ArrowInvalid as e:
                return (f"{name}: {g.type} does not cast to {w.type}: {e}",
                        None)
        if not g.equals(w):
            bad = pc.index(pc.not_equal(g, w).fill_null(True), True).as_py()
            return (f"{name} row {bad}: {g[bad].as_py()!r} != "
                    f"{w[bad].as_py()!r} (exact column)"), None
    reason, widest = None, 0.0
    for name in inexact:
        g, w = got.column(name), want.column(name)
        if g.null_count or w.null_count:
            if not pc.is_null(g).equals(pc.is_null(w)):
                return f"{name}: nulls differ", None
            g, w = g.fill_null(0.0), w.fill_null(0.0)
        g = g.to_numpy().astype(np.float64)
        w = w.to_numpy().astype(np.float64)
        apart, scale = np.abs(g - w), np.maximum(1.0, np.abs(w))
        off = ~(apart <= REL_TOL * scale)
        gaps = apart / scale
        # a NaN is off, and wider than any number
        gaps = np.where(np.isnan(gaps), np.inf, gaps)
        if len(gaps):
            widest = max(widest, float(gaps.max()))
        if off.any() and reason is None:
            bad = int(np.argmax(off))
            reason = f"{name} row {bad}: {g[bad]!r} vs expected {w[bad]!r}"
    return reason, widest
