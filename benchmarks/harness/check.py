"""The comparison that decides `correct`.

Keys, counts and integer columns compare exactly; double aggregates
within REL_TOL.  The tolerance is `chip_smoke.REL_TOL`: a DOUBLE on a
v5e is a pair of float32 (about 49 bits; ROADMAP R10) and the engine
sums in another order than the reference, so sums over tens of
millions of rows agree to about 1e-12 and not to the last bit; 1e-6 is
far above that and far below what a dropped batch or a float32
accumulator would show (one row in 62.9M moves q6's sum by 1e-8 only
if it is a small one; a lost file moves it by 2%, a float32 sum by
1e-4).
"""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

REL_TOL = 1e-6


def difference(got: pa.Table, want: pa.Table, ordered: bool):
    """None when `got` answers as `want` does, else the reason."""
    if got.schema.names != want.schema.names:
        return f"columns {got.schema.names} != {want.schema.names}"
    if got.num_rows != want.num_rows:
        return f"{got.num_rows} rows, expected {want.num_rows}"
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
                return f"{name}: {g.type} does not cast to {w.type}: {e}"
        if not g.equals(w):
            bad = pc.index(pc.not_equal(g, w).fill_null(True), True).as_py()
            return (f"{name} row {bad}: {g[bad].as_py()!r} != "
                    f"{w[bad].as_py()!r} (exact column)")
    for name in inexact:
        g, w = got.column(name), want.column(name)
        if g.null_count or w.null_count:
            if not pc.is_null(g).equals(pc.is_null(w)):
                return f"{name}: nulls differ"
            g, w = g.fill_null(0.0), w.fill_null(0.0)
        g = g.to_numpy().astype(np.float64)
        w = w.to_numpy().astype(np.float64)
        off = ~(np.abs(g - w) <= REL_TOL * np.maximum(1.0, np.abs(w)))
        if off.any():
            bad = int(np.argmax(off))
            return f"{name} row {bad}: {g[bad]!r} vs expected {w[bad]!r}"
    return None
