"""The control of the comparison that decides `correct`: the plain
reference put in the program's place, in the precision below the
DOUBLE the configurations state, float32.  Host only, no JAX:

    python3 -m benchmarks.selfcheck._f32_control <cell> <seed> [<seed> ...]

reads, at the cell's listed size and per seed, what `check.compare`
makes of two controls against the reference's own answers:

`stored`    the reference's answer with each double rounded once to
            float32: the least that any float32 path does to it;
`computed`  the reference's own arithmetic (`partial`, `combine` of the
            query's file) over DOUBLE columns cast to float32, its
            sums accumulated in float32.

Each has to read above `check.REL_TOL`, or differ in an exact column
(an order of rows that the rounding moved).  `test_mesh_cell.py` holds
both at the rehearsal's size.
"""

import contextlib
import json
import sys

import numpy as np
import pyarrow as pa

from benchmarks.harness import check, datagen, spec

_BINCOUNT = np.bincount


def _bincount32(x, weights=None, minlength=0):
    """`numpy.bincount` with its weighted sums accumulated in float32
    (numpy's own are float64 whatever the weights are)."""
    if weights is None:
        return _BINCOUNT(x, minlength=minlength)
    out = np.zeros(max(minlength, int(x.max()) + 1 if len(x) else 0),
                   np.float32)
    order = np.argsort(x, kind="stable")
    xs, ws = x[order], np.asarray(weights, np.float32)[order]
    starts = np.flatnonzero(np.r_[True, xs[1:] != xs[:-1]])
    if len(starts):
        out[xs[starts]] = np.add.reduceat(ws, starts)
    return out


@contextlib.contextmanager
def _sums_in_float32():
    np.bincount = _bincount32
    try:
        yield
    finally:
        np.bincount = _BINCOUNT


def _cast(cols: dict, dtype) -> dict:
    return {n: a.astype(dtype) if a.dtype == np.float64 else a
            for n, a in cols.items()}


def answers(cell: spec.Cell, seed: int, dtype=np.float64) -> list:
    """Per step of the round, the plain reference's answer with the
    DOUBLE columns of its inputs held in `dtype`."""
    out = []
    for step in cell.round:
        query = spec.module("queries", step.query)
        driver = step.table(query.DRIVER)
        gen = spec.module("generators", driver.generator)
        side = {
            role: _cast(datagen._whole(seed, t, tuple(query.COLUMNS[role])),
                        dtype)
            for role, t in step.tables if role != query.DRIVER}
        parts = []
        for i in range(driver.files):
            cols = gen.generate(seed, i, driver.rows_per_file,
                                query.COLUMNS[query.DRIVER])
            parts.append(query.partial(_cast(cols, dtype), side))
        out.append(query.combine(parts))
    return out


def stored(table: pa.Table) -> pa.Table:
    """Every double of the table rounded once to float32."""
    for at, field in enumerate(table.schema):
        if pa.types.is_floating(field.type):
            rounded = table.column(at).to_numpy().astype(np.float32)
            table = table.set_column(
                at, field, pa.array(rounded.astype(np.float64), field.type))
    return table


def readings(cell: spec.Cell, seed: int) -> dict:
    """Per control and query: (why `check.compare` fails it, None where
    it passes; the widest gap of a double)."""
    want = answers(cell, seed)
    with _sums_in_float32():
        lower = answers(cell, seed, np.float32)
    out = {"stored": {}, "computed": {}}
    for step, w, c in zip(cell.round, want, lower):
        ordered = spec.module("queries", step.query).ORDERED
        out["stored"][step.query] = check.compare(stored(w), w, ordered)
        out["computed"][step.query] = check.compare(c, w, ordered)
    return out


if __name__ == "__main__":
    listed = spec.load_cell(sys.argv[1])
    for s in sys.argv[2:]:
        print(json.dumps({"cell": listed.name, "seed": int(s),
                          "limit": check.REL_TOL,
                          **readings(listed, int(s))}), flush=True)
