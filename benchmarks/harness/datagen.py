"""Makes a cell's tables and each query's expected answer from --seed.

Runs before JAX or the engine is imported, in a pool of worker
processes that import numpy and pyarrow only.  File `i` of a table
depends on the seed and `i` alone (its generator seeds
`default_rng([seed, <table's id>, i])`), so files are made in parallel
and in any order.  The worker that has a file's arrays in
hand also computes, for every query that walks that table, the
query's partial answer over them; the partials are combined at the
end.  That is the plain reference: numpy and pyarrow, nothing of
`plan/`, `execs/` or `cpu/engine.py`.
"""

import dataclasses
import functools
import multiprocessing
import os
import time

import numpy as np
import pyarrow.parquet as pq

from benchmarks.harness import spec


@dataclasses.dataclass
class Data:
    paths: dict  # table name -> its files, in order
    expected: list  # per step of the round: the answer, a pyarrow Table
    seconds: float  # what making them took


def _generator(table: spec.Table):
    return spec.module("generators", table.generator)


@functools.lru_cache(maxsize=2)
def _whole(seed: int, table: spec.Table, columns: tuple) -> dict:
    """The named columns of a side table, every file of it, made again
    in the worker that needs them: cheaper than sending them there."""
    files = [_generator(table).generate(seed, i, table.rows_per_file,
                                        columns)
             for i in range(table.files)]
    return {name: np.concatenate([f[name] for f in files])
            for name in columns}


def _make_file(task: tuple) -> tuple:
    seed, table, index, path, steps = task
    gen = _generator(table)
    cols = gen.generate(seed, index, table.rows_per_file)
    pq.write_table(gen.to_arrow(cols, seed, index), path,
                   row_group_size=table.rows_per_file)
    partials = {}
    for at, step in steps:
        query = spec.module("queries", step.query)
        side = {role: _whole(seed, t, tuple(query.COLUMNS[role]))
                for role, t in step.tables if role != query.DRIVER}
        partials[at] = query.partial(cols, side)
    return table.name, index, partials


def generate(cell: spec.Cell, seed: int, workdir: str) -> Data:
    t0 = time.perf_counter()
    tasks = []
    paths: dict = {}
    for table in cell.tables():
        steps = [(at, step) for at, step in enumerate(cell.round)
                 if step.table(spec.module("queries", step.query).DRIVER)
                 == table]
        os.makedirs(os.path.join(workdir, table.name))
        paths[table.name] = [
            os.path.join(workdir, table.name, f"{table.name}-{i}.parquet")
            for i in range(table.files)]
        tasks += [(seed, table, i, path, steps)
                  for i, path in enumerate(paths[table.name])]
    # the largest files first, so that none starts last and alone
    tasks.sort(key=lambda t: -t[1].rows_per_file)
    partials: dict = {at: {} for at in range(len(cell.round))}
    workers = min(len(tasks), os.cpu_count() or 1)
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        for _, index, found in pool.imap_unordered(_make_file, tasks):
            for at, part in found.items():
                partials[at][index] = part
    expected = [
        spec.module("queries", step.query).combine(
            [partials[at][i] for i in sorted(partials[at])])
        for at, step in enumerate(cell.round)]
    return Data(paths, expected, time.perf_counter() - t0)
