"""Seconds per round the host spent between a mesh's stage programs
and in their launches: the union, over every thread, of the
`mesh.stack`, `mesh.shrink` and `mesh.launch` spans and of the
`pipe.readback` spans tagged `mesh.counts` (a stage boundary's counts
fetch) or `mesh.drain` (a child batch's row count, read as the stage
deals it to a shard).  Host seconds whether the chip was idle or not:
over them the three `idle_mesh_*` and `idle_sync_s` say how much of
this the chip waited for.  Nothing where the program records no
`mesh.*` span."""

import numpy as np

from benchmarks.harness import trace_reduce
from benchmarks.layer_metrics import _mesh_idle

NAME, UNIT, BETTER = "mesh_host_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "program_span", "round_wall_s"

SYNC_TAGS = ("mesh.counts", "mesh.drain")


def reduce(run):
    if not any(s.name in _mesh_idle.MESH for s in run.spans):
        return None
    mine = np.array(
        [(s.ts_ns, s.ts_ns + s.dur_ns) for s in run.spans
         if s.name in _mesh_idle.MESH
         or s.name == "pipe.readback" and s.attrs.get("tag") in SYNC_TAGS],
        dtype=np.float64)
    return trace_reduce.busy_ns(mine, mine[:, 0].min(), mine[:, 1].max()) \
        / 1e9 / len(run.rounds)
