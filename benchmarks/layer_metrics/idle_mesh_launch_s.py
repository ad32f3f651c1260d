"""Seconds per round the first chip sat idle while the host was inside
the call of a mesh stage program (a `mesh.launch` span open: the
dispatch gate, the trace and compile of a first call, the enqueue) and
neither an upload, sync or scan cause nor a `mesh.stack` or
`mesh.shrink` span held: the third of the mesh's three."""

from benchmarks.layer_metrics import _mesh_idle

NAME, UNIT, BETTER = "idle_mesh_launch_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"


def reduce(run):
    return _mesh_idle.idle_s(run, "mesh.launch")
