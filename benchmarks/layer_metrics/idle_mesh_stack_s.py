"""Seconds per round the first chip sat idle while the host stacked a
mesh stage's input (a `mesh.stack` span open: `shard_stack_rounds`'
eager pads up to the common capacity, a stack a leaf a device, the
placement of every piece onto its chip) and no upload, sync or scan
cause of `_idle.CAUSES` held: the first of the mesh's three, taken out
of what `idle_dispatch_s` or nobody held."""

from benchmarks.layer_metrics import _mesh_idle

NAME, UNIT, BETTER = "idle_mesh_stack_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"


def reduce(run):
    return _mesh_idle.idle_s(run, "mesh.stack")
