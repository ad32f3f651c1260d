"""Seconds per round the first chip sat idle while the host cut a mesh
stage's output into its shards' batches (a `mesh.shrink` span open:
`shrink_rounds`, `unstack_stage`, `unstack_round_stage`, a piece and a
cut a leaf a (round, shard), after the counts are on the host) and
neither an upload, sync or scan cause nor a `mesh.stack` span held:
the second of the mesh's three."""

from benchmarks.layer_metrics import _mesh_idle

NAME, UNIT, BETTER = "idle_mesh_shrink_s", "s", "lower"
LAYER, SOURCE, MOVES = "Several chips", "device_trace", "round_wall_s"


def reduce(run):
    return _mesh_idle.idle_s(run, "mesh.shrink")
