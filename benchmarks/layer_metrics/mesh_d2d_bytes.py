"""Bytes per round that crossed from chip to chip through
`jax.device_put`, outside any collective: `placement.place_piece`'s
`d2d_bytes` counter (the `nbytes` of every stage-input piece it found
on another chip than its shard's), as each `mesh.stack` span states its
change over its call, summed over the round's spans.  Every upload
lands on the first chip and `_shard_rounds` deals child batches to the
least-loaded shard, so most of a stage's input moves this way;
`exchange_bytes` and `collective_s` say nothing of it.  Nothing where
the program records no `mesh.stack` span."""

NAME, UNIT, BETTER = "mesh_d2d_bytes", "bytes", "lower"
LAYER, SOURCE, MOVES = "Several chips", "program_counter", "round_wall_s"


def reduce(run):
    moved = [s.attrs.get("d2d_bytes") or 0 for s in run.spans
             if s.name == "mesh.stack"]
    if not moved:
        return None
    return sum(moved) / len(run.rounds)
