"""Times per round a mesh stage stopped for a number from the chips:
`stage.mesh.counts.readbacks` (the counts fetches at a stage program's
boundary, `spmd.stage_counts` and `spmd.fetch`) plus
`stage.mesh.drain.readbacks` (a child batch's row count, read by
`_shard_rounds` as it deals the batch to a shard), both ticked by
`pipeline.device_read` whether the tracer is on or off.  At each the
host can prepare nothing of the next program until the number is
back.  They are part of `host_syncs`.  Nothing where the program has
neither counter."""

NAME, UNIT, BETTER = "mesh_syncs", "count", "lower"
LAYER, SOURCE, MOVES = "Several chips", "program_counter", "round_wall_s"


def reduce(run):
    found = [run.per_round(f"stage.mesh.{tag}.readbacks")
             for tag in ("counts", "drain")]
    if found == [None, None]:
        return None
    return sum(n or 0 for n in found)
