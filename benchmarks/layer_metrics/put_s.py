"""Seconds per round inside `wire.put` spans: the host's calls of
`jax.device_put`, which may return before the link is done with the
bytes.  `wire_bytes` over it is the link rate the uploading thread
sees."""

NAME, UNIT, BETTER = "put_s", "s", "lower"
LAYER, SOURCE, MOVES = "Wire encode and upload", "program_span", "round_wall_s"


def reduce(run):
    return run.span_seconds("wire.put")
