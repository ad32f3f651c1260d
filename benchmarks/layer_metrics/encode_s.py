"""Seconds per round inside `wire.encode` spans: host columns made
into wire components, on the thread that runs the plan."""

NAME, UNIT, BETTER = "encode_s", "s", "lower"
LAYER, SOURCE, MOVES = "Wire encode and upload", "program_span", "round_wall_s"


def reduce(run):
    return run.span_seconds("wire.encode")
