"""Bytes per round that the collective operators' all_to_alls were
sized to carry: `collectiveBytes` summed over the `TpuCollective*`
operators.  For each dispatched exchange program: rounds x shards x
the send buffer of n slots x the slot's capacity in rows x the
schema's device row width (data, validity, a string's padded
characters and length), reckoned on the host from shapes and counts
the stage holds anyway.  Padding is in it: it is what the collective
moves, not what is live (`exchange_rows` x the row width is that)."""

from benchmarks.layer_metrics import _collective

NAME, UNIT, BETTER = "exchange_bytes", "bytes", "lower"
LAYER, SOURCE, MOVES = "Several chips", "program_counter", "round_wall_s"


def reduce(run):
    return _collective.counts(run, "collectiveBytes")
