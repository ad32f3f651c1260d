"""The conditional pass's share of its HBM roofline, in percent: the
least bytes the round's `semi_cond` programs move, over the peak HBM
bandwidth, over the seconds the first chip spent in them (the union of
their `XLA Modules` intervals, `jit_tpu__Tpu*HashJoinExec__semi_cond`).

The least bytes are reckoned here, from the engine's `join.semi_cond`
events (one a chunk of pairs): each pair reads the columns the
condition reads on both sides (`cond_row_bytes`, their device widths
with validity), and each chunk writes a mask of the stream's capacity,
a byte a row.  Padding slots, the pair index arithmetic, the probe
state it reads and the gathers' real access patterns are left out, so
the count can only fall short of the traffic and the share can only
under-read.  Nothing where the trace or the spans hold none of it."""

from benchmarks.layer_metrics import _semi

NAME, UNIT, BETTER = "semi_cond_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def least_bytes(events) -> int:
    """HBM bytes the chunks cannot do without."""
    return sum(e.attrs["pairs"] * e.attrs["cond_row_bytes"]
               + e.attrs["stream_capacity"] for e in events)


def reduce(run):
    events = [s for s in run.spans if s.name == "join.semi_cond"]
    busy = _semi.busy_s(run, "semi_cond")
    if not events or not busy:
        return None
    per_round = least_bytes(events) / len(run.rounds)
    return 100.0 * per_round / run.peaks["hbm_bytes_per_s"] / busy
