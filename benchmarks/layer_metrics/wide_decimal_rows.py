"""Rows per round that the 128-bit decimal operations ran over: each
`decimal.wide` event's `rows` (a program's rows, padding included,
times how often it applies that operation: multiply, add_sub, rescale,
sum_update, merge, avg_finish), summed.  In q1 over DECIMAL(15,2) money
two products, two 128-bit accumulators and three averages a row."""

from benchmarks.layer_metrics import _decimal

NAME, UNIT, BETTER = "wide_decimal_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_counter", "round_wall_s"


def reduce(run):
    events = _decimal.wide_events(run)
    if not events:
        return None
    return sum(e.attrs["rows"] for e in events) / len(run.rounds)
