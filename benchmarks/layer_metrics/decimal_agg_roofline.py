"""The decimal aggregates' share of their HBM roofline, in percent: the
least bytes the round's aggregate programs move, over the peak HBM
bandwidth (`harness/peaks.py`), over the seconds the first chip spent
in them (`decimal_agg_busy_s`).

The least bytes are reckoned here from the engine's `agg.update` spans,
never from the engine's own arithmetic: each update reads its rows (the
span's `capacity`) times the device words of the decimal columns it
reads (`decimal_input_words`, 8 bytes a word), and writes at least one
partial row of its 128-bit accumulators (`wide_decimal`, 16 bytes
each).  The other columns it reads, the padding's real traffic, the
merges and the final pass are left out, so the count can only fall
short of the traffic and the share can only under-read."""

from benchmarks.layer_metrics import _decimal

NAME, UNIT, BETTER = "decimal_agg_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"

WORD_BYTES = 8
WIDE_BYTES = 16


def least_bytes(spans) -> int:
    return sum(s.attrs["capacity"] * s.attrs["decimal_input_words"]
               * WORD_BYTES + s.attrs.get("wide_decimal", 0) * WIDE_BYTES
               for s in spans)


def reduce(run):
    spans = _decimal.updates(run)
    busy = _decimal.busy_s(run)
    if not spans or not busy:
        return None
    per_round = least_bytes(spans) / len(run.rounds)
    return 100.0 * per_round / run.peaks["hbm_bytes_per_s"] / busy
