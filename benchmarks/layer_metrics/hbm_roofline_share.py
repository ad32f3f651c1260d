"""The least time the chip could take to read a round's input columns
once from HBM, as a share of the time it was busy.

Bytes: every query's rows times the widths of the columns it reads
(`spec.Step.input_bytes`, from the generators' COLUMN_BYTES), over the
peak HBM bandwidth of `peaks.py`.  Bound by bandwidth, not by
arithmetic: q6 and q1 do a few operations per byte.  Only where the
input is resident: from Parquet the chip reads what the host filtered.
"""

NAME, UNIT, BETTER = "hbm_roofline_share", "%", "higher"
LAYER, SOURCE, MOVES = "Operators", "device_trace", "round_wall_s"


def reduce(run):
    busy = run.busy_s()
    if not busy or not run.cell.resident:
        return None
    least = sum(step.input_bytes() for step in run.cell.round) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (busy / len(run.rounds))
