"""Thread-seconds per round inside `scan.decode.file` spans: the
Parquet decode of one file, on the thread that did it
(`tpu-scan-decode*`, or the `scan.decode` stage where the pool is not
used).  Summed over threads, so it can pass the round's wall; beside
`host_cpu_s` it says how much of the host's CPU is decode."""

NAME, UNIT, BETTER = "decode_s", "s", "lower"
LAYER, SOURCE, MOVES = "Scan and host decode", "program_span", "round_wall_s"


def reduce(run):
    return run.span_seconds("scan.decode.file")
