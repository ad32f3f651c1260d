"""Seconds per round the query's operators waited for the scan to hand
them a batch: `consumer_wait_s` of the pipeline stage they pop from,
`scan.upload` (`parallel/pipeline.stage_snapshot`), summed over the
threads that wait, so it can pass the round's wall where tasks scan
side by side.  `scan.decode`'s own figure is the upload stage waiting
for the decoder, inside the same wait, and is read only where a scan
runs without an upload stage.  Nothing where the round scans no file."""

NAME, UNIT, BETTER = "scan_wait_s", "s", "lower"
LAYER, SOURCE, MOVES = "Scan and host decode", "program_counter", \
    "round_wall_s"


def reduce(run):
    waited = run.per_round("stage.scan.upload.consumer_wait_s")
    if waited is None:
        waited = run.per_round("stage.scan.decode.consumer_wait_s")
    return waited
