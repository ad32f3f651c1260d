"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip, in GB
(1e9 bytes), over the whole process."""

NAME, UNIT, BETTER = "peak_hbm_gb", "GB", "lower"
LAYER, SOURCE, MOVES = "Memory", "program_counter", "round_wall_s"


def reduce(run):
    return run.memory_peak_bytes / 1e9 or None
