"""Process CPU seconds per round (`os.times()` user + system, every
thread): what the decode pool burns while the wall hides it behind the
host's cores."""

NAME, UNIT, BETTER = "host_cpu_s", "s", "lower"
LAYER, SOURCE, MOVES = "Scan and host decode", "host_clock", "round_wall_s"


def reduce(run):
    return run.per_round("cpu_s")
