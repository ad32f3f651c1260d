"""Wall of the first warm-up round, with the compile cache as the run
found it: compiling in a checkout's first run, reading the cache in
the later ones."""

NAME, UNIT, BETTER = "first_round_s", "s", "lower"
LAYER, SOURCE, MOVES = "Compiled programs", "host_clock", "setup_s"


def reduce(run):
    return run.warmup[0].wall_s
