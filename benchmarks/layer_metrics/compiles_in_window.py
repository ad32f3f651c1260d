"""Programs XLA's backend built or loaded inside the window
(`/jax/core/compile/backend_compile_duration` events), all rounds
together.  Should be 0: warm-up is there to see to it."""

NAME, UNIT, BETTER = "compiles_in_window", "count", "lower"
LAYER, SOURCE, MOVES = "Compiled programs", "program_counter", "rows_per_s"


def reduce(run):
    return sum(r.counters["backend_compiles"] for r in run.rounds)
