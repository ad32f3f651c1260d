"""TPC-DS query 67 for the cells that run it on a mesh of executors:
`q67.py`'s DataFrame and its plain reference, by import and unchanged
(the answer of four executors is the answer of one).

One thing is this file's own.  A program that has no collective
window operator plans q67's `rank()` above a collective aggregate
behind the LOCAL exchange, whose reduce partitions then hold batches
parked on different chips; the window's concat of them raises (JAX's
"incompatible devices"), but only after every program below the
window has been compiled for the mesh, the materialised Expand's
nine-key update among them: at the rehearsal's cut on four chips
that program had not reached its failure 300 s into a first round (my
chip run, PR 32).  Such a program cannot run these cells; it is told
so at once, before a round, and exits non-zero, as `q67.py` tells a
program whose last sort does not count its input.
"""

from benchmarks.queries import q67
from benchmarks.queries.q67 import (  # noqa: F401
    BEST,
    COLUMNS,
    DMS,
    DRIVER,
    KEYS,
    ORDERED,
    combine,
    partial,
)


def _refuse_a_program_without_a_collective_window() -> None:
    from spark_rapids_tpu.execs import collective

    if not hasattr(collective, "TpuCollectiveWindowExec"):
        raise SystemExit(
            "benchmarks.run REFUSED: this program has no collective window "
            "operator (execs/collective.py:TpuCollectiveWindowExec); under "
            "the mesh q67's rank() is handed batches that live on different "
            "chips and its collect fails (PERF.md section 6, PR 32)")


def build(session, frames):
    _refuse_a_program_without_a_collective_window()
    return q67.build(session, frames)
