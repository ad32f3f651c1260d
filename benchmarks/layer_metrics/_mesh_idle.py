"""The first chip's idle seconds under the host's work between a
mesh's stage programs, split out of what `_idle.py` puts down to
dispatch.

Shared by the three `idle_mesh_*` readers; no metric of its own.

On the mesh an `exec.<op>` span is the whole stage: it encloses the
drain of the child, every stacking of a stage's input, every launch,
every counts fetch and every shrink, so `idle_dispatch_s` is one number
for all of them.  `parallel/spmd.py` opens one span a call round three
of them: `mesh.stack` (`shard_stack_rounds`: the eager pads, a stack a
leaf a device, the placement), `mesh.shrink` (`shrink_rounds` and the
two `unstack_*`: a piece and a cut a leaf a (round, shard)) and
`mesh.launch` (the host's side of a stage program's dispatch); the
fourth, the counts fetch, is a `pipe.readback` and goes to sync.

The rule is `_idle.py`'s, with its clock offsets, its 1 ms limit, its
first chip and its window: an idle instant is offered to `CAUSES` in
order, `_idle.CAUSES` with the three mesh spans put before dispatch.
So what goes to upload, sync and scan is what `_idle.py` gives them,
and the three split what dispatch, or nobody, held: their sum is at
most `idle_dispatch_s` plus the idle seconds no cause of `_idle.py`
covered (a shrink after the stage's timed region has closed).
"""

import re

import numpy as np

from benchmarks.harness import trace_reduce
from benchmarks.harness.trace_reduce import gaps, merged
from benchmarks.layer_metrics import _idle

MESH = ("mesh.stack", "mesh.shrink", "mesh.launch")
_LAST = tuple(c for c in _idle.CAUSES if c[0] == "dispatch")
#: upload, sync, scan, the mesh's three, then dispatch
CAUSES = tuple(c for c in _idle.CAUSES if c not in _LAST) \
    + tuple((name, re.compile(re.escape(name))) for name in MESH) + _LAST


def attribute(ops: np.ndarray, spans: list, lo: float, hi: float) -> dict:
    """cause -> idle nanoseconds of [lo, hi] that go to it, as
    `_idle.attribute` reckons them, over this module's `CAUSES`."""
    busy = merged(ops, lo, hi)
    out, open_so_far, before = {}, np.zeros((0, 2)), 0.0
    for cause, names in CAUSES:
        mine = np.array([(s, e) for n, s, e in spans if names.fullmatch(n)],
                        dtype=np.float64).reshape(-1, 2)
        open_so_far = merged(np.vstack([open_so_far, mine]), lo, hi)
        neither = np.vstack([busy, gaps(open_so_far, lo, hi)])
        under = (hi - lo) - trace_reduce.busy_ns(neither, lo, hi)
        out[cause] = under - before
        before = under
    return out


def idle_by_cause(run):
    """cause -> idle seconds per round on the first chip, or None
    where there is no device trace or no common clock.  Worked out
    once per run."""
    if "_mesh_idle_by_cause" not in run.__dict__:
        run.__dict__["_mesh_idle_by_cause"] = _idle_by_cause(run)
    return run.__dict__["_mesh_idle_by_cause"]


def _idle_by_cause(run):
    if run.trace is None or not run.trace.chips:
        return None
    offset = _idle.clock_offset_ns(run)
    if offset is None:
        return None
    chip = run.trace.chips[0]
    lo, hi = trace_reduce.window(run.trace)
    spans = [(s.name, s.ts_ns + offset, s.ts_ns + s.dur_ns + offset)
             for s in run.spans if s.dur_ns]
    by_cause = attribute(chip.ops if len(chip.ops) else chip.modules,
                         spans, lo, hi)
    return {c: ns / 1e9 / len(run.rounds) for c, ns in by_cause.items()}


def idle_s(run, span: str):
    """The reader's answer: idle seconds per round under the mesh span
    so named; None where they cannot be told, or where the program
    records no such span (one from before them, or a cell of one
    chip)."""
    if not any(s.name == span for s in run.spans):
        return None
    found = idle_by_cause(run)
    return None if found is None else found[span]
