"""What the readers of the collective operators' counters share: a
metric summed, per round, over the `query.operator` instants of the
plan's `TpuCollective*` operators (the engine's history worker stamps
them at a query's end when the tracer is on; only a count that moved
is on an instant).  None where no collective operator reported the
metric: a cell of one chip, or a program that does not count it."""


def counts(run, key: str):
    found = [s.attrs[key] for s in run.spans
             if s.name == "query.operator" and key in s.attrs
             and s.attrs.get("desc", "").startswith("TpuCollective")]
    if not found:
        return None
    return sum(found) / len(run.rounds)
