"""Seconds per round in planning, tagging and lowering.

`query.plan` is opened by `session.py` round `plan_query`, inside
which `planner.py` opens `query.tag` and `query.lower`: the one span
covers all three, so they are not added up.
"""

NAME, UNIT, BETTER = "plan_s", "s", "lower"
LAYER, SOURCE, MOVES = "Front end and planner", "program_span", "round_wall_s"


def reduce(run):
    return run.span_seconds("query.plan")
