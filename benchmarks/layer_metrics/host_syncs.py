"""Times per round the host waited for the device to hand something
back: blocking readbacks counted by the pipeline stages
(`stage_snapshot()` `readbacks`) plus result batches fetched
(`query.fetch.batch` spans)."""

NAME, UNIT, BETTER = "host_syncs", "count", "lower"
LAYER, SOURCE, MOVES = "Result fetch", "program_counter", "round_wall_s"


def reduce(run):
    stages = {k.split(".readbacks")[0] for r in run.rounds
              for k in r.counters if k.endswith(".readbacks")}
    readbacks = sum(run.per_round(f"{s}.readbacks") or 0 for s in stages)
    fetches = sum(1 for s in run.spans if s.name == "query.fetch.batch")
    return readbacks + fetches / len(run.rounds)
