"""Rows per round that the aggregates' merge programs took in: the
`capacity` of the concatenated partials, summed over the round's
`agg.merge` spans.  Over `agg_groups` it says how many times a group
was merged again: a DISTINCT whose partials do not collapse pays every
re-merge of what is pending in full.  0 where the aggregates ran
(`agg.update` spans) and nothing was merged, which is what a side that
reaches its aggregate as one batch reads; nothing where the program
has neither span."""

NAME, UNIT, BETTER = "agg_merge_rows", "rows", "lower"
LAYER, SOURCE, MOVES = "Operators", "program_span", "round_wall_s"


def reduce(run):
    if not any(s.name in ("agg.update", "agg.merge") for s in run.spans):
        return None
    merged = sum(s.attrs.get("capacity") or 0 for s in run.spans
                 if s.name == "agg.merge")
    return merged / len(run.rounds)
