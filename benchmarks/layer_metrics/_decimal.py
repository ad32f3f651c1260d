"""What the readers of the wide-decimal metrics share: the engine's
`decimal.wide` events (one a 128-bit operation kind a program ran, with
the rows it ran over: `op`, `rows`) and the `agg.update` spans of the
aggregates that hold such arithmetic (`wide_decimal`, the 128-bit
accumulators; `decimal_input_words`, the device words a row of the
decimal columns the update reads).  The arithmetic runs inside the
aggregate's programs, `jit_tpu__TpuHashAggregateExec__*`.  Nothing
where the spans hold none of it: a program without wide decimals."""

from benchmarks.layer_metrics import _operators


def wide_events(run) -> list:
    return [s for s in run.spans if s.name == "decimal.wide"]


def updates(run) -> list:
    """The `agg.update` spans of aggregates with 128-bit arithmetic."""
    return [s for s in run.spans if s.name == "agg.update"
            and s.attrs.get("decimal_input_words") is not None]


def busy_s(run):
    """Per round, the first chip's seconds in the aggregate programs,
    where the round ran any 128-bit decimal arithmetic."""
    if not wide_events(run):
        return None
    return _operators.busy_s(run, "TpuHashAggregateExec")
