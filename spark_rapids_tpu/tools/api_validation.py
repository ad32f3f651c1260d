"""API validation: diff this engine's registries against the reference's
operator checklist.

TPU analog of the reference's api_validation module
(api_validation/src/main/scala/.../ApiValidation.scala:27-46 — a
reflection tool diffing each Gpu*Exec against its CPU counterpart to
catch drift).  Here the authoritative checklist is the reference's
replacement-rule inventory (SURVEY.md Appendix A, from
GpuOverrides.scala:773-3041), and the diff is against the LIVE
registries: SUPPORTED_EXPRS, SUPPORTED_AGGS, the exec conf table and
the session surface.  Run `python -m spark_rapids_tpu.tools.gen_docs`
to refresh docs/api_coverage.md; the coverage test keeps the count
honest per commit.
"""

from __future__ import annotations

#: reference expression rules (GpuOverrides.scala:773-2669 + shims)
REFERENCE_EXPRESSIONS = """
Abs Acos Acosh Add AddMonths AggregateExpression Alias And ArrayContains
Asin Asinh
AtLeastNNonNulls Atan Atanh AttributeReference Average BRound BitwiseAnd
BitwiseNot BitwiseOr BitwiseXor CaseWhen Cbrt Ceil CheckOverflow Coalesce
CollectList Concat ConcatWs Contains Cos Cosh Cot Count CreateArray
CreateNamedStruct CurrentRow DateAdd DateAddInterval DateDiff
DateFormatClass DateSub DayOfMonth DayOfWeek DayOfYear Divide ElementAt
EndsWith EqualNullSafe EqualTo Exp Explode Expm1 First Floor FromUnixTime
GetArrayItem GetJsonObject GetMapValue GetStructField GreaterThan
GreaterThanOrEqual Greatest Hour If In InSet InitCap InputFileBlockLength
InputFileBlockStart InputFileName IntegralDivide IsNaN IsNotNull IsNull
KnownFloatingPointNormalized Lag Last LastDay Lead Least Length LessThan
LessThanOrEqual Like Literal Log Log10 Log1p Log2 Logarithm Lower
MakeDecimal Max Md5 Min Minute MonotonicallyIncreasingID Month Multiply
Murmur3Hash NaNvl NormalizeNaNAndZero Not Or PivotFirst Pmod PosExplode
Pow PromotePrecision PythonUDF Quarter Rand Remainder Rint Round RowNumber
ScalarSubquery Second ShiftLeft ShiftRight ShiftRightUnsigned Signum Sin
Sinh Size SortOrder SparkPartitionID SpecifiedWindowFrame Sqrt StartsWith
StringLPad StringLocate StringRPad StringReplace StringSplit StringTrim
StringTrimLeft StringTrimRight Substring SubstringIndex Subtract Sum Tan
Tanh TimeAdd ToDegrees ToRadians ToUnixTimestamp UnaryMinus UnaryPositive
UnboundedFollowing UnboundedPreceding UnixTimestamp UnscaledValue Upper
WeekDay WindowExpression WindowSpecDefinition Year Cast RegExpReplace
AnsiCast TimeSub
""".split()

#: reference exec rules (GpuOverrides.scala:2774-3041 + shims)
REFERENCE_EXECS = """
BatchScanExec BroadcastExchangeExec BroadcastNestedLoopJoinExec
CartesianProductExec CoalesceExec CollectLimitExec CustomShuffleReaderExec
DataWritingCommandExec ExpandExec FilterExec GenerateExec GlobalLimitExec
HashAggregateExec LocalLimitExec ProjectExec RangeExec ShuffleExchangeExec
SortAggregateExec SortExec TakeOrderedAndProjectExec UnionExec WindowExec
BroadcastHashJoinExec FileSourceScanExec ShuffledHashJoinExec
SortMergeJoinExec ArrowEvalPythonExec MapInPandasExec
FlatMapGroupsInPandasExec AggregateInPandasExec WindowInPandasExec
FlatMapCoGroupsInPandasExec
""".split()

REFERENCE_SCANS = ["CSVScan", "ParquetScan", "OrcScan"]
REFERENCE_PARTITIONINGS = ["Hash", "Range", "RoundRobin", "Single"]

#: reference-name -> (module, attribute) implementing the same concept
#: under a TPU-idiomatic spelling.  Each entry is PROBED at validate()
#: time — a dropped implementation flips the doc back to missing.
_RENAMES = {
    "AttributeReference": ("spark_rapids_tpu.exprs.base",
                           "ColumnReference"),
    "PythonUDF": ("spark_rapids_tpu.udf.exprs", "OpaquePythonUDF"),
    "AggregateExpression": ("spark_rapids_tpu.exprs.aggregates",
                            "NamedAgg"),
    "SortOrder": ("spark_rapids_tpu.execs.sort", "SortKey"),
    "WindowSpecDefinition": ("spark_rapids_tpu.exprs.window",
                             "WindowSpec"),
    "SpecifiedWindowFrame": ("spark_rapids_tpu.exprs.window",
                             "WindowFrame"),
    "CurrentRow": ("spark_rapids_tpu.exprs.window", "CURRENT_ROW"),
    "UnboundedPreceding": ("spark_rapids_tpu.exprs.window", "UNBOUNDED"),
    "UnboundedFollowing": ("spark_rapids_tpu.exprs.window", "UNBOUNDED"),
    "Explode": ("spark_rapids_tpu.exprs.collections", "Explode"),
    "PosExplode": ("spark_rapids_tpu.exprs.collections", "Explode"),
    "InSet": ("spark_rapids_tpu.exprs.predicates", "In"),
    "CountDistinct": ("spark_rapids_tpu.exprs.aggregates",
                      "CountDistinct"),
    "UnixTimestamp": ("spark_rapids_tpu.exprs.datetime",
                      "UnixTimestampFromTs"),
    "ToUnixTimestamp": ("spark_rapids_tpu.exprs.datetime",
                        "UnixTimestampFromTs"),
    "ScalarSubquery": ("spark_rapids_tpu.exprs.subquery",
                       "ScalarSubquery"),
    # ANSI cast is the same Cast evaluator under the ansi.enabled conf
    # (the GpuCast.scala:166 ANSI matrix lives in exprs/cast.py)
    "AnsiCast": ("spark_rapids_tpu.exprs.cast", "Cast"),
}


def _known_expression_names() -> set:
    """Every expression/aggregate/window concept the engine implements,
    by reference name — live registries plus probed renames."""
    import importlib

    from spark_rapids_tpu.plan import planner as PL

    names = {c.__name__ for c in PL.SUPPORTED_EXPRS}
    names |= {c.__name__ for c in PL.SUPPORTED_AGGS}
    # window machinery is spec-based rather than per-rule
    from spark_rapids_tpu.exprs import window as W

    for cls in (W.WindowExpression, W.RowNumber, W.Rank, W.DenseRank,
                W.Lead, W.Lag):
        names.add(cls.__name__)
    for ref, (mod, attr) in _RENAMES.items():
        try:
            if hasattr(importlib.import_module(mod), attr):
                names.add(ref)
        except ImportError:
            pass
    return names


#: reference exec -> (module, class-name, note).  The class is resolved
#: via importlib at validate() time, exactly like the expression path —
#: a renamed or deleted implementation flips the entry to DRIFT instead
#: of silently reporting phantom coverage.  None = known-missing.
_EXEC_MAP: dict = {
    "BatchScanExec": ("spark_rapids_tpu.io.scan", "ParquetScanExec",
                      "+OrcScanExec/CsvScanExec"),
    "FileSourceScanExec": ("spark_rapids_tpu.io.scan", "ParquetScanExec",
                           "+pushdown, coalescing"),
    "BroadcastExchangeExec": ("spark_rapids_tpu.execs.join",
                              "TpuBroadcastHashJoinExec",
                              "broadcast build collection inside"),
    "BroadcastHashJoinExec": ("spark_rapids_tpu.execs.join",
                              "TpuBroadcastHashJoinExec", ""),
    "BroadcastNestedLoopJoinExec": ("spark_rapids_tpu.execs.join",
                                    "TpuBroadcastHashJoinExec",
                                    "cross/keyless-conditional path"),
    "CartesianProductExec": ("spark_rapids_tpu.execs.join",
                             "TpuShuffledHashJoinExec", "cross path"),
    "CoalesceExec": ("spark_rapids_tpu.execs.coalesce",
                     "TpuCoalescePartitionsExec", ""),
    "CollectLimitExec": ("spark_rapids_tpu.execs.limit",
                         "TpuCollectLimitExec", ""),
    "CustomShuffleReaderExec": ("spark_rapids_tpu.execs.adaptive",
                                "CoalescedShuffleReaderExec",
                                "AQE coalesced partition specs"),
    "DataWritingCommandExec": ("spark_rapids_tpu.io.write",
                               "FileWriteExec", "+Parquet/Csv/Orc"),
    "ExpandExec": ("spark_rapids_tpu.execs.expand", "TpuExpandExec",
                   "not materialised under an aggregate whose grouping "
                   "sets are nested: docs/fusion.md"),
    "FilterExec": ("spark_rapids_tpu.execs.basic", "TpuFilterExec", ""),
    "GenerateExec": ("spark_rapids_tpu.execs.generate",
                     "TpuGenerateExec", ""),
    "GlobalLimitExec": ("spark_rapids_tpu.execs.limit",
                        "TpuGlobalLimitExec", ""),
    "LocalLimitExec": ("spark_rapids_tpu.execs.limit",
                       "TpuLocalLimitExec", ""),
    "HashAggregateExec": ("spark_rapids_tpu.execs.aggregate",
                          "TpuHashAggregateExec", ""),
    "SortAggregateExec": ("spark_rapids_tpu.execs.aggregate",
                          "TpuHashAggregateExec", "sort-agnostic"),
    "ProjectExec": ("spark_rapids_tpu.execs.basic", "TpuProjectExec", ""),
    "RangeExec": ("spark_rapids_tpu.execs.basic", "TpuRangeExec", ""),
    "ShuffleExchangeExec": ("spark_rapids_tpu.execs.exchange",
                            "TpuShuffleExchangeExec", "+collective"),
    "ShuffledHashJoinExec": ("spark_rapids_tpu.execs.join",
                             "TpuShuffledHashJoinExec", ""),
    "SortMergeJoinExec": ("spark_rapids_tpu.execs.join",
                          "TpuShuffledHashJoinExec",
                          "hash join instead, like the reference"),
    "SortExec": ("spark_rapids_tpu.execs.sort", "TpuSortExec",
                 "out-of-core"),
    "TakeOrderedAndProjectExec": ("spark_rapids_tpu.execs.sort",
                                  "TpuTakeOrderedAndProjectExec", ""),
    "UnionExec": ("spark_rapids_tpu.execs.basic", "TpuUnionExec", ""),
    "WindowExec": ("spark_rapids_tpu.execs.window", "TpuWindowExec", ""),
    "ArrowEvalPythonExec": ("spark_rapids_tpu.execs.python_exec",
                            "TpuMapInArrowExec",
                            "arrow-batch python eval"),
    "MapInPandasExec": ("spark_rapids_tpu.execs.python_exec",
                        "TpuMapInPandasExec", ""),
    "FlatMapGroupsInPandasExec": ("spark_rapids_tpu.execs.python_exec",
                                  "TpuFlatMapGroupsInPandasExec", ""),
    "AggregateInPandasExec": ("spark_rapids_tpu.execs.python_exec",
                              "TpuAggregateInPandasExec", ""),
    "WindowInPandasExec": ("spark_rapids_tpu.execs.python_exec",
                           "TpuWindowInPandasExec",
                           "unbounded frames"),
    "FlatMapCoGroupsInPandasExec": (
        "spark_rapids_tpu.execs.python_exec",
        "TpuFlatMapCoGroupsInPandasExec", ""),
}


def _resolve_execs():
    """Probe every _EXEC_MAP entry against the live modules.  Returns
    (resolved {ref: display}, missing [ref], drift [ref]) where drift
    means the map names a module/class that does not exist."""
    import importlib

    resolved: dict = {}
    missing: list = []
    drift: list = []
    for ref, entry in _EXEC_MAP.items():
        if entry is None:
            missing.append(ref)
            continue
        mod, cls, note = entry
        try:
            ok = hasattr(importlib.import_module(mod), cls)
        except ImportError:
            ok = False
        if ok:
            resolved[ref] = f"{cls}" + (f" ({note})" if note else "")
        else:
            drift.append(ref)
    return resolved, sorted(missing), sorted(drift)


def validate() -> dict:
    """Return {'expressions': (supported, missing), 'execs': ...} by
    diffing the live registries against the reference checklist."""
    have = _known_expression_names()
    exprs_ok = sorted(n for n in REFERENCE_EXPRESSIONS if n in have)
    exprs_missing = sorted(n for n in set(REFERENCE_EXPRESSIONS) - have)

    resolved, missing, drift = _resolve_execs()
    exec_map = dict(resolved)
    for ref in missing:
        exec_map[ref] = None
    for ref in drift:
        exec_map[ref] = None

    return {
        "expressions": (exprs_ok, exprs_missing),
        "execs": (sorted(resolved), missing + drift, exec_map),
        "exec_drift": drift,
        "scans": (list(REFERENCE_SCANS), []),
        "partitionings": (list(REFERENCE_PARTITIONINGS), []),
    }


def assert_no_drift() -> None:
    """Hard pass: raise when the exec map names implementations that no
    longer resolve (the lint REG005 rule; tpulint calls this module the
    same way).  Missing-by-design entries (None) are fine — only DRIFT
    (a named module/class that vanished) fails."""
    drift = validate()["exec_drift"]
    if drift:
        raise AssertionError(
            "api_validation exec map drift (implementation vanished): "
            + ", ".join(drift)
            + " — update _EXEC_MAP in tools/api_validation.py")


def coverage_md() -> str:
    v = validate()
    eo, em = v["expressions"]
    xo, xm, xmap = v["execs"]
    lines = [
        "# API coverage vs the reference checklist",
        "",
        "Generated by `python -m spark_rapids_tpu.tools.gen_docs` from "
        "the live registries diffed against the reference's replacement "
        "rules (SURVEY.md Appendix A / GpuOverrides.scala) — do not "
        "edit.",
        "",
        f"## Expressions: {len(eo)}/{len(set(REFERENCE_EXPRESSIONS))}",
        "",
        "Missing: " + (", ".join(em) if em else "none"),
        "",
        f"## Execs: {len(xo)}/{len(xmap)}",
        "",
        "| reference exec | this engine |",
        "|---|---|",
    ]
    for k in sorted(xmap):
        lines.append(f"| {k} | {xmap[k] or '**missing**'} |")
    lines += [
        "",
        f"## Scans: {len(v['scans'][0])}/{len(REFERENCE_SCANS)} — "
        + ", ".join(v["scans"][0]),
        f"## Partitionings: {len(v['partitionings'][0])}"
        f"/{len(REFERENCE_PARTITIONINGS)} — "
        + ", ".join(v["partitionings"][0]),
        "",
    ]
    return "\n".join(lines)


if __name__ == "__main__":
    assert_no_drift()
    v = validate()
    eo, em = v["expressions"]
    xo, xm, _ = v["execs"]
    print(f"expressions {len(eo)} supported / {len(em)} missing; "
          f"execs {len(xo)} supported / {len(xm)} missing; no drift")
