"""Adaptive (runtime-statistics) execution.

The AQE analog (ref: sql-plugin AQE integration —
GpuCustomShuffleReaderExec.scala coalesced/skew shuffle reads,
GpuTransitionOverrides.scala:65-99 adaptive transitions, and Spark's
AdaptiveSparkPlanExec stage re-optimization): exchanges double as query
stages, and once a map stage materializes, downstream strategy decisions
re-plan against ACTUAL sizes instead of scan-time estimates.

Two adaptive rewrites, both driven by `materialize_stats()` (the
MapOutputStatistics analog on TpuShuffleExchangeExec):

- `TpuAdaptiveJoinExec`: defers the shuffled-vs-broadcast decision to
  runtime.  Both side's map stages run first; if one side's measured
  bytes fit the broadcast threshold the join executes as a broadcast
  hash join reading the already-shuffled blocks (no re-scan — the map
  output IS the build input), otherwise as the planned partition-wise
  join over coalesced reduce partitions.
- `CoalescedShuffleReaderExec`: groups adjacent reduce partitions until
  each group reaches the advisory byte target, so a shuffle that wrote
  many tiny partitions runs few reduce tasks (the
  coalesce-shuffle-partitions rule).

Design note: on TPU the payoff is larger than on GPU — every reduce
task dispatches compiled programs whose shapes bucket by batch size, so
fewer, fuller partitions mean fewer dispatches and better MXU/VPU
utilization, and a runtime broadcast switch removes a whole exchange's
worth of device round trips.
"""

from __future__ import annotations

import threading
from typing import Iterator, Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import register, get_conf
from spark_rapids_tpu.execs.base import TpuExec

ADAPTIVE_ENABLED = register(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Re-plan join strategy and reduce-partition grouping against "
    "measured map-output sizes once shuffle stages materialize (the "
    "spark.sql.adaptive.enabled analog).")

ADVISORY_PARTITION_BYTES = register(
    "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeBytes", 64 << 20,
    "Target bytes per reduce task after adaptive partition coalescing "
    "(the spark.sql.adaptive.advisoryPartitionSizeInBytes analog).")

SKEW_FACTOR = register(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor", 5.0,
    "A reduce partition is skewed when its bytes exceed this multiple "
    "of the median partition size (and the threshold below) — the "
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor analog.")

SKEW_THRESHOLD_BYTES = register(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThresholdBytes",
    64 << 20,
    "Minimum bytes before a partition is considered skewed (the "
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdBytes "
    "analog).")


#: one reduce-side read unit: (reduce_id, slice_index, slice_count).
#: (rid, 0, 1) reads the whole partition; (rid, i, k) reads the i-th of
#: k block-wise slices — the stream side of a skew split.  The build
#: side pairs each slice with a FULL (rid, 0, 1) read (build-side
#: completeness per split, Spark's OptimizeSkewedJoin contract).
PartSpec = tuple


def plan_coalesced_groups(part_bytes: Sequence[int],
                          target: int) -> list[list[int]]:
    """Group ADJACENT reduce partitions until each group reaches the
    advisory target (hash co-partitioning is preserved only by identical
    adjacent grouping on every side).  Empty partitions merge for
    free."""
    groups: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for rid, b in enumerate(part_bytes):
        cur.append(rid)
        cur_bytes += b
        if cur_bytes >= target:
            groups.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        groups.append(cur)
    return groups or [[0]]


def _skew_split_side(join_type: str) -> Optional[str]:
    """Which side may be sliced without changing join semantics: a
    sliced side's rows each appear in exactly one slice, so inner and
    <side>-preserving joins stay correct; the OTHER side must stay
    complete per slice (it is the hash-build / null-producing side)."""
    if join_type == "inner":
        return "either"
    if join_type in ("left_outer", "left_semi", "left_anti"):
        return "left"
    if join_type == "right_outer":
        return "right"
    return None  # full_outer: no sound single-side split


def plan_skew_groups(lbytes: Sequence[int], rbytes: Sequence[int],
                     target: int, factor: float, threshold: int,
                     join_type: str,
                     lblocks: Optional[Sequence[int]] = None,
                     rblocks: Optional[Sequence[int]] = None
                     ) -> Optional[tuple[list, list, int]]:
    """Skew-aware aligned read plans for both sides.

    Returns (left_groups, right_groups, n_splits) where each group is a
    list of PartSpec read units and the two lists pair 1:1 into
    partition-wise join tasks — or None when nothing is skewed (caller
    falls back to plain coalescing).  A skewed partition becomes k
    tasks: k slices on the splittable side, each paired with a FULL
    read of the partition on the other side (ref:
    GpuCustomShuffleReaderExec's PartialReducerPartitionSpec handling /
    Spark's OptimizeSkewedJoin)."""
    import statistics as _st

    side = _skew_split_side(join_type)
    if side is None or not lbytes:
        return None
    med_l = _st.median(lbytes)
    med_r = _st.median(rbytes)

    def skewed(b, med) -> bool:
        return b > threshold and b > factor * max(med, 1)

    lgroups: list[list[PartSpec]] = []
    rgroups: list[list[PartSpec]] = []
    plain: list[int] = []
    plain_bytes: list[int] = []
    n_splits = 0

    def flush_plain():
        if not plain:
            return
        for grp in plan_coalesced_groups(plain_bytes, target):
            rids = [plain[i] for i in grp]
            lgroups.append([(r, 0, 1) for r in rids])
            rgroups.append([(r, 0, 1) for r in rids])
        plain.clear()
        plain_bytes.clear()

    for rid, (lb, rb) in enumerate(zip(lbytes, rbytes)):
        split_left = skewed(lb, med_l) and side in ("left", "either")
        split_right = skewed(rb, med_r) and side in ("right", "either")
        if split_left and split_right:
            # slicing both sides of one partition needs the cartesian
            # pairing of slices; split only the bigger side instead
            if lb >= rb:
                split_right = False
            else:
                split_left = False
        if not (split_left or split_right):
            plain.append(rid)
            plain_bytes.append(lb + rb)
            continue
        flush_plain()
        big = lb if split_left else rb
        k = max(2, -(-big // max(target, 1)))
        # slices deal BLOCKS round-robin: more slices than committed
        # blocks would be empty tasks that still pay a full build-side
        # read + hash build each
        blocks = (lblocks if split_left else rblocks)
        if blocks is not None and rid < len(blocks):
            k = min(k, max(2, blocks[rid]))
        if blocks is not None and rid < len(blocks) and blocks[rid] <= 1:
            # a single-block partition cannot slice: leave it whole
            plain.append(rid)
            plain_bytes.append(lb + rb)
            continue
        n_splits += k
        for i in range(k):
            if split_left:
                lgroups.append([(rid, i, k)])
                rgroups.append([(rid, 0, 1)])
            else:
                lgroups.append([(rid, 0, 1)])
                rgroups.append([(rid, i, k)])
    if n_splits == 0:
        return None
    flush_plain()
    return lgroups, rgroups, n_splits


class CoalescedShuffleReaderExec(TpuExec):
    """Reduce-side reader exposing groups of shuffle-partition read
    units as single partitions (ref: GpuCustomShuffleReaderExec —
    CoalescedPartitionSpec for adjacent grouping and
    PartialReducerPartitionSpec for skew slices).

    Groups hold PartSpec units: plain int rids (whole partitions) or
    (rid, i, k) tuples reading the i-th of k block-wise slices of a
    skewed partition (blocks deal round-robin by index, which is
    deterministic: the map output order is fixed once committed)."""

    def __init__(self, exchange, groups: list):
        super().__init__(exchange)
        self.groups = [[(g, 0, 1) if isinstance(g, int) else tuple(g)
                        for g in grp] for grp in groups]
        # rids visited more than once (a sliced partition, or the full
        # partition paired against each slice) need the NON-consuming
        # exchange read; single-visit rids keep the consuming read that
        # frees blocks as early as possible
        counts: dict[int, int] = {}
        for grp in self.groups:
            for rid, _i, _k in grp:
                counts[rid] = counts.get(rid, 0) + 1
        self._multi_read = {r for r, c in counts.items() if c > 1}

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return len(self.groups)

    @property
    def output_partitioning(self):
        # grouped partitions still co-partition with any reader using
        # the SAME groups, but not with the raw partitioning width —
        # adaptive join builds both sides with identical groups
        return None

    def node_desc(self) -> str:
        n_raw = self.children[0].num_partitions
        n_split = sum(1 for grp in self.groups
                      for (_r, _i, k) in grp if k > 1)
        extra = f", {n_split} skew slices" if n_split else ""
        return (f"CoalescedShuffleReaderExec [{n_raw} -> "
                f"{len(self.groups)} partitions{extra}]")

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        ex = self.children[0]
        for rid, i, k in self.groups[p]:
            if rid in self._multi_read and hasattr(
                    ex, "execute_partition_keep"):
                source = ex.execute_partition_keep(rid)
            else:
                source = ex.execute_partition(rid)
            for bi, b in enumerate(source):
                if k == 1 or bi % k == i:
                    yield self._count_output(b)

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)


class TpuAdaptiveJoinExec(TpuExec):
    """Join whose physical strategy is chosen at first execution from
    measured map-output statistics (ref: Spark's
    DynamicJoinSelection/AdaptiveSparkPlanExec re-optimization, which
    the reference plugs into via GpuCustomShuffleReaderExec).

    Children are the two shuffle exchanges the static planner would
    have used for a partition-wise join; the runtime decision only ever
    *improves* on that plan (broadcast from materialized blocks, or
    coalesced reduce groups), so there is no regression risk relative
    to static planning."""

    def __init__(self, left_keys, right_keys, join_type: str,
                 left_exchange, right_exchange, condition=None,
                 null_safe=()):
        super().__init__(left_exchange, right_exchange)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition
        self.null_safe = tuple(null_safe)
        self._decided: Optional[TpuExec] = None
        self._decision = "undecided"
        self._lock = threading.Lock()
        #: set by the runtime-filter planner pass: which side hosts a
        #: filter-building map stage and must materialize FIRST, so the
        #: published filter prunes the other side's scans
        #: (plan/runtime_filter.py build-before-probe ordering)
        self.rf_build_first: Optional[str] = None
        # schema comes from the inner join exec; build one eagerly so
        # schema/explain work before execution (the static shape)
        self._template = self._make_shuffled(left_exchange,
                                             right_exchange)

    def _make_shuffled(self, lex, rex) -> TpuExec:
        from spark_rapids_tpu.execs.join import TpuShuffledHashJoinExec

        return TpuShuffledHashJoinExec(
            self.left_keys, self.right_keys, self.join_type, lex, rex,
            condition=self.condition, partition_wise=True,
            null_safe=self.null_safe)

    @property
    def schema(self) -> T.Schema:
        return self._template.schema

    @property
    def num_partitions(self) -> int:
        # STATIC width (the template's): reading partition counts must
        # never trigger _decide() — the planner inspects num_partitions
        # while building the tree, and materializing map stages at plan
        # time would execute scans for explain-only queries.  Shrunken
        # widths (broadcast/coalescing) leave the tail partitions empty;
        # EXPANDED widths (skew splits) overflow-drain through the last
        # static partition (see execute_partition).
        return self._template.num_partitions

    def node_desc(self) -> str:
        from spark_rapids_tpu.execs.join import describe_condition

        return (f"TpuAdaptiveJoinExec [{self.join_type}] "
                f"strategy={self._decision}"
                + describe_condition(self.join_type, self.condition))

    def additional_metrics(self):
        return [("adaptiveBroadcasts", "ESSENTIAL"),
                ("coalescedPartitions", "MODERATE"),
                ("skewSplits", "ESSENTIAL")]

    # -- runtime decision ------------------------------------------------ #

    def _decide(self) -> TpuExec:
        with self._lock:
            if self._decided is not None:
                return self._decided
            from spark_rapids_tpu.execs.join import (
                TpuBroadcastHashJoinExec,
            )
            from spark_rapids_tpu.plan.planner import (
                BROADCAST_THRESHOLD,
                broadcast_candidates,
            )

            conf = get_conf()
            thr = conf.get(BROADCAST_THRESHOLD)
            lex, rex = self.children
            if self.rf_build_first == "right":
                # build-before-probe: the right map stage streams the
                # join's build input through its runtime-filter
                # collector; materializing it first publishes the
                # filter before the left (probe) map stage scans
                rstats = rex.materialize_stats()
                lstats = lex.materialize_stats()
            else:
                lstats = lex.materialize_stats()
                rstats = rex.materialize_stats()
            lbytes = sum(b for b, _ in lstats)
            rbytes = sum(b for b, _ in rstats)

            jt = self.join_type
            candidates = broadcast_candidates(jt, lbytes, rbytes, thr)
            if candidates:
                side, nbytes = min(candidates, key=lambda c: c[1])
                self.metrics["adaptiveBroadcasts"].add(1)
                self._decision = (f"broadcast[{side} "
                                  f"{nbytes >> 10}KiB<=thr]")
                self._decided = TpuBroadcastHashJoinExec(
                    self.left_keys, self.right_keys, jt, lex, rex,
                    condition=self.condition, build_side=side,
                    null_safe=self.null_safe)
            else:
                target = conf.get(ADVISORY_PARTITION_BYTES)
                lb_list = [b for b, _ in lstats]
                rb_list = [b for b, _ in rstats]
                skew = plan_skew_groups(
                    lb_list, rb_list, target, conf.get(SKEW_FACTOR),
                    conf.get(SKEW_THRESHOLD_BYTES), jt,
                    lblocks=lex.block_counts()
                    if hasattr(lex, "block_counts") else None,
                    rblocks=rex.block_counts()
                    if hasattr(rex, "block_counts") else None)
                if skew is not None:
                    lgroups, rgroups, n_splits = skew
                    self.metrics["skewSplits"].add(n_splits)
                    self._decision = (f"shuffled[skew: {n_splits} "
                                      f"splits, {len(lgroups)} tasks]")
                    self._decided = self._make_shuffled(
                        CoalescedShuffleReaderExec(lex, lgroups),
                        CoalescedShuffleReaderExec(rex, rgroups))
                    self._adopt_metrics()
                    return self._decided
                per_part = [lb + rb for lb, rb in zip(lb_list, rb_list)]
                groups = plan_coalesced_groups(per_part, target)
                if len(groups) < len(per_part):
                    self.metrics["coalescedPartitions"].add(
                        len(per_part) - len(groups))
                    self._decision = (f"shuffled[{len(per_part)}->"
                                      f"{len(groups)} parts]")
                    self._decided = self._make_shuffled(
                        CoalescedShuffleReaderExec(lex, groups),
                        CoalescedShuffleReaderExec(rex, groups))
                else:
                    self._decision = "shuffled"
                    self._decided = self._template
            self._adopt_metrics()
            return self._decided

    def _adopt_metrics(self) -> None:
        # the decided exec is not a child, so metric collection would
        # miss it: adopt its Metric objects (live references) under
        # this node, keeping only the adaptive-specific ones
        own = {"adaptiveBroadcasts", "coalescedPartitions", "skewSplits"}
        for k, v in self._decided.metrics.items():
            if k not in own:
                self.metrics[k] = v

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        decided = self._decide()
        n_static = self._template.num_partitions
        if p < decided.num_partitions:
            yield from decided.execute_partition(p)
        # skew splitting can EXPAND the task count past the static
        # width the parent iterates (num_partitions must stay static:
        # parents read it before any partition executes, and deciding
        # at plan time would materialize map stages for explain-only
        # queries).  The last static partition drains the overflow so
        # no task is silently dropped.
        if p == n_static - 1:
            for q in range(n_static, decided.num_partitions):
                yield from decided.execute_partition(q)

    def execute(self) -> Iterator[ColumnarBatch]:
        yield from self._decide().execute()

    def close(self) -> None:
        # the decided exec is NOT a child (children stay the two
        # exchanges), so default propagation would miss its cleanup —
        # e.g. a runtime broadcast join's spillable build handle
        with self._lock:
            decided = self._decided
        if decided is not None and decided is not self._template:
            decided.close()
        self._template.close()  # idempotently closes the exchanges too
        super().close()
