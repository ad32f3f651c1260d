"""Collective (tier-2) exchange-bearing operators.

When the collective shuffle transport is active, the planner lowers
EVERY exchange-bearing pipeline — grouped aggregation, shuffled hash
join, distributed ORDER BY — into fused SPMD programs over the active
mesh (ref: the role GpuShuffleExchangeExecBase + RapidsShuffleTransport
play under GpuHashAggregateExec / GpuShuffledHashJoinBase /
GpuSortExec, re-designed for TPU: map-side work, the murmur3- or
range-routed `all_to_all` over the mesh axis, and reduce-side work are
single shard_map/jit programs — no host hop between map and reduce,
collectives ride ICI scheduled by XLA; SURVEY.md §5.8).

Inputs stream through BOUNDED per-shard rounds (conf
spark.rapids.tpu.shuffle.collective.roundRows): each round stacks at
most that many rows per shard — so a skewed or large child never forces
one stop-the-world host gather (the streaming discipline of the
reference's shuffle writer).

STAGE EXECUTION (docs/spmd.md): a whole query stage lowers to O(1)
partitioned pjit programs over the mesh with NamedSharding end-to-end
— rounds are a lax.scan INSIDE the compiled program (bucketed by
.spmd.bucketRounds), inputs arrive as global sharded arrays, and the
host syncs are one counts fetch a program boundary.  Each exec has ONE
driver (`_materialize`), and every program it dispatches compiles
through parallel/spmd.py's stage builders, i.e. through cached_jit."""

from __future__ import annotations

import dataclasses as _dc
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.column import pad_capacity
from spark_rapids_tpu.config import register, get_conf
from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.exprs.aggregates import NamedAgg
from spark_rapids_tpu.exprs.base import EvalContext, Expression
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.trace import ledger as _ledger

COLLECTIVE_ROUND_ROWS = register(
    "spark.rapids.tpu.shuffle.collective.roundRows", 1 << 20,
    "Per-shard row budget of one collective exchange round: child "
    "batches stream through the fused all_to_all program in rounds of "
    "at most this many rows per shard instead of one unbounded gather "
    "(the batch-at-a-time discipline of the reference's shuffle "
    "writer, GpuShuffleExchangeExec.scala:167-270).")

SPMD_BUCKET_ROUNDS = register(
    "spark.rapids.tpu.shuffle.collective.spmd.bucketRounds", 8,
    "Maximum exchange rounds folded into ONE partitioned stage "
    "program's in-program scan (agg and join stream stages; the sort "
    "stage folds ALL rounds into its single program because range "
    "bounds must see every round's sample).  Bounds the stage's "
    "resident input footprint at bucketRounds x roundRows rows per "
    "shard; round counts inside a bucket pad to a power of two so the "
    "scan length — part of the compiled program's key — takes a "
    "handful of values instead of one executable per data-dependent "
    "round count (docs/spmd.md).  The planner reads this at plan "
    "time (collective.stage_bucket_rounds), so the stage shape is "
    "part of the plan, not a collect-time surprise.",
    check=lambda v: v >= 1)


def stage_bucket_rounds(conf=None) -> int:
    """spmd.bucketRounds — THE planner seam deciding how collective
    stage boundaries compile.  Read at plan time and pinned into the
    exec (and therefore into explain()/the event log's plan report),
    so a conf flip after planning cannot silently change an
    already-planned stage's execution shape."""
    conf = conf or get_conf()
    return int(conf.get(SPMD_BUCKET_ROUNDS))


def _grid_rows(rounds) -> int:
    """Live rows of a rounds[r][d] grid whose counts the host holds
    (`_shard_rounds` pins them)."""
    return sum(b.concrete_num_rows() for shards in rounds for b in shards)


def _grid_capacity(rounds) -> int:
    """The capacity `shard_stack_rounds` unifies a grid to."""
    return max(b.capacity for shards in rounds for b in shards)


def _fold_groups(groups: list[list[ColumnarBatch]],
                 schema: T.Schema) -> list[ColumnarBatch]:
    """Per-shard batch lists -> one batch per shard (empty batches for
    shards that received nothing)."""
    out = []
    for group in groups:
        if not group:
            out.append(ColumnarBatch.empty(schema))
        elif len(group) == 1:
            out.append(group[0])
        else:
            out.append(concat_batches(group))
    return out


class _CollectiveBase(TpuExec):
    """Shared round-streaming driver for collective execs.

    Subclasses produce their output as ONE batch per mesh shard
    (`_materialize`); per-partition consumers (a sort, limit, or join
    stacked above) read shard p through `execute_partition(p)`."""

    mesh = None  # set by subclass __init__

    def _init_stage(self, bucket_rounds: Optional[int]) -> None:
        """Pin the stage execution shape at construction (= plan)
        time; the planner passes stage_bucket_rounds() through so the
        decision is part of the plan."""
        self.bucket_rounds = max(
            1, stage_bucket_rounds() if bucket_rounds is None
            else int(bucket_rounds))

    def _stage_desc(self) -> str:
        return f"stage=spmd(bucket={self.bucket_rounds})"

    @property
    def num_partitions(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    def _shard_rounds(self, child: TpuExec, size_to_rows: bool = False
                      ) -> Iterator[list[ColumnarBatch]]:
        """Drain child partitions into per-shard batch groups, yielding
        a round whenever any shard reaches the row budget.  Always
        yields at least one round (of empties) so downstream programs
        emit schema-correct output for empty inputs.  `size_to_rows`
        cuts a large batch to the capacity its counted rows pad to, as
        TpuSortExec does for a global sort: a filter's output keeps its
        input's capacity, and a stage is paid by capacity."""
        from spark_rapids_tpu.execs.sort import _COUNT_ABOVE_CAPACITY
        from spark_rapids_tpu.parallel.pipeline import device_read_int

        n = self.num_partitions
        budget = get_conf().get(COLLECTIVE_ROUND_ROWS)
        per_shard: list[list[ColumnarBatch]] = [[] for _ in range(n)]
        rows = [0] * n
        yielded = False
        for p in range(child.num_partitions):
            for b in child.execute_partition(p):
                r = device_read_int(b.num_rows, tag="mesh.drain")
                tgt = rows.index(min(rows))  # least-loaded shard
                b = _dc.replace(b, num_rows=r)
                if size_to_rows and b.capacity > _COUNT_ABOVE_CAPACITY:
                    b = b.shrink_to_capacity(pad_capacity(r))
                per_shard[tgt].append(b)
                rows[tgt] += r
                if max(rows) >= budget:
                    if "collectiveRounds" in self.metrics:
                        self.metrics["collectiveRounds"].add(1)
                    yield _fold_groups(per_shard, child.schema)
                    yielded = True
                    per_shard = [[] for _ in range(n)]
                    rows = [0] * n
        if any(rows) or not yielded:
            if "collectiveRounds" in self.metrics:
                self.metrics["collectiveRounds"].add(1)
            yield _fold_groups(per_shard, child.schema)

    def _tick_exchange(self, xs: ColumnarBatch, slot_capacity: int,
                       rows: Optional[int] = None) -> int:
        """Count one exchange program's dispatch over the stacked
        input `xs`: `collectiveBytes` is what its all_to_all was sized
        to carry — every shard's send buffer of n slots of
        `slot_capacity` rows, each round, at the schema's device row
        width — and `collectiveRows` the live `rows` that crossed
        (left out by the aggregate, whose `collectiveRows` are its
        groups).  From shapes and counts the host already holds: no
        readback.  Returns the row width, for the stage's span."""
        from spark_rapids_tpu.parallel import spmd as S

        n = self.num_partitions
        width = S.row_bytes(xs)
        n_rounds = S.stacked_rounds(xs)
        self.metrics["collectiveBytes"].add(
            n_rounds * n * n * slot_capacity * width)
        if rows is not None:
            self.metrics["collectiveRows"].add(rows)
        return width

    def _route_counted(self, rounds, key: tuple, route, span: str,
                       tag: str = "spmdxchg", **attrs):
        """A rounds[r][d] grid through an exchange whose send slots
        are sized by COUNTED rows.  The count program hashes every
        stacked round (`route`: per-shard batch -> partition ids) and
        counts the rows each shard sends each destination; the host
        fetches those (R, n, n) counts — the exchange's one readback —
        and the route program sends the rows through the all_to_all at
        `pad_capacity` of the largest (source, destination) count,
        under `span`.  Nothing is guessed, so nothing overflows: rows
        that all hash to one destination count a slot of the input's
        capacity.  The same counts say what every shard received, so
        the mid-stage boundary needs no fetch of its own: returns the
        received rows stacked at tight capacity (`spmd.restage`), the
        next stage program's input, and their (R, n) counts."""
        import numpy as np

        from spark_rapids_tpu.parallel import spmd as S
        from spark_rapids_tpu.parallel.exchange import (
            destination_counts,
            route_shard,
        )

        n = self.num_partitions
        xs = S.shard_stack_rounds(rounds, self.mesh)
        count = S.make_scan_stage(
            "spmdroutecount", self.mesh, key,
            lambda b: destination_counts(b, route(b), n),
            len(rounds), op=self.name)
        sent = S.fetch(count(xs))  # [round, source, destination]
        slot = pad_capacity(int(sent.max()))
        prog = S.make_exchange_scan_stage(
            self.mesh, key + (slot,),
            lambda b: route_shard(b, route(b), n, DATA_AXIS, slot),
            len(rounds), op=self.name, donate=True, tag=tag)
        rows = int(sent.sum())
        width = self._tick_exchange(xs, slot, rows)
        with _trace.span(span, input_capacity=_grid_capacity(rounds),
                         capacity=slot, rows=rows, rounds=len(rounds),
                         row_bytes=width, **attrs):
            routed = prog(xs)
        received = sent.sum(axis=1).astype(np.int32)
        return self._restage((routed, received)), received

    def _restage(self, *stacked):
        """`spmd.restage` of this stage's `(stacked output, counts)`
        pairs: the boundary between two of its programs."""
        from spark_rapids_tpu.parallel import spmd as S

        return S.restage(stacked, self.mesh, op=self.name)

    # -- per-partition serving ----------------------------------------- #

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """Output batches per mesh shard (subclass responsibility)."""
        raise NotImplementedError

    #: guards per-instance materialization-lock creation
    _MAT_GUARD = __import__("threading").Lock()

    def _shard_outputs(self) -> list[list[ColumnarBatch]]:
        """Materialize EXACTLY once even under concurrent per-partition
        consumers (an exchange's map-task pool drives every partition
        from its own thread; unsynchronized, N threads would run N
        overlapping SPMD programs and race the jit caches)."""
        import threading

        out = getattr(self, "_shards_out", None)
        if out is not None:
            return out
        with _CollectiveBase._MAT_GUARD:
            lk = getattr(self, "_mat_lock", None)
            if lk is None:
                lk = self._mat_lock = threading.Lock()
        with lk:
            out = getattr(self, "_shards_out", None)
            if out is None:
                # every mesh.* span and counts fetch of the stage says
                # which operator's stage it served
                with _trace.trace_context(op=self.name):
                    out = self._shards_out = self._materialize()
        return out

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        for b in self._shard_outputs()[p]:
            yield self._count_output(b)

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)


class TpuCollectiveHashAggregateExec(_CollectiveBase):
    """Grouped aggregation as fused SPMD programs over the active mesh.

    Per round: map-side update aggregation, then hash all_to_all on
    the group keys and reduce-side merge.  The stage runs the update
    as its own program, counts the partial rows and runs the exchange
    + merge program at THEIR capacity (`_materialize`).  Per-shard
    round results park on device, and a final per-shard local program
    (merge + finalize, no collectives) folds the rounds — same keys
    always land on the same shard, so the cross-round merge is local."""

    def __init__(self, groups: Sequence[Expression],
                 aggs: Sequence[NamedAgg], child: TpuExec, mesh,
                 bucket_rounds: Optional[int] = None):
        super().__init__(child)
        self.mesh = mesh
        self._init_stage(bucket_rounds)
        # the partial-mode exec carries every traceable phase we fuse
        self._agg = TpuHashAggregateExec(groups, aggs, child,
                                         mode="partial")
        self._schema = T.Schema(
            list(self._agg.partial_schema.fields[: self._agg.n_keys])
            + [na.output_field() for na in self._agg.aggs])
        self._rollup = self._take_rollup()

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        a = self._agg
        keys = ", ".join(e.name for e in a.groups)
        return (f"TpuCollectiveHashAggregateExec keys=[{keys}] "
                f"[all_to_all over mesh axis '{DATA_AXIS}' x"
                f"{self.num_partitions}] [{self._stage_desc()}]")

    def additional_metrics(self):
        return [("collectiveRows", "MODERATE"),
                ("collectiveBytes", "MODERATE"),
                ("collectiveRounds", "MODERATE"),
                ("collectivePartialRows", "MODERATE")]

    # -- fused phases ----------------------------------------------------- #

    def _pre(self, batch: ColumnarBatch) -> ColumnarBatch:
        return self._agg._update_batch(batch)

    def _merge(self, batch: ColumnarBatch) -> ColumnarBatch:
        return self._agg._merge_batch(batch)

    def _finalize(self, batch: ColumnarBatch) -> ColumnarBatch:
        merged = self._agg._merge_batch(batch)
        ctx = EvalContext.for_batch(merged)
        cols = [e.eval(ctx) for e in self._agg.final_exprs]
        return ColumnarBatch(cols, merged.num_rows, self._schema)

    def _key_ordinals(self) -> tuple:
        """The child columns the grouping keys are, where every key is
        a bare reference to one; else none."""
        from spark_rapids_tpu.exprs.base import BoundReference

        groups = self._agg.groups
        if not all(isinstance(g, BoundReference) for g in groups):
            return ()
        return tuple(g.ordinal for g in groups)

    def _take_rollup(self):
        """How to take the grouping-set Expand directly under this
        stage as the levels of one sort, as the one-chip aggregate
        does (`TpuHashAggregateExec._rollup_of`), or None: the Expand
        then runs as it stands and its rows enter the update program.
        Settled at plan time, from the plan's structure alone; a taken
        Expand is told so, and its rows never exist."""
        from spark_rapids_tpu.execs.expand import TpuExpandExec

        expand = self.children[0]
        if not isinstance(expand, TpuExpandExec) \
                or self._agg._absorbed_chain() is None:
            return None
        taken = self._agg._rollup_of(expand)
        if taken is not None:
            expand.taken_as_rollup = True
        return taken

    # -- driver ----------------------------------------------------------- #

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """The aggregation stage as O(1) partitioned programs.  Per
        round bucket: an update program (map-side partial aggregation,
        rounds folded into a lax.scan, no collective), ONE counts
        fetch and the boundary program (`spmd.restage`) that cuts the
        stacked partials to the capacity their largest count pads to,
        then an exchange program (in-program hash all_to_all ->
        reduce-side merge, the same scan) at THAT capacity — the
        shuffle carries groups, not the input round's padding — and
        one more counts fetch and boundary program, so a bucket's
        merged rounds wait at tight capacity.  After the last bucket:
        the buckets' rounds end to end (one more boundary program
        where there are several; one bucket goes on as it is) and one
        tail program (cross-round merge + finalize) — same keys
        always land on the same shard, so the cross-round fold is
        shard-local.  A group-by whose partials are as many as its
        rows counts its way back to the input's bucket, the boundary
        runs no program, and one path serves both.  Over a ROLLUP's
        Expand the map side is the rollup path's two programs
        (`_rollup_partials`)."""
        from spark_rapids_tpu.parallel import spmd as S
        from spark_rapids_tpu.parallel.exchange import exchange_shard

        rollup = self._rollup
        # a taken Expand is not run: the rows that would enter it do
        child = self.children[0] if rollup is None \
            else rollup.expand.children[0]
        n = self.num_partitions
        akey = self._agg._cache_key()
        ko = list(range(self._agg.n_keys))

        def counted_partials(bucket):
            """The update program over `bucket`, its stacked partials
            cut to their counted rows, and the slot the exchange
            leaves at: their capacity.  The stage entry may keep the
            grouping keys' dictionary sidecars, and the update then
            groups by code as the one-chip update does; the sidecars'
            shapes key the program."""
            xs = S.shard_stack_rounds(bucket, self.mesh,
                                      self._key_ordinals())
            sig = S.coded_signature(xs)
            update = S.make_update_scan_stage(
                self.mesh, akey + (("coded", sig),) if sig else akey,
                self._pre, len(bucket), op=self.name, donate=True)
            partials = update(xs)
            counts = S.stage_counts(partials)
            xs = self._restage((partials, counts))
            return xs, counts, S.stacked_capacity(xs), None

        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            # a bucket's merged rounds, stacked at tight capacity,
            # with their (R, n) counts
            shrunk: list[tuple] = []
            bucket: list = []

            def flush(bucket):
                bucket = S.pad_rounds_pow2(bucket, child.schema, n)
                input_cap = _grid_capacity(bucket)
                if rollup is None:
                    xs, counts, cap, slot = counted_partials(bucket)
                    how = {}
                else:
                    xs, counts, cap, slot = self._rollup_partials(
                        rollup, bucket, akey)
                    how = {"path": "rollup", "slot_capacity": slot,
                           "levels": len(rollup.shape.levels)}
                self.metrics["collectivePartialRows"].add(
                    int(counts.sum()))

                def xchg_body(partial: ColumnarBatch) -> ColumnarBatch:
                    return self._merge(exchange_shard(
                        partial, ko, n, DATA_AXIS, slot))

                prog = S.make_exchange_scan_stage(
                    self.mesh, akey if slot is None else akey + (slot,),
                    xchg_body, len(bucket), op=self.name, donate=True)
                width = self._tick_exchange(xs, slot or cap)
                with _trace.span("collective.agg.exchange",
                                 input_capacity=input_cap,
                                 capacity=cap,
                                 partial_rows=int(counts.max()),
                                 rounds=len(bucket), row_bytes=width,
                                 **how):
                    merged = prog(xs)
                received = S.stage_counts(merged)
                shrunk.append((self._restage((merged, received)),
                               received))

            for shards in self._shard_rounds(child):
                bucket.append(shards)
                if len(bucket) == self.bucket_rounds:
                    flush(bucket)
                    bucket = []
            if bucket:
                flush(bucket)
            xs2 = self._restage(*shrunk)
            tail = S.make_stage_tail(self.mesh, akey, self._finalize,
                                     S.stacked_rounds(xs2),
                                     op=self.name, donate=True)
            final = t.observe(tail(xs2))
        counts = S.stage_counts(final)
        out = []
        for d, b in enumerate(S.unstack_stage(final, counts)):
            self.metrics["collectiveRows"].add(int(counts[d]))
            out.append([b])
        return out

    def _rollup_partials(self, rollup, bucket, akey: tuple):
        """The map side of a ROLLUP, per shard and round what the
        one-chip exec does a batch (ops.groupby, the rollup path): the
        SORT program orders the rows that would have entered the
        Expand once and counts the groups of all levels; the host
        fetches those counts and the WRITE program emits the levels'
        partials at the capacity the largest pads to, with the rows
        each sends to each destination.  Those second counts size the
        exchange's send slots: a shard's partials spread over n
        destinations, so a slot holds about an n-th of them and the
        all_to_all, the received buffer and the reduce-side merge run
        at the rows that cross, not at n x the partials' capacity.
        Returns the stacked partials, their (R, n) counts, their
        capacity and the slot capacity."""
        from spark_rapids_tpu.execs.base import (
            NUM_OUTPUT_BATCHES,
            NUM_OUTPUT_ROWS,
        )
        from spark_rapids_tpu.exprs.hashing import partition_ids
        from spark_rapids_tpu.ops.groupby import rollup_sort, rollup_write
        from spark_rapids_tpu.parallel import spmd as S
        from spark_rapids_tpu.parallel.exchange import destination_counts

        agg, n = self._agg, self.num_partitions
        rkey = akey + ("rollup", rollup.expand.fuse_key())
        ko = list(range(agg.n_keys))
        # the absorbed Expand's own count: the rows it was handed
        expand = rollup.expand.metrics
        expand[NUM_OUTPUT_ROWS].add(_grid_rows(bucket))
        expand[NUM_OUTPUT_BATCHES].add(len(bucket))

        def sort_body(b: ColumnarBatch):
            proj = ColumnarBatch(
                agg._project_inputs(b, rollup.exprs, agg.n_keys - 1),
                b.num_rows, rollup.input_schema)
            return rollup_sort(proj, rollup.shape)

        sort = S.make_scan_stage("spmdrollupsort", self.mesh, rkey,
                                 sort_body, len(bucket), op=self.name,
                                 donate=True)
        ordered, breaks, totals = sort(
            S.shard_stack_rounds(bucket, self.mesh))
        counts = S.fetch(totals)
        cap = pad_capacity(int(counts.max()))

        def write_body(b: ColumnarBatch, brk):
            part = rollup_write(b, brk, rollup.shape, rollup.specs,
                                agg.partial_schema, cap)
            pid = partition_ids([part.columns[o] for o in ko], cap, n)
            return part, destination_counts(part, pid, n)

        write = S.make_scan_stage("spmdrollupwrite", self.mesh,
                                  rkey + (cap,), write_body, len(bucket),
                                  op=self.name, donate=True, n_args=2)
        partials, dests = write(ordered, breaks)
        slot = pad_capacity(int(S.fetch(dests).max()))
        return partials, counts, cap, slot


class TpuCollectiveHashJoinExec(_CollectiveBase):
    """Shuffled equi-join as fused SPMD programs (the collective analog
    of TpuShuffledHashJoinExec; ref: GpuShuffledHashJoinBase over
    GpuShuffleExchangeExec).  The build (right) side exchanges once by
    right-key hash; each stream bucket routes by left-key hash and
    joins locally against its shard's build rows — co-partitioning
    makes every match shard-local, exactly the property the reference
    gets from co-partitioned shuffle outputs.  Both sides leave
    through send slots sized by the rows the host COUNTED each shard
    to send each destination (`_materialize`), so an exchange is paid
    by the rows that cross and not by `n` times its input's padding."""

    SUPPORTED_TYPES = ("inner", "left_outer", "left_semi", "left_anti")

    def __init__(self, left_keys, right_keys, join_type: str,
                 left: TpuExec, right: TpuExec, mesh,
                 bucket_rounds: Optional[int] = None,
                 null_safe=()):
        from spark_rapids_tpu.execs.join import (
            _nullable_fields,
            normalize_null_safe,
        )

        assert join_type in self.SUPPORTED_TYPES, join_type
        super().__init__(left, right)
        self.mesh = mesh
        self._init_stage(bucket_rounds)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        # a NULL key hashes to one destination on both sides (the
        # hash skips it), so a null-safe match stays shard-local
        self.null_safe = normalize_null_safe(null_safe, len(self.left_keys))
        if join_type in ("left_semi", "left_anti"):
            self._schema = left.schema
        else:
            rf = _nullable_fields(right.schema) \
                if join_type == "left_outer" else list(right.schema.fields)
            self._schema = T.Schema(list(left.schema.fields) + rf)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        from spark_rapids_tpu.execs.join import describe_keys

        ks = describe_keys(self.left_keys, self.right_keys, self.null_safe)
        return (f"TpuCollectiveHashJoinExec {self.join_type} [{ks}] "
                f"[all_to_all x{self.num_partitions}] "
                f"[{self._stage_desc()}]")

    def additional_metrics(self):
        return [("buildRows", "MODERATE"),
                ("collectiveRows", "MODERATE"),
                ("collectiveBytes", "MODERATE"),
                ("collectiveRounds", "MODERATE")]

    # -- fused bodies ------------------------------------------------------ #

    def _route_build(self, batch: ColumnarBatch) -> jax.Array:
        from spark_rapids_tpu.exprs.hashing import partition_ids

        ctx = EvalContext.for_batch(batch)
        cols = [k.eval(ctx) for k in self.right_keys]
        return partition_ids(cols, batch.capacity, self.num_partitions)

    def _route_stream(self, stream: ColumnarBatch) -> jax.Array:
        from spark_rapids_tpu.exprs.hashing import partition_ids

        sctx = EvalContext.for_batch(stream)
        return partition_ids([k.eval(sctx) for k in self.left_keys],
                             stream.capacity, self.num_partitions)

    def _join_local(self, routed: ColumnarBatch, build: ColumnarBatch,
                    out_cap: int):
        from spark_rapids_tpu.ops.join import (
            expand_pairs,
            gather_joined,
            join_state,
        )

        rctx = EvalContext.for_batch(routed)
        bctx = EvalContext.for_batch(build)
        skc = [k.eval(rctx) for k in self.left_keys]
        bkc = [k.eval(bctx) for k in self.right_keys]
        jt = self.join_type
        st = join_state(build, routed, bkc, skc,
                        "inner" if jt in ("left_semi", "left_anti")
                        else jt, self.null_safe)
        if jt in ("left_semi", "left_anti"):
            keep = st.matched_s if jt == "left_semi" \
                else (st.live_s & ~st.matched_s)
            out = routed.compact(keep)
            return out, jnp.sum(keep).astype(jnp.int32)
        total = jnp.sum(st.cnt_s).astype(jnp.int32)
        s_idx, b_idx, pair_live, matched = expand_pairs(st, out_cap)
        out = gather_joined(build, routed, s_idx, b_idx, pair_live,
                            matched, jnp.minimum(total, out_cap),
                            self._schema, stream_first=True)
        return out, total

    # -- driver ------------------------------------------------------------ #

    def _join_key(self) -> tuple:
        from spark_rapids_tpu.execs.jit_cache import exprs_key

        key = ("cjoin", self.join_type, exprs_key(self.left_keys),
               exprs_key(self.right_keys), repr(self._schema))
        if self.null_safe:
            key += (("null_safe", self.null_safe),)
        return key

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """The join stage as O(1) partitioned programs per side.  A
        side's rounds (the build side once, the stream side a bucket)
        leave through send slots sized by COUNTED rows, as the
        window's stage does (`_route_counted`: a count program over
        the side's key hash, ONE fetch of the (R, n, n) destination
        counts — the one readback a side and bucket — and the route
        program, all rounds in one lax.scan, at `pad_capacity` of the
        largest count; no fetch after the exchange; the boundary
        program, `spmd.restage`, cuts what arrived to its counted
        rows).  The build side then folds to one batch a shard (a
        tail program); each stream bucket runs one probe program
        joining the TIGHT routed rounds against the resident build
        shard.  A side whose rows all hash
        to one destination counts its way back to a slot of the
        input's capacity: one path, nothing to set.  Overflow of the
        probe's output-capacity guess re-dispatches that bucket's
        probe program at the JoinGatherer-style re-bucketed
        capacity."""
        from spark_rapids_tpu.parallel import spmd as S

        n = self.num_partitions
        jkey = self._join_key()
        chunks: list[list[ColumnarBatch]] = [[] for _ in range(n)]
        semi_anti = self.join_type in ("left_semi", "left_anti")

        def exchanged(rounds, side: str, route):
            return self._route_counted(
                rounds, jkey + (side,), route,
                "collective.join.exchange", side=side)

        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            build_rounds = S.pad_rounds_pow2(
                list(self._shard_rounds(self.children[1])),
                self.children[1].schema, n)
            xs_b, bcounts = exchanged(build_rounds, "build",
                                      self._route_build)
            self.metrics["buildRows"].add(int(bcounts.sum()))
            btail = S.make_stage_tail(
                self.mesh, jkey + ("buildfold",), lambda b: b,
                S.stacked_rounds(xs_b), op=self.name, donate=True)
            build = btail(xs_b)

            def run_bucket(bucket):
                bucket = S.pad_rounds_pow2(bucket,
                                           self.children[0].schema, n)
                xs2, counts2 = exchanged(bucket, "stream",
                                         self._route_stream)
                # probe out-capacity from the LIVE routed maximum, not
                # the padded round capacity or the whole build side:
                # pad_capacity honors the pow2x3 bucket policy, so a
                # 5/8-full shard stops forcing expand_pairs to compute
                # on a worst-case pad.
                # An undershoot is safe: the totals check below
                # re-buckets and re-dispatches at the true capacity.
                live_max = int(counts2.max())
                cap_guess = 64 if semi_anti else pad_capacity(
                    max(live_max, 64))
                while True:
                    if not semi_anti:
                        _ledger.note_occupancy(max(live_max, 1),
                                               cap_guess)
                    prog = S.make_join_scan_stage(
                        self.mesh, jkey + (cap_guess,),
                        lambda s, b, c=cap_guess:
                            self._join_local(s, b, c),
                        S.stacked_rounds(xs2), op=self.name)
                    outs, totals = prog(xs2, build)
                    if semi_anti:
                        break
                    worst = int(S.fetch(totals).max())
                    if worst <= cap_guess:
                        break
                    # JoinGatherer-style re-bucket: recompile at the
                    # capacity the data actually needs
                    cap_guess = pad_capacity(worst)
                outs = t.observe(outs)
                per = S.unstack_round_stage(outs)
                for d in range(n):
                    chunks[d].extend(per[d])

            # `_shard_rounds` always yields a round, so a bucket runs
            bucket: list = []
            for shards in self._shard_rounds(self.children[0]):
                bucket.append(shards)
                if len(bucket) == self.bucket_rounds:
                    run_bucket(bucket)
                    bucket = []
            if bucket:
                run_bucket(bucket)
        return chunks


class TpuCollectiveSortExec(_CollectiveBase):
    """Distributed ORDER BY as fused SPMD programs (the collective
    analog of range-exchange + per-partition sort; ref:
    GpuRangePartitioner sketch/determineBounds + GpuSortExec).

    The route program samples sort keys over every parked round, pools
    the samples with an all_gather, derives range bounds in-program and
    routes every round through a range-bisect all_to_all; each shard
    then sorts locally — shard index order IS the total order."""

    SAMPLE_PER_SHARD = 256

    def __init__(self, keys, child: TpuExec, mesh,
                 bucket_rounds: Optional[int] = None):
        super().__init__(child)
        from spark_rapids_tpu.ops.partition import RangePartitioning

        self.mesh = mesh
        self._init_stage(bucket_rounds)
        self.keys = list(keys)
        n = int(mesh.shape[DATA_AXIS])
        self._part = RangePartitioning(self.keys, n).bind(child.schema)

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    def node_desc(self) -> str:
        ks = ", ".join(
            f"{k.expr.name}{' DESC' if k.descending else ''}"
            for k in self.keys)
        return (f"TpuCollectiveSortExec [{ks}] "
                f"[range all_to_all x{self.num_partitions}] "
                f"[{self._stage_desc()}]")

    def additional_metrics(self):
        return [("collectiveRows", "MODERATE"),
                ("collectiveBytes", "MODERATE"),
                ("collectiveRounds", "MODERATE")]

    def _sort_key(self) -> tuple:
        from spark_rapids_tpu.execs.jit_cache import exprs_key

        return (exprs_key([k.expr for k in self._part.keys]),
                tuple((k.descending, k.nulls_last)
                      for k in self._part.keys))

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """The distributed ORDER BY as TWO partitioned programs: the
        route program (in-program sampling at host-chosen fractional
        positions — no per-batch row-count sync — all_gather-pooled
        dynamic range bounds, the range-routed all_to_all over a
        scanned rounds axis), ONE mid-stage counts fetch and the
        boundary program (`spmd.restage`), then the tail program
        sorting each shard at tight capacity —
        shard index order IS the total order.  The sort stage ignores
        bucketRounds: bounds must see every round's sample, so every
        round is resident while the route program runs."""
        from spark_rapids_tpu.ops.sort import sort_permutation
        from spark_rapids_tpu.parallel import spmd as S

        child = self.children[0]
        part = self._part
        n = self.num_partitions
        skey = self._sort_key()

        def local_sort(b: ColumnarBatch) -> ColumnarBatch:
            # sort by the evaluated key batch (works for arbitrary
            # key expressions, not just column refs)
            perm = sort_permutation(part.key_batch(b),
                                    part.key_orders())
            return b.gather(perm, b.num_rows)

        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            rounds = S.pad_rounds_pow2(
                list(self._shard_rounds(child, size_to_rows=True)),
                child.schema, n)
            xs = S.shard_stack_rounds(rounds, self.mesh)
            self._tick_exchange(xs, _grid_capacity(rounds),
                                _grid_rows(rounds))
            fracs = S.sample_fracs(self.mesh, len(rounds),
                                   self.SAMPLE_PER_SHARD)
            rprog = S.make_sort_route_stage(
                self.mesh, skey, part, len(rounds),
                self.SAMPLE_PER_SHARD, op=self.name, donate=True)
            routed = rprog(xs, fracs)
            xs2 = self._restage((routed, S.stage_counts(routed)))
            tail = S.make_stage_tail(self.mesh, skey, local_sort,
                                     S.stacked_rounds(xs2),
                                     op=self.name, donate=True)
            out = t.observe(tail(xs2))
        return [[b] for b in S.unstack_stage(out, S.stage_counts(out))]


class TpuCollectiveWindowExec(_CollectiveBase):
    """A window with partition keys as fused SPMD programs (the
    collective analog of a hash exchange on `partition_by` under
    TpuWindowExec; ref: GpuWindowExec's required child distribution,
    ClusteredDistribution(partitionBy), over GpuShuffleExchangeExec).

    Rows route by the hash of the partition keys through an
    all_to_all, so a window partition is whole on the shard that owns
    it, and every shard runs the one-chip window program
    (`TpuWindowExec._window_batch`: one sort by partition and order
    keys, every window column from segmented scans) over the rows it
    received.  Only the partition keys decide the routing: a child
    hashed on more keys than these (an aggregate on all its group
    keys) has a partition's rows on several shards."""

    def __init__(self, window_exprs, child: TpuExec, mesh,
                 bucket_rounds: Optional[int] = None):
        from spark_rapids_tpu.execs.window import TpuWindowExec

        super().__init__(child)
        self.mesh = mesh
        self._init_stage(bucket_rounds)
        # carries the traceable window program and its cache key
        self._win = TpuWindowExec(window_exprs, child)
        assert self._win.spec.partition_by, \
            "a window without partition keys has one partition"

    @property
    def schema(self) -> T.Schema:
        return self._win.schema

    def node_desc(self) -> str:
        w = self._win
        fns = ", ".join(f"{we.fn.describe()}->{n}" for we, n in w.named)
        return (f"TpuCollectiveWindowExec [{fns}] over "
                f"({w.spec.describe()}) "
                f"[all_to_all x{self.num_partitions}] "
                f"[{self._stage_desc()}]")

    def additional_metrics(self):
        return [("collectiveRows", "MODERATE"),
                ("collectiveBytes", "MODERATE"),
                ("collectiveRounds", "MODERATE")]

    def _route(self, batch: ColumnarBatch) -> jax.Array:
        from spark_rapids_tpu.exprs.hashing import partition_ids

        ctx = EvalContext.for_batch(batch)
        return partition_ids(
            [e.eval(ctx) for e in self._win.spec.partition_by],
            batch.capacity, self.num_partitions)

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """The window stage as THREE partitioned programs and one host
        sync.  The count program hashes the partition keys of every
        parked round and counts the rows each shard sends to each
        destination; the host fetches those (R, n, n) counts — the
        stage's one readback, which gives the send slots' capacity,
        the rows every shard receives and therefore the stage's output
        counts too.  The route program sends the rows through the
        all_to_all at that slot capacity and the boundary program
        (`spmd.restage`) cuts what arrived to it; the tail program runs
        the window per shard over its received rounds at tight capacity.
        Like the sort, the stage ignores bucketRounds: a partition's
        rows may sit in any round, so every round is resident while
        the route program runs."""
        from spark_rapids_tpu.parallel import spmd as S

        child = self.children[0]
        n = self.num_partitions
        wkey = self._win._cache_key()

        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            rounds = S.pad_rounds_pow2(
                list(self._shard_rounds(child)), child.schema, n)
            xs2, received = self._route_counted(
                rounds, wkey, self._route, "collective.window.exchange",
                tag="spmdwinroute")
            tail = S.make_stage_tail(
                self.mesh, wkey, self._win._window_batch,
                S.stacked_rounds(xs2), op=self.name, donate=True)
            out = t.observe(tail(xs2))
        # a window emits the rows it was handed
        return [[b] for b in S.unstack_stage(out, received.sum(axis=0))]
