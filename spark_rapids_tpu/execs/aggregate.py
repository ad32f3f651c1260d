"""Hash-aggregate exec.

TPU re-design of GpuHashAggregateExec
(ref: sql-plugin/.../aggregate.scala:240,282-430): per input batch run an
*update* aggregation, then re-merge the accumulated partial results
whenever they grow past the target batch size (the reference concatenates
and re-aggregates the same way, aggregate.scala:387-395).  On TPU the
per-batch aggregation is the sort-based segmented kernel in ops.groupby —
one fused XLA program — instead of cudf's hash groupby.

Modes follow Spark/the reference:
- ``partial``:  keys ++ partial columns out (feeds an exchange);
- ``final``:    partial-layout in, merged + finalized out;
- ``complete``: full aggregation locally (single-partition plans).

Bounded memory: between input batches only the merged partial batch is
retained (size = O(#distinct keys seen)), matching the reference's
streaming design."""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.column import pad_capacity
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.execs.basic import output_field
from spark_rapids_tpu.exprs.aggregates import NamedAgg
from spark_rapids_tpu.exprs.base import (
    BoundReference,
    EvalContext,
    Expression,
    bind_references,
)
from spark_rapids_tpu.ops.groupby import (
    AggSpec,
    RollupLevels,
    groupby_aggregate,
    noting_paths,
    reduce_aggregate,
    rollup_sort,
    rollup_write,
)
from spark_rapids_tpu.trace import ledger as _ledger

#: total partial capacity the one-program fused drain (and the traced
#: device concat) accepts.  The stack+compact inside the program is
#: O(cap log cap) device work — trivial next to the 2-3 link round
#: trips the fusion saves — and must admit coded-group-by partials
#: whose capacity is the padded key domain (MAX_CODED_DOMAIN).
_FUSED_DRAIN_CAP = 1 << 18

#: partials at or below this capacity skip the per-batch sizing sync
#: and shrink entirely: the drain pins all their sizes in one batched
#: fetch instead.  Each skipped sync saves a blocking device_get
#: round trip.  Sized to cover
#: coded-group-by partials (capacity = padded key domain, up to
#: MAX_CODED_DOMAIN).  Module-level so tests can force the sizing path
#: on small data.
_DEFER_SYNC_CAP = 1 << 18


#: program key -> the group-by path its trace took (`sort`, `masked`,
#: `scatter`, `rollup`; `none` for a grand aggregate), for the `agg.*`
#: spans of every later run of that program in this process
_PATHS: dict = {}


def _noting_path(key: tuple, fn):
    """`fn`, remembering under `key` which group-by path it takes
    when it is traced.  The wrapper runs at trace time alone."""
    def traced(*args):
        with noting_paths() as paths:
            out = fn(*args)
        _PATHS[key] = "+".join(paths) or "none"
        return out
    return traced


def _as_device_rows(batch):
    if not isinstance(batch, ColumnarBatch):
        return batch  # EncodedBatch: traced count rides the wire comps
    # promotion hides num_rows from the ledger's occupancy scan; state
    # it while host-known (consumed by the dispatch this feeds)
    if _ledger.LEDGER.enabled and type(batch.num_rows) is int:
        _ledger.note_occupancy(batch.num_rows, batch.capacity)
    return batch.with_device_num_rows()


def _ordinals_read(e: Expression, into: set) -> None:
    """The ordinals of the bound references in `e`'s tree."""
    if isinstance(e, BoundReference):
        into.add(e.ordinal)
    for c in e.children:
        _ordinals_read(c, into)


@dataclasses.dataclass(frozen=True)
class _Rollup:
    """An absorbed grouping-set Expand taken as the levels of one sort:
    the update's inputs bound to the Expand's CHILD, the literal's
    column left out of them (`exprs`, `input_schema`, `specs`)."""

    expand: TpuExec
    shape: RollupLevels
    exprs: list
    input_schema: T.Schema
    specs: list


class TpuHashAggregateExec(TpuExec):
    def __init__(self, groups: Sequence[Expression], aggs: Sequence[NamedAgg],
                 child: TpuExec, mode: str = "complete",
                 goal_rows: Optional[int] = None,
                 input_schema: Optional[T.Schema] = None):
        """`input_schema`: for mode="final" only — the pre-aggregation
        schema the aggregate children refer to (the planner threads the
        original child schema across the partial/exchange/final split);
        defaults to the child schema for the other modes."""
        super().__init__(child)
        assert mode in ("partial", "final", "complete"), mode
        self.mode = mode
        from spark_rapids_tpu.memory.device_manager import (
            effective_batch_size_rows,
        )

        self.goal_rows = goal_rows or effective_batch_size_rows()

        child_schema = child.schema
        bind_schema = input_schema if mode == "final" else child_schema
        assert bind_schema is not None, "final mode requires input_schema"
        self.aggs = [NamedAgg(na.fn.bind(bind_schema), na.out_name)
                     for na in aggs]
        if mode == "final":
            # input already has partial layout: keys ++ partial columns
            self.partial_schema = child_schema
            self.groups = [BoundReference(i, f.dtype, f.nullable, f.name)
                           for i, f in enumerate(
                               child_schema.fields[: len(groups)])]
            self.n_keys = len(groups)
        else:
            self.groups = [bind_references(g, child_schema) for g in groups]
            self.n_keys = len(self.groups)
            key_fields = [output_field(g, i)
                          for i, g in enumerate(self.groups)]
            self.input_exprs = list(self.groups)
            partial_fields: list[T.Field] = []
            for na in self.aggs:
                ins = [bind_references(e, child_schema)
                       for e in na.fn.inputs()]
                self.input_exprs.extend(ins)
                for pi, pdt in enumerate(na.fn.partial_dtypes()):
                    partial_fields.append(
                        T.Field(f"{na.out_name}__p{pi}", pdt, True))
            if not self.input_exprs:
                # COUNT(*)-only grand aggregate: a zero-column projection
                # would lose the batch capacity (ColumnarBatch.capacity is
                # 0 with no columns); carry one constant column
                from spark_rapids_tpu.exprs.base import Literal

                self.input_exprs = [Literal.of(True)]
            self.update_input_schema = T.Schema(
                key_fields + [T.Field(f"__in{i}", e.dtype, e.nullable)
                              for i, e in enumerate(
                                  self.input_exprs[self.n_keys:])])
            self.partial_schema = T.Schema(key_fields + partial_fields)

        # ops over the partial layout for the merge phase
        self.merge_specs: list[AggSpec] = []
        po = self.n_keys
        for na in self.aggs:
            for op, pdt in zip(na.fn.merge_ops(), na.fn.partial_dtypes()):
                self.merge_specs.append(AggSpec(op, po, out_dtype=pdt))
                po += 1

        if mode == "partial":
            self._schema = self.partial_schema
        else:
            key_fields = list(self.partial_schema.fields[: self.n_keys])
            self._schema = T.Schema(
                key_fields + [na.output_field() for na in self.aggs])

        # finalize projection over the partial layout
        self.final_exprs: list[Expression] = [
            BoundReference(i, f.dtype, f.nullable, f.name)
            for i, f in enumerate(self.partial_schema.fields[: self.n_keys])]
        po = self.n_keys
        for na in self.aggs:
            refs = []
            for pdt in na.fn.partial_dtypes():
                pf = self.partial_schema.fields[po]
                refs.append(BoundReference(po, pf.dtype, pf.nullable, pf.name))
                po += 1
            self.final_exprs.append(na.fn.finalize_expr(refs))

        import threading

        self._jit_update = None
        self._jit_update_donated = None
        self._jit_merge = None
        self._jit_finalize = None
        self._jits = None
        #: how the absorbed Expand is taken as the levels of one sort
        #: (`_rollup_of`), settled with `_jits`; None: as it stands
        self._rollup: Optional[_Rollup] = None
        self._jit_lock = threading.Lock()

    def _cache_key(self) -> tuple:
        """Structural key for the global compile cache: covers everything
        the three traced phases read off `self`."""
        from spark_rapids_tpu.execs.jit_cache import exprs_key

        update_specs: tuple = ()
        if self.mode != "final":
            update_specs = tuple((s.op, s.ordinal, repr(s.out_dtype))
                                 for s in self._update_specs())
        return (
            "agg", self.mode, self.n_keys,
            exprs_key(getattr(self, "input_exprs", ())),
            repr(getattr(self, "update_input_schema", None)),
            update_specs,
            tuple((s.op, s.ordinal, repr(s.out_dtype))
                  for s in self.merge_specs),
            repr(self.partial_schema),
            exprs_key(self.final_exprs),
            repr(self._schema),
        )

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        keys = ", ".join(e.name for e in self.groups)
        outs = ", ".join(f"{na.fn.name}->{na.out_name}" for na in self.aggs)
        return f"TpuHashAggregateExec[{self.mode}] keys=[{keys}] [{outs}]"

    def additional_metrics(self):
        return [("numMerges", "MODERATE"), ("specHits", "MODERATE"),
                ("specOverflows", "MODERATE")]

    # -- traceable phases ------------------------------------------------ #

    def _update_specs(self) -> list[AggSpec]:
        specs = []
        io = self.n_keys
        for na in self.aggs:
            n_in = len(na.fn.inputs())
            ops = na.fn.update_ops()
            pdts = na.fn.partial_dtypes()
            for op, pdt in zip(ops, pdts):
                # all current fns have <=1 input; count_star reads none
                ord_ = io if n_in else 0
                specs.append(AggSpec(op, ord_, out_dtype=pdt))
            io += n_in
        return specs

    def _project_inputs(self, batch: ColumnarBatch, exprs, n_keys: int):
        """The update's input columns: `exprs` over `batch`, the first
        `n_keys` of them grouping keys (traceable)."""
        from spark_rapids_tpu.execs.jit_cache import expr_key

        ctx = EvalContext.for_batch(batch)
        # equal input expressions (q1's sum and avg of one column)
        # evaluate once and share their arrays, so the coded group-by
        # packs one matrix column for them (eval reads nothing that
        # expr_key leaves out)
        evaluated: dict = {}
        cols = []
        for e in exprs:
            k = expr_key(e)
            if k not in evaluated:
                evaluated[k] = e.eval(ctx)
            cols.append(evaluated[k])
        # Spark inserts NormalizeNaNAndZero under grouping keys (the
        # analyzer's NormalizeFloatingNumbers rule): -0.0 groups AS 0.0
        # and every NaN as the one canonical NaN — normalize here so
        # the emitted key VALUE is canonical too, not just the grouping
        from spark_rapids_tpu.columnar.column import Column as _Col

        for i in range(n_keys):
            c = cols[i]
            if isinstance(c, _Col) and isinstance(
                    c.dtype, (T.FloatType, T.DoubleType)):
                d = jnp.where(jnp.isnan(c.data), jnp.nan,
                              jnp.where(c.data == 0, 0.0, c.data)
                              ).astype(c.data.dtype)
                cols[i] = _Col(d, c.validity, c.dtype)
        return cols

    def _update_batch(self, batch: ColumnarBatch,
                      live_mask=None) -> ColumnarBatch:
        """Project inputs then run the update aggregation (traceable).
        `live_mask` carries fused WHERE predicates from an absorbed
        filter chain — masked rows never existed, but no compaction
        kernels are paid for them."""
        from spark_rapids_tpu.columnar.column import MIN_CAPACITY

        cols = self._project_inputs(batch, self.input_exprs, self.n_keys)
        proj = ColumnarBatch(cols, batch.num_rows, self.update_input_schema)
        specs = self._update_specs()
        if self.n_keys == 0:
            out = reduce_aggregate(proj, specs, self.partial_schema,
                                   live_mask)
            # exactly one live row: compact to the minimum bucket INSIDE
            # the program so no eager slicing (or giant partial buffers)
            # happens outside it
            return out.shrink_to_capacity(MIN_CAPACITY)
        return groupby_aggregate(proj, list(range(self.n_keys)), specs,
                                 self.partial_schema, live_mask)

    def _write_rollup(self, sorted_batch: ColumnarBatch, breaks,
                      total) -> ColumnarBatch:
        """The rollup path's second program, sized by the first one's
        count: the groups of all levels of one input batch, as a partial
        whose capacity is what its counted rows pad to (a level has at
        most as many groups as rows, so uncounted it would be levels x
        the input's capacity, nearly all of it padding).  The count is
        the one readback a large partial's sizing pays on every path."""
        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.parallel import pipeline as P

        shape, specs = self._rollup.shape, self._rollup.specs
        schema = self.partial_schema
        rows = P.device_read_int(total, tag="agg.size")
        capacity = pad_capacity(rows)
        write = cached_jit(
            self._path_keys[0] + ("write", capacity),
            lambda: lambda b, brk: rollup_write(b, brk, shape, specs,
                                                schema, capacity),
            op=self.name)
        with _trace.span("agg.rollup.write", capacity=capacity, rows=rows):
            out = write(sorted_batch, breaks)
        return dataclasses.replace(out, num_rows=rows)

    def _merge_batch(self, partial: ColumnarBatch) -> ColumnarBatch:
        if self.n_keys == 0:
            from spark_rapids_tpu.columnar.column import MIN_CAPACITY

            return reduce_aggregate(
                partial, self.merge_specs,
                self.partial_schema).shrink_to_capacity(MIN_CAPACITY)
        return groupby_aggregate(partial, list(range(self.n_keys)),
                                 self.merge_specs, self.partial_schema)

    def _merge_traced(self, partials: ColumnarBatch,
                      pending: int) -> ColumnarBatch:
        """The merge program over the `pending` partials, concatenated,
        under an `agg.merge` span that says how many partial rows went
        in."""
        rows = partials.num_rows
        with _trace.span("agg.merge", capacity=partials.capacity,
                         rows=rows if isinstance(rows, int) else None,
                         pending=pending,
                         path=_PATHS.get(self._path_keys[1])):
            return self._jit_merge(_as_device_rows(partials))

    def _tick_absorbed(self, batch) -> None:
        """Output rows of the absorbed execs whose count the input's
        fixes (a projection's, an expand's fan-out of it), deferred as
        every row metric is: their own `execute()` never runs.  Stops
        at the first exec that drops rows."""
        chain = self._absorbed_chain()
        rows = getattr(batch, "num_rows", None)
        if chain is None or rows is None:
            return
        from spark_rapids_tpu.execs.base import (
            NUM_OUTPUT_BATCHES,
            NUM_OUTPUT_ROWS,
        )
        from spark_rapids_tpu.execs.basic import TpuProjectExec

        times = 1
        for e in chain[0]:
            fanout = getattr(e, "fanout", None)
            if fanout is None and not isinstance(e, TpuProjectExec):
                return
            times *= fanout or 1
            e.metrics[NUM_OUTPUT_ROWS].add_lazy(rows, times)
            e.metrics[NUM_OUTPUT_BATCHES].add(1)

    def _drain_final_fused(self, pending, rows_hint: int):
        """Final drain as ONE program: concat (traced stack+compact) +
        merge + finalize, mode-dependent.  Saves 2-3 program executions
        per stream tail vs the stepwise drain — each execution has a
        fixed dispatch cost.  Returns None when the
        shapes don't qualify (large/nested partials), decided WITHOUT
        touching the handles (h.get() would unspill large partials to
        device just to reject them); the caller then runs the stepwise
        path."""
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        if (len(pending) == 1 and self.mode == "partial") \
                or rows_hint > _FUSED_DRAIN_CAP \
                or any(isinstance(f.dtype,
                                  (T.ListType, T.StructType, T.MapType))
                       for f in self.partial_schema.fields):
            return None
        batches = [h.get() for h in pending]
        if sum(b.capacity for b in batches) > _FUSED_DRAIN_CAP:
            return None
        from spark_rapids_tpu.columnar.batch import concat_batches_traced

        mode, n_parts = self.mode, len(batches)

        def prog(bs):
            b = concat_batches_traced(bs) if len(bs) > 1 else bs[0]
            if n_parts > 1 or mode == "final":
                b = self._merge_batch(b)
            if mode != "partial":
                b = self._finalize_batch(b)
            return b

        struct = tuple(
            (b.capacity, isinstance(b.num_rows, int),
             tuple(c.width for c in b.columns if hasattr(c, "width")))
            for b in batches)
        fn = cached_jit(("aggdrainfused", self._cache_key(), struct),
                        lambda: prog, op=self.name)
        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            out = t.observe(fn([b.with_device_num_rows()
                                for b in batches]))
        for h in pending:
            h.close()
        pending.clear()
        return out

    def _jit_concat_traced(self, batches: list[ColumnarBatch]):
        """Device-side stack+compact concat for small partials with
        traced row counts (see columnar.batch.concat_batches_traced).
        Returns None when a column kind is unsupported there."""
        from spark_rapids_tpu.columnar.batch import concat_batches_traced
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        if any(isinstance(f.dtype, (T.ListType, T.StructType, T.MapType))
               for f in batches[0].schema.fields):
            return None
        struct = tuple(
            (b.capacity,
             tuple(c.width for c in b.columns if hasattr(c, "width")))
            for b in batches)
        fn = cached_jit(("aggconcat_traced", self._cache_key(), struct),
                        lambda: concat_batches_traced, op=self.name)
        return fn(batches)

    def _jit_concat(self, batches: list[ColumnarBatch]) -> ColumnarBatch:
        """Concatenate pending partials in ONE compiled program: eager
        per-part update-slices would pay a dispatch round trip each on
        high-latency device links.  Row counts are already host ints
        (pinned after the sizing sync), so the whole concat is static."""
        from spark_rapids_tpu.execs.jit_cache import cached_jit

        struct = tuple(
            (b.capacity, b.num_rows,
             tuple(c.width for c in b.columns
                   if hasattr(c, "width")))
            for b in batches)
        fn = cached_jit(("aggconcat", self._cache_key(), struct),
                        lambda: lambda bs: concat_batches(bs),
                        op=self.name)
        return fn(batches)

    def _finalize_batch(self, partial: ColumnarBatch) -> ColumnarBatch:
        ctx = EvalContext.for_batch(partial)
        cols = [e.eval(ctx) for e in self.final_exprs]
        return ColumnarBatch(cols, partial.num_rows, self._schema)

    # -- streaming driver ------------------------------------------------ #

    @property
    def num_partitions(self) -> int:
        # partial aggregation is narrow (per input partition); final is
        # narrow too because the exchange already made partitions
        # key-disjoint; complete consumes everything into one partition
        if self.mode in ("partial", "final"):
            return self.children[0].num_partitions
        return 1

    @property
    def output_partitioning(self):
        """A final aggregate preserves the feeding exchange's hash
        distribution when that hash is over the group-key ordinals (the
        key columns keep positions and dtypes through finalization)."""
        if self.mode != "final":
            return None
        from spark_rapids_tpu.ops.partition import HashPartitioning

        part = getattr(self.children[0], "output_partitioning", None)
        if isinstance(part, HashPartitioning) and all(
                isinstance(e, BoundReference) and e.ordinal < self.n_keys
                for e in part.exprs):
            return part
        return None

    def _absorbed_chain(self):
        """(fns, source_node, keys) when the fusable child chain folds
        into the update program — the whole filter/project/update path
        then runs as ONE program execution per batch (each execution
        has a fixed dispatch cost).  None when the chain needs its own
        driver
        (ANSI error polling, partition-aware exprs, uncacheable keys).
        Side effect of absorption: the absorbed execs' per-node metrics
        do not tick (their execute() never runs)."""
        with self._jit_lock:
            cached = getattr(self, "_absorb", "unset")
            if cached != "unset":
                return cached
            from spark_rapids_tpu.execs.base import (
                FusableExec,
                fusion_enabled,
            )
            from spark_rapids_tpu.exprs.base import ansi_enabled

            result = None
            child = self.children[0]
            if (self.mode != "final" and fusion_enabled()
                    and isinstance(child, FusableExec)
                    and not ansi_enabled()):
                chain, node, aware, keys = child.fusion_chain()
                if not aware and all(k is not None for k in keys):
                    result = (chain, node, tuple(keys))
            self._absorb = result
            return result

    def _source_node(self) -> TpuExec:
        ch = self._absorbed_chain()
        return ch[1] if ch is not None else self.children[0]

    def _rollup_of(self, expand) -> Optional["_Rollup"]:
        """How to take `expand`, the absorbed exec directly under the
        update, as the levels of one sort (ops.groupby, the rollup
        path), or None to run it as it stands.  Decided from the plan's
        structure alone: the projections have a grouping-set rewrite's
        form, the grouping keys are its NULLed columns and its literal
        (and columns it passes whole), no aggregate reads either, and
        the sets of kept keys are nested."""
        form = expand.grouping_form()
        if form is None or not all(type(g) is BoundReference
                                   for g in self.groups):
            return None
        sources, kept, gid_column, gids = form
        key_columns = [g.ordinal for g in self.groups]
        nulled = {c for c, k in enumerate(kept)
                  if c != gid_column and len(k) < len(gids)}
        if len(set(key_columns)) < len(key_columns) \
                or gid_column not in key_columns \
                or not nulled <= set(key_columns):
            return None
        reads: set = set()
        for e in self.input_exprs[self.n_keys:]:
            _ordinals_read(e, reads)
        if reads & (nulled | {gid_column}):
            return None
        # the update's inputs without the literal: the literal's column
        # is written from the level, not sorted or gathered
        gid_position = key_columns.index(gid_column)
        keys = [i for i in range(self.n_keys) if i != gid_position]

        def ordinal(i: int) -> int:
            return i - (i > gid_position)

        # a key that more sets keep is dropped later: major in the sort
        chain = sorted(keys, key=lambda i: -len(kept[key_columns[i]]))
        levels = []
        for p, gid in enumerate(gids):
            held = [i for i in keys if p in kept[key_columns[i]]]
            if set(held) != set(chain[:len(held)]):
                return None  # CUBE, unrelated sets: no one sort serves
            levels.append((len(held), gid))

        def to_child(e: Expression) -> Expression:
            if type(e) is BoundReference:
                return BoundReference(sources[e.ordinal], e.dtype,
                                      e.nullable, e.name)
            return e

        fields = self.update_input_schema.fields
        return _Rollup(
            expand,
            RollupLevels(tuple(ordinal(i) for i in chain), tuple(levels),
                         tuple(ordinal(i) for i in keys), gid_position),
            [e.transform_up(to_child)
             for i, e in enumerate(self.input_exprs) if i != gid_position],
            T.Schema([f for i, f in enumerate(fields)
                      if i != gid_position]),
            [AggSpec(s.op, ordinal(s.ordinal), s.out_dtype)
             for s in self._update_specs()])

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if self.mode == "complete":
            assert self.num_partitions == 1
            if p == 0:
                yield from self.execute()
            return
        yield from self._run_stream(self._source_node().execute_partition(p),
                                    emit_empty_default=(p == 0))

    def execute(self) -> Iterator[ColumnarBatch]:
        if self.mode == "complete":
            yield from self._run_stream(self._source_node().execute(),
                                        emit_empty_default=True)
        else:
            for p in range(self.num_partitions):
                yield from self.execute_partition(p)

    def _run_stream(self, source,
                    emit_empty_default: bool) -> Iterator[ColumnarBatch]:
        chain = self._absorbed_chain()
        with self._jit_lock:
            # exchange map tasks run partial aggregates concurrently; a
            # field-by-field lazy init could be observed half-done
            if self._jits is None:
                from spark_rapids_tpu.execs.jit_cache import cached_jit

                key = self._cache_key()
                execs = chain[0] if chain is not None else []
                ckeys = chain[2] if chain is not None else ()
                from spark_rapids_tpu.execs.basic import TpuFilterExec
                from spark_rapids_tpu.execs.expand import TpuExpandExec

                # a grouping-set Expand directly under the update, its
                # sets nested: its levels come from one sort of the
                # rows that enter it, and it expands none
                rollup = self._rollup_of(execs[-1]) \
                    if execs and isinstance(execs[-1], TpuExpandExec) \
                    else None
                if rollup is not None:
                    execs = execs[:-1]
                    rollup.expand.taken_as_rollup = True
                self._rollup = rollup

                # filters become row MASKS (no compaction kernels) when
                # nothing in the chain multiplies rows — row positions
                # then stay stable through the whole chain, and the
                # masked rows simply never join a group
                as_masks = not any(e.MULTIPLIES_ROWS for e in execs)
                stages = []  # ("mask", cond) | ("fn", batch_fn)
                for e in execs:
                    if as_masks and isinstance(e, TpuFilterExec):
                        stages.append(("mask", e.condition))
                    else:
                        stages.append(("fn", e.make_batch_fn()))

                def chained(b):
                    from spark_rapids_tpu.columnar.transfer import (
                        EncodedBatch,
                    )
                    from spark_rapids_tpu.exprs.base import EvalContext

                    if isinstance(b, EncodedBatch):
                        b = b.decode()  # wire decode fused in-program
                    mask = None
                    for kind, st in stages:
                        if kind == "mask":
                            pred = st.eval(EvalContext.for_batch(b))
                            m = pred.data.astype(bool) & pred.validity
                            mask = m if mask is None else (mask & m)
                        else:
                            b = st(b)
                    return b, mask

                def update_full(b):
                    b, mask = chained(b)
                    if rollup is None:
                        return self._update_batch(b, mask)
                    proj = ColumnarBatch(
                        self._project_inputs(b, rollup.exprs,
                                             self.n_keys - 1),
                        b.num_rows, rollup.input_schema)
                    return rollup_sort(proj, rollup.shape, mask)

                upd_key = key + ("absorb", ckeys, "update") \
                    if rollup is None else key + ("absorb", ckeys, "rollup")
                upd = cached_jit(upd_key,
                                 lambda: _noting_path(upd_key, update_full),
                                 op=self.name)
                # the donated twin: same traced program, wire
                # components donate_argnums'd so XLA reuses their HBM
                # for the partial columns.  A SEPARATE cached program
                # (cached_jit folds the donation state into the key)
                # because the plain one also serves decoded batches
                # whose arrays — shared validity masks, dictionary
                # sidecars — must never be donated.
                from spark_rapids_tpu.execs.jit_cache import (
                    donation_enabled,
                )

                upd_d = cached_jit(
                    upd_key, lambda: _noting_path(upd_key, update_full),
                    op=self.name,
                    donate=(0,)) if donation_enabled() else None
                self._path_keys = (upd_key, key + ("merge",))
                self._jits = (
                    upd, upd_d,
                    cached_jit(key + ("merge",),
                               lambda: _noting_path(key + ("merge",),
                                                    self._merge_batch),
                               op=self.name),
                    cached_jit(key + ("final",),
                               lambda: self._finalize_batch,
                               op=self.name))
            (self._jit_update, self._jit_update_donated,
             self._jit_merge, self._jit_finalize) = self._jits

        from spark_rapids_tpu.memory import SpillPriorities, get_store
        from spark_rapids_tpu.parallel import speculation as SP

        store = get_store()
        # pending partials are spillable between merges (the reference
        # plans the same: aggregate.scala:378-386 spill-of-running-agg)
        pending: list = []  # SpillableBatch handles
        #: id(handle) -> (ReadbackFuture, est) for partials whose
        #: sizing readback rides the async harvester (speculative
        #: sizing): the drain reconciles them before its batched fetch
        futs: dict = {}
        pred = SP.predictor(self._cache_key() + ("sizing",)) \
            if SP.speculation_enabled() \
            and SP.tag_enabled("agg.size") else None

        #: handle-ids whose sizing future already fed the predictor —
        #: a drain RE-RUN after an OOM (spill-retry rung) must not
        #: double-observe the same count
        observed: set = set()

        def finish_drain() -> None:
            """COMMIT a drain: release the drained partials.  Kept
            separate from drain_pending so the escalation ladder can
            build (and re-build, after a spill) the drained batch while
            the source partials stay registered — only after the
            consumer of the drain succeeded are they dropped."""
            for h in pending:
                futs.pop(id(h), None)
                h.close()
            pending.clear()
            observed.clear()

        def drain_pending(commit: bool = True) -> ColumnarBatch:
            import dataclasses

            acquired: list = []
            try:
                batches = []
                for h in pending:
                    batches.append(h.get())
                    acquired.append(h)
                # reconcile async sizing futures first: in steady state
                # the harvester already holds the counts, so this is
                # free — a not-yet-done future is the one place the old
                # blocking per-batch sync can still surface (accounted
                # as such)
                for i, h in enumerate(pending):
                    entry = futs.get(id(h))
                    if entry is None \
                            or isinstance(batches[i].num_rows, int):
                        continue
                    fut, est, speculated = entry
                    n = int(fut.result())
                    if pred is not None and id(h) not in observed:
                        observed.add(id(h))
                        pred.observe(n)
                        if speculated:
                            if n <= est:
                                self.metrics["specHits"].add(1)
                                SP.record_hit("agg.size", est, n)
                            else:
                                self.metrics["specOverflows"].add(1)
                                SP.record_overflow("agg.size", est, n)
                    batches[i] = dataclasses.replace(batches[i],
                                                     num_rows=n)
                traced = [i for i, b in enumerate(batches)
                          if not isinstance(b.num_rows, int)]
                if (traced and len(batches) > 1
                        and sum(b.capacity for b in batches)
                        <= _FUSED_DRAIN_CAP):
                    # small partials: concatenate ON DEVICE
                    # (stack+compact, traced total) so the drain needs
                    # no sizing fetch at all — the query's only D2H
                    # round trip stays the final result pull
                    out = self._jit_concat_traced(batches)
                    if out is not None:
                        if commit:
                            finish_drain()
                        return out
                # deferred sizing: pin every traced row count in ONE
                # batched D2H fetch (per-batch device_get round trips
                # dominate grouped-aggregate wall time on high-latency
                # device links)
                if traced:
                    from spark_rapids_tpu.parallel.pipeline import (
                        device_read_many,
                    )

                    ns = device_read_many(
                        [batches[i].num_rows for i in traced],
                        tag="agg.drain")
                    for i, n in zip(traced, ns):
                        batches[i] = dataclasses.replace(
                            batches[i], num_rows=int(n))
                if len(batches) == 1:
                    out = batches[0]
                elif self.n_keys == 0:
                    # grand aggregate: partials are fixed one-row
                    # min-bucket batches, so the concat program's static
                    # key is stable — compile once, then one dispatch
                    # per drain
                    out = self._jit_concat(batches)
                else:
                    # grouped: partial sizes are data-dependent; jitting
                    # here would recompile per distinct row-count
                    # combination
                    out = concat_batches(batches)
            except BaseException:
                # a failed (uncommitted) drain must leave every partial
                # evictable again so the spill-retry rung can actually
                # release pressure before the re-run
                for h in acquired:
                    h.unpin()
                raise
            if commit:
                finish_drain()
            return out

        try:
            yield from self._execute_inner(store, pending, futs, pred,
                                           drain_pending, finish_drain,
                                           source, emit_empty_default)
        finally:
            # a raise (or generator close) anywhere above must not leak
            # registrations into the process-global store
            for h in pending:
                h.close()
            pending.clear()
            futs.clear()

    def _execute_inner(self, store, pending, futs, pred, drain_pending,
                       finish_drain, source, emit_empty_default):
        from spark_rapids_tpu.memory import SpillPriorities
        from spark_rapids_tpu.parallel import speculation as SP

        import dataclasses

        from spark_rapids_tpu.execs import retry as R
        from spark_rapids_tpu.parallel import pipeline as P

        pending_rows = 0

        from spark_rapids_tpu.columnar.transfer import (
            EncodedBatch,
            repair_donated_memo,
            run_consuming,
        )
        from spark_rapids_tpu.execs.base import record_fused_dispatch

        # donated-unit resume bookkeeping: update-output id -> the
        # EncodedBatch memoizing it, so a rollback can repair a memo
        # whose registered copy was spilled (see guarded_retire)
        donated_units: dict = {}

        _ch = self._absorbed_chain()
        # the update itself counts as a chain member: a chain of N
        # fusable execs absorbed into the update is N+1 operators in
        # one program
        chain_len = (len(_ch[0]) + 1) if _ch is not None else 1

        levels = {} if self._rollup is None \
            else {"levels": len(self._rollup.shape.levels)}

        def dispatch(batch):
            """Async half: the update program for batch k+1 is
            dispatched before batch k's sizing sync retires (the same
            lookahead shape as the join probe loop).  Wire-form
            batches route through the DONATED update twin when
            donation is on: run_consuming marks the batch consumed
            and memoizes the output, so a ladder re-run of this unit
            resumes instead of re-executing over donated buffers."""
            if self.mode == "final":
                return batch  # already partial layout
            self._tick_absorbed(batch)
            with _trace.span("agg.update",
                             capacity=getattr(batch, "capacity", None),
                             path=_PATHS.get(self._path_keys[0]),
                             **levels), \
                    MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                enc = isinstance(batch, EncodedBatch)
                if enc and self._jit_update_donated is not None:
                    # a retry-ladder re-run of a consumed batch
                    # RESUMES from the memoized output — no program
                    # launches, so the fused-dispatch stats must not
                    # tick (q*_fused_dispatch_savings would otherwise
                    # over-report under --chaos)
                    resumed = batch.consumed
                    out = run_consuming(self._jit_update_donated, batch)
                    donated_units[id(out)] = batch
                    if not resumed:
                        record_fused_dispatch(chain_len,
                                              decode_fused=True)
                else:
                    out = self._jit_update(_as_device_rows(batch))
                    record_fused_dispatch(chain_len, decode_fused=enc)
                return t.observe(out)

        def merge_and_park(park):
            """Re-merge the pending partials as ONE transaction on the
            OOM escalation ladder: drain (uncommitted, restartable) +
            merge under spill-retry, then `park(merged)` registers the
            result — only after THAT succeeds are the drained partials
            released.  Any retryable failure up to the park leaves
            `pending` intact, so the batch ladder can re-run the whole
            unit without losing drained state (the failure mode a
            naive drain-then-merge would silently corrupt)."""
            state: dict = {}

            def att():
                if "b" not in state:
                    state["b"] = drain_pending(commit=False)
                return self._merge_traced(state["b"], len(pending))

            try:
                merged = R.run_with_oom_retry(att, desc="agg.merge")
                self.metrics["numMerges"].add(1)
                old = list(pending)
                del pending[:]  # park appends the merged entry fresh
                try:
                    R.run_with_oom_retry(lambda: park(merged),
                                         desc="agg.park")
                except BaseException:
                    # park failed for good: restore the drained
                    # partials — the ladder re-runs from intact state
                    pending[:0] = old
                    raise
            except BaseException:
                # ESCALATION with a completed (uncommitted) drain in
                # hand: drop the drain's pins so the partials are
                # evictable again — otherwise each ladder re-run
                # re-drains and re-pins, and release_pressure can
                # never spill exactly the dominant memory
                if "b" in state:
                    for h in pending:
                        h.unpin()
                raise
            fresh = list(pending)
            pending[:] = old
            finish_drain()  # release old partials (+ their futs/marks)
            pending[:] = fresh
            return merged

        def _register_speculative(part) -> None:
            """Speculative sizing for a big partial: the count readback
            goes to the async harvester (submitted BEFORE register — a
            register under pressure may immediately spill the batch),
            the partial stays unshrunk until the drain reconciles, and
            merge bookkeeping runs on the predicted estimate.  An
            overshoot only costs the dead padded rows the drain trims;
            an undershoot only means one merge triggers a batch late."""
            nonlocal pending_rows
            est = pred.predict(cap_ceiling=part.capacity) \
                if pred is not None else None
            speculated = est is not None
            if est is None:
                est = part.capacity
                SP.record_sync("agg.size")  # warm-up: estimate is the
                # conservative capacity bound, not a prediction
            fut = P.device_read_async(part.num_rows, tag="agg.size")
            h = store.register(part, SpillPriorities.AGGREGATE_PARTIAL)
            pending.append(h)
            futs[id(h)] = (fut, est, speculated)
            pending_rows += est

        def retire(part):
            nonlocal pending_rows
            if self._rollup is not None:
                with MetricTimer(self.metrics[TOTAL_TIME],
                                 op=self.name) as t:
                    part = t.observe(self._write_rollup(*part))
            if (not isinstance(part.num_rows, int)
                    and part.capacity <= _DEFER_SYNC_CAP):
                pending.append(store.register(
                    part, SpillPriorities.AGGREGATE_PARTIAL))
                pending_rows += part.capacity  # upper bound; drain pins
                if len(pending) > 1 and pending_rows >= min(
                        self.goal_rows, 2 * _DEFER_SYNC_CAP):
                    # bound pending without a sizing sync: re-merge via
                    # the traced concat; the merged partial stays traced
                    def park(m):
                        pending.append(store.register(
                            m, SpillPriorities.AGGREGATE_PARTIAL))

                    with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                        merged = t.observe(merge_and_park(park))
                    pending_rows = merged.capacity
                return
            if pred is not None and not isinstance(part.num_rows, int):
                _register_speculative(part)
                if len(pending) > 1 and pending_rows >= self.goal_rows:
                    def park(m):
                        nonlocal pending_rows
                        pending_rows = 0
                        _register_speculative(m)

                    with MetricTimer(self.metrics[TOTAL_TIME],
                                     op=self.name) as t:
                        t.observe(merge_and_park(park))
                return
            # one sizing sync per batch (free when the update emitted a
            # static count, e.g. grand aggregates); pin the host int into
            # the batch so downstream concat/shrink never re-syncs
            n = P.device_read_int(part.num_rows, tag="agg.size")
            part = dataclasses.replace(part, num_rows=n)
            part = part.shrink_to_capacity(pad_capacity(n))
            pending.append(store.register(
                part, SpillPriorities.AGGREGATE_PARTIAL))
            pending_rows += n
            if len(pending) > 1 and pending_rows >= self.goal_rows:
                def park(m):
                    nonlocal pending_rows
                    # sized before register: a register under pressure
                    # may immediately spill the merged batch
                    pr = P.device_read_int(m.num_rows, tag="agg.size")
                    m = dataclasses.replace(m, num_rows=pr)
                    m = m.shrink_to_capacity(pad_capacity(pr))
                    pending.append(store.register(
                        m, SpillPriorities.AGGREGATE_PARTIAL))
                    pending_rows = pr

                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
                    t.observe(merge_and_park(park))

        # Batch-granular OOM split-and-retry: one ladder unit =
        # update-dispatch + retire for one input batch.  retire's side
        # effects (partial registration, merge bookkeeping) roll back
        # on failure so a re-run — at full size or at the split size —
        # starts from clean state; the merge itself is transactional
        # (merge_and_park) so drained partials are never lost to a
        # mid-merge OOM.
        def guarded_retire(part):
            nonlocal pending_rows
            n0 = len(pending)
            r0 = pending_rows
            try:
                retire(part)
            except BaseException:
                eb = donated_units.get(id(part))
                if eb is not None and len(pending) > n0:
                    # every retire path registers the update output
                    # FIRST, so pending[n0] holds part's registration:
                    # if pressure spilled it (deleting the arrays the
                    # memoized donated_out references), restore the
                    # memo through the handle BEFORE the sweep below
                    # drops the only surviving copy — the re-run's
                    # resume must hand downstream a live batch
                    repair_donated_memo(eb, pending[n0])
                for h in pending[n0:]:
                    futs.pop(id(h), None)
                    h.close()
                del pending[n0:]
                pending_rows = r0
                raise
            donated_units.pop(id(part), None)
            return ()

        dispatch_guarded, retire_guarded = R.guarded_pipeline(
            dispatch, guarded_retire, desc="agg.update")
        for _ in P.pipelined(source, dispatch_guarded, retire_guarded,
                             tag="agg.update"):
            pass  # retire yields nothing; pipelined drives the overlap

        if not pending:
            if self.n_keys > 0 or not emit_empty_default:
                return  # grouped aggregate of empty input: no rows
            # grand aggregate of empty input: one default row (only the
            # first partition emits it); absorbed chains start from the
            # SOURCE node's schema (the chain may include projections)
            eb = ColumnarBatch.empty(self._source_node().schema)
            if self.mode != "final":
                eb = self._jit_update(_as_device_rows(eb))
            pending.append(store.register(
                eb, SpillPriorities.AGGREGATE_PARTIAL))

        out = self._drain_final_fused(pending, pending_rows)
        if out is not None:
            yield self._count_output(out)
            return
        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            single = len(pending) == 1
            state: dict = {}

            def final_att():
                # uncommitted drain: a retryable failure anywhere in
                # the tail (concat, merge, finalize) spills + re-runs
                # with every partial still registered
                if "b" not in state:
                    state["b"] = drain_pending(commit=False)
                m = state["b"]
                if not single or self.mode == "final":
                    m = self._merge_traced(m, len(pending))
                if self.mode == "partial":
                    return m
                return self._jit_finalize(_as_device_rows(m))

            out = R.run_with_oom_retry(final_att, desc="agg.drain")
            finish_drain()
            # the drained partials' concat: this frame stays suspended
            # while the operators above work on `out`
            state.clear()
            if not isinstance(out.num_rows, int) \
                    and out.capacity > _DEFER_SYNC_CAP:
                # a merge's output keeps the capacity of the partials
                # that went in, and every operator above pays by
                # capacity: count the groups (one readback, as every
                # large partial pays) and size the batch to them
                n = P.device_read_int(out.num_rows, tag="agg.size")
                out = dataclasses.replace(out, num_rows=n) \
                    .shrink_to_capacity(pad_capacity(n))
            t.observe(out)
        yield self._count_output(out)
