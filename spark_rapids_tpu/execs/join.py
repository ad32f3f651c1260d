"""Join execs.

TPU counterparts of GpuShuffledHashJoinBase / GpuBroadcastHashJoinExec /
GpuHashJoin (ref: sql-plugin/.../GpuShuffledHashJoinBase.scala:28,
shims/spark301/.../GpuBroadcastHashJoinExec.scala,
sql/rapids/execution/GpuHashJoin.scala:62): the build side is collected
into a single device batch (the reference requires the same,
RequireSingleBatch), then every stream batch probes it through the dense
group-id kernel in ops.join.  Output sizing mirrors JoinGatherer: one
device->host sync per stream batch reads the pair count, then a
statically-shaped expansion program (globally cached per capacity
bucket) emits the joined batch at the bucket of the counted pairs, in
chunks of `join.outputChunkRows`.  The join counts, then expands: it
guesses no capacity (`expandRows` over `expandCapacityRows` is the
fill, over a half).

Three physical strategies (chosen by the planner, like GpuOverrides
choosing BroadcastHashJoin vs ShuffledHashJoin by build-side size):
- `TpuShuffledHashJoinExec` (default): wide — consume everything, one
  output partition;
- `TpuShuffledHashJoinExec(partition_wise=True)`: children are
  co-hash-partitioned exchanges; partition p joins build part p against
  stream part p (bounded memory, partition-parallel);
- `TpuBroadcastHashJoinExec`: small build side collected ONCE and shared
  across all stream partitions (the broadcast), stream stays partitioned
  — dimension tables never shuffle.

Join types: inner, left_outer, right_outer (side-swapped), full_outer,
left_semi, left_anti, cross.  Inner joins with a residual condition and
keyless conditional inner joins (nested-loop via the constant-key cross
trick) apply the condition as a post-filter; conditional outer joins
fall back to the CPU engine (as the reference falls back for cases cudf
cannot express).

**Conditional semi and anti joins** (`EXISTS` / `NOT EXISTS` with a
correlation beside the equalities, TPC-DS q16's `cs_warehouse_sk <>
cs2.cs_warehouse_sk`) run on the device.  The probe is the plain semi
probe; the pair count is read back once, as an inner join reads it,
and the pairs are walked in `join.outputChunkRows` chunks at the
count's bucket.  Each chunk is ONE cached program keyed with the lead
tag `semi_cond` (`jit_tpu__<op>__semi_cond(<fingerprint>)`): it
gathers the columns the condition reads for its pairs, evaluates the
condition and ORs `TRUE and not NULL` into a mask at the pair's stream
row, carried across chunks.  A semi join keeps the masked rows, an
anti join the live rows the mask misses, through the shared
`__semi_compact` program.  A NULL condition is not TRUE, and a stream
row with a NULL (`=`) key has no pairs, so the anti join keeps it:
NOT EXISTS, not NOT IN.  A semi or anti join without a condition
compiles what it did before.

**A semi or anti join probes a dense stream.**  Each probe sorts the
whole build side beside its stream batch, and an exchange hands a
partition in as many blocks as its producer had batches: q16's EXISTS
probed 247 blocks of a few rows each against millions of build rows,
a round of 203 s on a v5e.  So a semi or anti join's stream child is
a `TpuCoalesceBatchesExec` (`counts_vary`: no program takes a shape
from a block's count) with `join.outputChunkRows` as its row target:
the output is at most the stream.  A lone batch, or one whose count
is still on the device, passes untouched.

**Null-safe keys.**  Each key pair is `=` (a NULL key matches nothing)
or, where `null_safe` marks it, `<=>` (NULL equals NULL and nothing
else): `ops.join.compute_gids` ranks the keys with grouping equality
either way and leaves a null-safe key out of the flags that bar a row.
`DataFrame.intersect` / `subtract` lower to `left_semi` / `left_anti`
joins whose every key is null-safe, as Spark's
ReplaceIntersectWithSemiJoin / ReplaceExceptWithAntiJoin do.  The flag
is part of a program's cache key only where a key has it, so a plain
join compiles what it always did.  `left_semi` and `left_anti` have run
on a v5e since PR 38 (`tpcds-sf10-setops.q38-q87`: three keys, two of
them strings); their probe programs are named apart from the other
joins' (`__left_semi_probe`, `__left_anti_probe`, and the shared
`__semi_compact`)."""

from __future__ import annotations

import dataclasses
import threading
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.column import pad_capacity
from spark_rapids_tpu.execs.base import MetricTimer, TOTAL_TIME, TpuExec
from spark_rapids_tpu.execs.coalesce import TpuCoalesceBatchesExec
from spark_rapids_tpu.exprs.base import (
    EvalContext,
    Expression,
    bind_references,
)
from spark_rapids_tpu.config import get_conf, register
from spark_rapids_tpu.ops.join import (
    expand_pairs,
    gather_joined,
    join_state,
)

JOIN_OUTPUT_CHUNK_ROWS = register(
    "spark.rapids.tpu.sql.join.outputChunkRows", 1 << 22,
    "Join output is produced in spillable chunks of at most this many "
    "rows per stream batch instead of one data-dependent gather (the "
    "JoinGatherer target-size chunking, ref: JoinGatherer.scala:55).")

JOIN_TYPES = ("inner", "left_outer", "right_outer", "full_outer",
              "left_semi", "left_anti", "cross")
#: join types that run a residual condition on the device
CONDITIONAL_TYPES = ("inner", "left_semi", "left_anti")


def normalize_null_safe(null_safe, n_keys: int) -> tuple:
    """Which of a join's `n_keys` key pairs compare with `<=>`, one
    bool a pair; `()` where none does, so that a plain join's cache
    keys and descriptions are what they were before the flag."""
    if isinstance(null_safe, bool):
        null_safe = (null_safe,) * n_keys
    flags = tuple(bool(f) for f in null_safe)
    if not any(flags):
        return ()
    if len(flags) != n_keys:
        raise ValueError(f"{len(flags)} null-safe flags for "
                         f"{n_keys} join keys")
    return flags


def describe_keys(left_keys, right_keys, null_safe) -> str:
    """`a=b, c<=>d`: a join's key pairs, as `explain()` prints them."""
    return ", ".join(
        f"{l.name}{'<=>' if at < len(null_safe) and null_safe[at] else '='}"
        f"{r.name}"
        for at, (l, r) in enumerate(zip(left_keys, right_keys)))


def describe_condition(join_type: str, condition) -> str:
    """` cond=<condition>` for a semi or anti join that has one, as
    `explain()` prints it beside the keys; empty otherwise."""
    if condition is None or join_type not in ("left_semi", "left_anti"):
        return ""
    return f" cond={condition!r}"


def _nullable_fields(schema: T.Schema) -> list[T.Field]:
    return [T.Field(f.name, f.dtype, True) for f in schema.fields]


class _HashJoinBase(TpuExec):
    """Shared machinery: schema/keys resolution, build collection, the
    probe-expand-condition loop, full-outer unmatched emission."""

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 build_side: Optional[str] = None,
                 null_safe: Sequence[bool] = ()):
        if join_type in ("left_semi", "left_anti") \
                and not isinstance(left, TpuCoalesceBatchesExec):
            # the stream's blocks concatenated: each probe sorts the
            # whole build side (module docstring)
            left = TpuCoalesceBatchesExec(
                left, target_rows=get_conf().get(JOIN_OUTPUT_CHUNK_ROWS),
                counts_vary=True)
        super().__init__(left, right)
        assert join_type in JOIN_TYPES, join_type
        self.join_type = join_type
        self.null_safe = normalize_null_safe(null_safe, len(left_keys))
        if join_type == "cross" or not left_keys:
            # cross product AND keyless conditional inner joins (nested
            # loop): equi-join on a constant key — every pair shares the
            # single group, the residual condition filters
            from spark_rapids_tpu.exprs.base import Literal

            if join_type not in ("cross", "inner"):
                raise NotImplementedError(
                    "keyless joins only for inner/cross (planner falls "
                    "back otherwise)")
            left_keys = [Literal.of(1)]
            right_keys = [Literal.of(1)]
        self.left_keys = [bind_references(k, left.schema) for k in left_keys]
        self.right_keys = [bind_references(k, right.schema)
                           for k in right_keys]
        if condition is not None and join_type not in CONDITIONAL_TYPES:
            raise NotImplementedError(
                f"residual join conditions only on {CONDITIONAL_TYPES} "
                f"joins, not {join_type} (planner falls back otherwise)")
        joined_schema = T.Schema(list(left.schema.fields)
                                 + list(right.schema.fields))
        self.condition = (bind_references(condition, joined_schema)
                          if condition is not None else None)

        # build = the side NOT preserved by an outer/semi/anti join;
        # inner/cross may build either side (planner picks the smaller)
        if join_type in ("inner", "cross") and build_side is not None:
            assert build_side in ("left", "right")
            self.build_is_right = build_side == "right"
        else:
            self.build_is_right = join_type != "right_outer"
        lf, rf = list(left.schema.fields), list(right.schema.fields)
        if join_type in ("left_outer", "full_outer"):
            rf = _nullable_fields(right.schema)
        if join_type in ("right_outer", "full_outer"):
            lf = _nullable_fields(left.schema)
        if join_type in ("left_semi", "left_anti"):
            self._schema = left.schema
        else:
            self._schema = T.Schema(lf + rf)

    @property
    def schema(self) -> T.Schema:
        return self._schema

    def node_desc(self) -> str:
        ks = describe_keys(self.left_keys, self.right_keys, self.null_safe)
        return f"{self.name} {self.join_type} [{ks}]" + describe_condition(
            self.join_type, self.condition)

    def additional_metrics(self):
        return [("buildRows", "MODERATE"), ("probeBatches", "MODERATE"),
                ("streamRows", "MODERATE"),
                ("unmatchedBuildRows", "MODERATE"),
                # pairs counted, and the capacities their expansion
                # programs ran at, summed: the ratio is the fill
                ("expandRows", "MODERATE"),
                ("expandCapacityRows", "MODERATE"),
                # a conditional semi or anti join's pairs the condition
                # was evaluated over, and the capacities of those chunks
                ("condPairs", "MODERATE"),
                ("condCapacityRows", "MODERATE")]

    @property
    def _build_child(self) -> TpuExec:
        return self.children[1] if self.build_is_right else self.children[0]

    @property
    def _stream_child(self) -> TpuExec:
        return self.children[0] if self.build_is_right else self.children[1]

    # -- build collection ------------------------------------------------ #

    def _collect_batches(self, batches) -> Optional[ColumnarBatch]:
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        store = get_store()
        handles = []
        try:
            for bb in batches:
                handles.append(store.register(
                    bb, SpillPriorities.JOIN_BUILD))
            if not handles:
                return None
            collected = [h.get() for h in handles]
            b = collected[0] if len(collected) == 1 \
                else concat_batches(collected)
        finally:
            for h in handles:
                h.close()
        rows = b.concrete_num_rows()
        self.metrics["buildRows"].add(rows)
        _trace.event("join.build", op=self.name, rows=rows,
                     capacity=b.capacity, batches=len(collected),
                     join_type=self.join_type,
                     null_safe=sum(self.null_safe),
                     condition=self.condition is not None)
        return b

    def _empty_build(self) -> ColumnarBatch:
        return ColumnarBatch.empty(self._build_child.schema)

    # -- probe machinery ------------------------------------------------- #

    def _probe(self, build: ColumnarBatch, stream: ColumnarBatch):
        """Traceable: key eval + join state (tuple of arrays)."""
        build_keys = self.right_keys if self.build_is_right else self.left_keys
        stream_keys = self.left_keys if self.build_is_right else self.right_keys
        bctx = EvalContext.for_batch(build)
        sctx = EvalContext.for_batch(stream)
        bkc = [k.eval(bctx) for k in build_keys]
        skc = [k.eval(sctx) for k in stream_keys]
        # the stream side is the preserved side for every outer variant
        jt = "left_outer" if self.join_type in (
            "left_outer", "right_outer", "full_outer") else "inner" \
            if self.join_type == "cross" else self.join_type
        st = join_state(build, stream, bkc, skc, jt, self.null_safe)
        total = jnp.sum(st.cnt_s).astype(jnp.int32)
        return st, total

    def _expand(self, build, stream, st, total, offset, out_cap: int):
        s_idx, b_idx, pair_live, matched = expand_pairs(st, out_cap,
                                                        offset)
        num_rows = jnp.clip(
            jnp.asarray(total, jnp.int32)
            - jnp.asarray(offset, jnp.int32), 0, out_cap)
        stream_first = self.build_is_right
        return gather_joined(build, stream, s_idx, b_idx, pair_live,
                             matched, num_rows, self._schema,
                             stream_first=stream_first)

    def _cache_key(self) -> tuple:
        """Computed once per exec: the serialization is recursive and the
        hot probe loop must not re-pay it per stream batch."""
        key = getattr(self, "_ck", None)
        if key is None:
            from spark_rapids_tpu.execs.jit_cache import exprs_key

            key = self._ck = (
                "join", self.join_type, self.build_is_right,
                exprs_key(self.left_keys), exprs_key(self.right_keys),
                # the child schema split matters too: cached closures read
                # the stream/build child schemas, and two joins with the
                # same joined output but different left/right splits must
                # not share programs
                repr(self.children[0].schema), repr(self.children[1].schema),
                repr(self._schema))
            if self.null_safe:
                key = self._ck = key + (("null_safe", self.null_safe),)
        return key

    def _jit_expand(self, out_cap: int):
        """One cached jitted expansion program per output bucket (the
        JoinGatherer-chunking analog of compile caching); memoized per
        instance so the per-batch path is a dict hit."""
        cache = getattr(self, "_expand_cache", None)
        if cache is None:
            cache = self._expand_cache = {}
        fn = cache.get(out_cap)
        if fn is None:
            from functools import partial

            from spark_rapids_tpu.execs.jit_cache import cached_jit

            fn = cache[out_cap] = cached_jit(
                self._cache_key() + ("expand", out_cap),
                lambda: partial(self._expand, out_cap=out_cap),
                op=self.name)
        return fn

    def _cond_columns(self) -> tuple:
        """The joined-schema ordinals a semi or anti join's condition
        reads, ascending (the stream's columns first: such a join
        builds right), and the condition rebound to them in that
        order; computed once."""
        found = getattr(self, "_cond_cols", None)
        if found is None:
            from spark_rapids_tpu.exprs.base import BoundReference

            ordinals: set = set()

            def note(node):
                if isinstance(node, BoundReference):
                    ordinals.add(node.ordinal)
                return node

            self.condition.transform_up(note)
            # a condition that reads no column still needs a batch of
            # the pairs' capacity: it reads the stream's first
            ordinals = sorted(ordinals) or [0]
            at = {o: i for i, o in enumerate(ordinals)}
            cond = self.condition.transform_up(
                lambda node: dataclasses.replace(
                    node, ordinal=at[node.ordinal])
                if isinstance(node, BoundReference) else node)
            found = self._cond_cols = (tuple(ordinals), cond)
        return found

    def _semi_cond(self, build, stream, st, total, offset, mask,
                   out_cap: int):
        """Traceable: one chunk of a conditional semi or anti join's
        pairs.  Gathers the columns the condition reads for the pairs
        [offset, offset + out_cap), evaluates it, and ORs `TRUE and not
        NULL` by each pair's stream row into `mask` (ignored at offset
        0, where it is any stream-capacity bool).  Returns the carried
        mask and the rows to keep were this the last chunk."""
        s_idx, b_idx, pair_live, _ = expand_pairs(st, out_cap, offset)
        ordinals, cond = self._cond_columns()
        n_stream = stream.num_cols
        fields = (list(stream.schema.fields)
                  + list(build.schema.fields))
        cols = [stream.columns[o].gather(s_idx, pair_live)
                if o < n_stream
                else build.columns[o - n_stream].gather(b_idx, pair_live)
                for o in ordinals]
        num_rows = jnp.clip(
            jnp.asarray(total, jnp.int32)
            - jnp.asarray(offset, jnp.int32), 0, out_cap)
        pairs = ColumnarBatch(cols, num_rows,
                              T.Schema([fields[o] for o in ordinals]))
        p = cond.eval(EvalContext.for_batch(pairs))
        passed = p.data.astype(bool) & p.validity & pair_live
        hit = jnp.zeros((stream.capacity,), bool).at[s_idx].max(passed)
        first = jnp.asarray(offset, jnp.int32) == 0
        mask = jnp.where(first, False, mask) | hit
        keep = mask if self.join_type == "left_semi" \
            else st.live_s & ~mask
        return mask, keep

    def _jit_semi_cond(self, out_cap: int):
        """One cached program a chunk bucket; its key leads with the
        tag `semi_cond`, so a device trace names it apart."""
        cache = getattr(self, "_semi_cond_cache", None)
        if cache is None:
            cache = self._semi_cond_cache = {}
        fn = cache.get(out_cap)
        if fn is None:
            from functools import partial

            from spark_rapids_tpu.execs.jit_cache import cached_jit, expr_key

            fn = cache[out_cap] = cached_jit(
                ("semi_cond",) + self._cache_key()
                + (expr_key(self.condition), out_cap),
                lambda: partial(self._semi_cond, out_cap=out_cap),
                op=self.name)
        return fn

    def _cond_row_bytes(self, build, stream) -> int:
        """Device bytes a pair reads of the columns the condition
        reads, both sides summed: each column's leaves over its
        capacity (values, validity, a string's lengths)."""
        ordinals, _ = self._cond_columns()
        n_stream = stream.num_cols
        out = 0
        for o in ordinals:
            col = stream.columns[o] if o < n_stream \
                else build.columns[o - n_stream]
            out += sum(leaf.nbytes // max(1, col.capacity)
                       for leaf in jax.tree_util.tree_leaves(col))
        return out

    def _semi_condition(self, build, stream, st, total, n_total: int,
                        chunk: int, compact) -> Iterator[ColumnarBatch]:
        """A conditional semi or anti join's stream batch, once its
        pair count is on the host: the condition over every pair in
        chunks, then the one compaction."""
        if n_total == 0:
            # no pair: a semi join keeps nothing, an anti join every row
            if self.join_type == "left_anti":
                yield self._count_output(stream)
            return
        mask = st.matched_s  # any stream-capacity bool: chunk 0 drops it
        off = 0
        while off < n_total:
            out_cap = pad_capacity(min(n_total - off, chunk))
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                mask, keep = self._jit_semi_cond(out_cap)(
                    build, stream, st, total,
                    jnp.asarray(off, jnp.int32), mask)
            pairs = min(n_total - off, out_cap)
            self.metrics["condPairs"].add(pairs)
            self.metrics["condCapacityRows"].add(out_cap)
            if _trace.TRACER.enabled:
                _trace.event("join.semi_cond", op=self.name,
                             join_type=self.join_type, pairs=pairs,
                             capacity=out_cap,
                             stream_capacity=stream.capacity,
                             cond_row_bytes=self._cond_row_bytes(
                                 build, stream))
            off += out_cap
        with MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            out = t.observe(compact(stream, keep))
        yield self._count_output(out)

    @property
    def _jit_condition(self):
        fn = getattr(self, "_cond_fn", None)
        if fn is None:
            from spark_rapids_tpu.execs.jit_cache import (
                cached_jit,
                expr_key,
            )

            cond = self.condition

            def apply(batch):
                ctx = EvalContext.for_batch(batch)
                p = cond.eval(ctx)
                return batch.compact(p.data.astype(bool) & p.validity)

            fn = self._cond_fn = cached_jit(
                ("join_cond", expr_key(cond)), lambda: apply,
                op=self.name)
        return fn

    def _join_stream(self, build: Optional[ColumnarBatch],
                     stream_batches) -> Iterator[ColumnarBatch]:
        """Probe every stream batch against the build batch; for
        full_outer, finish with the unmatched build rows.

        The stream loop is SOFTWARE-PIPELINED (parallel.pipeline): the
        probe for batch k+1 is dispatched before batch k's single
        pair-count readback, so JAX's async dispatch runs probe(k+1)
        concurrently with the readback wait — the one structural
        serialization of the join stream loop (ref: the
        reference gets the same overlap from JoinGatherer's bounded
        gathers + the stream iterator's prefetch).

        The join COUNTS, THEN EXPANDS: retire(k) reads batch k's pair
        count and only then dispatches its expansion, at the count's
        own capacity bucket.  An expansion costs by the capacity it
        runs at, not by its rows (0.33-0.36 us a row of capacity on a
        v5e), so a bucket guessed ahead of the count and one too large
        doubles the join's largest program; the readback it would
        spare is some 2 ms behind the next batch's probe."""
        if build is None:
            if self.join_type in ("inner", "left_semi", "cross"):
                return  # empty build: no output
            build = self._empty_build()

        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.parallel import pipeline as P

        sizes_output = self.join_type not in ("left_semi", "left_anti")
        # a conditional semi or anti join counts its pairs as an inner
        # join does, then keeps rows by the condition over them
        conditional = not sizes_output and self.condition is not None
        # a semi or anti probe's key leads with a tag of its own, so a
        # device trace tells its program from the other joins' probes
        probe_key = self._cache_key() + ("probe",) if sizes_output \
            else (f"{self.join_type}_probe",) + self._cache_key()
        jit_probe = cached_jit(probe_key, lambda: self._probe,
                               op=self.name)
        jit_semi_compact = cached_jit(
            ("semi_compact",), lambda: lambda stream, keep:
            stream.compact(keep), op=self.name)
        matched_b_acc = None
        chunk = get_conf().get(JOIN_OUTPUT_CHUNK_ROWS)

        build = build.with_device_num_rows()

        def dispatch(stream):
            """Async half: probe dispatch (+ semi/anti compaction,
            which needs no readback)."""
            nonlocal matched_b_acc
            self.metrics["probeBatches"].add(1)
            # deferred like every row metric: no readback here
            self.metrics["streamRows"].add_lazy(stream.num_rows)
            out = None
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name,
                             join_type=self.join_type,
                             null_safe=sum(self.null_safe),
                             condition=self.condition is not None,
                             capacity=stream.capacity) as t:
                stream = stream.with_device_num_rows()
                st, total = jit_probe(build, stream)
                if self.join_type == "full_outer":
                    m = st.matched_b
                    matched_b_acc = m if matched_b_acc is None \
                        else (matched_b_acc | m)
                if not sizes_output and not conditional:
                    keep = st.matched_s if self.join_type == "left_semi" \
                        else (st.live_s & ~st.matched_s)
                    out = t.observe(jit_semi_compact(stream, keep))
                else:
                    t.observe(total)
            return stream, st, total, out

        def retire(entry):
            """Sizing half: the one blocking readback a stream batch,
            then the expansion in chunks sized by what it read."""
            stream, st, total, out = entry
            if out is not None:
                yield self._count_output(out)
                return
            with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                n_total = P.device_read_int(total, tag="join.probe")
            if conditional:
                yield from self._semi_condition(build, stream, st, total,
                                                n_total, chunk,
                                                jit_semi_compact)
                return
            self.metrics["expandRows"].add(n_total)
            # target-size chunks, spillable between yields (ref:
            # JoinGatherer.scala:55,138 — output in bounded gathers,
            # never one giant batch); expand_pairs is offset-windowed,
            # so a chunk redoes none of the one before.  Each chunk's
            # compute gets its own timed region so consumer time
            # between yields never lands in this operator's clock.
            off = 0
            while off < n_total:
                out_cap = pad_capacity(min(n_total - off, chunk))
                with MetricTimer(self.metrics[TOTAL_TIME], op=self.name):
                    o = self._jit_expand(out_cap)(
                        build, stream, st, total,
                        jnp.asarray(off, jnp.int32))
                    if self.condition is not None:
                        o = self._jit_condition(o)
                self.metrics["expandCapacityRows"].add(out_cap)
                if _trace.TRACER.enabled:
                    _trace.event("join.expand", op=self.name,
                                 join_type=self.join_type,
                                 rows=min(n_total - off, out_cap),
                                 capacity=out_cap, offset=off)
                yield self._count_output(o)
                off += out_cap

        # Batch-granular OOM split-and-retry (execs/retry.py): each
        # stream batch is one ladder unit.  dispatch failures carry
        # their error into the ladder as the first failure; retire
        # failures discard the in-flight entry and RE-DISPATCH from
        # the input batch — at the split size after a bisect.
        from spark_rapids_tpu.execs.retry import guarded_pipeline

        dispatch_guarded, retire_guarded = guarded_pipeline(
            dispatch, retire, desc="join.probe")
        yield from P.pipelined(stream_batches, dispatch_guarded,
                               retire_guarded, tag="join.probe")

        if self.join_type == "full_outer":
            yield from self._emit_unmatched_build(build, matched_b_acc)

    def _emit_unmatched_build(self, build: ColumnarBatch,
                              matched_b: Optional[jax.Array]):
        """Remaining full-outer rows: build rows no stream batch matched,
        with NULLs for the stream side."""
        if matched_b is None:
            matched_b = jnp.zeros((build.capacity,), bool)

        def unmatched(build, matched_b):
            keep = build.row_mask() & ~matched_b
            compacted = build.compact(keep)
            stream_schema = self._stream_child.schema
            null_cols = []
            from spark_rapids_tpu.exprs.base import Literal

            ctx = EvalContext.for_batch(compacted)
            dead = jnp.zeros((compacted.capacity,), bool)
            for f in stream_schema.fields:
                lit_null = Literal.of(None, f.dtype) \
                    if not isinstance(f.dtype, T.StringType) \
                    else Literal.of(None, T.STRING)
                c = lit_null.eval(ctx)
                null_cols.append(c.with_validity(dead))
            if self.build_is_right:
                cols = null_cols + list(compacted.columns)
            else:
                cols = list(compacted.columns) + null_cols
            return ColumnarBatch(cols, compacted.num_rows, self._schema)

        from spark_rapids_tpu.execs.jit_cache import cached_jit
        from spark_rapids_tpu.parallel import pipeline as P

        # the one readback sizes the batch (every operator above pays
        # by capacity, and a side that mostly matched leaves few rows);
        # under the span, and counted as the stage's readback, so that
        # the chip's wait for it has a name
        with _trace.span("join.unmatched", op=self.name,
                         join_type=self.join_type,
                         build_capacity=build.capacity) as sp, \
                MetricTimer(self.metrics[TOTAL_TIME], op=self.name) as t:
            out = cached_jit(self._cache_key() + ("unmatched",),
                             lambda: unmatched,
                             op=self.name)(build, matched_b)
            rows = P.device_read_int(out.num_rows, tag="join.unmatched")
            sp.note(rows=rows)
            out = t.observe(dataclasses.replace(out, num_rows=rows)
                            .shrink_to_capacity(pad_capacity(rows)))
        self.metrics["unmatchedBuildRows"].add(rows)
        if rows:
            yield self._count_output(out)


class TpuRuntimeFilterBuildExec(TpuExec):
    """Streaming pass-through inserted by the runtime-filter planner
    pass (plan/runtime_filter.py) on the BUILD side of an eligible
    join: every batch flows through unchanged while its join-key
    columns fold into device-resident Bloom bits + min/max
    accumulators; when the last partition drains, the finished filter
    is fetched once (a few KB) and published to the probe side's
    scans.

    Sits either directly under the join (wide/broadcast shapes — the
    join collects build before streaming probe) or under the build
    exchange (partition-wise/adaptive shapes — the map stage drains the
    whole build input before the probe stage materializes, with
    execs/adaptive.py ordering build-before-probe).  Per-batch updates
    are async device dispatches; the one blocking readback happens at
    finalize, through the sanctioned pipeline API."""

    def __init__(self, child: TpuExec, entries):
        super().__init__(child)
        #: [(bound key Expression, RuntimeFilter)]
        self.entries = list(entries)
        self._lock = threading.Lock()
        self._acc = None  # merged per-filter device states
        self._parts_done: set = set()
        self._published = False

    @property
    def schema(self) -> T.Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    @property
    def output_partitioning(self):
        return self.children[0].output_partitioning

    def node_desc(self) -> str:
        ks = ", ".join(rf.describe() for _k, rf in self.entries)
        return f"{self.name} [{ks}]"

    def additional_metrics(self):
        return [("rfBuildTime", "ESSENTIAL"), ("rfKeys", "MODERATE")]

    def _jit_update(self):
        fn = getattr(self, "_update_fn", None)
        if fn is None:
            from spark_rapids_tpu.execs.jit_cache import (
                cached_jit,
                exprs_key,
            )
            from spark_rapids_tpu.plan import runtime_filter as RF

            entries = self.entries
            specs = tuple((rf.n_bits, rf.n_hashes, rf.is64, rf.use_bloom)
                          for _k, rf in entries)

            def update(states, batch):
                ctx = EvalContext.for_batch(batch)
                live = batch.row_mask()
                out = []
                for (key, rf), st in zip(entries, states):
                    col = key.eval(ctx)
                    contrib = live & col.validity
                    out.append(RF.device_update(
                        st, col, contrib, rf.n_bits, rf.n_hashes,
                        rf.is64, rf.use_bloom))
                return tuple(out)

            fn = self._update_fn = cached_jit(
                ("rf.update", exprs_key([k for k, _ in entries]), specs,
                 repr(self.schema)), lambda: update, op=self.name)
        return fn

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.plan import runtime_filter as RF

        states = [RF.device_init_state(rf.n_bits, rf.use_bloom)
                  for _k, rf in self.entries]
        update = self._jit_update()
        for batch in self.children[0].execute_partition(p):
            from spark_rapids_tpu.columnar.transfer import EncodedBatch

            if isinstance(batch, EncodedBatch):
                # key eval needs decoded columns; the consumer above
                # still receives the original wire-form batch
                decoded = batch.decode_now()
            else:
                decoded = batch
            with MetricTimer(self.metrics[TOTAL_TIME],
                             op=self.name) as t:
                states = update(tuple(states),
                                decoded.with_device_num_rows())
                t.observe(states)
            yield self._count_output(batch)
        self._merge_and_maybe_publish(p, states)

    def _merge_and_maybe_publish(self, p: int, states) -> None:
        from spark_rapids_tpu.plan import runtime_filter as RF

        with self._lock:
            if self._published:
                return
            if self._acc is None:
                self._acc = list(states)
            else:
                self._acc = [RF.device_merge_states(a, s)
                             for a, s in zip(self._acc, states)]
            self._parts_done.add(p)
            if len(self._parts_done) < self.num_partitions:
                return
            self._published = True
            acc = self._acc
            self._acc = None
        for (_k, rf), st in zip(self.entries, acc):
            RF.finalize(rf, st)
            self.metrics["rfKeys"].add(rf.n_keys)
            self.metrics["rfBuildTime"].add(int(rf.build_ms * 1e6))

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)


class TpuShuffledHashJoinExec(_HashJoinBase):
    """partition_wise=False: wide — collect the whole build side, stream
    every partition, one output partition.  partition_wise=True: children
    are co-hash-partitioned on the join keys; partition p joins build
    part p against stream part p (ref: the exchange-fed
    GpuShuffledHashJoinExec plan shape)."""

    def __init__(self, *args, partition_wise: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.partition_wise = partition_wise
        if partition_wise:
            assert (self._build_child.num_partitions
                    == self._stream_child.num_partitions), \
                "partition-wise join needs co-partitioned children"

    @property
    def num_partitions(self) -> int:
        return self._stream_child.num_partitions if self.partition_wise \
            else 1

    def node_desc(self) -> str:
        pw = " partition_wise" if self.partition_wise else ""
        return super().node_desc() + pw

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        if not self.partition_wise:
            assert self.num_partitions == 1
            if p == 0:
                yield from self.execute()
            return
        build = self._collect_batches(
            self._build_child.execute_partition(p))
        yield from self._join_stream(
            build, self._stream_child.execute_partition(p))

    def execute(self) -> Iterator[ColumnarBatch]:
        if self.partition_wise:
            for p in range(self.num_partitions):
                yield from self.execute_partition(p)
            return
        build = self._collect_batches(self._build_child.execute())
        yield from self._join_stream(build, self._stream_child.execute())


class TpuBroadcastHashJoinExec(_HashJoinBase):
    """Small build side collected once and shared across all stream
    partitions — the dimension side of a star join never shuffles
    (ref: GpuBroadcastHashJoinExec; here 'broadcast' = one shared
    device-resident batch, since a single process serves every task;
    multi-host broadcast rides the exchange layer later).

    full_outer is excluded: unmatched-build emission needs matched flags
    merged across ALL stream partitions, which a streaming narrow exec
    cannot do (the planner keeps full_outer on the shuffled path).

    The collected build batch lives in the buffer store as a spillable
    entry (high BROADCAST priority, so it spills last) instead of being
    pinned un-spillably for the exec's lifetime: each stream partition
    pins it only while joining, and builds near the broadcast threshold
    times many concurrent joins stay inside the HBM budget manager."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        assert self.join_type != "full_outer", \
            "broadcast join cannot implement full_outer"
        self._build_lock = threading.Lock()
        self._build_handle = None  # Optional[SpillableBatch]
        self._build_done = False

    @property
    def num_partitions(self) -> int:
        return self._stream_child.num_partitions

    def _get_build_handle(self):
        from spark_rapids_tpu.memory import SpillPriorities, get_store

        with self._build_lock:
            if not self._build_done:
                b = self._collect_batches(self._build_child.execute())
                if b is not None:
                    self._build_handle = get_store().register(
                        b, SpillPriorities.BROADCAST)
                    self._build_handle.unpin()
                self._build_done = True
            return self._build_handle

    def execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        h = self._get_build_handle()
        build = h.get() if h is not None else None
        try:
            yield from self._join_stream(
                build, self._stream_child.execute_partition(p))
        finally:
            if h is not None:
                h.unpin()

    def execute(self) -> Iterator[ColumnarBatch]:
        for p in range(self.num_partitions):
            yield from self.execute_partition(p)

    def close(self) -> None:
        with self._build_lock:
            if self._build_handle is not None:
                self._build_handle.close()
                self._build_handle = None
            self._build_done = False
        super().close()
