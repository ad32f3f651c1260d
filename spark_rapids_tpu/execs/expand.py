"""Expand exec: every input row emitted once per projection list.

TPU re-design of GpuExpandExec (ref: sql-plugin/.../GpuExpandExec.scala:
67,150 — cudf evaluates each projection over the batch and emits the
concatenated tables).  Here all projections evaluate inside ONE compiled
program: results stack to (n_projections, capacity) per column and a
vectorized gather interleaves them into a prefix-compact output of
capacity `n_projections * capacity` with `n_projections * num_rows` live
rows — no per-projection kernel launches, no host loop.

Not materialised at all: under an aggregate that absorbs it, where its
projections are a grouping-set rewrite's and the sets are nested
(ROLLUP).  The aggregate reads `grouping_form()` and takes every
level from one sort of the rows that enter this exec
(`execs/aggregate.py:_rollup_of`; `ops/groupby.py`, the rollup path)."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn, pad_width
from spark_rapids_tpu.execs.base import BatchFn, FusableExec, TpuExec
from spark_rapids_tpu.exprs.base import (
    BoundReference,
    EvalContext,
    Expression,
    Literal,
)


class TpuExpandExec(FusableExec):
    MULTIPLIES_ROWS = True

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 schema: T.Schema, child: TpuExec):
        super().__init__(child)
        self.projections = [list(p) for p in projections]
        self._schema = schema
        #: set by an aggregate that absorbed this exec and takes its
        #: nested grouping sets from one sort of the rows that enter it
        #: (ops.groupby, the rollup path): no row is then expanded
        self.taken_as_rollup = False

    @property
    def schema(self) -> T.Schema:
        return self._schema

    @property
    def fanout(self) -> int:
        """Output rows per input row."""
        return 1 if self.taken_as_rollup else len(self.projections)

    def node_desc(self) -> str:
        how = ", taken as rollup levels of one sort" \
            if self.taken_as_rollup else ""
        return f"TpuExpandExec [{len(self.projections)} projections{how}]"

    def grouping_form(self):
        """`(sources, kept, gid_column, gids)` where the projections
        have the form a grouping-set rewrite gives them, else None:
        every output column but one is, in each projection, the same
        child column's reference or a NULL literal (`sources[c]` the
        child's ordinal, `kept[c]` the projections that pass it), and
        the one left is an integer literal in every projection
        (`gids`, at `gid_column`, where `sources` has None)."""
        sources: list = []
        kept: list = []
        gid_column, gids = None, ()
        for column in zip(*self.projections):
            refs = {e.ordinal for e in column
                    if type(e) is BoundReference}
            others = [e for e in column if type(e) is not BoundReference]
            if len(refs) == 1 and all(
                    type(e) is Literal and e.value is None
                    for e in others):
                sources.append(refs.pop())
                kept.append(frozenset(
                    p for p, e in enumerate(column)
                    if type(e) is BoundReference))
            elif gid_column is None and not refs and all(
                    type(e) is Literal and type(e.value) is int
                    and isinstance(e.dtype, T.IntegralType)
                    for e in others):
                gid_column = len(sources)
                gids = tuple(e.value for e in others)
                sources.append(None)
                kept.append(frozenset())
            else:
                return None
        if gid_column is None:
            return None
        return sources, kept, gid_column, gids

    def fuse_key(self):
        from spark_rapids_tpu.execs.jit_cache import exprs_key

        return ("expand", tuple(exprs_key(p) for p in self.projections),
                repr(self._schema))

    def fusion_exprs(self):
        return tuple(e for p in self.projections for e in p)

    def make_batch_fn(self) -> BatchFn:
        projections = self.projections
        schema = self._schema
        n_proj = len(projections)

        def fn(batch: ColumnarBatch) -> ColumnarBatch:
            cap = batch.capacity
            ctx = EvalContext.for_batch(batch)
            evaluated = [[e.eval(ctx) for e in proj]
                         for proj in projections]
            n = jnp.asarray(batch.num_rows, jnp.int32)
            cap_out = cap * n_proj
            j = jnp.arange(cap_out, dtype=jnp.int32)
            n_safe = jnp.maximum(n, 1)
            p_of_j = jnp.clip(j // n_safe, 0, n_proj - 1)
            i_of_j = j - p_of_j * n_safe
            live = j < n * n_proj
            out_cols = []
            for ci, f in enumerate(schema.fields):
                per_proj = [evaluated[p][ci] for p in range(n_proj)]
                if isinstance(f.dtype, T.StringType):
                    w = pad_width(max(
                        (c.width if isinstance(c, StringColumn) else 1)
                        for c in per_proj))
                    chars, lengths, valid = [], [], []
                    for c in per_proj:
                        if isinstance(c, StringColumn):
                            ch = c.chars
                            if c.width < w:
                                ch = jnp.pad(
                                    ch, ((0, 0), (0, w - c.width)))
                            chars.append(ch)
                            lengths.append(c.lengths.astype(jnp.int32))
                            valid.append(c.validity)
                        else:  # typed-null projection slot
                            chars.append(jnp.zeros((cap, w), jnp.uint8))
                            lengths.append(jnp.zeros(cap, jnp.int32))
                            valid.append(jnp.zeros(cap, bool))
                    sc = jnp.stack(chars)       # (n_proj, cap, w)
                    sl = jnp.stack(lengths)
                    sv = jnp.stack(valid)
                    out_cols.append(StringColumn(
                        sc[p_of_j, i_of_j], sl[p_of_j, i_of_j],
                        sv[p_of_j, i_of_j] & live))
                else:
                    phys = T.to_numpy_dtype(f.dtype)
                    data, valid = [], []
                    for c in per_proj:
                        if isinstance(c, Column) \
                                and not isinstance(c.dtype, T.NullType):
                            data.append(c.data.astype(phys))
                            valid.append(c.validity)
                        else:  # NULL slot (masked grouping column)
                            data.append(jnp.zeros(cap, phys))
                            valid.append(jnp.zeros(cap, bool))
                    sd = jnp.stack(data)        # (n_proj, cap)
                    sv = jnp.stack(valid)
                    out_cols.append(Column(
                        sd[p_of_j, i_of_j],
                        sv[p_of_j, i_of_j] & live, f.dtype))
            return ColumnarBatch(out_cols, n * n_proj, schema)

        return fn
