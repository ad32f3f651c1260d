"""Expand exec: every input row emitted once per projection list.

TPU re-design of GpuExpandExec (ref: sql-plugin/.../GpuExpandExec.scala:
67,150 — cudf evaluates each projection over the batch and emits the
concatenated tables).  Here all projections evaluate inside ONE compiled
program: results stack to (n_projections, capacity) per column and a
vectorized gather interleaves them into a prefix-compact output of
capacity `n_projections * capacity` with `n_projections * num_rows` live
rows — no per-projection kernel launches, no host loop."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn, pad_width
from spark_rapids_tpu.execs.base import BatchFn, FusableExec, TpuExec
from spark_rapids_tpu.exprs.base import EvalContext, Expression


class TpuExpandExec(FusableExec):
    MULTIPLIES_ROWS = True

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 schema: T.Schema, child: TpuExec):
        super().__init__(child)
        self.projections = [list(p) for p in projections]
        self._schema = schema

    @property
    def schema(self) -> T.Schema:
        return self._schema

    @property
    def fanout(self) -> int:
        """Output rows per input row."""
        return len(self.projections)

    def node_desc(self) -> str:
        return f"TpuExpandExec [{len(self.projections)} projections]"

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
