"""Equi-join kernels.

TPU re-design of the reference's hash-join core (ref: sql-plugin/.../sql/
rapids/execution/GpuHashJoin.scala:62,190 and JoinGatherer.scala:55 —
cudf builds device hash tables and emits gather maps).  XLA has no
device hash table, and join output size is data-dependent, so the design
here is different by construction:

1. **Dense key ranks instead of a hash table**: build-side and
   stream-side key columns are concatenated and run through the same
   lexsort + boundary machinery as group-by, yielding a dense int32
   `gid` per row where equal SQL keys (any column mix, incl. strings)
   share a gid.  Equality then reduces to integer equality — no
   collisions, no probing.
2. **Counting + offset expansion instead of gather-map growth**: per
   stream row the number of build matches is `counts[gid]`; an
   exclusive scan gives each stream row its output offset, and the
   output pair table of static capacity is filled by a vectorized
   searchsorted over the scan (the JoinGatherer chunking analog: the
   caller sizes the output from the returned total and can re-invoke
   with a bigger bucket).

NULL join keys never match (SQL equality), are excluded from counts,
and surface only through the outer-join unmatched paths.  A key the
caller marks null-safe (`<=>`: INTERSECT and EXCEPT compare so) keeps
the rank the sort gave it, where NULL already equals NULL, and bars no
row."""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.ops.groupby import _keys_equal_adjacent
from spark_rapids_tpu.ops.sort import (
    SortOrder,
    sort_permutation,
    stable_argsort,
)


def _pad_string_widths(a: StringColumn, b: StringColumn
                       ) -> tuple[StringColumn, StringColumn]:
    w = max(a.width, b.width)
    pa_ = jnp.pad(a.chars, ((0, 0), (0, w - a.width)))
    pb = jnp.pad(b.chars, ((0, 0), (0, w - b.width)))
    return (StringColumn(pa_, a.lengths, a.validity),
            StringColumn(pb, b.lengths, b.validity))


def _concat_key_cols(build: list[AnyColumn], stream: list[AnyColumn]
                     ) -> list[AnyColumn]:
    out = []
    for cb, cs in zip(build, stream):
        if isinstance(cb, StringColumn):
            cb, cs = _pad_string_widths(cb, cs)
            out.append(StringColumn(
                jnp.concatenate([cb.chars, cs.chars]),
                jnp.concatenate([cb.lengths, cs.lengths]),
                jnp.concatenate([cb.validity, cs.validity])))
        else:
            out.append(Column(jnp.concatenate([cb.data, cs.data]),
                              jnp.concatenate([cb.validity, cs.validity]),
                              cb.dtype))
    return out


def compute_gids(build_keys: list[AnyColumn], stream_keys: list[AnyColumn],
                 live_b: jax.Array, live_s: jax.Array,
                 null_safe: Sequence[bool] = ()):
    """Dense rank over the union of both sides' keys.  The rank is SQL
    grouping equality (NULL equals NULL); `null_b` / `null_s` then bar
    every row with a NULL in a key that is not `null_safe`.

    Returns (gid_b, gid_s, null_b, null_s, n_combined_capacity)."""
    cap_b = live_b.shape[0]
    cap_s = live_s.shape[0]
    capc = cap_b + cap_s
    combined = _concat_key_cols(build_keys, stream_keys)
    live = jnp.concatenate([live_b, live_s])
    schema = T.Schema([T.Field(f"k{i}", c.dtype) for i, c in
                       enumerate(combined)])
    orders = [SortOrder(i) for i in range(len(combined))]
    keys_batch = ColumnarBatch(list(combined), capc, schema)
    perm = sort_permutation(keys_batch, orders)
    # dead rows must not pollute groups: push them last by re-sorting on
    # (dead, key) — emulate by stable argsort on dead flag after key sort
    dead_sorted = jnp.take(~live, perm)
    perm = jnp.take(perm, stable_argsort(dead_sorted))

    sorted_cols = [c.gather(perm) for c in combined]
    live_sorted = jnp.take(live, perm)
    same = jnp.ones((capc,), bool)
    for c in sorted_cols:
        same = same & _keys_equal_adjacent(c)
    idx = jnp.arange(capc, dtype=jnp.int32)
    is_start = live_sorted & ((idx == 0) | ~same)
    gid_sorted = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    gid_sorted = jnp.where(live_sorted, gid_sorted, capc - 1)
    # invert permutation
    gid = jnp.zeros((capc,), jnp.int32).at[perm].set(gid_sorted)
    null_flags = jnp.zeros((capc,), bool)
    for at, c in enumerate(combined):
        if not (at < len(null_safe) and null_safe[at]):
            null_flags = null_flags | ~c.validity
    return (gid[:cap_b], gid[cap_b:], null_flags[:cap_b],
            null_flags[cap_b:], capc)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class JoinState:
    """Traceable intermediate state shared by sizing and expansion."""

    gid_s: jax.Array
    cnt_s: jax.Array  # matches per stream row (outer rows forced to >=1)
    matched_s: jax.Array
    cum_excl: jax.Array
    start_by_gid: jax.Array
    build_rows_sorted: jax.Array
    live_s: jax.Array
    matched_b: jax.Array  # per build row (for full outer)
    live_b: jax.Array


def join_state(build: ColumnarBatch, stream: ColumnarBatch,
               build_key_cols: list[AnyColumn],
               stream_key_cols: list[AnyColumn],
               join_type: str,
               null_safe: Sequence[bool] = ()) -> JoinState:
    live_b = build.row_mask()
    live_s = stream.row_mask()
    gid_b, gid_s, null_b, null_s, capc = compute_gids(
        build_key_cols, stream_key_cols, live_b, live_s, null_safe)

    joinable_b = live_b & ~null_b
    joinable_s = live_s & ~null_s
    counts = jax.ops.segment_sum(
        joinable_b.astype(jnp.int32),
        jnp.where(joinable_b, gid_b, capc), num_segments=capc)
    starts = jnp.cumsum(counts) - counts
    # stable order of build rows by gid: row at starts[g]+j is the j-th
    # build row with gid g
    build_sort = stable_argsort(jnp.where(joinable_b, gid_b, capc))

    cnt = jnp.where(joinable_s, jnp.take(counts, gid_s), 0)
    matched_s = cnt > 0
    if join_type in ("left_outer", "full_outer"):
        cnt_eff = jnp.where(live_s & ~matched_s, 1, cnt)
    else:
        cnt_eff = cnt
    cum = jnp.cumsum(cnt_eff) - cnt_eff

    stream_counts = jax.ops.segment_sum(
        joinable_s.astype(jnp.int32),
        jnp.where(joinable_s, gid_s, capc), num_segments=capc)
    matched_b = joinable_b & (jnp.take(stream_counts, gid_b) > 0)

    return JoinState(gid_s=gid_s, cnt_s=cnt_eff, matched_s=matched_s,
                     cum_excl=cum, start_by_gid=starts,
                     build_rows_sorted=build_sort, live_s=live_s,
                     matched_b=matched_b, live_b=live_b)


def expand_pairs(state: JoinState, out_cap: int, offset=0
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Produce (stream_idx, build_idx, pair_live, build_matched) arrays
    of static length out_cap for output pairs [offset, offset+out_cap)
    — the JoinGatherer chunk window (ref: JoinGatherer.scala:55
    gatherNext(n)); offset may be a traced scalar so ONE compiled
    program serves every chunk."""
    total = jnp.sum(state.cnt_s).astype(jnp.int32)
    i = jnp.arange(out_cap, dtype=jnp.int32) + jnp.asarray(
        offset, jnp.int32)
    s = jnp.searchsorted(state.cum_excl, i, side="right").astype(
        jnp.int32) - 1
    s = jnp.clip(s, 0, state.cum_excl.shape[0] - 1)
    j = i - jnp.take(state.cum_excl, s)
    pair_live = i < total
    matched = jnp.take(state.matched_s, s)
    gid = jnp.take(state.gid_s, s)
    pos = jnp.take(state.start_by_gid, gid) + j
    pos = jnp.clip(pos, 0, state.build_rows_sorted.shape[0] - 1)
    b = jnp.take(state.build_rows_sorted, pos)
    return s, b, pair_live, matched


def gather_joined(build: ColumnarBatch, stream: ColumnarBatch,
                  s_idx: jax.Array, b_idx: jax.Array, pair_live: jax.Array,
                  matched: jax.Array, num_rows,
                  out_schema: T.Schema,
                  stream_first: bool = True) -> ColumnarBatch:
    scols = [c.gather(s_idx, pair_live) for c in stream.columns]
    bcols = [c.gather(b_idx, pair_live & matched) for c in build.columns]
    cols = scols + bcols if stream_first else bcols + scols
    return ColumnarBatch(cols, num_rows, out_schema)
