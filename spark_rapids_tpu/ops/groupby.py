"""Group-by aggregation: four paths to one answer.

TPU counterpart of cudf's `Table.groupBy(...).aggregate(...)` as used by
GpuHashAggregateExec (ref: sql-plugin/.../aggregate.scala:240,366).  cudf
uses a device hash table; here every path is a program of static shape
whose output is prefix-compact with `num_groups` live rows (a traced
scalar).  `groupby_aggregate` picks, at trace time and from what the
batch shows:

1. **Coded, masked** (`_coded_groupby`, `_segment_sums(masked=True)`):
   every key carries the wire's dictionary sidecar, so a row's combined
   code IS its dense group id in a static domain of K segments, and
   K x m (m matrix columns) is at most `MAX_MASKED_CELLS`.  The sums are
   compare + select + reduce, no scatter: 5.6 ms for q1's update of
   2^20 rows (K 81, m 11) on a TPU v5e.
2. **Coded, scatter** (`_segment_sums(masked=False)`): the same dense
   ids, K up to `MAX_CODED_DOMAIN`, through one `jax.ops.segment_sum`.
   A scatter-add is serial in its rows on the TPU: 95-98 ms for 2^20
   rows whatever K and m are (91 ns a row; it was q1's whole cost).
3. **Sort** (the rest of `groupby_aggregate`): any other keys, floats
   and merged partials among them (a partial carries no codes):

       sort rows by key -> mark segment starts -> segment_{sum,min,max}

   with output capacity equal to the input's.  The keys of the groups
   are gathered from each segment's first row (one more stable pass
   finds them); the per-spec `_eval_agg` still scatters (q3's and
   q67's aggregates; ROADMAP S2).

The fourth is not `groupby_aggregate`'s to pick: the aggregate exec
takes it where the plan under its update is a grouping-set Expand
whose sets are nested (ROLLUP; `execs/aggregate.py`).

4. **Rollup** (`rollup_sort`, then `rollup_write`): the nested sets are
   prefixes of one key list, so rows sorted ONCE by that list are
   sorted for every set, and each set's segment starts are read off
   the same sorted batch.  The Expand is never materialised: the sort,
   the gathers and the key comparisons run at the rows that entered
   it, not at rows x sets.  Two programs with one count between them:
   the first sorts and counts the groups of all sets, the second
   writes them at the capacity that count pads to.

The times are the chip's (PERF.md section 6, PR 26).  Aggregations are
expressed as (update, merge) pairs the way Spark aggregate modes are
(Partial -> PartialMerge/Final), so multi-batch and post-shuffle merging
reuse the same kernels on the partial-result columns.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    AnyColumn,
    Column,
    StringColumn,
    pad_capacity,
)
from spark_rapids_tpu.ops.sort import (
    SortOrder,
    sort_permutation,
    stable_argsort,
)

_TRACING = threading.local()


@contextlib.contextmanager
def noting_paths():
    """Collects, while a program is traced on this thread, the path
    each `groupby_aggregate` in it takes (`masked`, `scatter`, `sort`):
    the choice is made from static shapes, once a trace, so whoever
    compiles the program keeps it for the spans of its later runs."""
    prev = getattr(_TRACING, "paths", None)
    _TRACING.paths = paths = []
    try:
        yield paths
    finally:
        _TRACING.paths = prev


def _note_path(path: str) -> None:
    paths = getattr(_TRACING, "paths", None)
    if paths is not None:
        paths.append(path)


def _keys_equal_adjacent(col: AnyColumn) -> jax.Array:
    """row i equal to row i-1 under SQL grouping (NULL == NULL)."""
    if isinstance(col, StringColumn):
        chars_eq = jnp.all(col.chars == jnp.roll(col.chars, 1, axis=0), axis=1)
        len_eq = col.lengths == jnp.roll(col.lengths, 1)
        data_eq = chars_eq & len_eq
    else:
        data_eq = col.data == jnp.roll(col.data, 1)
        if isinstance(col.dtype, (T.FloatType, T.DoubleType)):
            # NaN == NaN for grouping; -0.0 groups with 0.0 via pre-normalize
            both_nan = jnp.isnan(col.data) & jnp.isnan(jnp.roll(col.data, 1))
            data_eq = data_eq | both_nan
    valid_eq = col.validity == jnp.roll(col.validity, 1)
    null_pair = (~col.validity) & (~jnp.roll(col.validity, 1))
    return valid_eq & (data_eq | null_pair)


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregation over a value ordinal.  `op` in
    {sum, count, count_star, min, max, first, last}; avg is planned as
    sum+count and finalized by the exec (the way the reference splits
    GpuAverage into update/merge expressions, AggregateFunctions.scala)."""

    op: str
    ordinal: int  # ignored for count_star
    out_dtype: Optional[T.DataType] = None


def _sum_dtype(dt: T.DataType) -> T.DataType:
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        return T.DOUBLE
    if isinstance(dt, T.DecimalType):
        return T.DecimalType(min(dt.precision + 10, T.DecimalType.MAX_PRECISION),
                             dt.scale)
    return T.LONG


def agg_output_dtype(spec: AggSpec, value_dtype: Optional[T.DataType]
                     ) -> T.DataType:
    if spec.out_dtype is not None:
        return spec.out_dtype
    if spec.op in ("count", "count_star"):
        return T.LONG
    if spec.op == "sum":
        assert value_dtype is not None
        return _sum_dtype(value_dtype)
    assert value_dtype is not None
    return value_dtype


#: widest combined (dict ++ NULL) key domain the coded fast path takes;
#: past this the padded segment arrays outgrow the win over sorting.
#: 2^17 keeps the segment matrix a few MB (trivial next to a
#: multi-hundred-ms device lexsort of the input rows) while admitting
#: e.g. a (store x item) TPC-DS grouping of ~18K combined domain.
MAX_CODED_DOMAIN = 1 << 17

#: most segments x matrix columns (K x m) the coded path reduces as a
#: masked sum; above it the same matrix goes through `segment_sum`.  A
#: scatter-add on the TPU costs 91 ns a row whatever K and m are (serial
#: in its rows: 95-98 ms for 2^20 DOUBLE rows at K 9..4,225 and m 11 or
#: 30, 80 ms for int64), a masked sum about 7 us per K x m for the same
#: rows (6.3 ms at K 81, m 11; 82 ms at K 1,089), so they cross near
#: K x m = 14,000 (DOUBLE) and 12,000 (int64); 2^13 keeps the masked
#: form at three quarters of the scatter's time or less (TPU v5e,
#: `scripts/sweep_coded_reduce.py`; PERF.md section 6, PR 26).
MAX_MASKED_CELLS = 1 << 13

#: rows of one masked partial sum: the row axis is folded to
#: (cap / lanes, lanes) and reduced over the major axis first, so the
#: inner loop is lane-wise adds with no cross-lane reduce.  q1's update
#: at 2^20 rows: 8.0 ms at 128 and 256, 6.2 at 1,024, 5.6 at 4,096,
#: 7.2 at 16,384 (same sweep)
_MASKED_LANES = 4096


def _segment_sums(cols: list, seg: jax.Array, K: int,
                  masked: bool) -> jax.Array:
    """Per-segment sums of same-dtype `(cap,)` columns, as `(K, m)`.
    Rows whose `seg` is K (dead or filtered) land in no segment.  Both
    forms accumulate in the columns' own dtype (DOUBLE or int64, which
    wraps); they differ only in the order the terms are added."""
    if not masked:
        with jax.named_scope("groupby.coded.scatter"):
            return jax.ops.segment_sum(jnp.stack(cols, axis=1), seg,
                                       num_segments=K)
    with jax.named_scope("groupby.coded.masked"):
        cap = seg.shape[0]
        lanes = math.gcd(cap, _MASKED_LANES)
        M = jnp.stack(cols, axis=0).reshape(len(cols), cap // lanes, lanes)
        hit = seg.reshape(1, cap // lanes, lanes) \
            == jnp.arange(K, dtype=jnp.int32)[:, None, None]
        # (K, m, cap/lanes, lanes) is never materialised: XLA fuses the
        # select into the reduce (compare + select + reduce, no scatter)
        part = jnp.sum(jnp.where(hit[:, None], M[None], 0), axis=2)
        return jnp.sum(part, axis=2)


def _coded_key_domains(key_cols: Sequence[AnyColumn]) -> Optional[list[int]]:
    """Per-key dictionary sizes when EVERY key column carries the wire
    dict sidecar (codes + device dictionary) and the combined domain is
    small, else None.  Static decision: dict sizes are array shapes.
    Both string ("sdict") and fixed-width numeric ("dict") sidecars
    qualify."""
    ks: list[int] = []
    total = 1
    for kc in key_cols:
        if getattr(kc, "codes", None) is None:
            return None
        if isinstance(kc, StringColumn):
            padded = int(kc.dict_chars.shape[0])
        else:
            if isinstance(kc.dtype, (T.FloatType, T.DoubleType)):
                # a Parquet dictionary may hold -0.0 and 0.0 (or two
                # NaN payloads) as distinct entries; raw codes would
                # split groups SQL merges.  Float keys take the sort
                # path, whose keys normalize both.
                return None
            padded = int(kc.dict_values.shape[0])
        # the wire pads the dictionary to its pow2 capacity bucket; a
        # tight (16-bucketed) bound on the true entry count rides in
        # dict_len — using the padded capacity would overestimate the
        # combined domain (compounding per key), spuriously exceeding
        # MAX_CODED_DOMAIN and padding the segment matrix
        k = kc.dict_len if kc.dict_len is not None else padded
        ks.append(k)
        total *= k + 1  # +1: the NULL group rides past the dictionary
        if total > MAX_CODED_DOMAIN:
            return None
    return ks


def _coded_groupby(batch: ColumnarBatch, key_ordinals: Sequence[int],
                   ks: list[int], aggs: Sequence[AggSpec],
                   out_schema: T.Schema,
                   live_mask=None) -> ColumnarBatch:
    """Sort-free group-by over dictionary codes (the analog of cudf's
    hash groupby for low-cardinality keys, ref: aggregate.scala:240-430):
    each row's combined code IS its dense group id, so the whole
    aggregation is segment reductions over a static code domain: no
    O(n log n) lexsort of the key bytes.

    Every sum/count-family aggregate packs into ONE (rows, m) DOUBLE
    matrix (integer sums into a second, int64 one), a column per
    distinct operand, reduced by `_segment_sums`: a masked sum while
    K x m <= `MAX_MASKED_CELLS`, else `segment_sum` (the module header
    has what each costs on the chip).  Compaction is a cumsum + one
    gather; only min/max/first/last fall back to per-spec segment ops.
    Output is compact (capacity = padded domain size), orders of
    magnitude below the input bucket."""
    from spark_rapids_tpu.columnar.column import MIN_CAPACITY

    cap = batch.capacity
    live = batch.row_mask()
    if live_mask is not None:
        live = live & live_mask
    key_cols = [batch.columns[o] for o in key_ordinals]

    K = 1
    for k in ks:
        K *= k + 1
    seg = jnp.zeros((cap,), jnp.int32)
    for kc, k in zip(key_cols, ks):
        pid = jnp.where(kc.validity, jnp.clip(kc.codes.astype(jnp.int32),
                                              0, k - 1), k)
        seg = seg * (k + 1) + pid
    seg = jnp.where(live, seg, K)  # dead rows drop out of segment ops

    # pack the sum/count family into one f64 matrix (and one i64 matrix
    # for integer-typed sums, whose wrap-on-overflow semantics f64
    # cannot reproduce); slot 0 = live-ones: count_star AND occupancy.
    # One column per distinct operand: specs that read the same traced
    # arrays (q1's sum and avg of one input, a count beside a sum) share
    # it, keyed by the identity of the input's data and validity.
    f64_cols: list = [jnp.where(live, 1.0, 0.0)]
    i64_cols: list = []
    seen: dict = {}

    def column_of(cols: list, key: tuple, make) -> int:
        if key not in seen:
            cols.append(make())
            seen[key] = len(cols) - 1
        return seen[key]

    slots: list = []  # per spec: ("f64"/"i64", value_slot, nvalid_slot)
    for spec in aggs:
        if spec.op == "count_star":
            slots.append(("star",))
            continue
        vcol = batch.columns[spec.ordinal]
        if spec.op != "count" and not (spec.op == "sum"
                                       and isinstance(vcol, Column)):
            slots.append(("segop",))
            continue
        valid = vcol.validity & live
        nv = column_of(f64_cols, ("nv", id(vcol.validity)),
                       lambda: valid.astype(jnp.float64))
        if spec.op == "count":
            slots.append(("count", nv))
            continue
        out_dtype = agg_output_dtype(spec, vcol.dtype)
        operand = (id(vcol.data), id(vcol.validity))
        if np.dtype(T.to_numpy_dtype(out_dtype)).kind == "f":
            vs = column_of(f64_cols, ("f64",) + operand, lambda: jnp.where(
                valid, vcol.data.astype(jnp.float64), 0.0))
            slots.append(("f64", vs, nv, out_dtype))
        else:
            vs = column_of(i64_cols, ("i64",) + operand, lambda: jnp.where(
                valid, vcol.data.astype(jnp.int64),
                jnp.asarray(0, jnp.int64)))
            slots.append(("i64", vs, nv, out_dtype))

    # one choice per program, from what the trace sees: masked work
    # grows with K x m, the scatter's with neither
    m = len(f64_cols) + len(i64_cols)
    masked = K * m <= MAX_MASKED_CELLS
    _note_path("masked" if masked else "scatter")
    if _trace.TRACER.enabled:
        _trace.event("groupby.coded_reduce",
                     kind="masked" if masked else "scatter",
                     K=K, m=m, cap=cap)
    S = _segment_sums(f64_cols, seg, K, masked)
    Si = _segment_sums(i64_cols, seg, K, masked) if i64_cols else None

    occ = S[:, 0] > 0.0
    ranks = jnp.cumsum(occ.astype(jnp.int32))
    num_groups = ranks[-1]
    out_cap = max(MIN_CAPACITY, pad_capacity(K))
    # inv[g] = segment id of the g-th occupied segment (binary search of
    # the rank prefix — one gather-free kernel, no scatter)
    inv = jnp.clip(
        jnp.searchsorted(ranks, jnp.arange(out_cap, dtype=jnp.int32) + 1,
                         side="left").astype(jnp.int32), 0, K - 1)
    group_live = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
    Sc = jnp.take(S, inv, axis=0)
    Sic = jnp.take(Si, inv, axis=0) if Si is not None else None

    need_segop = any(s[0] == "segop" for s in slots)
    if need_segop:
        dest = jnp.where(occ, ranks - 1, out_cap)
        row_seg = jnp.take(
            jnp.concatenate([dest, jnp.full((1,), out_cap, jnp.int32)]),
            jnp.minimum(seg, K))

    # keys: decode each compact slot's segment id back to its dict entry
    out_cols: list[AnyColumn] = []
    key_ids: list[jax.Array] = []
    sid = inv
    for k in reversed(ks):
        key_ids.append(sid % (k + 1))
        sid = sid // (k + 1)
    key_ids.reverse()
    for kc, k, kid in zip(key_cols, ks, key_ids):
        valid_g = (kid < k) & group_live
        if isinstance(kc, StringColumn):
            dchars = jnp.concatenate(
                [kc.dict_chars,
                 jnp.zeros((1, kc.dict_chars.shape[1]), jnp.uint8)])
            dlens = jnp.concatenate(
                [kc.dict_lens.astype(jnp.int32),
                 jnp.zeros((1,), jnp.int32)])
            chars = jnp.take(dchars, kid, axis=0) \
                * valid_g[:, None].astype(jnp.uint8)
            lengths = jnp.take(dlens, kid) * valid_g.astype(jnp.int32)
            out_cols.append(StringColumn(chars, lengths, valid_g))
        else:
            dvals = jnp.concatenate(
                [kc.dict_values,
                 jnp.zeros((1,), kc.dict_values.dtype)])
            out_cols.append(Column(jnp.take(dvals, kid), valid_g,
                                   kc.dtype))

    for spec, slot in zip(aggs, slots):
        if slot[0] == "star":
            out_cols.append(Column(Sc[:, 0].astype(jnp.int64),
                                   group_live, T.LONG))
        elif slot[0] == "count":
            out_cols.append(Column(Sc[:, slot[1]].astype(jnp.int64),
                                   group_live, T.LONG))
        elif slot[0] == "f64":
            _, vs, nv, out_dtype = slot
            out_cols.append(Column(
                Sc[:, vs].astype(T.to_numpy_dtype(out_dtype)),
                group_live & (Sc[:, nv] > 0), out_dtype))
        elif slot[0] == "i64":
            _, vs, nv, out_dtype = slot
            out_cols.append(Column(
                Sic[:, vs].astype(T.to_numpy_dtype(out_dtype)),
                group_live & (Sc[:, nv] > 0), out_dtype))
        else:
            out_cols.append(_eval_agg(spec, batch, row_seg, live,
                                      group_live, out_cap, cap))
    assert len(out_schema) == len(key_cols) + len(aggs)
    return ColumnarBatch(out_cols, num_groups, out_schema)


def _plain(col: AnyColumn) -> AnyColumn:
    """`col` without a dictionary sidecar, as a partial carries none."""
    if isinstance(col, StringColumn):
        return StringColumn(col.chars, col.lengths, col.validity)
    if isinstance(col, Column):
        return Column(col.data, col.validity, col.dtype)
    return col


def groupby_aggregate(batch: ColumnarBatch, key_ordinals: Sequence[int],
                      aggs: Sequence[AggSpec],
                      out_schema: T.Schema,
                      live_mask=None) -> ColumnarBatch:
    """One-batch group-by.  Output columns = keys ++ aggs, prefix-compact
    with num_groups live rows.  Traceable (fixed shapes throughout).
    `live_mask` further restricts the live rows (a fused WHERE: the
    aggregate masks filtered rows instead of paying a compaction)."""
    ks = _coded_key_domains([batch.columns[o] for o in key_ordinals])
    if ks is not None:
        return _coded_groupby(batch, key_ordinals, ks, aggs, out_schema,
                              live_mask)
    _note_path("sort")
    cap = batch.capacity
    live = batch.row_mask()
    if live_mask is not None:
        live = live & live_mask
    orders = [SortOrder(o) for o in key_ordinals]
    perm = sort_permutation(batch, orders, live=live)
    sorted_batch = batch.gather(perm, batch.num_rows)
    live_sorted = jnp.take(live, perm)

    key_cols = [sorted_batch.columns[o] for o in key_ordinals]
    same_as_prev = jnp.ones((cap,), bool)
    for kc in key_cols:
        same_as_prev = same_as_prev & _keys_equal_adjacent(kc)
    idx = jnp.arange(cap, dtype=jnp.int32)
    is_start = live_sorted & ((idx == 0) | ~same_as_prev)
    seg_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    # dead rows -> out-of-range segment (dropped by segment_* ops)
    seg_id = jnp.where(live_sorted, seg_id, cap)
    num_groups = jnp.sum(is_start.astype(jnp.int32))

    out_cols: list[AnyColumn] = []
    # keys: the value at each segment's first row.  The g-th group's
    # first row is the g-th row that starts a segment: one more stable
    # pass puts those rows first, in order, and each key array is then
    # ONE gather.  (A scatter to [0, num_groups) is the same answer at
    # 91 ns a row and array on the TPU, where a gather takes a tenth:
    # q67's update writes 23 key arrays of 4.7M rows.)
    first_rows = stable_argsort(~is_start)
    group_live = idx < num_groups
    for kc in key_cols:
        out_cols.append(_plain(kc.gather(first_rows, group_live)))

    for spec in aggs:
        out_cols.append(_eval_agg(spec, sorted_batch, seg_id, live_sorted,
                                  group_live, cap, cap))
    n_keys = len(key_cols)
    assert len(out_schema) == n_keys + len(aggs)
    return ColumnarBatch(out_cols, num_groups, out_schema)


@dataclasses.dataclass(frozen=True)
class RollupLevels:
    """Nested grouping sets over one key list, as static structure.

    `chain`: the batch ordinals of the grouping keys, ordered by the set
    that drops them, the key every set keeps first.  `levels`: one
    `(depth, gid)` a set, in the order the sets were written: the set
    keeps `chain[:depth]`, reads NULL for `chain[depth:]`, and `gid` is
    the literal that tells it from the others.  `key_ordinals`: the
    same keys in the order the output lists them; `gid_position`: where
    among them the literal's column goes."""

    chain: tuple
    levels: tuple
    key_ordinals: tuple
    gid_position: int


def rollup_sort(batch: ColumnarBatch, shape: RollupLevels,
                live_mask=None) -> tuple:
    """First half of the rollup path: ONE sort of `batch` (the grouping
    keys and the aggregates' inputs of the rows that would have entered
    the Expand) serves every level.  Returns the sorted batch, live rows
    first and counted by its `num_rows`; `breaks`, per row the position
    in `shape.chain` of the first key that differs from the row before
    (the chain's length where none does, -1 for the first row), so that
    a row starts a group of a level exactly where `breaks < depth`; and
    the groups of all levels together, one scalar, which sizes
    `rollup_write`'s output.  Traceable."""
    _note_path("rollup")
    cap = batch.capacity
    live = batch.row_mask()
    if live_mask is not None:
        live = live & live_mask
    perm = sort_permutation(batch, [SortOrder(o) for o in shape.chain],
                            live=live)
    n_live = jnp.sum(live.astype(jnp.int32))
    sorted_batch = ColumnarBatch(
        [_plain(c.gather(perm)) for c in batch.columns], n_live,
        batch.schema)
    # one pass over the keys, minor key first, leaves the major-most
    # key that differs
    breaks = jnp.full((cap,), len(shape.chain), jnp.int32)
    for pos in reversed(range(len(shape.chain))):
        same = _keys_equal_adjacent(sorted_batch.columns[shape.chain[pos]])
        breaks = jnp.where(same, breaks, pos)
    breaks = jnp.where(jnp.arange(cap, dtype=jnp.int32) == 0, -1, breaks)
    live_sorted = sorted_batch.row_mask()
    total = sum(jnp.sum((live_sorted & (breaks < depth)).astype(jnp.int32))
                for depth, _ in shape.levels)
    return sorted_batch, breaks, total


def rollup_write(sorted_batch: ColumnarBatch, breaks: jax.Array,
                 shape: RollupLevels, aggs: Sequence[AggSpec],
                 out_schema: T.Schema, out_capacity: int) -> ColumnarBatch:
    """Second half: the groups of every level, written prefix-compact
    into `out_capacity` rows (at least `rollup_sort`'s total), the
    levels one after another.  A level's sums are `_eval_agg` over the
    one sorted batch with its own segment ids, so two levels that hold
    the same rows add the same terms in the same order: their DOUBLE
    sums are bit-equal, which a rank above them relies on.  Keys past a
    level's depth read NULL; the literal's column its `gid`.
    Traceable."""
    cap = sorted_batch.capacity
    n_levels = len(shape.levels)
    live_sorted = sorted_batch.row_mask()
    depths = jnp.asarray([d for d, _ in shape.levels], jnp.int32)
    starts = live_sorted[None, :] & (breaks[None, :] < depths[:, None])
    counts = jnp.sum(starts.astype(jnp.int32), axis=1)
    ends = jnp.cumsum(counts)
    offsets = ends - counts
    out_idx = jnp.arange(out_capacity, dtype=jnp.int32)
    group_live = out_idx < ends[-1]
    level_of = jnp.minimum(
        jnp.sum((out_idx[:, None] >= ends[None, :]).astype(jnp.int32),
                axis=1), n_levels - 1)
    in_level = [group_live & (level_of == lv) for lv in range(n_levels)]

    # keys: a group's are its first row's.  One stable pass a level puts
    # the rows that start a group first, in order (a scan, so the sort
    # inside is compiled once); every key array is then ONE gather at
    # the output's capacity
    first_rows = jax.lax.map(stable_argsort, ~starts)
    src = first_rows[level_of,
                     jnp.clip(out_idx - jnp.take(offsets, level_of),
                              0, cap - 1)]
    depth_of = jnp.take(depths, level_of)
    position = {o: pos for pos, o in enumerate(shape.chain)}
    out_cols: list[AnyColumn] = []
    for o in shape.key_ordinals:
        out_cols.append(_plain(sorted_batch.columns[o].gather(
            src, group_live & (position[o] < depth_of))))
    gid_field = out_schema.fields[shape.gid_position]
    gids = jnp.asarray([g for _, g in shape.levels],
                       T.to_numpy_dtype(gid_field.dtype))
    out_cols.insert(shape.gid_position, Column(
        jnp.take(gids, level_of), group_live, gid_field.dtype))

    segs = jnp.where(live_sorted[None, :],
                     jnp.cumsum(starts.astype(jnp.int32), axis=1) - 1
                     + offsets[:, None], out_capacity)
    for spec in aggs:
        parts = [_eval_agg(spec, sorted_batch, segs[lv], live_sorted,
                           in_level[lv], out_capacity, cap)
                 for lv in range(n_levels)]
        data, validity = parts[0].data, parts[0].validity
        for lv in range(1, n_levels):
            data = jnp.where(in_level[lv], parts[lv].data, data)
            validity = validity | parts[lv].validity
        out_cols.append(Column(data, validity, parts[0].dtype))
    assert len(out_schema) == len(out_cols)
    return ColumnarBatch(out_cols, ends[-1], out_schema)


def _minmax_sentinel(phys, op: str):
    """Identity element masking NULL slots for min/max reductions."""
    if jnp.issubdtype(phys, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, phys)
    info = jnp.iinfo(phys)
    return jnp.asarray(info.max if op == "min" else info.min, phys)


def _firstlast_pos(valid: jax.Array, op: str, cap: int) -> jax.Array:
    """Per-row candidate position for first/last non-null selection."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    return jnp.where(valid, idx, cap if op == "first" else -1)


def _eval_agg(spec: AggSpec, sorted_batch: ColumnarBatch, seg_id: jax.Array,
              live_sorted: jax.Array, group_live: jax.Array,
              num_segments: int, row_cap: int) -> Column:
    """One aggregation as segment reductions.  `seg_id[row_cap]` maps
    each row to its output segment in [0, num_segments) (out-of-range =
    dropped); output arrays have length `num_segments`.  The sort path
    passes num_segments == row_cap; the coded path a compact domain."""
    if spec.op == "count_star":
        ones = live_sorted.astype(jnp.int64)
        counts = jax.ops.segment_sum(ones, seg_id,
                                     num_segments=num_segments)
        return Column(counts, group_live, T.LONG)

    vcol = sorted_batch.columns[spec.ordinal]
    valid = vcol.validity & live_sorted
    nvalid = jax.ops.segment_sum(valid.astype(jnp.int64), seg_id,
                                 num_segments=num_segments)

    if spec.op == "count":  # validity-only: works for ANY column kind
        return Column(nvalid, group_live, T.LONG)
    assert isinstance(vcol, Column), f"agg over {vcol.dtype} unsupported"

    out_dtype = agg_output_dtype(spec, vcol.dtype)
    phys = T.to_numpy_dtype(out_dtype)
    if spec.op == "sum":
        vals = jnp.where(valid, vcol.data.astype(phys), jnp.asarray(0, phys))
        sums = jax.ops.segment_sum(vals, seg_id, num_segments=num_segments)
        return Column(sums, group_live & (nvalid > 0), out_dtype)
    if spec.op in ("min", "max"):
        vals = jnp.where(valid, vcol.data.astype(phys),
                         _minmax_sentinel(phys, spec.op))
        f = jax.ops.segment_min if spec.op == "min" else jax.ops.segment_max
        if jnp.issubdtype(jnp.dtype(phys), jnp.floating):
            # Spark float total order: NaN is GREATEST.  segment_max's
            # IEEE NaN propagation already realizes that; min must
            # instead IGNORE NaN unless the whole group is NaN (then
            # the answer is NaN, not NULL).
            isnan = valid & jnp.isnan(vcol.data)
            if spec.op == "min":
                vals = jnp.where(isnan, _minmax_sentinel(phys, "min"),
                                 vals)
            n_nan = jax.ops.segment_sum(isnan.astype(jnp.int64), seg_id,
                                        num_segments=num_segments)
            out = f(vals, seg_id, num_segments=num_segments)
            if spec.op == "min":
                out = jnp.where(n_nan == nvalid,
                                jnp.asarray(jnp.nan, phys), out)
            return Column(out, group_live & (nvalid > 0), out_dtype)
        out = f(vals, seg_id, num_segments=num_segments)
        return Column(out, group_live & (nvalid > 0), out_dtype)
    if spec.op in ("first", "last"):
        # first/last non-null within the segment, in sorted-batch order
        pos = _firstlast_pos(valid, spec.op, row_cap)
        f = jax.ops.segment_min if spec.op == "first" else jax.ops.segment_max
        sel = f(pos, seg_id, num_segments=num_segments)
        sel_clipped = jnp.clip(sel, 0, row_cap - 1)
        out = jnp.take(vcol.data, sel_clipped).astype(phys)
        return Column(out, group_live & (nvalid > 0), out_dtype)
    if spec.op in ("first_any", "last_any"):
        # Spark default (ignoreNulls=false): first/last LIVE row of the
        # segment regardless of validity; a NULL first value stays NULL
        base = "first" if spec.op == "first_any" else "last"
        pos = _firstlast_pos(live_sorted, base, row_cap)
        f = jax.ops.segment_min if base == "first" else jax.ops.segment_max
        sel = f(pos, seg_id, num_segments=num_segments)
        sel_clipped = jnp.clip(sel, 0, row_cap - 1)
        out = jnp.take(vcol.data, sel_clipped).astype(phys)
        sel_valid = jnp.take(vcol.validity, sel_clipped)
        return Column(out, group_live & sel_valid, out_dtype)
    raise ValueError(f"unknown agg op {spec.op}")


def reduce_aggregate(batch: ColumnarBatch, aggs: Sequence[AggSpec],
                     out_schema: T.Schema,
                     live_mask=None) -> ColumnarBatch:
    """Grand aggregate (no keys): one output row.  Separate path because
    there is no sort: plain masked reductions."""
    cap = batch.capacity
    live = batch.row_mask()
    if live_mask is not None:
        live = live & live_mask
    out_cols: list[AnyColumn] = []
    one_live = jnp.arange(cap, dtype=jnp.int32) < 1
    for spec in aggs:
        if spec.op == "count_star":
            n = jnp.sum(live.astype(jnp.int64))
            out_cols.append(Column(jnp.zeros(cap, jnp.int64).at[0].set(n),
                                   one_live, T.LONG))
            continue
        vcol = batch.columns[spec.ordinal]
        valid = vcol.validity & live
        nvalid = jnp.sum(valid.astype(jnp.int64))
        if spec.op == "count":  # validity-only: any column kind
            out_cols.append(Column(
                jnp.zeros(cap, jnp.int64).at[0].set(nvalid), one_live, T.LONG))
            continue
        assert isinstance(vcol, Column)
        out_dtype = agg_output_dtype(spec, vcol.dtype)
        phys = T.to_numpy_dtype(out_dtype)
        if spec.op == "sum":
            s = jnp.sum(jnp.where(valid, vcol.data.astype(phys),
                                  jnp.asarray(0, phys)))
        elif spec.op in ("min", "max"):
            vals = jnp.where(valid, vcol.data.astype(phys),
                             _minmax_sentinel(phys, spec.op))
            if jnp.issubdtype(jnp.dtype(phys), jnp.floating):
                # Spark float total order (see _eval_agg): max keeps
                # IEEE NaN propagation (NaN greatest); min ignores NaN
                # unless every valid value is NaN
                isnan = valid & jnp.isnan(vcol.data)
                if spec.op == "min":
                    vals = jnp.where(
                        isnan, _minmax_sentinel(phys, "min"), vals)
                    s = jnp.where(jnp.sum(isnan.astype(jnp.int64))
                                  == nvalid,
                                  jnp.asarray(jnp.nan, phys),
                                  jnp.min(vals))
                else:
                    s = jnp.max(vals)
            else:
                s = jnp.min(vals) if spec.op == "min" else jnp.max(vals)
        elif spec.op in ("first", "last"):
            pos = _firstlast_pos(valid, spec.op, cap)
            sel = jnp.min(pos) if spec.op == "first" else jnp.max(pos)
            s = jnp.take(vcol.data, jnp.clip(sel, 0, cap - 1)).astype(phys)
        elif spec.op in ("first_any", "last_any"):
            base = "first" if spec.op == "first_any" else "last"
            pos = _firstlast_pos(live, base, cap)
            sel = jnp.min(pos) if base == "first" else jnp.max(pos)
            sel_c = jnp.clip(sel, 0, cap - 1)
            s = jnp.take(vcol.data, sel_c).astype(phys)
            sel_ok = jnp.take(vcol.validity, sel_c)
            data = jnp.zeros(cap, phys).at[0].set(s)
            out_cols.append(Column(
                data, one_live & sel_ok & (jnp.sum(live) > 0), out_dtype))
            continue
        else:
            raise ValueError(f"unknown agg op {spec.op}")
        data = jnp.zeros(cap, phys).at[0].set(s.astype(phys))
        out_cols.append(Column(data, one_live & (nvalid > 0), out_dtype))
    return ColumnarBatch(out_cols, 1, out_schema)
