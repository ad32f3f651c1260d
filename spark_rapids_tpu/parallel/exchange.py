"""Collective hash-partitioned exchange (the TPU shuffle fast path).

The reference implements shuffle as N x N point-to-point pulls over UCX
with device bounce buffers and a flatbuffer control plane
(ref: RapidsShuffleClient.scala:96, BufferSendState.scala:53,
shuffle-plugin/.../UCX.scala).  On TPU the idiomatic equivalent is a
single fused XLA program per exchange:

    partition ids (Spark-parity murmur3 pmod)
      -> stable sort rows by destination
      -> scatter into a (n_dest, capacity) send buffer
      -> lax.all_to_all over the mesh axis (ICI/DCN, compiler-scheduled)
      -> compact received rows

Rows travel with an explicit *occupancy* mask (a row can be occupied yet
carry NULL columns), so the received buffer compacts into the standard
prefix-compact ColumnarBatch invariant.  The whole step — including any
fused upstream project/filter and downstream partial aggregation — is one
jit-compiled SPMD program via shard_map; there is no host round-trip
between map and reduce sides.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.exprs.hashing import partition_ids
from spark_rapids_tpu.ops.sort import stable_argsort
from spark_rapids_tpu.parallel.mesh import DATA_AXIS


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check off — every collective
    step / SPMD stage program builds through this one wrapper."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _sharded_jit(mapped) -> Callable:
    """jit a shard_map program and route its dispatch through the
    process-wide collective gate (jit_cache.serialize_sharded): the
    step builders below are the only multi-device programs compiled
    outside cached_jit, and an unguarded concurrent launch can starve
    XLA's CPU collective thread pool mid-rendezvous
    (docs/pod_serving.md)."""
    from spark_rapids_tpu.execs.jit_cache import serialize_sharded

    return serialize_sharded(jax.jit(mapped))


def take_piece(arr: jax.Array, idx: tuple):
    """``arr[idx]`` for leading-dim integer indices, resolved against
    the array's addressable shards.  An eager ``__getitem__`` on a
    PARTITIONED array compiles and launches a cross-device gather —
    an unguarded multi-device program that can rendezvous against a
    concurrently launched one and starve XLA's CPU collective pool
    (the jit_cache._SHARDED_DISPATCH_LOCK deadlock, through the eager
    door).  A stage output's (round, shard) piece is wholly resident
    on its shard's device, so the local-shard slice below is both
    collective-free and copy-free; anything not covered by a local
    shard falls back to the plain (single-device) getitem."""
    try:
        shards = arr.addressable_shards
    except (AttributeError, RuntimeError):
        return arr[idx]
    for s in shards:
        sl = s.index
        loc = []
        for i, g in enumerate(idx):
            start = sl[i].start or 0
            stop = sl[i].stop if sl[i].stop is not None \
                else arr.shape[i]
            if not (start <= g < stop):
                break
            loc.append(g - start)
        else:
            return s.data[tuple(loc)]
    return arr[idx]


def _stack_parts(parts: list):
    """``jnp.stack`` for per-device leaves that may be COMMITTED to
    distinct devices (take_piece's local-shard slices are).  An eager
    jnp.stack of committed arrays on different devices is an
    incompatible-devices error, so the committed case assembles the
    stacked global array shard-by-shard with
    make_array_from_single_device_arrays — no cross-device op at all;
    duplicated-device pieces fall back to placement-routed moves onto
    the first piece's device."""
    try:
        return jnp.stack(parts)
    except ValueError:
        devsets = [getattr(p, "devices", lambda: None)() for p in parts]
        singles = all(ds is not None and len(ds) == 1
                      for ds in devsets)
        if singles:
            devs = [next(iter(ds)) for ds in devsets]
            if len(set(devs)) == len(devs):
                from jax.sharding import NamedSharding
                shape = (len(parts),) + parts[0].shape
                mesh = Mesh(np.asarray(devs), ("stack",))
                sh = NamedSharding(
                    mesh, P("stack", *([None] * parts[0].ndim)))
                return jax.make_array_from_single_device_arrays(
                    shape, sh, [p[None] for p in parts])
        from spark_rapids_tpu.parallel import placement as _placement

        target = next((next(iter(ds)) for ds in devsets if ds), None)
        if target is None:
            raise
        return jnp.stack([_placement.place_piece(p, target)
                          for p in parts])


def stack_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Stack per-device batches into one batch whose leaves carry a leading
    device axis (num_rows becomes an int32 vector)."""
    schema = batches[0].schema
    cols: list[AnyColumn] = []
    for ci in range(batches[0].num_cols):
        parts = [b.columns[ci] for b in batches]
        if isinstance(parts[0], StringColumn):
            cols.append(StringColumn(
                _stack_parts([p.chars for p in parts]),
                _stack_parts([p.lengths for p in parts]),
                _stack_parts([p.validity for p in parts])))
        else:
            cols.append(Column(
                _stack_parts([p.data for p in parts]),
                _stack_parts([p.validity for p in parts]),
                parts[0].dtype))
    n_rows = jnp.asarray([b.concrete_num_rows() for b in batches], jnp.int32)
    return ColumnarBatch(cols, n_rows, schema)


def unstack_batch(stacked: ColumnarBatch) -> list[ColumnarBatch]:
    n_dev = stacked.columns[0].data.shape[0] if isinstance(
        stacked.columns[0], Column) else stacked.columns[0].chars.shape[0]
    counts = np.asarray(jax.device_get(stacked.num_rows))
    out = []
    for d in range(n_dev):
        cols: list[AnyColumn] = []
        for c in stacked.columns:
            if isinstance(c, StringColumn):
                cols.append(StringColumn(take_piece(c.chars, (d,)),
                                         take_piece(c.lengths, (d,)),
                                         take_piece(c.validity, (d,))))
            else:
                cols.append(Column(take_piece(c.data, (d,)),
                                   take_piece(c.validity, (d,)),
                                   c.dtype))
        out.append(ColumnarBatch(cols, int(counts[d]),
                                 stacked.schema))
    return out


def _squeeze0(batch: ColumnarBatch) -> ColumnarBatch:
    cols: list[AnyColumn] = []
    for c in batch.columns:
        if isinstance(c, StringColumn):
            cols.append(StringColumn(c.chars[0], c.lengths[0], c.validity[0]))
        else:
            cols.append(Column(c.data[0], c.validity[0], c.dtype))
    return ColumnarBatch(cols, batch.num_rows[0], batch.schema)


def _unsqueeze0(batch: ColumnarBatch) -> ColumnarBatch:
    cols: list[AnyColumn] = []
    for c in batch.columns:
        if isinstance(c, StringColumn):
            cols.append(StringColumn(c.chars[None], c.lengths[None],
                                     c.validity[None]))
        else:
            cols.append(Column(c.data[None], c.validity[None], c.dtype))
    return ColumnarBatch(cols, batch.num_rows[None], batch.schema)


def route_shard(batch: ColumnarBatch, pid: jax.Array,
                n_dest: int, axis_name: str) -> ColumnarBatch:
    """Per-shard body: send each live row of this shard's batch to the
    destination in `pid` via all_to_all; returns the rows this shard
    owns afterwards (capacity = n_dest * input capacity,
    prefix-compact).  `pid` entries for dead rows are ignored."""
    cap = batch.capacity
    live = batch.row_mask()
    pid = jnp.where(live, pid, jnp.int32(n_dest))  # dead rows -> dropped

    order = stable_argsort(pid)
    spid = jnp.take(pid, order)
    # rank of each row within its destination group
    first_pos = jnp.searchsorted(spid, spid, side="left")
    rank = jnp.arange(cap, dtype=jnp.int32) - first_pos.astype(jnp.int32)
    slot = spid * cap + rank  # OOB for dead rows (spid == n_dest)

    def scatter(x, fill=0):
        out_shape = (n_dest * cap,) + x.shape[1:]
        return jnp.full(out_shape, fill, x.dtype).at[slot].set(
            jnp.take(x, order, axis=0), mode="drop")

    occ = jnp.zeros((n_dest * cap,), bool).at[slot].set(
        jnp.ones((cap,), bool), mode="drop")
    sent_cols: list[AnyColumn] = []
    for c in batch.columns:
        if isinstance(c, StringColumn):
            sent_cols.append(StringColumn(
                scatter(c.chars), scatter(c.lengths), scatter(c.validity)))
        else:
            sent_cols.append(Column(scatter(c.data), scatter(c.validity),
                                    c.dtype))

    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, split_axis=0,
                  concat_axis=0, tiled=True)
    occ = a2a(occ)
    recv_cols: list[AnyColumn] = []
    for c in sent_cols:
        if isinstance(c, StringColumn):
            recv_cols.append(StringColumn(a2a(c.chars), a2a(c.lengths),
                                          a2a(c.validity)))
        else:
            recv_cols.append(Column(a2a(c.data), a2a(c.validity), c.dtype))

    # compact occupied rows to a prefix (stable: preserves sender order)
    corder = stable_argsort(~occ)
    n_out = jnp.sum(occ).astype(jnp.int32)
    out_live = jnp.arange(n_dest * cap, dtype=jnp.int32) < n_out
    out_cols: list[AnyColumn] = []
    for c in recv_cols:
        g = c.gather(corder)
        out_cols.append(g.with_validity(g.validity & out_live))
    return ColumnarBatch(out_cols, n_out, batch.schema)


def exchange_shard(batch: ColumnarBatch, key_ordinals: Sequence[int],
                   n_dest: int, axis_name: str) -> ColumnarBatch:
    """route_shard with Spark-parity murmur3-pmod hash routing."""
    key_cols = [batch.columns[o] for o in key_ordinals]
    pid = partition_ids(key_cols, batch.capacity, n_dest)
    return route_shard(batch, pid, n_dest, axis_name)


def make_hash_exchange_step(
    mesh: Mesh,
    key_ordinals: Sequence[int],
    axis_name: str = DATA_AXIS,
    pre: Optional[Callable[[ColumnarBatch], ColumnarBatch]] = None,
    post: Optional[Callable[[ColumnarBatch], ColumnarBatch]] = None,
) -> Callable[[ColumnarBatch], ColumnarBatch]:
    """Build the jitted SPMD exchange program.  `pre`/`post` are traceable
    per-shard batch transforms fused into the same program (map-side
    project/filter/partial-agg, reduce-side merge-agg) — the analog of the
    reference pipelining partitioning and aggregation around its shuffle,
    but in ONE compiled program."""
    n_dest = mesh.shape[axis_name]

    def shard_fn(stacked: ColumnarBatch) -> ColumnarBatch:
        b = _squeeze0(stacked)
        if pre is not None:
            b = pre(b)
        b = exchange_shard(b, key_ordinals, n_dest, axis_name)
        if post is not None:
            b = post(b)
        return _unsqueeze0(b)

    mapped = _shard_map(shard_fn, mesh, P(axis_name),
                       P(axis_name))
    return _sharded_jit(mapped)


def make_route_step(
    mesh: Mesh,
    pid_fn: Callable[..., jax.Array],
    axis_name: str = DATA_AXIS,
    n_extra: int = 0,
) -> Callable:
    """Generalized exchange: `pid_fn(batch, *extras) -> int32[capacity]`
    computes each row's destination shard (hash, range-bounds bisect,
    round-robin — any traceable rule).  `extras` are REPLICATED batch
    args (e.g. sampled range bounds) passed through to pid_fn, so one
    compiled program serves every bounds value."""
    n_dest = mesh.shape[axis_name]

    def shard_fn(stacked: ColumnarBatch, *extras):
        b = _squeeze0(stacked)
        pid = pid_fn(b, *extras)
        b = route_shard(b, pid, n_dest, axis_name)
        return _unsqueeze0(b)

    in_specs = (P(axis_name),) + (P(),) * n_extra
    mapped = _shard_map(shard_fn, mesh, in_specs,
                       P(axis_name))
    return _sharded_jit(mapped)


def make_local_step(
    mesh: Mesh,
    fn: Callable[[ColumnarBatch], ColumnarBatch],
    axis_name: str = DATA_AXIS,
) -> Callable:
    """Per-shard local transform (no collectives) over stacked shard
    batches — the reduce-side tail of a multi-round exchange (final
    merge, local sort) runs through this."""

    def shard_fn(stacked: ColumnarBatch) -> ColumnarBatch:
        return _unsqueeze0(fn(_squeeze0(stacked)))

    mapped = _shard_map(shard_fn, mesh, P(axis_name),
                       P(axis_name))
    return _sharded_jit(mapped)


def make_join_step(
    mesh: Mesh,
    shard_fn: Callable[[ColumnarBatch, ColumnarBatch],
                       tuple[ColumnarBatch, jax.Array]],
    axis_name: str = DATA_AXIS,
) -> Callable:
    """Two-input SPMD step for the collective shuffled join: shard_fn
    gets (stream_shard, build_shard) per device and returns the joined
    shard plus a scalar diagnostic (the true output row count, for the
    host-side capacity-overflow check)."""

    def wrapped(stream_stacked, build_stacked):
        out, total = shard_fn(_squeeze0(stream_stacked),
                              _squeeze0(build_stacked))
        return _unsqueeze0(out), total[None]

    mapped = _shard_map(wrapped, mesh,
                        (P(axis_name), P(axis_name)),
                        (P(axis_name), P(axis_name)))
    return _sharded_jit(mapped)
