"""Collective hash-partitioned exchange: the in-program half.

The reference implements shuffle as N x N point-to-point pulls over UCX
with device bounce buffers and a flatbuffer control plane
(ref: RapidsShuffleClient.scala:96, BufferSendState.scala:53,
shuffle-plugin/.../UCX.scala).  On TPU the idiomatic equivalent is a
per-shard body traced inside one fused XLA program:

    partition ids (Spark-parity murmur3 pmod)
      -> stable sort rows by destination
      -> scatter into a (n_dest, capacity) send buffer
      -> lax.all_to_all over the mesh axis (ICI/DCN, compiler-scheduled)
      -> compact received rows

Rows travel with an explicit *occupancy* mask (a row can be occupied yet
carry NULL columns), so the received buffer compacts into the standard
prefix-compact ColumnarBatch invariant.  Nothing here compiles a
program: `route_shard` / `exchange_shard` are shard_map bodies, and
`_shard_map`, `_squeeze0`, `_unsqueeze0` and `take_piece` are the
pieces parallel/spmd.py builds its stage programs from — the stage
builders there (through cached_jit) are the only place a multi-device
program is compiled, so any fused upstream project/filter and
downstream partial aggregation ride in the same program with no host
round-trip between map and reduce sides.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import AnyColumn, Column, StringColumn
from spark_rapids_tpu.exprs.hashing import partition_ids
from spark_rapids_tpu.ops.sort import stable_argsort


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check off — every SPMD stage
    program builds through this one wrapper."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


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


def destination_counts(batch: ColumnarBatch, pid: jax.Array,
                       n_dest: int) -> jax.Array:
    """Per-shard body: how many live rows of this shard's batch `pid`
    sends to each destination, (n_dest,) int32 — what the host reads to
    size `route_shard`'s send slots.  A compare and a sum a
    destination, no scatter."""
    live = batch.row_mask()
    dests = jnp.arange(n_dest, dtype=jnp.int32)
    return jnp.sum((live[:, None] & (pid[:, None] == dests[None, :]))
                   .astype(jnp.int32), axis=0)


def route_shard(batch: ColumnarBatch, pid: jax.Array,
                n_dest: int, axis_name: str,
                slot_capacity: Optional[int] = None) -> ColumnarBatch:
    """Per-shard body: send each live row of this shard's batch to the
    destination in `pid` via all_to_all; returns the rows this shard
    owns afterwards (capacity = n_dest * slot capacity,
    prefix-compact).  `pid` entries for dead rows are ignored.

    A destination's send slot holds `slot_capacity` rows: the input's
    capacity where none is given (any source may send a full round to
    one destination), else what the host COUNTED no (source,
    destination) pair to exceed (`destination_counts`) — the send
    buffer, the all_to_all and everything after it are then sized to
    the rows that cross and not to `n_dest` times the input's
    padding."""
    cap = batch.capacity
    slot_cap = cap if slot_capacity is None else slot_capacity
    live = batch.row_mask()
    pid = jnp.where(live, pid, jnp.int32(n_dest))  # dead rows -> dropped

    order = stable_argsort(pid)
    spid = jnp.take(pid, order)
    # rank of each row within its destination group
    first_pos = jnp.searchsorted(spid, spid, side="left")
    rank = jnp.arange(cap, dtype=jnp.int32) - first_pos.astype(jnp.int32)
    # OOB for dead rows (spid == n_dest)
    slot = spid * slot_cap + rank

    def scatter(x, fill=0):
        out_shape = (n_dest * slot_cap,) + x.shape[1:]
        return jnp.full(out_shape, fill, x.dtype).at[slot].set(
            jnp.take(x, order, axis=0), mode="drop")

    occ = jnp.zeros((n_dest * slot_cap,), bool).at[slot].set(
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
    out_live = jnp.arange(n_dest * slot_cap, dtype=jnp.int32) < n_out
    out_cols: list[AnyColumn] = []
    for c in recv_cols:
        g = c.gather(corder)
        out_cols.append(g.with_validity(g.validity & out_live))
    return ColumnarBatch(out_cols, n_out, batch.schema)


def exchange_shard(batch: ColumnarBatch, key_ordinals: Sequence[int],
                   n_dest: int, axis_name: str,
                   slot_capacity: Optional[int] = None) -> ColumnarBatch:
    """route_shard with Spark-parity murmur3-pmod hash routing."""
    key_cols = [batch.columns[o] for o in key_ordinals]
    pid = partition_ids(key_cols, batch.capacity, n_dest)
    return route_shard(batch, pid, n_dest, axis_name, slot_capacity)
