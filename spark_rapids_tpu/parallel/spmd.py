"""SPMD whole-stage execution: one pjit program per query stage.

Driving an exchange from the host round by round (stack per-shard
batches on the default device, dispatch one shard_map step, unstack,
host-sync every shard's row count, shrink, fold) costs one program
dispatch plus 2n host round-trips a round — the dispatch-soup
anti-pattern the DeviceLedger exists to expose, and the opposite of how
pjit/GSPMD programs are meant to run (SNIPPETS [1][2]: partitioned
compilation with `PartitionSpec` + donation; [3]: mesh/`NamedSharding`
helpers).

Here a query stage (exchange + its fused agg/join/sort work) lowers to
O(1) partitioned XLA programs over the active mesh with
`NamedSharding` end-to-end, and these builders are the only place the
collective tier (execs/collective.py) compiles a program —

- inputs arrive as GLOBAL sharded arrays: per-shard round batches are
  assembled with `jax.make_array_from_single_device_arrays` under
  ``NamedSharding(mesh, P(None, "data"))`` (leading axis = exchange
  rounds, second axis = mesh shard), so GSPMD never reshards at
  dispatch and nothing round-trips through one host-stacked array;
- the hash/range exchange is an IN-PROGRAM collective: the per-round
  ``all_to_all`` body of parallel/exchange.py runs inside a
  ``lax.scan`` over the rounds axis — R exchange rounds compile once
  and dispatch once, instead of R host dispatches;
- per-round host syncs are DEFERRED to program boundaries: one
  ``stage_counts`` fetch of a program's output row-count array
  replaces the per-round per-shard `concrete_num_rows` + shrink
  choreography (the aggregate stage fetches twice a bucket, three
  times over a ROLLUP, and once more after its tail: its update
  program's counts size the exchange, docs/spmd.md).  Every such
  fetch goes through `pipeline.device_read` (tag ``mesh.counts``),
  which counts it, and the host's work between the programs has one
  span a call: ``mesh.stack``, ``mesh.shrink``, ``mesh.launch``;
- a stage is taken apart and put together ONCE each: `shard_stack_rounds`
  stacks per-shard batches at its ENTRY, `unstack_stage` /
  `unstack_round_stage` cut per-shard batches at its EXIT, and BETWEEN
  two of its programs stands `restage`, one cached program from a
  program's stacked output to the next one's stacked input (the cut
  to the counted capacity, several buckets' rounds end to end, rounds
  up to a power of two), shard-local, where the host once ran some
  five eager programs a leaf a (round, shard) piece.

Programs compile through execs/jit_cache.cached_jit with the sharding
spec pair folded into the structural key (plus parallel.mesh.mesh_key,
so same-shaped meshes over different devices never share an
executable); donation composes — a stage's freshly assembled global
input is single-use and may be donated into the program.  The ledger
entry carries ``{"devices": n, "rounds": R}`` so partitioned programs
attribute per-device busy time and in-program collective rounds in
bench/analyze (docs/spmd.md).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    concat_batches_traced,
)
from spark_rapids_tpu.columnar.column import (
    AnyColumn,
    Column,
    MIN_CAPACITY,
    StringColumn,
    pad_capacity,
    pad_width,
)
from spark_rapids_tpu.parallel.exchange import (
    _shard_map,
    _squeeze0,
    _unsqueeze0,
    route_shard,
    take_piece,
)
from spark_rapids_tpu.parallel.mesh import DATA_AXIS, mesh_key
from spark_rapids_tpu.parallel.pipeline import device_read


def rounds_sharding(mesh) -> NamedSharding:
    """Sharding of a round-stacked stage input: leaves are
    (rounds, n_shards, capacity, ...), sharded over the mesh axis."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def stage_sharding(mesh) -> NamedSharding:
    """Sharding of a per-shard stage output: leaves are
    (n_shards, capacity, ...)."""
    return NamedSharding(mesh, P(DATA_AXIS))


# ------------------------------------------------------------------ #
# Capacity unification
# ------------------------------------------------------------------ #


def repad_batch(batch: ColumnarBatch, cap: int,
                widths: dict[int, int]) -> ColumnarBatch:
    """Pad a batch to a common capacity/string-width profile so
    per-shard leaves stack into one array with leading device (and
    round) axes."""
    cols: list[AnyColumn] = []
    for ci, c in enumerate(batch.columns):
        if isinstance(c, StringColumn):
            w = widths[ci]
            chars = c.chars
            if c.width < w:
                chars = jnp.pad(chars, ((0, 0), (0, w - c.width)))
            if c.capacity < cap:
                pad = cap - c.capacity
                chars = jnp.pad(chars, ((0, pad), (0, 0)))
                cols.append(StringColumn(
                    chars,
                    jnp.pad(c.lengths, (0, pad)),
                    jnp.pad(c.validity, (0, pad))))
            else:
                cols.append(StringColumn(chars, c.lengths, c.validity))
        else:
            if c.capacity < cap:
                pad = cap - c.capacity
                cols.append(Column(jnp.pad(c.data, (0, pad)),
                                   jnp.pad(c.validity, (0, pad)),
                                   c.dtype))
            else:
                cols.append(c)
    return ColumnarBatch(cols, batch.num_rows, batch.schema)


def unify_batches(batches: Sequence[ColumnarBatch]
                  ) -> list[ColumnarBatch]:
    """Pad batches to ONE capacity/width profile (max over the set,
    width pow2-padded) so their leaves stack into rectangular arrays."""
    cap = max(b.capacity for b in batches)
    widths: dict[int, int] = {}
    for b in batches:
        for ci, c in enumerate(b.columns):
            if isinstance(c, StringColumn):
                widths[ci] = max(widths.get(ci, 1), c.width)
    for ci in widths:
        widths[ci] = pad_width(widths[ci])
    return [repad_batch(b, cap, widths) for b in batches]


# ------------------------------------------------------------------ #
# Global sharded-array assembly (stage entry)
# ------------------------------------------------------------------ #


def _assemble(mesh, per_dev: list, control: bool = False) -> jax.Array:
    """One global (R, n, ...) array from one (R, ...) piece per mesh
    device: each piece is placed onto ITS shard's device through
    parallel/placement.py (device-born pieces are adopted zero-copy;
    host-born ones are counted and uploaded) and the global array is
    assembled without ever materializing a host-stacked copy
    (`jax.make_array_from_single_device_arrays` — the NamedSharding
    idiom of SNIPPETS [3])."""
    from spark_rapids_tpu.parallel import placement as _placement

    devs = list(mesh.devices.flat)
    pieces = [_placement.place_piece(p[:, None], d, control=control)
              for p, d in zip(per_dev, devs)]
    shape = (per_dev[0].shape[0], len(devs)) + tuple(
        per_dev[0].shape[1:])
    return jax.make_array_from_single_device_arrays(
        shape, rounds_sharding(mesh), pieces)


def _leaves(batch: ColumnarBatch) -> list:
    """Every array of a flat-schema batch's columns."""
    out: list = []
    for c in batch.columns:
        out += (c.chars, c.lengths, c.validity) \
            if isinstance(c, StringColumn) else (c.data, c.validity)
    return out


def _with_leaves(like: ColumnarBatch, leaves, num_rows) -> ColumnarBatch:
    """`like`'s columns over other arrays, given in `_leaves`' order."""
    it = iter(leaves)
    cols: list[AnyColumn] = []
    for c in like.columns:
        if isinstance(c, StringColumn):
            cols.append(StringColumn(next(it), next(it), next(it)))
        else:
            cols.append(Column(next(it), next(it), c.dtype))
    return ColumnarBatch(cols, num_rows, like.schema)


def shard_stack_rounds(rounds: Sequence[Sequence[ColumnarBatch]],
                       mesh, keys: Sequence[int] = ()) -> ColumnarBatch:
    """`_stack_rounds` under ONE ``mesh.stack`` span a call, which
    says what the host did for the stage's input: the batches
    `unify_batches` had to pad up to the common capacity, what
    `place_piece` found on its device, moved chip to chip (with the
    bytes) or uploaded (the difference of `placement.stats()` over
    the call), and `coded_keys`, the columns whose dictionary sidecar
    the stacked input carries.  `keys`: the ordinals of a group-by's
    grouping keys, whose sidecars `coded_key_shapes` may keep; every
    other entry passes none, and every other column loses its."""
    flat = [b for shards in rounds for b in shards]
    coded = coded_key_shapes(flat, keys)
    if not _trace.TRACER.enabled:
        return _stack_rounds(rounds, mesh, coded)
    from spark_rapids_tpu.parallel import placement as _placement

    before = _placement.stats()
    with _trace.span("mesh.stack", rounds=len(rounds),
                     shards=len(rounds[0])) as sp:
        out = _stack_rounds(rounds, mesh, coded)
        after = _placement.stats()
        stacked = [x.shape[2:] for x in _leaves(out)]
        sp.note(capacity=stacked[0][0],
                leaves=len(jax.tree_util.tree_leaves(out.columns)),
                repadded=sum([x.shape for x in _leaves(b)] != stacked
                             for b in flat),
                coded_keys=len(coded),
                **{k: after[k] - before[k]
                   for k in ("host_uploads", "device_born",
                             "d2d_transfers", "d2d_bytes")})
    return out


def _dictionary(c: AnyColumn):
    """The dictionary array of a column's sidecar: a string's
    characters, a number's values."""
    return c.dict_chars if isinstance(c, StringColumn) else c.dict_values


def coded_key_shapes(flat: Sequence[ColumnarBatch], keys: Sequence[int]
                     ) -> dict[int, tuple]:
    """Which grouping keys of a map-side update keep their dictionary
    sidecar through the stage entry, so the update program groups by
    code as the one-chip update does: ordinal -> `(k, w, dict_len)`,
    the largest dictionary capacity among the batches, its string
    width padded by `pad_width` (0 for a number) and the largest
    `dict_len`.  Empty unless every non-empty batch carries a sidecar
    on every key and `_coded_key_domains`, the rule the update program
    applies, takes the keys at those `dict_len`s."""
    from spark_rapids_tpu.ops.groupby import _coded_key_domains

    live = [b for b in flat if b.concrete_num_rows()]
    if not keys or not live or any(
            getattr(b.columns[o], "codes", None) is None
            for b in live for o in keys):
        return {}
    shapes: dict[int, tuple] = {}
    probe = []
    for o in keys:
        cols = [b.columns[o] for b in live]
        dicts = [_dictionary(c) for c in cols]
        bound = max(int(d.shape[0]) if c.dict_len is None else c.dict_len
                    for c, d in zip(cols, dicts))
        width = pad_width(max(int(d.shape[1]) for d in dicts)) \
            if isinstance(cols[0], StringColumn) else 0
        shapes[o] = (max(int(d.shape[0]) for d in dicts), width, bound)
        probe.append(dataclasses.replace(cols[0], dict_len=bound))
    return shapes if _coded_key_domains(probe) is not None else {}


def coded_signature(batch: ColumnarBatch) -> tuple:
    """The static shapes of the sidecars a stacked input carries, for
    the key of the program it enters: `(ordinal, k, w, dict_len)` a
    carried column (`w` 0 for a number); empty where none is."""
    return tuple(
        (ci, int(_dictionary(c).shape[2]),
         int(c.dict_chars.shape[3]) if isinstance(c, StringColumn) else 0,
         c.dict_len)
        for ci, c in enumerate(batch.columns)
        if getattr(c, "codes", None) is not None)


def _fit(x, shape: tuple):
    """`x` padded with zeros up to `shape`, axis by axis."""
    if tuple(x.shape) == shape:
        return x
    return jnp.pad(x, tuple((0, s - have)
                            for s, have in zip(shape, x.shape)))


def _stack_sidecar(rounds, mesh, ci: int, shape: tuple,
                   cap: int) -> dict:
    """Column `ci`'s dictionary sidecar over R rounds of n per-shard
    batches, as `_stack_rounds` stacks the other leaves: codes int32
    `(R, n, cap)`, the dictionary `(R, n, k, w)` (a number's
    `(R, n, k)`), each (round, shard) with its own dictionary, which
    the update program indexes inside its scan (the update moves no
    row between shards).  An empty batch gets zero codes and a zero
    dictionary; its rows are dead, so they are never read.  Returns
    the fields to replace on the stacked column."""
    k, w, bound = shape
    like = next(b.columns[ci] for shards in rounds for b in shards
                if b.concrete_num_rows())
    if isinstance(like, StringColumn):
        names = ("codes", "dict_chars", "dict_lens")
        shapes = ((cap,), (k, w), (k,))
        dtypes = (jnp.int32, jnp.uint8, jnp.int32)
    else:
        names = ("codes", "dict_values")
        shapes = ((cap,), (k,))
        dtypes = (jnp.int32, like.dict_values.dtype)
    r_count = len(rounds)
    per_dev = []
    for d, dev in enumerate(mesh.devices.flat):
        shard = [shards[d] for shards in rounds]
        if not any(b.concrete_num_rows() for b in shard):
            # born on its own chip: nothing to move
            per_dev.append([jnp.zeros((r_count,) + s, t, device=dev)
                            for s, t in zip(shapes, dtypes)])
            continue
        pieces = [[_fit(jnp.asarray(getattr(b.columns[ci], nm), t), s)
                   for nm, s, t in zip(names, shapes, dtypes)]
                  if b.concrete_num_rows() else
                  [jnp.zeros(s, t) for s, t in zip(shapes, dtypes)]
                  for b in shard]
        per_dev.append([jnp.stack(x) for x in zip(*pieces)])
    return dict(zip(names, (_assemble(mesh, list(x))
                            for x in zip(*per_dev))), dict_len=bound)


def _stack_rounds(rounds: Sequence[Sequence[ColumnarBatch]], mesh,
                  coded: Optional[dict] = None) -> ColumnarBatch:
    """Assemble R rounds of n per-shard batches into ONE global sharded
    batch: every leaf becomes a (R, n, capacity, ...) jax Array under
    ``NamedSharding(mesh, P(None, "data"))``, with shard d's slice
    resident on mesh device d.  num_rows becomes an int32 (R, n)
    global array.  This is the stage INPUT contract of every SPMD
    stage program.  The columns of `coded` (`coded_key_shapes`) keep
    their dictionary sidecar (`_stack_sidecar`); every other loses
    its."""
    n = int(mesh.shape[DATA_AXIS])
    flat = [b for shards in rounds for b in shards]
    assert flat and len(flat) == len(rounds) * n
    unified = unify_batches(flat)
    r_count = len(rounds)
    leaves = [_leaves(b) for b in unified]
    stacked = [
        _assemble(mesh, [jnp.stack([leaves[r * n + d][li]
                                    for r in range(r_count)])
                         for d in range(n)])
        for li in range(len(leaves[0]))]
    num_rows = _assemble(mesh, [
        np.asarray([flat[r * n + d].concrete_num_rows()
                    for r in range(r_count)], np.int32)
        for d in range(n)], control=True)
    out = _with_leaves(unified[0], stacked, num_rows)
    if coded:
        cap = unified[0].capacity
        cols = list(out.columns)
        for ci, shape in coded.items():
            cols[ci] = dataclasses.replace(
                cols[ci], **_stack_sidecar(rounds, mesh, ci, shape, cap))
        out = ColumnarBatch(cols, num_rows, out.schema)
    return out


def _pow2(rounds: int) -> int:
    """The next power of two, a round count's bucket."""
    return 1 << (rounds - 1).bit_length() if rounds > 1 else 1


def pad_rounds_pow2(rounds: list, schema: T.Schema, n: int) -> list:
    """Pad a round list with rounds of empty shard batches up to the
    next power of two, so the in-program scan length (part of the
    compiled program's key) takes a handful of bucketed values instead
    of minting one executable per data-dependent round count."""
    want = _pow2(len(rounds))
    out = list(rounds)
    while len(out) < want:
        out.append([ColumnarBatch.empty(schema) for _ in range(n)])
    return out


def sample_fracs(mesh, n_rounds: int, k: int,
                 seed: int = 0x52414E47) -> jax.Array:
    """Deterministic per-(round, shard) sample-position fractions in
    [0, 1) for the sort stage's in-program sampling, assembled as a
    global (R, n, k) sharded array."""
    n = int(mesh.shape[DATA_AXIS])
    rng = np.random.default_rng(seed)
    fr = rng.random((n_rounds, n, k), dtype=np.float32)
    # host-chosen control plane (k floats per round-shard), not data
    return _assemble(mesh, [fr[:, d] for d in range(n)], control=True)


# ------------------------------------------------------------------ #
# Stage exit: ONE host sync, then unstack + shrink into per-shard
# batches for a one-chip consumer
# ------------------------------------------------------------------ #


def stage_counts(batch: ColumnarBatch) -> np.ndarray:
    """THE stage-exit sync: fetch the output row-count array (shape
    (n,) or (R, n)) in one readback.  Everything the host needs per
    round (live rows per shard, shrink sizes) comes out of this single
    fetch.  Through `device_read`, as every fetch at a stage boundary
    is: `stage.mesh.counts.readbacks` counts it and a `pipe.readback`
    span tagged ``mesh.counts`` times it."""
    return np.asarray(device_read(batch.num_rows, tag="mesh.counts"))


def fetch(arr) -> np.ndarray:
    """Host fetch of a small stage-exit array (the join stage's
    per-round true totals, an exchange's destination counts) — one
    readback at a stage boundary, never inside the round loop, counted
    and timed as `stage_counts`' is."""
    return np.asarray(device_read(arr, tag="mesh.counts"))


def row_bytes(batch: ColumnarBatch) -> int:
    """Device bytes one row of a round-stacked batch takes: every
    column's data, validity and, for strings, the padded character
    matrix and the lengths.  Read off the arrays' shapes on the host."""
    return sum(leaf.dtype.itemsize * int(np.prod(leaf.shape[3:]))
               for leaf in _leaves(batch))


def _slice_shard(batch: ColumnarBatch, idx: tuple, rows: int,
                 device=None) -> ColumnarBatch:
    # take_piece, not plain getitem: the (round, shard) piece of a
    # partitioned stage output is wholly resident on one device, and
    # an eager getitem on the sharded array would launch an unguarded
    # cross-device gather (exchange.take_piece documents the hazard)
    out = _with_leaves(
        batch, [take_piece(x, idx) for x in _leaves(batch)], rows)
    out = out.shrink_to_capacity(max(MIN_CAPACITY,
                                     pad_capacity(rows)))
    if device is not None:
        from spark_rapids_tpu.parallel import placement as _placement
        out = _placement.adopt_batch(out, device)
    return out


def _shrink_span(batch: ColumnarBatch, counts: np.ndarray,
                 pieces: int):
    """The ``mesh.shrink`` span round one cut of a stage's stacked
    output into `pieces` batches for a one-chip consumer, opened once
    its counts are on the host: a `take_piece` and a
    `shrink_to_capacity` a leaf a piece (``path="pieces"``; the
    mid-stage boundary's says ``path="program"``, `restage`)."""
    if not _trace.TRACER.enabled:
        return _trace.span("mesh.shrink")
    leaves = _leaves(batch)
    return _trace.span("mesh.shrink", path="pieces", pieces=pieces,
                       rows=int(counts.sum()),
                       capacity=leaves[0].shape[counts.ndim],
                       leaves=len(leaves))


def _adoption_devices(mesh) -> Optional[list]:
    """Mesh device list when producer-side adoption is on (mesh
    serving), else None — the default keeps shrink outputs wherever
    slicing left them, bit-for-bit the pre-placement behavior."""
    if mesh is None:
        return None
    from spark_rapids_tpu.serving import mesh_serving_enabled
    if not mesh_serving_enabled():
        return None
    return list(mesh.devices.flat)


def unstack_stage(batch: ColumnarBatch,
                  counts: Optional[np.ndarray] = None,
                  mesh=None) -> list[ColumnarBatch]:
    """Split a (n, capacity, ...) stage output into n shrunk per-shard
    batches using the stage-exit counts (fetched once if not given).
    Under mesh serving (pass the mesh) shard d's batch adopts mesh
    device d at this producer boundary."""
    if counts is None:
        counts = stage_counts(batch)
    devs = _adoption_devices(mesh)
    with _shrink_span(batch, counts, counts.shape[0]):
        return [_slice_shard(batch, (d,), int(counts[d]),
                             devs[d] if devs else None)
                for d in range(counts.shape[0])]


def unstack_round_stage(batch: ColumnarBatch,
                        counts: Optional[np.ndarray] = None,
                        mesh=None) -> list[list[ColumnarBatch]]:
    """Split a (R, n, capacity, ...) stage output into per-shard lists
    of per-round shrunk batches (empty rounds dropped)."""
    if counts is None:
        counts = stage_counts(batch)
    r_count, n = counts.shape
    devs = _adoption_devices(mesh)
    out: list[list[ColumnarBatch]] = [[] for _ in range(n)]
    with _shrink_span(batch, counts, int(np.count_nonzero(counts))):
        for d in range(n):
            for r in range(r_count):
                rows = int(counts[r, d])
                if rows:
                    out[d].append(_slice_shard(
                        batch, (r, d), rows, devs[d] if devs else None))
    return out


# ------------------------------------------------------------------ #
# Mid-stage boundary: ONE program from a stage program's stacked
# output to the next one's stacked input
# ------------------------------------------------------------------ #


def stacked_rounds(batch: ColumnarBatch) -> int:
    """The rounds of a round-stacked batch, read off its `num_rows`."""
    return int(batch.num_rows.shape[0])


def stacked_capacity(batch: ColumnarBatch) -> int:
    """The capacity a round-stacked batch's leaves stand at."""
    return int(_leaves(batch)[0].shape[2])


def _restaged_shapes(parts: Sequence[list], counts: Sequence[np.ndarray]
                     ) -> list[tuple]:
    """The shapes, leaf for leaf, the next stage program's input has:
    the rounds of every part, up to a power of two; the capacity the
    largest counted piece pads to (what cutting every piece to
    `pad_capacity` of its rows and unifying them again comes to, and
    never more than a part already has); string widths up to their
    `pad_width` bucket."""
    r2 = _pow2(sum(c.shape[0] for c in counts))
    cap2 = max(min(lv[0].shape[2],
                   max(MIN_CAPACITY, pad_capacity(int(c.max()))))
               for lv, c in zip(parts, counts))
    return [(r2, x.shape[1], cap2) + tuple(
                pad_width(max(w)) for w in zip(
                    *(lv[li].shape[3:] for lv in parts)))
            for li, x in enumerate(parts[0])]


def restage(stacked: Sequence[tuple], mesh,
            op: Optional[str] = None) -> ColumnarBatch:
    """THE mid-stage boundary: what one stage program returned (or the
    programs of several buckets, in order), each round-stacked
    `(R, n, capacity, ...)` a leaf with every shard's slice on its own
    chip and paired with the `(R, n)` row counts the host already
    holds for it, to the NEXT stage program's stacked input.  One
    cached program a call (tag ``spmdrestage``, through `_stage_jit`
    like every stage program; the inputs donated): per leaf a slice
    on the capacity axis to what the largest counted piece pads to,
    the parts' rounds end to end, rounds of zeros up to a power of two
    (`pad_rounds_pow2` gives the reason).  Nothing is gathered and
    nothing leaves its chip; `num_rows` is the host's counts, an
    argument of the program.  Keyed by what the shapes are, so a new
    seed mints one only where it mints the next stage program too:
    that one is keyed by the same capacity bucket.  Where the output
    would have the input's shapes (a group-by whose partials are as
    many as its rows) no program runs and the stacked batch goes on
    as it is.

    Cutting before the next program is what keeps its merge, sort or
    join work proportional to the LIVE rows: an exchange program's
    outputs carry the receive capacity per shard, n x the send
    slot's.  Under mesh serving the output is born on its shards'
    devices, so the next stage finds every piece in place.

    ONE ``mesh.shrink`` span a call, ``path="program"``, with `pieces`,
    `rows`, `capacity` (the input's), `to_capacity`, `leaves` and
    `skipped`; the program's ``mesh.launch`` nests inside it."""
    n = int(mesh.shape[DATA_AXIS])
    first = stacked[0][0]
    parts = [_leaves(b) for b, _ in stacked]
    counts = [np.asarray(c, np.int32) for _, c in stacked]
    assert all(c.shape == (lv[0].shape[0], n)
               for lv, c in zip(parts, counts)), "counts a (round, shard)"
    want = _restaged_shapes(parts, counts)
    r2, _, cap2 = want[0][:3]
    skip = len(parts) == 1 and [x.shape for x in parts[0]] == want
    span = _trace.span("mesh.shrink")
    if _trace.TRACER.enabled:
        span = _trace.span(
            "mesh.shrink", path="program",
            pieces=n * sum(c.shape[0] for c in counts),
            rows=sum(int(c.sum()) for c in counts),
            capacity=max(lv[0].shape[2] for lv in parts),
            to_capacity=cap2, leaves=len(want), skipped=skip)
    with span:
        if skip:
            return first

        def make():
            def fit(x, shape: tuple):
                # per shard (R, 1, capacity, ...): cut or pad every
                # axis past the shard's to the output's
                x = x[(slice(None), slice(None))
                      + tuple(slice(0, s) for s in shape[2:])]
                return jnp.pad(x, ((0, 0), (0, 0)) + tuple(
                    (0, s - have) for s, have in
                    zip(shape[2:], x.shape[2:])))

            def shard_fn(shard_parts, num_rows):
                out = []
                for li, shape in enumerate(want):
                    x = jnp.concatenate(
                        [fit(lv[li], shape) for lv in shard_parts])
                    out.append(jnp.pad(
                        x, ((0, r2 - x.shape[0]),)
                        + ((0, 0),) * (x.ndim - 1)))
                return out, num_rows

            return _shard_map(shard_fn, mesh,
                              (P(None, DATA_AXIS),) * 2,
                              P(None, DATA_AXIS))

        key = tuple(tuple((x.shape, str(x.dtype)) for x in lv)
                    for lv in parts)
        prog = _stage_jit(
            ("spmdrestage", key, cap2, r2), make, mesh, op,
            (rounds_sharding(mesh),) * 2, rounds_sharding(mesh),
            (0,), r2)
        num_rows = np.zeros((r2, n), np.int32)
        live = np.concatenate(counts)
        num_rows[:len(live)] = live
        return _with_leaves(first, *prog(parts, num_rows))


# ------------------------------------------------------------------ #
# Stage program builders (compiled via cached_jit: sharding + mesh in
# the key, ledger meta = {devices, rounds})
# ------------------------------------------------------------------ #


def _tree_index(tree, r: int):
    return jax.tree_util.tree_map(lambda leaf: leaf[r], tree)


def _concat_rounds(ys, n_rounds: int,
                   squeeze: bool = False) -> ColumnarBatch:
    """Fold a rounds-stacked pytree into one traced batch.  `squeeze`
    strips the per-shard device axis first — program INPUTS carry it
    (leaves (R, 1, cap, ...)); in-body scan outputs do not."""
    parts = [_tree_index(ys, r) for r in range(n_rounds)]
    if squeeze:
        parts = [_squeeze0(p) for p in parts]
    if n_rounds == 1:
        return parts[0]
    merged = concat_batches_traced(parts)
    assert merged is not None, \
        "collective schemas are flat (supports_schema gates nesting)"
    return merged


def _stage_jit(key: tuple, make_fn, mesh, op, in_shardings,
               out_shardings, donate, n_rounds: int):
    from spark_rapids_tpu.execs.jit_cache import cached_jit

    n = int(mesh.shape[DATA_AXIS])
    prog = cached_jit(
        key + (mesh_key(mesh),), make_fn, op=op,
        in_shardings=in_shardings, out_shardings=out_shardings,
        donate=donate,
        meta={"devices": n, "rounds": n_rounds})

    def launch(*args):
        # the host's side of one dispatch of a stage program
        if not _trace.TRACER.enabled:
            return prog(*args)
        with _trace.span("mesh.launch", op=op, program=key[0],
                         rounds=n_rounds, devices=n):
            return prog(*args)

    return launch


def _rounds_scan_stage(tag: str, mesh, key: tuple, body: Callable,
                       n_rounds: int, op: Optional[str],
                       donate: bool):
    """A program that scans `body` (per-shard round batch -> per-shard
    batch) over the rounds axis: round-stacked in, round-stacked out."""
    axis = DATA_AXIS

    def make():
        def shard_fn(xs: ColumnarBatch) -> ColumnarBatch:
            def sbody(carry, x):
                return carry, _unsqueeze0(body(_squeeze0(x)))
            _, ys = jax.lax.scan(sbody, jnp.int32(0), xs)
            return ys

        return _shard_map(shard_fn, mesh, P(None, axis),
                          P(None, axis))

    return _stage_jit(
        (tag, key, n_rounds), make, mesh, op,
        (rounds_sharding(mesh),), rounds_sharding(mesh),
        (0,) if donate else None, n_rounds)


def make_update_scan_stage(mesh, key: tuple, body: Callable,
                           n_rounds: int, op: Optional[str] = None,
                           donate: bool = False):
    """The MAP-SIDE program of the aggregate stage: lax.scan over the
    rounds axis applying `body` (the partial-aggregate update) per
    shard, NO collective.  Emits the round-stacked partials at the
    input's capacity with their (R, n) row counts; the host fetches
    those counts once and the boundary program cuts the partials to
    their counted rows (`restage`) BEFORE the exchange program, so the
    all_to_all and the reduce-side merge are sized to the groups the
    shuffle carries and not to the input round's padding."""
    return _rounds_scan_stage("spmdupdate", mesh, key, body, n_rounds,
                              op, donate)


def make_exchange_scan_stage(mesh, key: tuple, body: Callable,
                             n_rounds: int, op: Optional[str] = None,
                             donate: bool = False,
                             tag: str = "spmdxchg"):
    """The EXCHANGE program of a stage: lax.scan over the rounds axis
    applying `body` (per-shard round batch -> per-shard batch; the
    in-program all_to_all — exchange_shard / route_shard — lives
    inside `body`, as do any fused map/reduce phases).  Emits the
    round-stacked per-shard outputs at the receive capacity, n x the
    send slot's (the input's capacity, or what `body` was told the
    host counted); the boundary program cuts them ONCE (`restage`)
    before the tail program, so the tail's work is proportional to
    live rows, not padding."""
    return _rounds_scan_stage(tag, mesh, key, body, n_rounds, op,
                              donate)


def make_scan_stage(tag: str, mesh, key: tuple, body: Callable,
                    n_rounds: int, op: Optional[str] = None,
                    donate: bool = False, n_args: int = 1):
    """A program that scans `body` over the rounds axis of `n_args`
    round-stacked pytrees: `body` is handed one round's per-shard
    pieces and returns any pytree of per-shard arrays (batches, row
    arrays, counts), which leave round-stacked.  What the stages whose
    programs pass more than one batch between them are built from: the
    rollup's sort and write programs, the window's destination
    count."""
    axis = DATA_AXIS

    def make():
        def shard_fn(*xs):
            def sbody(carry, x):
                out = body(*_tree_index(x, 0))
                return carry, jax.tree_util.tree_map(
                    lambda leaf: jnp.asarray(leaf)[None], out)
            _, ys = jax.lax.scan(sbody, jnp.int32(0), xs)
            return ys

        return _shard_map(shard_fn, mesh, (P(None, axis),) * n_args,
                          P(None, axis))

    return _stage_jit(
        (tag, key, n_rounds), make, mesh, op,
        (rounds_sharding(mesh),) * n_args, rounds_sharding(mesh),
        tuple(range(n_args)) if donate else None, n_rounds)


def make_stage_tail(mesh, key: tuple, fn: Callable, n_rounds: int,
                    op: Optional[str] = None, donate: bool = False):
    """The TAIL program of a stage: concatenate the (shrunk,
    re-assembled) per-shard rounds and apply `fn` — the agg's
    cross-round merge + finalize, the sort's local sort, the join
    build side's fold.  No collectives: the exchange already owns
    placement, so the tail is pure per-shard work at tight
    capacity."""
    axis = DATA_AXIS

    def make():
        def shard_fn(xs: ColumnarBatch) -> ColumnarBatch:
            merged = _concat_rounds(xs, n_rounds, squeeze=True)
            return _unsqueeze0(fn(merged))

        return _shard_map(shard_fn, mesh, P(None, axis), P(axis))

    return _stage_jit(
        ("spmdtail", key, n_rounds), make, mesh, op,
        (rounds_sharding(mesh),), stage_sharding(mesh),
        (0,) if donate else None, n_rounds)


def make_join_scan_stage(mesh, key: tuple, join_fn: Callable,
                         n_rounds: int, op: Optional[str] = None):
    """Join probe program: scan the PRE-ROUTED stream rounds against
    the resident per-shard build batch — `join_fn(stream_shard,
    build_shard) -> (joined, total)` runs entirely in-program.
    Outputs round-stacked joined batches plus per-(round, shard) true
    totals for the host's stage-exit capacity-overflow check (the one
    decision that stays on the host, because it re-COMPILES at a
    bigger bucket).  Inputs are NOT donated: an overflow re-dispatches
    the same arrays."""
    axis = DATA_AXIS

    def make():
        def shard_fn(xs: ColumnarBatch, build: ColumnarBatch):
            b = _squeeze0(build)

            def body(carry, x):
                s = _squeeze0(x)
                out, total = join_fn(s, b)
                return carry, (_unsqueeze0(out), total[None])
            _, (ys, totals) = jax.lax.scan(body, jnp.int32(0), xs)
            return ys, totals

        return _shard_map(
            shard_fn, mesh, (P(None, axis), P(axis)),
            (P(None, axis), P(None, axis)))

    return _stage_jit(
        ("spmdjoin", key, n_rounds), make, mesh, op,
        (rounds_sharding(mesh), stage_sharding(mesh)),
        (rounds_sharding(mesh), rounds_sharding(mesh)),
        None, n_rounds)


def _all_gather_concat(b: ColumnarBatch, n: int,
                       axis: str) -> ColumnarBatch:
    """Pool one prefix-compact per-shard batch across the mesh INSIDE
    the program: all_gather every leaf, rebuild liveness from the
    gathered row counts, compact.  Every shard holds the identical
    pooled result afterwards (replicated by construction)."""
    rows_all = jax.lax.all_gather(
        jnp.asarray(b.num_rows, jnp.int32), axis)  # (n,)
    cap = b.capacity

    def ag(x):
        return jax.lax.all_gather(x, axis, tiled=True)

    cols: list[AnyColumn] = []
    for c in b.columns:
        if isinstance(c, StringColumn):
            cols.append(StringColumn(ag(c.chars), ag(c.lengths),
                                     ag(c.validity)))
        else:
            cols.append(Column(ag(c.data), ag(c.validity), c.dtype))
    idx = jnp.arange(n * cap, dtype=jnp.int32)
    live = (idx % cap) < jnp.take(rows_all, idx // cap)
    return ColumnarBatch(cols, n * cap, b.schema).compact(live)


def make_sort_sample_stage(mesh, key: tuple, part, n_rounds: int,
                           sample_k: int, op: Optional[str] = None):
    """Pass 1 of the BUCKETED distributed ORDER BY (mesh serving,
    docs/pod_serving.md): scan one bucket's rounds gathering per-shard
    sort-key samples at host-chosen fractional positions — the sample
    half of `make_sort_route_stage`, emitted as a round-stacked stage
    OUTPUT instead of being consumed in-program.  A million-round sort
    samples bucket by bucket (one bucket stacked at a time) instead of
    assembling every round into one resident global array.  Inputs are
    NOT donated: the same rounds re-stack for the route pass."""
    axis = DATA_AXIS

    def make():
        def shard_fn(xs: ColumnarBatch, fracs: jax.Array):
            def sample_body(carry, xf):
                x, frac = xf
                b = _squeeze0(x)
                kb = part.key_batch(b)
                rows = jnp.asarray(b.num_rows, jnp.int32)
                cap = b.capacity
                pos = jnp.clip(
                    (frac[0] * rows.astype(jnp.float32)).astype(
                        jnp.int32),
                    0, jnp.maximum(rows - 1, 0))
                n_valid = (sample_k * rows + cap - 1) // cap
                return carry, _unsqueeze0(kb.gather(pos, n_valid))
            _, samples = jax.lax.scan(sample_body, jnp.int32(0),
                                      (xs, fracs))
            return samples

        return _shard_map(
            shard_fn, mesh, (P(None, axis), P(None, axis)),
            P(None, axis))

    return _stage_jit(
        ("spmdsortsample", key, n_rounds, sample_k), make, mesh, op,
        (rounds_sharding(mesh), rounds_sharding(mesh)),
        rounds_sharding(mesh), None, n_rounds)


def make_bounds_route_stage(mesh, key: tuple, part, n_rounds: int,
                            op: Optional[str] = None,
                            donate: bool = False):
    """Pass 2 of the bucketed distributed ORDER BY: scan one bucket's
    rounds through the range-routed all_to_all, with the bounds batch
    riding as a REPLICATED program argument — one compiled program
    serves every bounds value, so the bucket count never mints
    executables."""
    n = int(mesh.shape[DATA_AXIS])
    axis = DATA_AXIS

    def make():
        def shard_fn(xs: ColumnarBatch, bounds: ColumnarBatch):
            def route_body(carry, x):
                b = _squeeze0(x)
                pid = part.partition_ids_with_bounds(b, bounds)
                return carry, _unsqueeze0(
                    route_shard(b, pid, n, axis))
            _, routed = jax.lax.scan(route_body, jnp.int32(0), xs)
            return routed

        return _shard_map(
            shard_fn, mesh, (P(None, axis), P()), P(None, axis))

    return _stage_jit(
        ("spmdboundsroute", key, n_rounds), make, mesh, op,
        (rounds_sharding(mesh), NamedSharding(mesh, P())),
        rounds_sharding(mesh), (0,) if donate else None, n_rounds)


def make_sort_route_stage(mesh, key: tuple, part, n_rounds: int,
                          sample_k: int, op: Optional[str] = None,
                          donate: bool = False):
    """The exchange program of a distributed ORDER BY:

    1. scan rounds gathering per-shard sort-key samples at host-chosen
       fractional positions (sample count proportional to each round's
       live rows, so a 10-row tail batch cannot outweigh a full one);
    2. all_gather the samples and compute range bounds IN-PROGRAM
       (`choose_bounds_dynamic` — every shard derives identical bounds
       from the identical pooled sample);
    3. scan rounds again through the range-routed all_to_all.

    Emits the round-stacked routed rounds; after the mid-stage shrink
    the tail program (`make_stage_tail` with the local sort) sorts
    each shard at tight capacity — shard index order IS the total
    order.  Samples are sized in-program, so no row count leaves the
    device to size them."""
    from spark_rapids_tpu.ops.range_partition import (
        choose_bounds_dynamic,
    )

    n = int(mesh.shape[DATA_AXIS])
    axis = DATA_AXIS
    orders = part.key_orders()

    def make():
        def shard_fn(xs: ColumnarBatch, fracs: jax.Array):
            def sample_body(carry, xf):
                x, frac = xf
                b = _squeeze0(x)
                kb = part.key_batch(b)
                rows = jnp.asarray(b.num_rows, jnp.int32)
                cap = b.capacity
                pos = jnp.clip(
                    (frac[0] * rows.astype(jnp.float32)).astype(
                        jnp.int32),
                    0, jnp.maximum(rows - 1, 0))
                n_valid = (sample_k * rows + cap - 1) // cap
                return carry, kb.gather(pos, n_valid)
            _, samples = jax.lax.scan(sample_body, jnp.int32(0),
                                      (xs, fracs))
            pooled = _all_gather_concat(
                _concat_rounds(samples, n_rounds), n, axis)
            bounds = choose_bounds_dynamic(pooled, orders, n)

            def route_body(carry, x):
                b = _squeeze0(x)
                pid = part.partition_ids_with_bounds(b, bounds)
                return carry, _unsqueeze0(
                    route_shard(b, pid, n, axis))
            _, routed = jax.lax.scan(route_body, jnp.int32(0), xs)
            return routed

        return _shard_map(
            shard_fn, mesh, (P(None, axis), P(None, axis)),
            P(None, axis))

    return _stage_jit(
        ("spmdsortroute", key, n_rounds, sample_k), make, mesh, op,
        (rounds_sharding(mesh), rounds_sharding(mesh)),
        rounds_sharding(mesh), (0,) if donate else None, n_rounds)
