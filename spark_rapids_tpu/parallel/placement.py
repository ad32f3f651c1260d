"""Per-shard stage-input placement: the device-born data contract of
pod-scale serving (docs/pod_serving.md).

The reference system's shuffle story is LOCALITY: RapidsShuffleManager
moves blocks device-to-device over UCX so a child task's inputs are
already resident where they are consumed (PAPER.md 2.10), and the TPU
mapping of that story is ICI collectives plus per-shard placement
(PAPER.md 5.8).  Before this module the SPMD tier broke that contract
at every stage boundary: ``spmd._assemble`` called a raw
``jax.device_put`` per shard piece, so even a shard that a previous
stage had just produced ON its mesh device round-tripped through the
default device on re-assembly.

This module is the single choke point for moving a stage-input leaf
onto its mesh device (tpulint SRC016 forbids raw ``jax.device_put`` of
stage inputs anywhere else in execs// parallel/):

- :func:`place_piece` classifies and performs the move — a host-born
  source (numpy / python) counts ``host_uploads``; a jax Array already
  resident on the target device counts ``device_born`` and skips the
  copy when it is exactly placed; anything else is a
  ``d2d_transfers`` device-to-device move, whose bytes add to
  ``d2d_bytes`` (what crosses chips through ``device_put``, outside
  any collective);
- :func:`adopt_batch` is the PRODUCER-side half: stage outputs adopt
  their shard's device as a stage's exit cuts them (spmd.unstack_*),
  so the next stage's assembly finds every piece device-born (between
  two programs of one stage `spmd.restage` is a partitioned program:
  its outputs are born on their shards' devices);
- the counters surface as ``placement.*`` event-log counters and the
  ``placement_host_uploads`` bench field — steady state under mesh
  serving is ZERO host uploads (the smoke gate
  tools/bench_smoke.run_mesh_serving_smoke asserts it).

Control-plane leaves (the tiny int32 row-count arrays assembled from
host ``concrete_num_rows`` values) are tallied separately as
``control_uploads``: they are genuinely host-born by design and their
bytes are O(rounds), not O(rows) — counting them as data uploads would
hide a real data-plane regression behind a constant.
"""

from __future__ import annotations

import threading

import jax

_STATS = {"host_uploads": 0, "device_born": 0, "d2d_transfers": 0,
          "d2d_bytes": 0, "control_uploads": 0, "adoptions": 0}
_LOCK = threading.Lock()


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[key] += n


def place_piece(x, device, control: bool = False):
    """Move one per-shard stage-input piece onto ``device``, counting
    the move's class.  Returns a single-device array suitable for
    ``jax.make_array_from_single_device_arrays``."""
    if not isinstance(x, jax.Array):
        _bump("control_uploads" if control else "host_uploads")
        return jax.device_put(x, device)
    try:
        devs = x.devices()
    except Exception:
        devs = None
    if devs is not None and device in devs:
        _bump("device_born")
        if len(devs) == 1:
            return x  # already exactly placed: zero-copy adoption
        return jax.device_put(x, device)
    with _LOCK:
        _STATS["d2d_transfers"] += 1
        _STATS["d2d_bytes"] += x.nbytes
    return jax.device_put(x, device)


def adopt_batch(batch, device):
    """Producer-side adoption: commit every column leaf of a per-shard
    batch onto ITS mesh device, so the consuming stage's assembly finds
    the pieces device-born instead of paying a transfer per leaf.
    Leaves already resident on ``device`` are untouched (adoption is
    idempotent and free in steady state).  Columns move as pytrees, so
    every column kind (string dictionaries, list/struct/map children)
    adopts uniformly; ``num_rows`` is deliberately left alone — host
    ints must stay host ints."""
    import dataclasses

    def move(leaf):
        if isinstance(leaf, jax.Array):
            try:
                if leaf.devices() == {device}:
                    return leaf
            except Exception:
                pass
            _bump("adoptions")
            return jax.device_put(leaf, device)
        return leaf  # host scalars/aux stay put

    cols = [jax.tree_util.tree_map(move, c) for c in batch.columns]
    return dataclasses.replace(batch, columns=cols)


def stats() -> dict[str, int]:
    """Process-cumulative placement counters (the ``placement.*``
    event-log surface)."""
    with _LOCK:
        return dict(_STATS)


def reset_stats() -> None:
    """Test/bench isolation (the reset_stage_counters discipline)."""
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0
