"""Runtime join filters: build-side key pruning pushed into the scan.

The sideways-information-passing / Bloom-join idea (Spark's
InSubqueryExec-based DPP and the reference family's later
GpuBloomFilterAggregate work) re-designed for the TPU deployment shape:
the design premise is that the host->device WIRE is the scarce resource
(its bandwidth on the chip is not measured yet, ROADMAP S3), so the
selective side of a join
must reduce the expensive side *before it moves* — the filter is built
ON DEVICE from the build side's join keys (a few fused scatter
programs), fetched ONCE as a small bitset + min/max pair, and applied
ON HOST inside the probe side's scan at three successively cheaper
points:

1. row-group pruning: the filter's [min, max] range joins the pushed
   predicate's footer-statistics checks (io/pushdown.py) — pruned row
   groups are never even decoded;
2. dictionary-LUT pruning in the fast native decoder (io/fastpar.py):
   the Bloom/range probe evaluates on the Parquet DICTIONARY (tens..
   thousands of values) and row filtering becomes one numpy gather;
3. a post-decode numpy mask in the host-prefilter path
   (io/pa_filter.py / io/scan.py) for everything else —
   non-reachable rows are dropped before encode+upload.

Soundness: a filter only ever DROPS probe rows whose key provably (min/
max) or probabilistically-never (Bloom: no-means-no, yes-means-maybe)
matches any build key.  For the eligible join types (inner, left_semi)
such rows contribute nothing to the output, so pruning — including NULL
keys, which never equi-match — is a pure IO optimization.  Outer and
anti joins preserve non-matching rows and are never filtered (tpulint
PL005 hard-errors if such a plan is ever built by hand).

The host and device Bloom share one bit layout — ``k`` double-hashed
murmur3 probes ``(h1 + i*h2) mod m`` over a little-endian uint32 word
array — with the host side running the numpy murmur3 mirrors in
exprs/hashing.py (parity pinned by test_runtime_filter.py).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import register

RF_ENABLED = register(
    "spark.rapids.tpu.sql.runtimeFilter.enabled", True,
    "Build Bloom + min-max filters from the build side of eligible "
    "joins (inner, left_semi; equi-keys) and apply them host-side "
    "inside the probe side's scan, so probe rows whose join key cannot "
    "match any build key never cross the host->device link (the "
    "sideways-information-passing / Bloom-join analog of Spark's "
    "runtime filters).  Disabled, plans are bit-for-bit identical to "
    "the un-filtered shape.")

RF_MINMAX_ENABLED = register(
    "spark.rapids.tpu.sql.runtimeFilter.minMaxEnabled", True,
    "Include the build keys' [min, max] range in runtime filters: "
    "applied to Parquet row-group footer statistics (whole row groups "
    "skipped before decode) and as a host row mask.")

RF_BLOOM_ENABLED = register(
    "spark.rapids.tpu.sql.runtimeFilter.bloomEnabled", True,
    "Include a murmur3 double-hashed Bloom filter of the build keys in "
    "runtime filters (built on device, fetched once, probed on host).")

RF_MAX_BUILD_ROWS = register(
    "spark.rapids.tpu.sql.runtimeFilter.maxBuildRows", 1 << 22,
    "Skip runtime-filter creation when the build side's estimated row "
    "count exceeds this (an unselective build side prunes little and "
    "its Bloom bitset grows with it).")

RF_FPP = register(
    "spark.rapids.tpu.sql.runtimeFilter.fpp", 0.01,
    "Target Bloom false-positive probability; sizes the bitset from "
    "the build side's estimated rows.  False positives only reduce "
    "pruning, never correctness.",
    check=lambda v: 0.0 < v < 1.0)

#: join types whose probe side may be pruned by build-side keys
ELIGIBLE_JOIN_TYPES = ("inner", "left_semi")

#: key dtypes with a host/device hash-parity story (fixed-width
#: integral lanes; floats are excluded — NaN/-0.0 normalization has no
#: pruning payoff on join keys)
_SUPPORTED_32 = (T.ByteType, T.ShortType, T.IntegerType, T.DateType)
_SUPPORTED_64 = (T.LongType, T.TimestampType)

#: murmur3 seeds for the double-hash scheme (h_i = h1 + i*h2 mod m);
#: seed 1 is Spark's default hash seed, seed 2 is the classic Murmur3
#: test seed — any fixed pair works as long as host and device agree
BLOOM_SEED1 = 42
BLOOM_SEED2 = 0x9747B28C

_INT64_MAX = (1 << 63) - 1
_INT64_MIN = -(1 << 63)


def supported_key_dtype(dt: T.DataType) -> bool:
    return isinstance(dt, _SUPPORTED_32 + _SUPPORTED_64)


def bloom_params(n_est: int, fpp: float) -> tuple[int, int]:
    """(n_bits, n_hashes) for an expected key count at the target fpp;
    n_bits is a power of two so the device/host index math is one AND."""
    n_est = max(int(n_est), 1)
    bits = -n_est * math.log(fpp) / (math.log(2.0) ** 2)
    m = 1 << max(6, math.ceil(math.log2(max(bits, 64.0))))
    k = max(1, min(6, round(math.log(2.0) * m / n_est)))
    return m, k


# --------------------------------------------------------------------- #
# Process-global stats (the bench/tests observation surface, like
# parallel.speculation's registry)
# --------------------------------------------------------------------- #

_STATS_LOCK = threading.Lock()
_STATS = {"filters_built": 0, "build_rows": 0, "build_ms": 0.0,
          "pruned_rows": 0, "row_groups_pruned": 0}


def stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k == "build_ms" else 0


def _record(key: str, v) -> None:
    with _STATS_LOCK:
        _STATS[key] += v


def record_pruned_rows(n: int) -> None:
    if n:
        _record("pruned_rows", int(n))


def record_row_groups_pruned(n: int) -> None:
    if n:
        _record("row_groups_pruned", int(n))


# --------------------------------------------------------------------- #
# The filter object
# --------------------------------------------------------------------- #

_NEXT_ID = [0]
_ID_LOCK = threading.Lock()


class RuntimeFilter:
    """One published (or pending) runtime filter for a single join key.

    Built by the build side's TpuRuntimeFilterBuildExec, consumed by
    probe-side scans.  Consumers never block on it: an unpublished
    filter simply applies nothing (pruning is an optimization, the join
    itself stays the source of truth)."""

    def __init__(self, key_name: str, dtype: T.DataType, join_type: str,
                 n_bits: int, n_hashes: int, use_minmax: bool,
                 use_bloom: bool, build_desc: str = ""):
        with _ID_LOCK:
            _NEXT_ID[0] += 1
            self.rf_id = _NEXT_ID[0]
        self.key_name = key_name
        self.dtype = dtype
        self.join_type = join_type
        self.n_bits = n_bits
        self.n_hashes = n_hashes
        self.use_minmax = use_minmax
        self.use_bloom = use_bloom
        self.build_desc = build_desc
        self.is64 = isinstance(dtype, _SUPPORTED_64)
        self._ready = threading.Event()
        self.min_val: Optional[int] = None
        self.max_val: Optional[int] = None
        self.bloom_words = None  # np.uint32[n_bits/32] when published
        self.n_keys = 0
        self.build_ms = 0.0

    # -- publication (build side) ------------------------------------- #

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    def publish(self, min_val: int, max_val: int, n_keys: int,
                bloom_words, build_ms: float) -> None:
        self.min_val = int(min_val)
        self.max_val = int(max_val)
        self.n_keys = int(n_keys)
        self.bloom_words = bloom_words
        self.build_ms = build_ms
        self._ready.set()
        _record("filters_built", 1)
        _record("build_rows", int(n_keys))
        _record("build_ms", build_ms)

    # -- probing (host side) ------------------------------------------ #

    def range_may_match(self, lo, hi) -> bool:
        """Could any key in [lo, hi] (ints) survive this filter's
        min/max?  Conservative: unknown stats keep the row group."""
        if not self.ready:
            return True
        if self.n_keys == 0:
            return False  # empty build side: nothing can match
        if not self.use_minmax or lo is None or hi is None:
            return True
        return not (hi < self.min_val or lo > self.max_val)

    def probe_host(self, values, validity=None):
        """bool[n] keep-mask for int64 numpy key values.  NULL slots
        (validity False) are dropped: NULL keys never equi-match, and
        the eligible join types emit nothing for them."""
        import numpy as np

        values = np.asarray(values, np.int64)
        mask = np.ones(len(values), bool) if validity is None \
            else np.asarray(validity, bool).copy()
        if not self.ready:
            return np.ones(len(values), bool)
        if self.n_keys == 0:
            return np.zeros(len(values), bool)
        if self.use_minmax:
            mask &= (values >= self.min_val) & (values <= self.max_val)
        if self.use_bloom and self.bloom_words is not None:
            from spark_rapids_tpu.exprs.hashing import (
                np_hash_int32_block,
                np_hash_int64_blocks,
            )

            if self.is64:
                h1 = np_hash_int64_blocks(values, BLOOM_SEED1)
                h2 = np_hash_int64_blocks(values, BLOOM_SEED2)
            else:
                w = values.astype(np.int32)
                h1 = np_hash_int32_block(w, BLOOM_SEED1)
                h2 = np_hash_int32_block(w, BLOOM_SEED2)
            m_mask = np.uint32(self.n_bits - 1)
            words = self.bloom_words
            for i in range(self.n_hashes):
                idx = (h1 + np.uint32(i) * h2) & m_mask
                bit = (words[idx >> np.uint32(5)]
                       >> (idx & np.uint32(31))) & np.uint32(1)
                mask &= bit.astype(bool)
        return mask

    def describe(self) -> str:
        parts = []
        if self.use_minmax:
            parts.append("minmax")
        if self.use_bloom:
            parts.append(f"bloom[{self.n_bits}b x{self.n_hashes}]")
        state = f"ready n={self.n_keys}" if self.ready else "pending"
        return (f"rf#{self.rf_id} key={self.key_name} "
                f"({'+'.join(parts) or 'none'}, {self.join_type}, "
                f"{state})")


# --------------------------------------------------------------------- #
# Device-side build helpers (traced inside the build exec's jitted
# per-batch update; see execs/join.py TpuRuntimeFilterBuildExec)
# --------------------------------------------------------------------- #


def device_key_hashes(col, is64: bool):
    """(h1, h2) uint32 hash lanes of a device key Column — the traced
    twin of the numpy pair in probe_host."""
    import jax.numpy as jnp

    from spark_rapids_tpu.exprs.hashing import (
        hash_int32_block,
        hash_int64_blocks,
    )

    if is64:
        v = col.data.astype(jnp.int64)
        return (hash_int64_blocks(v, BLOOM_SEED1),
                hash_int64_blocks(v, BLOOM_SEED2))
    w = col.data.astype(jnp.int32)
    return (hash_int32_block(w, BLOOM_SEED1),
            hash_int32_block(w, BLOOM_SEED2))


def device_update(state, col, contrib, n_bits: int, n_hashes: int,
                  is64: bool, use_bloom: bool):
    """Fold one batch's key column into (bits_u8, lo, hi, count).

    ``bits_u8`` is a byte-per-bit scatter target (scatter-max of 0/1 is
    OR; XLA has no scatter-or) packed to uint32 words only at finalize.
    ``contrib`` masks live, non-NULL rows; dead rows scatter 0 (no
    bit)."""
    import jax.numpy as jnp

    bits, lo, hi, count = state
    v = col.data.astype(jnp.int64)
    if v.shape[0] == 0:  # zero-capacity batch: nothing to fold
        return state
    lo = jnp.minimum(lo, jnp.min(
        jnp.where(contrib, v, jnp.int64(_INT64_MAX))))
    hi = jnp.maximum(hi, jnp.max(
        jnp.where(contrib, v, jnp.int64(_INT64_MIN))))
    count = count + jnp.sum(contrib.astype(jnp.int64))
    if use_bloom:
        h1, h2 = device_key_hashes(col, is64)
        one = contrib.astype(jnp.uint8)
        mask = jnp.uint32(n_bits - 1)
        for i in range(n_hashes):
            idx = (h1 + jnp.uint32(i) * h2) & mask
            bits = bits.at[idx.astype(jnp.int32)].max(one)
    return bits, lo, hi, count


def device_init_state(n_bits: int, use_bloom: bool):
    import jax.numpy as jnp

    bits = jnp.zeros((n_bits if use_bloom else 1,), jnp.uint8)
    return (bits, jnp.int64(_INT64_MAX), jnp.int64(_INT64_MIN),
            jnp.int64(0))


def device_merge_states(a, b):
    import jax.numpy as jnp

    return (jnp.maximum(a[0], b[0]), jnp.minimum(a[1], b[1]),
            jnp.maximum(a[2], b[2]), a[3] + b[3])


def device_pack_bits(bits_u8):
    """byte-per-bit uint8[m] -> little-endian uint32[m/32] words (the
    wire form the host probe indexes)."""
    import jax.numpy as jnp

    m = bits_u8.shape[0]
    b = bits_u8.reshape(m // 32, 32).astype(jnp.uint32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32)[None, :],
                   axis=1, dtype=jnp.uint32)


def finalize(rf: RuntimeFilter, state) -> None:
    """Fetch the accumulated filter state (ONE small transfer) and
    publish.  Lives here — not in execs/ — so the blocking readback
    routes through the sanctioned pipeline API in one audited place.
    ``build_ms`` records THIS step's wall time (bit packing + the D2H
    fetch): the synchronous cost the filter adds to the critical path —
    the per-batch update dispatches ride the build stream asynchronously
    and land in the build exec's totalTime."""
    import numpy as np

    from spark_rapids_tpu import trace as _trace
    from spark_rapids_tpu.parallel.pipeline import device_read_many

    bits, lo, hi, count = state
    t0 = time.perf_counter()
    with _trace.span("rf.build", rf=rf.rf_id, key=rf.key_name):
        packed = device_pack_bits(bits) if rf.use_bloom else None
        fetch = [lo, hi, count] + ([packed] if packed is not None else [])
        host = device_read_many(fetch, tag="rf.build")
        words = np.asarray(host[3], np.uint32) if rf.use_bloom else None
        build_ms = (time.perf_counter() - t0) * 1e3
        rf.publish(int(host[0]), int(host[1]), int(host[2]), words,
                   build_ms)


# --------------------------------------------------------------------- #
# Planner pass: filter injection over the lowered physical plan
# --------------------------------------------------------------------- #


def _probe_scan_targets(node, ordinal: int):
    """Scans reachable from the probe subtree through schema-preserving
    execs, with the probe key ordinal stable at every hop.  Returns
    [(scan_exec, column_name)]; an unmodeled node kind ends that branch
    (no target — never a wrong one)."""
    from spark_rapids_tpu.execs.adaptive import CoalescedShuffleReaderExec
    from spark_rapids_tpu.execs.basic import (
        TpuCoalesceBatchesExec,
        TpuFilterExec,
    )
    from spark_rapids_tpu.execs.coalesce import TpuCoalescePartitionsExec
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.execs.join import TpuRuntimeFilterBuildExec
    from spark_rapids_tpu.io.scan import OrcScanExec, ParquetScanExec

    passthrough = (TpuShuffleExchangeExec, TpuFilterExec,
                   TpuCoalesceBatchesExec, TpuCoalescePartitionsExec,
                   CoalescedShuffleReaderExec, TpuRuntimeFilterBuildExec)
    out = []
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, (ParquetScanExec, OrcScanExec)):
            fields = n.schema.fields
            if ordinal < len(fields):
                name = fields[ordinal].name
                file_cols = n.columns
                part = {f.name for f in n.partition_fields}
                readable = (name in part or file_cols is None
                            or name in file_cols)
                if readable:
                    out.append((n, name))
        elif isinstance(n, passthrough):
            stack.extend(n.children)
    return out


def _eligible_key_pairs(left_keys, right_keys, build_is_right: bool,
                        null_safe=()):
    """[(key_index, build_key_expr, probe_key_ordinal, dtype)] for key
    columns a filter can be built+pushed for: matching supported
    dtypes, probe side a plain bound column, and a plain `=` (a filter
    drops the probe side's NULL keys, which a `<=>` key may match)."""
    from spark_rapids_tpu.exprs.base import BoundReference

    build_keys = right_keys if build_is_right else left_keys
    probe_keys = left_keys if build_is_right else right_keys
    out = []
    for i, (bk, pk) in enumerate(zip(build_keys, probe_keys)):
        if not isinstance(pk, BoundReference):
            continue
        if i < len(null_safe) and null_safe[i]:
            continue
        try:
            bdt, pdt = bk.dtype, pk.dtype
        except Exception:
            continue
        if bdt != pdt or not supported_key_dtype(pdt):
            continue
        out.append((i, bk, pk.ordinal, pdt))
    return out


def inject_runtime_filters(root, conf) -> list[RuntimeFilter]:
    """Walk the lowered plan; for each eligible join, wrap the build
    side with a key-collecting pass-through exec and register the
    resulting filters on every probe-side scan they can reach.  Also
    flips the adaptive join's stage order to build-before-probe so the
    filter is published before the probe side's map stage scans."""
    use_minmax = conf.get(RF_MINMAX_ENABLED)
    use_bloom = conf.get(RF_BLOOM_ENABLED)
    if not conf.get(RF_ENABLED) or not (use_minmax or use_bloom):
        return []
    from spark_rapids_tpu.execs.adaptive import TpuAdaptiveJoinExec
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.execs.join import (
        TpuRuntimeFilterBuildExec,
        _HashJoinBase,
    )
    from spark_rapids_tpu.plan.cost import exec_estimated_rows

    max_rows = conf.get(RF_MAX_BUILD_ROWS)
    fpp = conf.get(RF_FPP)
    filters: list[RuntimeFilter] = []

    for node in list(root._walk()):
        if isinstance(node, TpuAdaptiveJoinExec):
            jt = node.join_type
            # the adaptive template always builds right for eligible
            # types (only right_outer flips, and it is ineligible)
            build_idx = 1
            left_keys, right_keys = node.left_keys, node.right_keys
            build_is_right = True
        elif isinstance(node, _HashJoinBase) and node.condition is None:
            jt = node.join_type
            build_is_right = node.build_is_right
            build_idx = 1 if build_is_right else 0
            left_keys, right_keys = node.left_keys, node.right_keys
        else:
            continue
        if jt not in ELIGIBLE_JOIN_TYPES:
            continue
        pairs = _eligible_key_pairs(left_keys, right_keys,
                                    build_is_right, node.null_safe)
        if not pairs:
            continue
        build_child = node.children[build_idx]
        probe_child = node.children[1 - build_idx]
        # build-side selectivity gate (the cost.py posture: never act
        # on an unknown estimate)
        est = exec_estimated_rows(build_child)
        if est is None or est > max_rows:
            continue
        n_bits, n_hashes = bloom_params(est, fpp)

        entries = []
        for _i, bk, probe_ord, dt in pairs:
            targets = _probe_scan_targets(probe_child, probe_ord)
            if not targets:
                continue
            rf = RuntimeFilter(
                targets[0][1], dt, jt, n_bits, n_hashes,
                use_minmax, use_bloom,
                build_desc=f"{node.name}[{jt}]")
            for scan, col_name in targets:
                scan.runtime_filters.append((col_name, rf))
            entries.append((bk, rf))
            filters.append(rf)
        if not entries:
            continue
        # wrap the build side BELOW its exchange (the whole build input
        # streams through the map stage exactly once) or directly when
        # there is no exchange (wide/broadcast joins collect build
        # first by construction)
        if isinstance(build_child, TpuShuffleExchangeExec):
            build_child.children[0] = TpuRuntimeFilterBuildExec(
                build_child.children[0], entries)
        else:
            node.children[build_idx] = TpuRuntimeFilterBuildExec(
                build_child, entries)
        if isinstance(node, TpuAdaptiveJoinExec):
            node.rf_build_first = "right"
    if filters:
        root._runtime_filters = filters
    return filters


def render_runtime_filters(root) -> list[str]:
    """explain() lines: one per build site and one per probe scan
    application, with pruned-row counts once executed."""
    from spark_rapids_tpu.execs.join import TpuRuntimeFilterBuildExec

    lines: list[str] = []
    for node in root._walk():
        if isinstance(node, TpuRuntimeFilterBuildExec):
            for _k, rf in node.entries:
                lines.append(
                    f"build {rf.describe()} <- {rf.build_desc} "
                    f"[{node.children[0].name}]")
        rfs = getattr(node, "runtime_filters", None)
        if rfs:
            for col_name, rf in rfs:
                pruned = node.metrics["rfPrunedRows"].value \
                    if "rfPrunedRows" in node.metrics else 0
                lines.append(
                    f"apply rf#{rf.rf_id} on {node.name}.{col_name} "
                    f"(rfPrunedRows={pruned})")
    return lines
