"""Device discovery, selection, and memory-budget initialization.

Analog of GpuDeviceManager (ref: GpuDeviceManager.scala:125
`initializeGpuAndMemory` — one accelerator per executor, pool sizes
computed from the device's physical memory, pinned-host pool setup).
The TPU version asks the PJRT client instead of CUDA:

- `discover()` enumerates `jax.devices()` with kind/ordinal/memory;
- `select_device(conf)` picks this process's chip
  (`spark.rapids.tpu.deviceOrdinal`, -1 = first of the preferred
  platform) — the 1-accelerator-per-executor model;
- `store_budget(conf)` sizes the spill store's HBM budget as a
  FRACTION of the selected chip's actual memory
  (memory_stats()['bytes_limit']) — the computeRmmInitSizes analog;
  the CPU backend, which reports host RAM, and an explicitly set
  memory.hbm.budgetBytes keep the conf figure.  Every BufferStore
  built without an explicit budget (the one a plain TpuSession gets)
  is sized by it; `initialize(conf)` installs such a store eagerly
  and returns the device it was sized for;
- `HostBufferPool` is the pinned-host-pool analog: recycled numpy
  staging buffers for SYNCHRONOUS host paths (the spill serializer,
  columnar/serde.py).  jax exposes no true pinned allocations and its
  H2D transfers complete asynchronously (a recycled source buffer
  would race the wire), so the win is alloc/zeroing churn on the
  spill path, not DMA pinning — documented divergence.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import numpy as np

from spark_rapids_tpu.config import register, get_conf

DEVICE_ORDINAL = register(
    "spark.rapids.tpu.deviceOrdinal", -1,
    "Which local device this process owns (the 1-accelerator-per-"
    "executor model, ref: GpuDeviceManager); -1 picks the first "
    "device of the preferred platform.")

MEMORY_FRACTION = register(
    "spark.rapids.tpu.memory.fraction", 0.8,
    "Fraction of the selected device's reported memory given to the "
    "spill store's HBM budget when the runtime reports a limit (the "
    "spark.rapids.memory.gpu.allocFraction analog).")

HOST_POOL_BYTES = register(
    "spark.rapids.tpu.memory.hostPool.maxBytes", 256 << 20,
    "Upper bound on recycled host staging buffers held by the "
    "HostBufferPool (the pinned-host pool analog).")

BATCH_ROWS_AUTO = register(
    "spark.rapids.tpu.sql.batchSizeRows.auto", False,
    "Scale the DEFAULT batchSizeRows with the selected device's HBM: "
    "rows = pow2 floor of memory.fraction * HBM / 2KiB-per-row working "
    "set (≈32 live copies of a 64B row: the batch, its program "
    "temporaries and double-buffered successors), clamped to "
    "maxBatchCapacity — bigger chips run denser batches without "
    "retuning (the computeRmmInitSizes idea applied to batch sizing).  "
    "An EXPLICITLY set batchSizeRows always wins, and backends that "
    "report no real chip memory (the CPU test backend) keep the static "
    "default (docs/occupancy.md).")

#: HBM bytes budgeted per batch row under batchSizeRows.auto — ~32
#: concurrent live copies of a ~64-byte row (inputs, fused-program
#: temporaries, double-buffered successors, spill headroom)
_AUTO_ROW_BYTES = 2048


def device_alloc_checkpoint(nbytes: int) -> None:
    """The ``alloc.device`` fault-injection seam (robustness/faults.py):
    BufferStore.reserve consults it before admitting a device
    reservation, standing in for the alloc-failure hook XLA does not
    expose (the reference's DeviceMemoryEventHandler.onAllocFailure).
    Disarmed it is one global read; armed, an injected
    RESOURCE_EXHAUSTED here drives the store's spill-and-retry path and,
    past that, the batch split-and-retry ladder (execs/retry.py)."""
    from spark_rapids_tpu.robustness import faults as _faults

    _faults.fault_point("alloc.device", nbytes=nbytes)


@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    ordinal: int
    platform: str
    kind: str
    memory_bytes: Optional[int]


def discover() -> list[DeviceInfo]:
    """All PJRT devices visible to this process."""
    import jax

    out = []
    for i, d in enumerate(jax.devices()):
        # None on backends that keep no allocator statistics (CPU)
        stats = d.memory_stats()
        mem = (stats.get("bytes_limit")
               or stats.get("bytes_reservable_limit")) if stats else None
        out.append(DeviceInfo(i, d.platform, getattr(d, "device_kind",
                                                     d.platform), mem))
    return out


def device_fields() -> dict:
    """The device JAX handed this process, as it reports it: what every
    launcher's JSON carries, so that no round can be read as a chip
    round without having been one."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def select_device(conf=None):
    """This process's device (jax device object)."""
    import jax

    conf = conf or get_conf()
    devs = jax.devices()
    ordinal = conf.get(DEVICE_ORDINAL)
    if 0 <= ordinal < len(devs):
        return devs[ordinal]
    return devs[0]


def selected_info(conf=None) -> DeviceInfo:
    """DeviceInfo of this process's device."""
    import jax

    return discover()[jax.devices().index(select_device(conf))]


def effective_batch_size_rows(conf=None) -> int:
    """batchSizeRows after HBM scaling: the conf value verbatim unless
    batchSizeRows.auto is on AND the conf sits at its default AND the
    selected device reports real chip memory — then the default scales
    with the HBM budget (pow2 floor of fraction * HBM / _AUTO_ROW_BYTES,
    clamped to [default, maxBatchCapacity]).  Every consumer of
    BATCH_SIZE_ROWS that sizes device batches routes through here."""
    from spark_rapids_tpu.config import BATCH_SIZE_ROWS, MAX_CAPACITY

    conf = conf or get_conf()
    rows = int(conf.get(BATCH_SIZE_ROWS))
    if not conf.get(BATCH_ROWS_AUTO) or rows != BATCH_SIZE_ROWS.default:
        return rows
    info = selected_info(conf)
    if not info.memory_bytes or info.platform == "cpu":
        # CPU test backends report host RAM as "device" memory
        return rows
    budget = int(info.memory_bytes * conf.get(MEMORY_FRACTION))
    scaled = max(1, budget // _AUTO_ROW_BYTES)
    scaled = 1 << (scaled.bit_length() - 1)
    return int(min(max(scaled, rows), conf.get(MAX_CAPACITY)))


def store_budget(conf=None) -> int:
    """The spill store's device budget in bytes: memory.fraction of the
    selected chip's reported HBM.  The conf figure stands when it was
    set explicitly, and on the CPU backend (which reports host RAM as
    "device" memory, or nothing).  A real chip that reports no limit
    is an error: a budget guessed for another chip is how a 16 GB part
    gets a store that overruns it."""
    from spark_rapids_tpu.memory.store import HBM_BUDGET_BYTES

    conf = conf or get_conf()
    budget = conf.get(HBM_BUDGET_BYTES)
    if budget != HBM_BUDGET_BYTES.default:
        return budget
    info = selected_info(conf)
    if info.platform == "cpu":
        return budget
    if not info.memory_bytes:
        raise RuntimeError(
            f"{info.platform} device {info.ordinal} ({info.kind}) reports "
            "no memory limit; set spark.rapids.tpu.memory.hbm.budgetBytes")
    return int(info.memory_bytes * conf.get(MEMORY_FRACTION))


def initialize(conf=None) -> DeviceInfo:
    """Install the process BufferStore, sized by store_budget, now;
    returns the chosen device's info."""
    from spark_rapids_tpu.memory.store import BufferStore, reset_store

    conf = conf or get_conf()
    reset_store(BufferStore(device_budget=store_budget(conf)))
    return selected_info(conf)


class HostBufferPool:
    """Recycled host staging buffers, bucketed by rounded size (the
    pinned-host-pool shape without real page pinning)."""

    _instance: Optional["HostBufferPool"] = None
    _ilock = threading.Lock()

    def __init__(self, max_bytes: Optional[int] = None):
        self._free: dict[int, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self._held = 0
        self.max_bytes = max_bytes if max_bytes is not None \
            else get_conf().get(HOST_POOL_BYTES)

    @classmethod
    def get(cls) -> "HostBufferPool":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = HostBufferPool()
            return cls._instance

    @staticmethod
    def _bucket(nbytes: int) -> int:
        b = 4096
        while b < nbytes:
            b <<= 1
        return b

    def take(self, nbytes: int) -> np.ndarray:
        """A uint8 buffer of >= nbytes (first nbytes NOT zeroed)."""
        b = self._bucket(nbytes)
        with self._lock:
            lst = self._free.get(b)
            if lst:
                buf = lst.pop()
                self._held -= buf.nbytes
                return buf
        return np.empty(b, np.uint8)

    def give(self, buf: np.ndarray) -> None:
        """Return a buffer taken from the pool (callers must not keep
        references)."""
        if buf.dtype != np.uint8 or buf.ndim != 1:
            return
        b = buf.nbytes
        if (b & (b - 1)) or b < 4096:
            return  # not a pool bucket
        with self._lock:
            if self._held + b > self.max_bytes:
                return  # over budget: let it be collected
            self._free.setdefault(b, []).append(buf)
            self._held += b
