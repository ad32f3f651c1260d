"""Device discovery / selection / budget init (GpuDeviceManager analog)
and the recycled host staging pool."""

import numpy as np

from spark_rapids_tpu.config import get_conf
from spark_rapids_tpu.memory import device_manager as DM
from spark_rapids_tpu.memory.store import HBM_BUDGET_BYTES, get_store, reset_store


def test_discover_lists_devices():
    devs = DM.discover()
    assert devs, "no devices discovered"
    assert devs[0].ordinal == 0
    assert devs[0].platform


def test_select_device_ordinal():
    conf = get_conf()
    old = conf.get(DM.DEVICE_ORDINAL)
    try:
        conf.set(DM.DEVICE_ORDINAL.key, 0)
        import jax

        assert DM.select_device(conf) is jax.devices()[0]
        conf.set(DM.DEVICE_ORDINAL.key, 10_000)  # out of range -> first
        assert DM.select_device(conf) is jax.devices()[0]
    finally:
        conf.set(DM.DEVICE_ORDINAL.key, old)


def test_initialize_installs_store():
    conf = get_conf()
    info = DM.initialize(conf)
    try:
        store = get_store()
        # CPU test backend: fraction sizing must NOT apply; the conf
        # budget stands
        assert store.device_budget == conf.get(HBM_BUDGET_BYTES)
        assert info.platform == "cpu"
    finally:
        reset_store()


def _as_chip(monkeypatch, memory_bytes):
    monkeypatch.setattr(
        DM, "selected_info",
        lambda conf=None: DM.DeviceInfo(0, "tpu", "TPU v5 lite",
                                        memory_bytes))


def test_plain_store_is_sized_from_the_chip(monkeypatch):
    """The store a plain TpuSession gets (get_store() with no plugin
    in sight) takes memory.fraction of the chip's reported limit."""
    conf = get_conf()
    limit = 16 * 10**9
    _as_chip(monkeypatch, limit)
    reset_store()
    try:
        assert get_store().device_budget == int(
            limit * conf.get(DM.MEMORY_FRACTION))
    finally:
        reset_store()


def test_explicit_budget_wins_over_the_chip(monkeypatch):
    conf = get_conf()
    _as_chip(monkeypatch, 16 * 10**9)
    conf.set(HBM_BUDGET_BYTES.key, 1 << 20)
    assert DM.store_budget(conf) == 1 << 20


def test_chip_without_a_memory_limit_is_an_error(monkeypatch):
    import pytest

    _as_chip(monkeypatch, None)
    with pytest.raises(RuntimeError, match="reports no memory limit"):
        DM.store_budget(get_conf())


def test_host_buffer_pool_recycles():
    pool = DM.HostBufferPool(max_bytes=1 << 20)
    a = pool.take(5000)
    assert a.nbytes == 8192 and a.dtype == np.uint8
    pool.give(a)
    b = pool.take(6000)
    assert b is a  # recycled, same bucket
    # over-budget buffers are dropped, not held
    big = pool.take(1 << 21)
    pool.give(big)
    pool.give(pool.take(1 << 21))
    held = sum(x.nbytes for lst in pool._free.values() for x in lst)
    assert held <= pool.max_bytes
