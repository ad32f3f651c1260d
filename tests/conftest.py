"""Test harness config: the suite runs on the CPU platform with 8
virtual devices, so the multi-chip sharding paths are exercised without
TPU hardware.  On the chip the program is driven by chip_smoke.py."""

from spark_rapids_tpu.platform import pin_cpu_platform

pin_cpu_platform(8)

# The suite's wall clock is dominated by per-test jit compiles of the
# same operator programs; the package's persistent compilation cache
# (spark_rapids_tpu.compile_cache_dir) makes repeat runs skip them.
# Only the admission threshold is lowered here — the directory is the
# package's to decide.
import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)

import pytest  # noqa: E402


@pytest.fixture
def leak_check():
    """Reusable process-residency leak gauge (docs/robustness.md):
    snapshots semaphore permits in use, BufferStore bytes per tier,
    live prefetch stage threads and the in-flight shared-scan count at
    setup, and asserts at teardown that every gauge returned EXACTLY
    to baseline (with a bounded settle wait for stage threads still
    unwinding).  Yields the snapshot callable so tests can also diff
    mid-test.  Suite-wide usage: test_serving.py, test_work_share.py
    and test_cancellation.py wrap it in a module-level autouse
    fixture, turning "no leaks" from a one-off assert into coverage
    every test in those modules carries."""
    import time as _time

    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.memory.store import peek_store
    from spark_rapids_tpu.parallel.pipeline import live_stage_threads
    from spark_rapids_tpu.serving.work_share import SCAN_REGISTRY

    def snap() -> dict:
        store = peek_store()
        ss = store.spill_stats() if store is not None else {
            "device_used": 0, "host_used": 0, "disk_used": 0}
        return {
            "semaphore_in_use": TpuSemaphore.usage_now()["in_use"],
            "store_device_bytes": ss["device_used"],
            "store_host_bytes": ss["host_used"],
            "store_disk_bytes": ss["disk_used"],
            "stage_threads": live_stage_threads(),
            "scan_inflight": SCAN_REGISTRY.inflight(),
        }

    before = snap()
    yield snap
    deadline = _time.monotonic() + 5.0
    after = snap()
    while after != before and _time.monotonic() < deadline:
        _time.sleep(0.05)  # stage threads may still be joining
        after = snap()
    assert after == before, (
        f"process residency leaked: before={before} after={after}")


#: tier-1 modules that run with the runtime lock-order tracker ARMED:
#: the concurrency-heavy suites double as a continuous deadlock hunt —
#: any lock-order cycle the tests' interleavings ever exhibit raises
#: LockCycleError right there instead of hanging a future soak
#: (docs/concurrency.md)
_LOCK_TRACKED_MODULES = frozenset((
    "test_serving",
    "test_cancellation",
    "test_work_share",
    "test_chaos",
))


@pytest.fixture(autouse=True)
def _arm_lock_tracker(request):
    """Force-arm the lock tracker for the modules above (forced
    installs survive sync_conf, so in-test sessions carrying the
    default conf cannot disarm it mid-test); verify no cycle formed."""
    if request.module.__name__ not in _LOCK_TRACKED_MODULES:
        yield
        return
    from spark_rapids_tpu.robustness import lock_tracker

    lock_tracker.install(forced=True)
    yield
    cycles = lock_tracker.cycle_count()
    graph = lock_tracker.order_graph()
    lock_tracker.disarm()
    assert cycles == 0, (
        f"lock-order cycle detected during test: graph={graph}")


@pytest.fixture(autouse=True)
def _isolate_conf():
    """Snapshot/restore the thread-local conf so a test's conf.set()
    can't leak into later tests (sessions share the thread-local)."""
    from spark_rapids_tpu.config import get_conf, set_conf

    conf = get_conf()
    saved = dict(conf._values)
    yield
    conf._values.clear()
    conf._values.update(saved)
    set_conf(conf)  # undo any set_conf() swap too
