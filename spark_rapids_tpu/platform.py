"""Virtual CPU devices for tests: multi-chip sharding is validated on
a mesh of N CPU devices that XLA's host platform is told to expose."""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def pin_cpu_platform(n_devices: int):
    """Give tests >= n_devices virtual CPU devices: pins the CPU platform
    and sets XLA's host device count.

    Must be called before any backend initializes (first jnp op /
    jax.devices() call).  Returns the CPU device list; raises RuntimeError
    with a diagnostic when the backend was already initialized with fewer
    devices.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_COUNT_FLAG}={n_devices}")

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; the count check below decides
    devs = jax.devices("cpu")
    if len(devs) < n_devices:
        raise RuntimeError(
            f"need {n_devices} CPU devices, have {len(devs)}; the JAX "
            "backend initialized before pin_cpu_platform could raise "
            f"{_COUNT_FLAG} (run in a fresh process, or export "
            f"JAX_PLATFORMS=cpu XLA_FLAGS={_COUNT_FLAG}={n_devices} first)")
    return devs
