"""Plugin entry point and lifecycle.

TPU analog of the reference's plugin bring-up (ref: SQLPlugin.scala +
Plugin.scala:179 RapidsExecutorPlugin — driver/executor init, config
snapshot, shutdown hooks).  In this in-process engine the "plugin" owns
process-wide runtime state: the buffer store, the task semaphore, the
compiled-program cache, and the frontend adapter (shim).

Frontend shims (ref: the shims/ spark301..spark311 version adapters,
SURVEY §2.11): the reference re-targets one plugin across Spark
versions by routing version-specific APIs through a shim layer.  Here
the equivalent seam is the FRONTEND adapter — what translates a user
API into this engine's logical plans.  The native DataFrame frontend is
the default; a SQL-text or Substrait frontend plugs in through the
same registry without touching the engine."""

from __future__ import annotations

import atexit
import threading
from typing import Callable, Optional

from spark_rapids_tpu.config import register

NET_SHUFFLE_REGISTRY = register(
    "spark.rapids.tpu.shuffle.registry.address", "",
    "host:port of the shuffle peer registry (shuffle/net.py "
    "HeartbeatServer).  When set, plugin bring-up starts a TCP block "
    "server for this process's shuffle outputs and joins the registry "
    "with heartbeats (ref: Plugin.scala:197 heartbeat endpoint + "
    "RapidsShuffleHeartbeatManager).  Empty disables the network tier.")

NET_SHUFFLE_ADVERTISE = register(
    "spark.rapids.tpu.shuffle.server.advertiseHost", "",
    "Routable address peers should fetch this executor's blocks from. "
    "Empty = auto: loopback when the registry is on loopback, else "
    "this host's resolved address (cross-machine peers must never be "
    "handed 127.0.0.1 — they would fetch from themselves).  The block "
    "server binds 0.0.0.0 whenever the advertised host is non-local.")

_SHIMS: dict[str, Callable] = {}
_lock = threading.Lock()


def register_frontend(name: str, factory: Callable) -> None:
    """Register a frontend adapter: factory(conf) -> session-like
    object exposing this engine's DataFrame surface."""
    with _lock:
        _SHIMS[name] = factory


def frontend(name: str = "native"):
    with _lock:
        fe = _SHIMS.get(name)
    if fe is not None:
        return fe
    # bundled adapters register on import; load them before giving up
    try:
        import spark_rapids_tpu.frontends  # noqa: F401
    except ImportError:
        pass
    with _lock:
        try:
            return _SHIMS[name]
        except KeyError:
            raise KeyError(
                f"no frontend {name!r} registered "
                f"(have: {sorted(_SHIMS)})") from None


class TpuPlugin:
    """Process-wide lifecycle owner (SQLPlugin analog)."""

    _instance: Optional["TpuPlugin"] = None

    def __init__(self, conf=None):
        from spark_rapids_tpu.config import TpuConf, set_conf

        self.conf = conf or TpuConf()
        set_conf(self.conf)
        self._closed = False
        self.device_info = None
        self.block_server = None
        self.heartbeat_client = None
        # device discovery + memory-budget sizing (the
        # GpuDeviceManager.initializeGpuAndMemory step)
        from spark_rapids_tpu.memory import device_manager

        self.device_info = device_manager.initialize(self.conf)
        self._maybe_start_network_shuffle()
        atexit.register(self.shutdown)

    def _maybe_start_network_shuffle(self) -> None:
        """Executor bring-up of the cross-process shuffle tier (ref:
        Plugin.scala:197 RapidsShuffleHeartbeatEndpoint start): when a
        registry address is configured, serve this process's blocks
        over TCP and join the peer registry with periodic heartbeats."""
        registry = self.conf.get(NET_SHUFFLE_REGISTRY)
        if not registry:
            return
        try:
            import os
            import socket as _socket

            from spark_rapids_tpu.shuffle.net import (
                HeartbeatClient,
                ShuffleBlockServer,
            )

            host, port = registry.rsplit(":", 1)
            local_registry = host in ("127.0.0.1", "localhost", "::1")
            advertise = self.conf.get(NET_SHUFFLE_ADVERTISE)
            if not advertise:
                advertise = "127.0.0.1" if local_registry \
                    else _socket.gethostbyname(_socket.gethostname())
            bind = "127.0.0.1" if advertise in ("127.0.0.1",
                                                "localhost") \
                else "0.0.0.0"
            from spark_rapids_tpu.columnar.serde import (
                SHUFFLE_COMPRESSION,
            )

            self.block_server = ShuffleBlockServer(
                host=bind,
                codec=self.conf.get(SHUFFLE_COMPRESSION)).start()
            bport = self.block_server.address[1]
            self.heartbeat_client = HeartbeatClient(
                host, int(port), f"executor-{os.getpid()}",
                advertise, bport)
            self.heartbeat_client.register()
            self.heartbeat_client.start_background()
        except Exception:
            # degraded mode: local + collective tiers still work (the
            # reference likewise falls back when UCX cannot start)
            if self.block_server is not None:
                self.block_server.shutdown()
                self.block_server = None
            self.heartbeat_client = None

    @classmethod
    def get_or_create(cls, conf=None) -> "TpuPlugin":
        with _lock:
            if cls._instance is None or cls._instance._closed:
                cls._instance = TpuPlugin(conf)
            return cls._instance

    def session(self, frontend_name: str = "native"):
        return frontend(frontend_name)(self.conf)

    def shutdown(self) -> None:
        """Release process-wide resources (executor shutdown hook,
        ref: RapidsExecutorPlugin.shutdown)."""
        if self._closed:
            return
        self._closed = True
        from spark_rapids_tpu.execs import jit_cache
        from spark_rapids_tpu.memory import reset_store

        if self.heartbeat_client is not None:
            self.heartbeat_client.stop()
            self.heartbeat_client = None
        if self.block_server is not None:
            try:
                self.block_server.shutdown()
            except Exception:
                pass
            self.block_server = None
        try:
            # reset_store() closes any existing store itself; calling
            # get_store() here would lazily build one just to close it
            reset_store()
        except Exception:
            pass
        try:
            jit_cache.clear()
        except Exception:
            pass


def _native_frontend(conf):
    from spark_rapids_tpu.session import TpuSession

    return TpuSession(conf)


register_frontend("native", _native_frontend)
