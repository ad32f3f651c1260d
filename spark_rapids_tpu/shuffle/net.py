"""Cross-process shuffle transport: TCP block server/client + peer
registry.

Counterpart of the reference's network shuffle tier (ref:
RapidsShuffleServer.scala:70 serving catalog buffers,
RapidsShuffleClient.scala:96 MetadataRequest/TransferRequest fetch
protocol, RapidsShuffleHeartbeatManager.scala:51-114 driver-side peer
registry).  Re-designed for this engine's substrate:

- blocks travel as the serde frame format (columnar/serde.py) over a
  length-prefixed TCP stream — the host-serialized tier; the
  device-to-device tier is the collective transport (SURVEY.md §5.8);
- the server serves blocks NON-destructively out of the local
  spillable shuffle manager (get_host pins, unpin after send), so a
  reducer can re-fetch after a failure — the reference's
  catalog-backed BufferSendState behavior;
- fetch failures surface as FetchFailedError, classified retryable by
  execs/retry.py so the standard task-retry machinery provides
  elasticity (the FetchFailedException contract).

Everything is stdlib sockets + threads: no external RPC dependency.
"""

from __future__ import annotations

import json
import socket
import socketserver
import struct
import threading
import time
from typing import Iterator, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.serde import (
    deserialize_arrays,
    serialize_arrays,
)
from spark_rapids_tpu.config import register

HEARTBEAT_INTERVAL_S = register(
    "spark.rapids.tpu.shuffle.heartbeat.intervalSeconds", 5.0,
    "Executor-to-registry heartbeat period (ref: "
    "spark.rapids.shuffle.transport.earlyStart.heartbeatInterval).")

HEARTBEAT_TIMEOUT_S = register(
    "spark.rapids.tpu.shuffle.heartbeat.timeoutSeconds", 30.0,
    "A peer missing heartbeats this long is pruned from the registry "
    "and no longer handed to new executors.")

FETCH_MAX_ATTEMPTS = register(
    "spark.rapids.tpu.shuffle.fetch.maxAttempts", 3,
    "Connection/read attempts per block fetch before FetchFailedError "
    "propagates to the task-retry layer (ref: "
    "spark.shuffle.io.maxRetries).  Between attempts the client backs "
    "off exponentially with jitter; callers that supply a resolver "
    "(net.peer_resolver over the heartbeat registry) get the peer "
    "address re-resolved before every retry after the first (with "
    "only two attempts budgeted, before that sole retry) — a "
    "restarted peer on a fresh port is found early, not only on the "
    "last-ditch attempt.  The query's cancel token is honored between "
    "attempts (a cancelled reducer stops reconnecting immediately).",
    check=lambda v: v >= 1)

FETCH_BACKOFF_S = register(
    "spark.rapids.tpu.shuffle.fetch.retryWaitSeconds", 0.05,
    "Base sleep between fetch attempts (doubles per attempt, +-50% "
    "jitter so reducers hammered off the same dying peer do not "
    "reconnect in lockstep; ref: spark.shuffle.io.retryWait).")

FETCH_TIMEOUT_S = register(
    "spark.rapids.tpu.shuffle.fetch.timeoutSeconds", 30.0,
    "Per-ATTEMPT socket timeout (connect and reads) for block "
    "fetches; a hung peer costs one attempt, not the whole fetch "
    "budget.")


class FetchFailedError(RuntimeError):
    """A remote shuffle block could not be fetched (peer died,
    connection reset, truncated stream).  Retryable: the task retry
    path re-runs the attempt, which re-resolves peers (the
    FetchFailedException -> stage-retry contract of the reference's
    RapidsShuffleIterator)."""


# ------------------------------------------------------------------ #
# Wire helpers: every message is <Q length><payload>
# ------------------------------------------------------------------ #


def _send_msg(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(struct.pack("<Q", len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise FetchFailedError(
                f"connection closed mid-message ({len(buf)}/{n} bytes)")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock: socket.socket) -> bytes:
    (n,) = struct.unpack("<Q", _recv_exact(sock, 8))
    return _recv_exact(sock, n)


# ------------------------------------------------------------------ #
# Block server (executor side)
# ------------------------------------------------------------------ #


class _BlockHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:  # one request per connection
        try:
            req = json.loads(_recv_msg(self.request).decode())
        except Exception:
            return
        if req.get("op") != "fetch":
            _send_msg(self.request, json.dumps(
                {"error": "bad op"}).encode())
            return
        manager = self.server.shuffle_manager  # type: ignore[attr-defined]
        sid, rid = int(req["shuffle_id"]), int(req["reduce_id"])
        if not manager.knows_shuffle(sid):
            # restarted peer / stale address: the blocks are LOST, not
            # empty — the reducer must get a retryable failure, never
            # silently consume zero rows
            _send_msg(self.request, json.dumps(
                {"error": f"unknown shuffle {sid} (blocks lost; "
                          "peer restarted?)"}).encode())
            return
        _send_msg(self.request, json.dumps({"streaming": True}).encode())
        # one block serialized + sent at a time (the bounce-buffer
        # windowing discipline: peak memory is one frame, each block
        # pinned only while its bytes stream out); an EMPTY frame
        # terminates the stream (frames always start with the magic)
        for arrays in manager.serve_host(sid, rid):
            frame = serialize_arrays(arrays, self.server.codec)  # type: ignore
            raw = sum(int(a.nbytes) for a in arrays.values())
            self.server.count_bytes(raw, len(frame))  # type: ignore
            _send_msg(self.request, frame)
        _send_msg(self.request, b"")


class ShuffleBlockServer:
    """Serves this process's shuffle blocks over TCP (ref:
    RapidsShuffleServer — metadata + transfer responses built from the
    catalog, windowed through bounce buffers; here the serde staging
    buffer plays the bounce-buffer role)."""

    def __init__(self, manager=None, host: str = "127.0.0.1",
                 port: int = 0, codec: str = "none"):
        from spark_rapids_tpu.columnar.compression import get_bytes_codec
        from spark_rapids_tpu.shuffle.manager import get_shuffle_manager

        get_bytes_codec(codec)  # fail fast on a typo'd codec conf
        self._srv = socketserver.ThreadingTCPServer(
            (host, port), _BlockHandler, bind_and_activate=True)
        self._srv.daemon_threads = True
        self._srv.shuffle_manager = manager or get_shuffle_manager()
        self._srv.codec = codec
        # bytes accounting (the shuffleWriteBytes/compression-ratio
        # observability the reference surfaces per-codec)
        self._bytes_lock = threading.Lock()
        self._raw_bytes = 0
        self._wire_bytes = 0

        srv_self = self

        def count_bytes(raw: int, wire: int) -> None:
            with srv_self._bytes_lock:
                srv_self._raw_bytes += raw
                srv_self._wire_bytes += wire

        self._srv.count_bytes = count_bytes
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True,
            name="tpu-shuffle-server")

    def bytes_stats(self) -> dict:
        """{'raw': bytes before codec, 'wire': framed bytes sent,
        'codec': this server's frame codec, 'codecs': the process-wide
        per-codec registry stats} — the shuffle tier's view of the ONE
        stats surface the H2D wire and spill tiers also report
        through (columnar/compression/; docs/wire_compression.md)."""
        from spark_rapids_tpu.columnar import compression as WC

        with self._bytes_lock:
            return {"raw": self._raw_bytes, "wire": self._wire_bytes,
                    "codec": self._srv.codec, "codecs": WC.stats()}

    @property
    def address(self) -> tuple[str, int]:
        return self._srv.server_address[:2]

    def start(self) -> "ShuffleBlockServer":
        self._thread.start()
        return self

    def shutdown(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()


def _fetch_once(host: str, port: int, shuffle_id: int, reduce_id: int,
                timeout: float) -> list[dict]:
    """One fetch attempt (the previous whole-fetch body): any transport
    problem raises FetchFailedError.  ``timeout`` bounds the connect
    AND every read on this attempt's socket."""
    from spark_rapids_tpu.robustness import faults as _faults

    try:
        _faults.fault_point("shuffle.fetch", shuffle_id=shuffle_id,
                            reduce_id=reduce_id)
        with socket.create_connection((host, port),
                                      timeout=timeout) as sock:
            _send_msg(sock, json.dumps({
                "op": "fetch", "shuffle_id": shuffle_id,
                "reduce_id": reduce_id}).encode())
            head = json.loads(_recv_msg(sock).decode())
            if "error" in head:
                raise FetchFailedError(head["error"])
            out = []
            while True:
                frame = _recv_msg(sock)
                if not frame:  # end-of-stream marker
                    break
                out.append(deserialize_arrays(frame))
            return out
    except FetchFailedError:
        raise
    except (OSError, ValueError, json.JSONDecodeError) as e:
        raise FetchFailedError(
            f"fetch {shuffle_id}/{reduce_id} from {host}:{port} "
            f"failed: {e}") from e
    except RuntimeError as e:
        # the shuffle.fetch fault seam injects RuntimeErrors carrying
        # transport markers; surface them under the same contract a
        # real connection reset would
        from spark_rapids_tpu.execs.retry import classify

        if classify(e) != "retryable":
            raise
        raise FetchFailedError(
            f"fetch {shuffle_id}/{reduce_id} from {host}:{port} "
            f"failed: {e}") from e


def fetch_blocks(host: str, port: int, shuffle_id: int, reduce_id: int,
                 timeout: Optional[float] = None,
                 resolve_peer=None) -> list[dict]:
    """Fetch one reduce partition's blocks from a peer as host-array
    dicts, with BOUNDED RETRIES inside the fetch itself (ref:
    RetryingBlockTransferor / spark.shuffle.io.maxRetries): each
    attempt gets its own socket timeout; between attempts the client
    honors the query's cancel token (a cancelled reducer raises
    QueryCancelled instead of reconnecting) and sleeps a jittered
    doubling backoff; from the SECOND retry on, every attempt first
    re-resolves the peer through ``resolve_peer`` (typically
    HeartbeatManager.live_peers via ``peer_resolver``) — a restarted
    executor re-registers on a fresh port, and finding it early saves
    whole backoff rounds hammering a dead address (the first retry
    skips resolution: transient resets on a LIVE peer are the common
    case and the registry round trip is not free).  Only after the
    budget is spent does FetchFailedError propagate — the task-retry
    layer then provides the coarser elasticity, as before."""
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.robustness import faults as _faults
    from spark_rapids_tpu.serving.cancel import check_point

    conf = get_conf()
    if timeout is None:
        timeout = conf.get(FETCH_TIMEOUT_S)
    attempts = max(1, conf.get(FETCH_MAX_ATTEMPTS))
    backoff = conf.get(FETCH_BACKOFF_S)
    caught: list[BaseException] = []
    for attempt in range(attempts):
        try:
            out = _fetch_once(host, port, shuffle_id, reduce_id,
                              timeout)
        except FetchFailedError as e:
            if attempt == attempts - 1:
                raise
            caught.append(e)
            check_point()  # cancelled mid-fetch: stop reconnecting
            from spark_rapids_tpu.execs.retry import _sleep_backoff

            _sleep_backoff(backoff, attempt)
            if resolve_peer is not None \
                    and attempt >= min(1, attempts - 2):
                # persistent failure (two attempts on this address
                # died): re-resolve before EVERY further attempt — a
                # restarted peer re-registers with a fresh endpoint
                # and is found as early as the registry knows it,
                # not only before the last-ditch attempt.  With only
                # two attempts budgeted the sole retry IS the final
                # attempt, so resolution fires before it (min clamp)
                # rather than never
                try:
                    fresh = resolve_peer()
                except Exception as re_exc:  # noqa: BLE001 — resolver is best-effort
                    from spark_rapids_tpu.execs.retry import classify

                    classify(re_exc)
                    fresh = None
                if fresh is not None:
                    host, port = fresh
            continue
        for e in caught:
            _faults.note_recovered(e, action="fetch_retry")
        return out
    raise caught[-1]  # unreachable; keeps type checkers honest


def peer_resolver(registry, executor_id: str):
    """A ``resolve_peer`` callback over a HeartbeatManager (or any
    object with ``live_peers()``): the freshest (host, port) the
    registry knows for ``executor_id``, else None."""
    def resolve() -> Optional[tuple[str, int]]:
        for eid, h, p in registry.live_peers():
            if eid == executor_id:
                return h, p
        return None

    return resolve


def read_remote(host: str, port: int, shuffle_id: int, reduce_id: int,
                schema, timeout: Optional[float] = None,
                resolve_peer=None) -> Iterator[ColumnarBatch]:
    """Fetch + upload: remote blocks as device batches."""
    from spark_rapids_tpu.memory.store import _host_to_batch

    for arrays in fetch_blocks(host, port, shuffle_id, reduce_id,
                               timeout=timeout,
                               resolve_peer=resolve_peer):
        yield _host_to_batch(arrays, schema)


# ------------------------------------------------------------------ #
# Peer registry (driver side) + executor heartbeat client
# ------------------------------------------------------------------ #


class HeartbeatManager:
    """Driver-side peer registry (ref:
    RapidsShuffleHeartbeatManager.scala:51 registerExecutor /
    :81 executorHeartbeat): executors register their block-server
    endpoint; each heartbeat returns peers that appeared since the
    executor last asked; silent peers age out."""

    def __init__(self, timeout_s: Optional[float] = None):
        from spark_rapids_tpu.config import get_conf

        self._lock = threading.Lock()
        #: executor_id -> (host, port, last_seen, join_seq)
        self._peers: dict[str, tuple[str, int, float, int]] = {}
        #: executor_id -> highest join_seq already reported to it
        self._acked: dict[str, int] = {}
        self._seq = 0
        self._timeout = timeout_s if timeout_s is not None \
            else get_conf().get(HEARTBEAT_TIMEOUT_S)

    def register(self, executor_id: str, host: str,
                 port: int) -> list[tuple[str, str, int]]:
        now = time.monotonic()
        with self._lock:
            self._prune(now)  # never hand long-dead peers to a joiner
            self._seq += 1
            self._peers[executor_id] = (host, port, now, self._seq)
            self._acked[executor_id] = self._seq
            return [(eid, h, p) for eid, (h, p, _, _)
                    in self._peers.items() if eid != executor_id]

    def heartbeat(self, executor_id: str) -> list[tuple[str, str, int]]:
        """Refresh liveness; returns peers NEW since the last call."""
        now = time.monotonic()
        with self._lock:
            entry = self._peers.get(executor_id)
            if entry is None:
                raise KeyError(f"unregistered executor {executor_id}")
            self._peers[executor_id] = entry[:2] + (now, entry[3])
            self._prune(now)
            last = self._acked.get(executor_id, 0)
            fresh = [(eid, h, p) for eid, (h, p, _, seq)
                     in self._peers.items()
                     if seq > last and eid != executor_id]
            self._acked[executor_id] = self._seq
            return fresh

    def live_peers(self) -> list[tuple[str, str, int]]:
        with self._lock:
            self._prune(time.monotonic())
            return [(eid, h, p) for eid, (h, p, _, _)
                    in self._peers.items()]

    def _prune(self, now: float) -> None:
        dead = [eid for eid, (_, _, seen, _) in self._peers.items()
                if now - seen > self._timeout]
        for eid in dead:
            del self._peers[eid]
            self._acked.pop(eid, None)


class _RegistryHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        try:
            req = json.loads(_recv_msg(self.request).decode())
        except Exception:
            return
        mgr: HeartbeatManager = self.server.manager  # type: ignore
        try:
            if req["op"] == "register":
                peers = mgr.register(req["executor_id"], req["host"],
                                     int(req["port"]))
            elif req["op"] == "heartbeat":
                peers = mgr.heartbeat(req["executor_id"])
            else:
                raise ValueError(f"bad op {req['op']!r}")
            resp = {"peers": peers}
        except Exception as e:
            resp = {"error": str(e)}
        _send_msg(self.request, json.dumps(resp).encode())


class HeartbeatServer:
    """TCP front for a HeartbeatManager (the driver plugin endpoint)."""

    def __init__(self, manager: Optional[HeartbeatManager] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.manager = manager or HeartbeatManager()
        self._srv = socketserver.ThreadingTCPServer(
            (host, port), _RegistryHandler, bind_and_activate=True)
        self._srv.daemon_threads = True
        self._srv.manager = self.manager
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True,
            name="tpu-shuffle-registry")

    @property
    def address(self) -> tuple[str, int]:
        return self._srv.server_address[:2]

    def start(self) -> "HeartbeatServer":
        self._thread.start()
        return self

    def shutdown(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()


class HeartbeatClient:
    """Executor-side registry client: register once, then periodic
    heartbeats; accumulates the known-peer table (the executor's
    `transport.connect(peer)` trigger in the reference)."""

    def __init__(self, registry_host: str, registry_port: int,
                 executor_id: str, block_host: str, block_port: int):
        self._addr = (registry_host, registry_port)
        self.executor_id = executor_id
        self._me = (block_host, block_port)
        self.peers: dict[str, tuple[str, int]] = {}
        self._timer: Optional[threading.Timer] = None
        self._stopped = False

    def _call(self, payload: dict) -> list:
        try:
            with socket.create_connection(self._addr,
                                          timeout=10.0) as sock:
                _send_msg(sock, json.dumps(payload).encode())
                resp = json.loads(_recv_msg(sock).decode())
        except (OSError, ValueError) as e:
            raise FetchFailedError(f"registry unreachable: {e}") from e
        if "error" in resp:
            raise FetchFailedError(resp["error"])
        return resp["peers"]

    def register(self) -> None:
        peers = self._call({
            "op": "register", "executor_id": self.executor_id,
            "host": self._me[0], "port": self._me[1]})
        for eid, h, p in peers:
            self.peers[eid] = (h, p)

    def heartbeat(self) -> None:
        for eid, h, p in self._call({"op": "heartbeat",
                                     "executor_id": self.executor_id}):
            self.peers[eid] = (h, p)

    def start_background(self, interval_s: Optional[float] = None
                         ) -> None:
        from spark_rapids_tpu.config import get_conf

        interval = interval_s if interval_s is not None \
            else get_conf().get(HEARTBEAT_INTERVAL_S)

        def tick():
            if self._stopped:
                return
            try:
                self.heartbeat()
            except FetchFailedError as e:
                # pruned after a long stall (registry said
                # "unregistered")?  re-register — otherwise this
                # executor stays invisible to new peers forever
                if "unregistered" in str(e):
                    try:
                        self.register()
                    except FetchFailedError:
                        pass
                # registry unreachable: keep last-known peers
            except Exception:
                # any other failure (malformed registry response, socket
                # teardown race) must not kill the heartbeat chain — a
                # dead chain silently ages this executor out of the
                # registry
                pass
            finally:
                if not self._stopped:
                    self._timer = threading.Timer(interval, tick)
                    self._timer.daemon = True
                    self._timer.start()

        tick()

    def stop(self) -> None:
        self._stopped = True
        if self._timer is not None:
            self._timer.cancel()
