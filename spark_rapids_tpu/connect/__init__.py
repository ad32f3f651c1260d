"""Spark Connect-style plan ingress: the engine's wire front door.

The reference's defining capability is "the user's job, unchanged" — a
real Spark hands its plans to the plugin seam (ref: SQLPlugin.scala:
26-31) and the plugin accelerates whatever Catalyst produced.  The
TPU-idiomatic mirror is this package:
an external process serializes a plan, ships it over TCP, and the FULL
serving stack executes it —

- ``connect/server.py``: length-prefixed framed TCP server (the
  shuffle/net.py idiom) accepting ExecutePlan-style requests — a
  Substrait plan (JSON or dict form) or SQL text, plus session conf
  overrides, SQL parameter bindings, a tenant id, and an optional
  deadline — translated through the existing frontends
  (frontends/substrait.py, frontends/sql.py) and routed through
  admission control + weighted-fair queuing, the prepared-plan cache
  keyed by the wire plan's structural key, cross-tenant result/scan
  sharing, and cancellation/deadline propagation: a dropped client
  connection cancels the in-flight query via its CancelToken, and a
  wire deadline becomes ``spark.rapids.tpu.serving.deadlineMs``
  (enforced from the admission queue — expiry while queued sheds with
  zero device work);
- ``connect/client.py``: the engine-free client (stdlib + pyarrow
  ONLY) plus the shared framing helpers; results stream back as Arrow
  IPC frames, one per device batch, backpressured by the socket;
- ``python -m spark_rapids_tpu.tools.connect_client``: the stand-alone
  CLI client.

Auth posture: none — the server binds loopback by default and trusts
its network, like the reference's shuffle transport (docs/connect.md).
"""

from __future__ import annotations

from spark_rapids_tpu.config import register

MAX_FRAME_BYTES = register(
    "spark.rapids.tpu.connect.maxFrameBytes", 64 << 20,
    "Upper bound on one connect wire frame (request JSON or response "
    "Arrow IPC batch).  The length prefix is validated against this "
    "BEFORE any payload allocation on both ends (tpulint SRC014), so "
    "a corrupt or hostile length costs 8 bytes of read, never a giant "
    "allocation; oversized requests are rejected with an error frame "
    "and the connection closed, without killing the server.",
    check=lambda v: v >= 1024)

BATCH_ROWS = register(
    "spark.rapids.tpu.connect.batchRows", 0,
    "Row cap per response Arrow frame (0 = the engine's device batch "
    "size as produced by the streaming fetch path).  A wire request's "
    "batch_rows field overrides per query.",
    check=lambda v: v >= 0)

SEND_BUFFER_BYTES = register(
    "spark.rapids.tpu.connect.sendBufferBytes", 0,
    "SO_SNDBUF for response streaming on the server side (0 = OS "
    "default).  Smaller buffers tighten the backpressure loop — the "
    "engine's bounded prefetch stalls as soon as the CLIENT stops "
    "reading, instead of after megabytes of kernel buffering — at "
    "the cost of more syscalls; the disconnect-cancellation tests "
    "pin it low to make client-drop detection deterministic.",
    check=lambda v: v >= 0)

SOCKET_TIMEOUT_S = register(
    "spark.rapids.tpu.connect.socketTimeoutSeconds", 120.0,
    "Per-connection socket timeout on the server (reads of the next "
    "request and writes of response frames).  A stalled or vanished "
    "client trips this, the handler cancels any in-flight query via "
    "its CancelToken and the connection closes; other connections are "
    "unaffected.",
    check=lambda v: v > 0)
