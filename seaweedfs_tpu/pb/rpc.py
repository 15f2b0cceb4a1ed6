"""JSON-over-gRPC: the control-plane mesh without protoc codegen.

Capability-equivalent to the reference's generated stubs + connection cache
(weed/pb/grpc_client_server.go): every service is a name -> handler map
registered through grpc generic handlers; payloads are dicts.  A message
whose top-level values are all JSON travels as that JSON.  A message with
top-level `bytes`/`bytearray`/`memoryview` values (the shard chunks of
CopyFile and VolumeEcShardRead, like the reference's protobuf `bytes`
fields) travels as an envelope: a 0x00 tag (never the first byte of JSON),
a 4-byte big-endian header length, a JSON header holding the other fields
and each bytes field's name and length, then the payloads raw, in order;
it arrives as a dict with `bytes` values.  Bytes nested deeper, such as KV
values, travel base64 via to_b64/from_b64.  Unary and bidi-streaming
methods cover everything the reference's 6 protos use (heartbeat streams,
shard copy streams, metadata subscribe streams).

Error convention: a handler raising RpcError(msg) (or any Exception) aborts
the call with the message in the gRPC status details; clients re-raise it
as RpcError.

Tracing: every outgoing call attaches the ambient trace id as
`x-trace-id` metadata (util/tracing.py); the server wrappers adopt it
for the handler's duration, so a filer request's master Assign carries
the same trace id as the originating HTTP hop.  Attaching a Tracer to
`RpcServer.tracer` records one span per handled method, with the stage
tags its handler summed: `frame_s` (building or parsing messages, JSON
or envelope, and any base64, on the thread that handles or consumes
them), `raw_bytes` (payload bytes that went raw in an envelope) and, on a
stream a client consumes, `recv_s` (blocked waiting for the next message).
"""

from __future__ import annotations

import base64
import json
import threading
import time
from concurrent import futures
from typing import Callable, Iterator

import grpc

from ..util import faults, tracing
from ..util.retry import default_connect_timeout, default_rpc_timeout
from ..util.weedlog import logger

LOG = logger(__name__)


class RpcError(Exception):
    pass


# process-global mTLS config (security/tls.py TlsConfig); when set, every
# new RpcServer port and every new pooled channel is mutual-TLS — the
# reference's security.toml [grpc.*] applies the same way, per process
_TLS = None


def set_tls(tls_config) -> None:
    global _TLS
    _TLS = tls_config
    POOL.close()     # cached insecure channels must not outlive the flip


def clear_tls() -> None:
    global _TLS
    _TLS = None
    POOL.close()


def tls_enabled() -> bool:
    """Whether gRPC runs under mutual TLS (set_tls)."""
    return _TLS is not None


def _channel_credentials():
    ca, cert, key = _TLS.read()
    return grpc.ssl_channel_credentials(
        root_certificates=ca, private_key=key, certificate_chain=cert)


def _server_credentials():
    ca, cert, key = _TLS.read()
    return grpc.ssl_server_credentials(
        [(key, cert)], root_certificates=ca,
        require_client_auth=True)


def to_b64(raw: bytes) -> str:
    with tracing.stage("frame"):
        return base64.b64encode(raw).decode("ascii")


def from_b64(s: str) -> bytes:
    with tracing.stage("frame"):
        return base64.b64decode(s)


_RAW_TAG = b"\x00"
_RAW_TYPES = (bytes, bytearray, memoryview)


def _ser(d: dict) -> bytes:
    # grpc serializes a streamed response on the handler thread right
    # after the handler's generator yields it, inside the handler's
    # trace scope, so this lands on the streaming RPC's own span
    with tracing.stage("frame"):
        raw = [k for k, v in d.items() if isinstance(v, _RAW_TYPES)]
        if not raw:
            return json.dumps(d, separators=(",", ":")).encode()
        views = [memoryview(d[k]) for k in raw]
        sizes = [v.nbytes for v in views]
        head = json.dumps(
            {"fields": {k: v for k, v in d.items() if k not in raw},
             "raw": [[k, n] for k, n in zip(raw, sizes)]},
            separators=(",", ":")).encode()
        tracing.add("raw_bytes", sum(sizes))
        return b"".join([_RAW_TAG, len(head).to_bytes(4, "big"), head,
                         *views])


def _de(b: bytes) -> dict:
    with tracing.stage("frame"):
        if not b:
            return {}
        if b[:1] != _RAW_TAG:
            return json.loads(b)
        at = 5 + int.from_bytes(b[1:5], "big")
        head = json.loads(b[5:at])
        out = head["fields"]
        start = at
        for name, size in head["raw"]:
            out[name] = b[at:at + size]     # the one copy of the payload
            at += size
        if at != len(b):
            raise ValueError(f"envelope of {len(b)} bytes declares {at}")
        tracing.add("raw_bytes", at - start)
        return out


def _trace_metadata() -> "list[tuple[str, str]] | None":
    if not tracing.enabled():
        return None
    tid = tracing.current_trace_id()
    if not tid:
        return None
    md = [(tracing.TRACE_METADATA_KEY, tid)]
    sid = tracing.current_span_id()
    if sid:
        # the calling span becomes the server-side span's parent
        md.append((tracing.SPAN_METADATA_KEY, sid))
    return md


def _incoming_trace_ids(context) -> tuple[str, str]:
    """-> (trace_id, parent_span_id) from the invocation metadata."""
    tid = parent = ""
    try:
        for key, value in context.invocation_metadata() or ():
            if key == tracing.TRACE_METADATA_KEY:
                tid = value
            elif key == tracing.SPAN_METADATA_KEY:
                parent = value
    except Exception as e:
        # fakes/in-process contexts may not implement metadata at all;
        # a request without a trace id is fine, a crashed handler is not
        LOG.debug("invocation metadata unreadable: %s", e)
    # metadata is client-controlled: bound it like the HTTP headers
    return tracing.clamp_id(tid), tracing.clamp_id(parent)


class RpcServer:
    """One grpc.Server hosting one or more named services."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 max_workers: int = 16):
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[("grpc.max_receive_message_length", 256 << 20),
                     ("grpc.max_send_message_length", 256 << 20)])
        self.host = host
        self._requested_port = port
        self.port = 0
        self.tracer: "tracing.Tracer | None" = None

    def add_service(self, service: str,
                    unary: dict[str, Callable[[dict], dict]] | None = None,
                    stream: dict[str, Callable[[Iterator[dict]],
                                               Iterator[dict]]] | None = None
                    ) -> None:
        handlers = {}
        for name, fn in (unary or {}).items():
            handlers[name] = grpc.unary_unary_rpc_method_handler(
                self._wrap_unary(fn, f"{service}/{name}"),
                request_deserializer=_de, response_serializer=_ser)
        for name, fn in (stream or {}).items():
            handlers[name] = grpc.stream_stream_rpc_method_handler(
                self._wrap_stream(fn, f"{service}/{name}"),
                request_deserializer=_de, response_serializer=_ser)
        self._server.add_generic_rpc_handlers(
            [grpc.method_handlers_generic_handler(service, handlers)])

    def _record(self, label: str, tid: str, t0: float, p0: float,
                status: str, slow_log: bool = True, span_id: str = "",
                parent_id: str = "", tags: "dict | None" = None
                ) -> None:
        """`t0` is the wall-clock span START (cross-server alignment);
        `p0` the perf-counter twin the DURATION derives from — wall
        deltas bend under NTP (weedlint WL120).  `tags`: the stage tags
        the handler summed (tracing.stage / tracing.add)."""
        tracer = self.tracer  # attached after construction; read late
        if tracer is not None:
            tracer.record(label, tid, t0, time.perf_counter() - p0,
                          status=status, slow_log=slow_log,
                          span_id=span_id, parent_id=parent_id,
                          **tracing.copy_tags(tags))

    def _wrap_unary(self, fn, label: str):
        def h(request: dict, context) -> dict:
            # WEED_TRACE=0: no id minting, no scope, no span — the same
            # zero-cost branch the HTTP dispatch takes
            traced = tracing.enabled()
            if traced:
                tid, parent = _incoming_trace_ids(context)
                tid = tid or tracing.new_trace_id()
                sid = tracing.new_span_id()
                tags: dict = {}
                t0 = time.time()
                p0 = time.perf_counter()
            status = "ok"
            try:
                if faults.ACTIVE:
                    # server-side dispatch chaos: drop/error abort before
                    # the handler runs (the peer-crashed-mid-request
                    # shape); delay sleeps inside the handler slot
                    p = faults.hit("rpc.handle",
                                   f"{self.host}:{self.port}/{label}")
                    if p is not None:
                        raise RpcError(
                            f"injected fault #{p.rule_id}: {p.mode} "
                            f"{label}")
                if not traced:
                    return fn(request) or {}
                with tracing.trace_scope(tid, sid, tags):
                    return fn(request) or {}
            except RpcError as e:
                status = "error"
                context.abort(grpc.StatusCode.UNKNOWN, str(e))
            except Exception as e:  # surface the message to the caller
                status = "error"
                context.abort(grpc.StatusCode.INTERNAL,
                              f"{type(e).__name__}: {e}")
            finally:
                if traced:
                    self._record(label, tid, t0, p0, status,
                                 span_id=sid, parent_id=parent,
                                 tags=tags)
        return h

    def _wrap_stream(self, fn, label: str):
        def h(request_iterator, context):
            traced = tracing.enabled()
            if traced:
                tid, parent = _incoming_trace_ids(context)
                tid = tid or tracing.new_trace_id()
                sid = tracing.new_span_id()
                tags: dict = {}
                t0 = time.time()
                p0 = time.perf_counter()
            status = "ok"

            def faulted():
                # server-side stream chaos (rpc.handle): refuse at
                # dispatch AND cut established streams per message —
                # the shape a partitioned/crashed peer presents to a
                # long-lived metadata subscription
                key = f"{self.host}:{self.port}/{label}"
                if faults.ACTIVE:
                    p = faults.hit("rpc.handle", key)
                    if p is not None:
                        raise RpcError(f"injected fault #{p.rule_id}: "
                                       f"{p.mode} {label}")
                for item in fn(request_iterator):
                    if faults.ACTIVE:
                        p = faults.hit("rpc.handle", key)
                        if p is not None:
                            raise RpcError(
                                f"injected fault #{p.rule_id}: "
                                f"{p.mode} {label}")
                    yield item

            try:
                if not traced:
                    yield from faulted()
                    return
                with tracing.trace_scope(tid, sid, tags):
                    yield from faulted()
            except RpcError as e:
                status = "error"
                context.abort(grpc.StatusCode.UNKNOWN, str(e))
            except Exception as e:
                status = "error"
                context.abort(grpc.StatusCode.INTERNAL,
                              f"{type(e).__name__}: {e}")
            finally:
                # a stream's span lasts the connection (heartbeats and
                # metadata subscriptions live for hours) — its duration
                # is lifetime, not latency, so keep it out of the slow
                # log
                if traced:
                    self._record(label, tid, t0, p0, status,
                                 slow_log=False, span_id=sid,
                                 parent_id=parent, tags=tags)
        return h

    def start(self) -> int:
        target = f"{self.host}:{self._requested_port}"
        if _TLS is not None:
            self.port = self._server.add_secure_port(
                target, _server_credentials())
        else:
            self.port = self._server.add_insecure_port(target)
        self._server.start()
        return self.port

    def stop(self, grace: float = 0.2) -> None:
        self._server.stop(grace)

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"


class RpcClient:
    """Per-(address, service) client over a shared channel."""

    def __init__(self, address: str, service: str,
                 channel: grpc.Channel | None = None):
        self.address = address
        self.service = service
        if channel is None:
            options = [("grpc.max_receive_message_length", 256 << 20),
                       ("grpc.max_send_message_length", 256 << 20)]
            channel = grpc.secure_channel(
                address, _channel_credentials(), options=options) \
                if _TLS is not None \
                else grpc.insecure_channel(address, options=options)
        self._channel = channel

    def call(self, method: str, payload: dict | None = None,
             timeout: "float | None" = None) -> dict:
        """Unary call.  ``timeout=None`` takes the process default
        (WEED_RPC_TIMEOUT via util/retry.py) — per-attempt deadlines are
        policy, not per-call-site constants."""
        if timeout is None:
            timeout = default_rpc_timeout()
        if faults.ACTIVE:
            self._maybe_fault(method)
        fn = self._channel.unary_unary(
            f"/{self.service}/{method}",
            request_serializer=_ser, response_deserializer=_de)
        try:
            out = fn(payload or {}, timeout=timeout,
                     metadata=_trace_metadata())
        except grpc.RpcError as e:
            # boot-race grace: a channel that has NEVER connected and
            # reports UNAVAILABLE most likely dialed a peer that is
            # still binding its port (an S3 gateway racing its filer at
            # cluster start) — grpc then parks the subchannel in
            # reconnect backoff and every call fails fast for seconds.
            # Wait bounded for readiness and retry ONCE.  A channel
            # that connected even once skips this, so dead-server
            # failures keep failing fast everywhere else.
            if getattr(self._channel, "_weed_connected", False) \
                    or e.code() != grpc.StatusCode.UNAVAILABLE:
                raise RpcError(e.details() or str(e.code())) from None
            try:
                grpc.channel_ready_future(self._channel).result(
                    timeout=min(timeout, default_connect_timeout()))
            except grpc.FutureTimeoutError:
                raise RpcError(e.details() or str(e.code())) from None
            try:
                out = fn(payload or {}, timeout=timeout,
                         metadata=_trace_metadata())
            except grpc.RpcError as e2:
                raise RpcError(e2.details()
                               or str(e2.code())) from None
        self._channel._weed_connected = True
        return out

    def _maybe_fault(self, method: str) -> None:
        """Client-side rpc chaos (util/faults.py ``rpc.call``): 'drop'
        and 'error' surface as RpcError like a dead/refusing peer."""
        p = faults.hit("rpc.call",
                       f"{self.address}/{self.service}/{method}")
        if p is not None:
            raise RpcError(
                f"injected fault #{p.rule_id}: "
                f"{'dropped' if p.mode == 'drop' else 'error'} "
                f"{self.service}/{method} @ {self.address}")

    def stream(self, method: str, requests: Iterator[dict],
               timeout: float | None = None) -> Iterator[dict]:
        # streams honor the same rpc.call chaos rules as unary calls:
        # a partitioned peer refuses NEW subscriptions (checked at open)
        # and cuts ESTABLISHED ones (checked per received message) —
        # both halves matter for partition-tolerance tests, where a
        # long-lived SubscribeMetadata stream must actually die
        if faults.ACTIVE:
            self._maybe_fault(method)
        # responses arrive as bytes and are parsed here, on the consuming
        # thread (grpc would parse them on its channel thread), so the
        # consumer's span sees the wait and the parse apart
        fn = self._channel.stream_stream(
            f"/{self.service}/{method}",
            request_serializer=_ser, response_deserializer=None)
        try:
            responses = fn(requests, timeout=timeout,
                           metadata=_trace_metadata())
            while True:
                with tracing.stage("recv"):
                    raw = next(responses, None)
                if raw is None:
                    return
                if faults.ACTIVE:
                    self._maybe_fault(method)
                yield _de(raw)
        except grpc.RpcError as e:
            raise RpcError(e.details() or str(e.code())) from None

    def close(self) -> None:
        self._channel.close()


class GrpcConnectionPool:
    """Global channel cache, one per target address
    (pb/grpc_client_server.go connection cache)."""

    def __init__(self):
        self._channels: dict[str, grpc.Channel] = {}
        self._lock = threading.Lock()

    def client(self, address: str, service: str) -> RpcClient:
        with self._lock:
            ch = self._channels.get(address)
            if ch is None:
                options = [
                    ("grpc.max_receive_message_length", 256 << 20),
                    ("grpc.max_send_message_length", 256 << 20)]
                if _TLS is not None:
                    ch = grpc.secure_channel(
                        address, _channel_credentials(), options=options)
                else:
                    ch = grpc.insecure_channel(address, options=options)
                self._channels[address] = ch
        return RpcClient(address, service, ch)

    def close(self) -> None:
        with self._lock:
            for ch in self._channels.values():
                ch.close()
            self._channels.clear()


POOL = GrpcConnectionPool()
