"""HTTP plumbing for the public data path.

The reference serves its data plane over net/http muxes
(weed/server/*_handlers*.go).  Here: a lean persistent-connection
serving loop with a prefix router (handlers get a Request and return
Response) plus a shared keep-alive client pool — no external web
framework.

Server side: `HttpServer` owns its accept loop and parses requests with
a buffered reader per connection instead of BaseHTTPRequestHandler's
email-parser pipeline — on 1KB blobs the stdlib handler costs more than
the disk read.  Responses go out through ONE gather-write (sendmsg) of
prebuilt status/header bytes + body.

Client side: `http_request` rides a process-wide per-host connection
pool (bounded, keep-alive, stale-socket retry-once) so no hot path
opens a TCP connection per request.  `WEED_HTTP_POOL` caps connections
per host; when the pool is exhausted callers briefly block for a
returned connection and then overflow with a throwaway one, so bursts
degrade to the old behavior instead of deadlocking.
"""

from __future__ import annotations

import json
import os
import socket
import threading
from seaweedfs_tpu.util import locks
import time
import urllib.error
import urllib.parse
import urllib.request
from dataclasses import dataclass, field
from typing import Callable, Optional

from . import faults, tracing
from .weedlog import logger

LOG = logger(__name__)


class CIDict(dict):
    """Case-insensitive header map (HTTP header names are
    case-insensitive; aws-sdk-js sends lowercase names)."""

    def __init__(self, items=None):
        super().__init__()
        for k, v in dict(items or {}).items():
            self[k] = v

    def __setitem__(self, key, value):
        super().__setitem__(key.lower(), value)

    def __getitem__(self, key):
        return super().__getitem__(key.lower())

    def get(self, key, default=None):
        return super().get(key.lower(), default)

    def __contains__(self, key):
        return super().__contains__(key.lower())


@dataclass
class Request:
    method: str
    path: str            # path without query string
    query: dict[str, list[str]]
    headers: CIDict
    body: bytes
    remote_addr: str = ""  # client IP (audit logging)
    # streaming request body (routes registered with stream_body=True):
    # a BodyReader/ChunkedBodyReader over the connection instead of a
    # materialized `body`.  Handlers that don't understand streams call
    # materialize_body() and get exactly the old behavior.
    body_stream: "object | None" = None
    content_length: int = 0   # declared length; -1 = chunked/unknown
    # the route handler matched at parse time (serving loop only):
    # dispatch uses this instead of re-scanning the route table
    handler: "object | None" = None

    def qs(self, key: str, default: str = "") -> str:
        vals = self.query.get(key)
        return vals[0] if vals else default

    def materialize_body(self) -> bytes:
        """Buffer a streamed body fully (the pre-streaming behavior) —
        the escape hatch for handlers that need the whole payload
        (signed-body verification, XML parses)."""
        if self.body_stream is not None:
            self.body = self.body_stream.read_all()
            self.body_stream = None
        return self.body


class BodyReader:
    """Streaming request body with a declared Content-Length: read(n)
    pulls straight off the connection's buffered reader, so a handler
    consuming in chunk-size pieces keeps peak memory at O(piece), not
    O(body)."""

    def __init__(self, rf, length: int):
        self._rf = rf
        self.length = length
        self.consumed = 0

    @property
    def done(self) -> bool:
        return self.consumed >= self.length

    def read(self, n: int = -1) -> bytes:
        remaining = self.length - self.consumed
        if remaining <= 0:
            return b""
        want = remaining if n is None or n < 0 else min(n, remaining)
        piece = self._rf.read(want)
        if len(piece) < want:
            raise _BadRequest("truncated body")
        self.consumed += len(piece)
        return piece

    def read_all(self) -> bytes:
        return self.read(-1)  # weedlint: disable=WL130

    def drain(self, cap: int) -> bool:
        """Discard up to `cap` unread bytes; True when fully drained
        (keep-alive framing intact)."""
        while not self.done and cap > 0:
            piece = self.read(min(cap, 64 << 10))
            cap -= len(piece)
        return self.done


class ChunkedBodyReader:
    """Streaming Transfer-Encoding: chunked request body (same interface
    as BodyReader; length unknown).  read_all() keeps the historical
    64MB pre-dispatch cap — an unbounded chunk stream only passes
    through this reader when the handler consumes it incrementally."""

    MATERIALIZE_CAP = 64 << 20

    def __init__(self, rf):
        self._rf = rf
        self.length = -1
        self.consumed = 0
        self._chunk_left = 0
        self._eof = False

    @property
    def done(self) -> bool:
        return self._eof

    def _next_chunk(self) -> None:
        size_line = self._rf.readline(_MAX_LINE)
        if not size_line:
            raise _BadRequest("truncated chunked body")
        try:
            size = int(size_line.split(b";", 1)[0].strip(), 16)
        except ValueError:
            raise _BadRequest("bad chunk size") from None
        if size == 0:
            while True:     # drain trailers to the blank line
                t = self._rf.readline(_MAX_LINE)
                if t in (b"\r\n", b"\n", b""):
                    break
            self._eof = True
            return
        self._chunk_left = size

    def read(self, n: int = -1) -> bytes:
        out = bytearray()
        while not self._eof and (n < 0 or len(out) < n):
            if self._chunk_left == 0:
                self._next_chunk()
                if self._eof:
                    break
            want = self._chunk_left if n < 0 \
                else min(self._chunk_left, n - len(out))
            piece = self._rf.read(want)
            if len(piece) < want:
                raise _BadRequest("truncated chunk")
            out += piece
            self.consumed += len(piece)
            self._chunk_left -= len(piece)
            if self._chunk_left == 0:
                self._rf.read(2)  # trailing CRLF
        return bytes(out)

    def read_all(self) -> bytes:
        out = bytearray()
        while not self._eof:
            out += self.read(1 << 20)
            if len(out) > self.MATERIALIZE_CAP:
                raise _BadRequest("chunked body too large")
        return bytes(out)

    def drain(self, cap: int) -> bool:
        while not self._eof and cap > 0:
            cap -= len(self.read(min(cap, 64 << 10)))
        return self._eof


class StreamBody:
    """Streaming response body: an iterator of byte pieces plus the
    total length (the serving loop still advertises Content-Length —
    large-object GETs stream chunk by chunk instead of materializing
    the whole object in filer memory)."""

    __slots__ = ("it", "length")

    def __init__(self, it, length: int):
        self.it = it
        self.length = length


class FileRegion:
    """Zero-copy response body: `count` bytes at `offset` of file
    descriptor `fd`, sent with os.sendfile; `fallback` holds the same
    (already CRC-verified) bytes for paths where sendfile can't run.
    The region owns the (dup'ed) fd and closes it after the send."""

    __slots__ = ("fd", "offset", "count", "fallback")

    def __init__(self, fd: int, offset: int, count: int, fallback):
        self.fd = fd
        self.offset = offset
        self.count = count
        self.fallback = fallback

    def close(self) -> None:
        if self.fd >= 0:
            try:
                os.close(self.fd)
            except OSError:
                pass
            self.fd = -1


def parse_byte_range(spec: str, size: int) -> "tuple[int, int] | None":
    """One RFC 7233 byte-range spec ('a-b', 'a-', '-n') -> [start, stop)
    clamped to `size`, or None when unsatisfiable.  A multi-range list
    answers with its FIRST range (single-range semantics, the common-
    server behavior) — shared by the filer and volume read handlers so
    both ends of a ranged chunk fetch agree on the math."""
    if "," in spec:
        spec = spec.split(",", 1)[0].strip()
    try:
        first, _, last = spec.partition("-")
        if first == "":            # suffix form: last N bytes
            n = int(last)
            if n <= 0:
                return None
            return (max(0, size - n), size)
        start = int(first)
        stop = int(last) + 1 if last else size
    except ValueError:
        return None
    if start >= size or start < 0 or stop <= start:
        return None
    return (start, min(stop, size))


def _body_len(body) -> int:
    if isinstance(body, StreamBody):
        return body.length
    if isinstance(body, FileRegion):
        return body.count
    return len(body)


def _body_bytes(body) -> bytes:
    """Materialized view of any response-body shape (fault injection and
    other cold paths that must slice real bytes)."""
    if isinstance(body, StreamBody):
        return b"".join(bytes(p) for p in body.it)  # weedlint: disable=WL130
    if isinstance(body, FileRegion):
        return bytes(body.fallback)
    return bytes(body)


@dataclass
class Response:
    status: int = 200
    body: bytes = b""    # bytes/memoryview | StreamBody | FileRegion
    content_type: str = "application/octet-stream"
    headers: dict[str, str] = field(default_factory=dict)

    @classmethod
    def json(cls, obj, status: int = 200) -> "Response":
        return cls(status=status, body=json.dumps(obj).encode(),
                   content_type="application/json")

    @classmethod
    def error(cls, msg: str, status: int = 500) -> "Response":
        return cls.json({"error": msg}, status=status)


Handler = Callable[[Request], Response]


# -- fast response emit -----------------------------------------------------
# The data path prebuilds status lines and common header bytes, caches
# the Date header per second, and hands the socket ONE writev-style
# gather of status+headers+body (sendmsg), so a small read is a single
# syscall and a single packet.

_STATUS_LINES: dict[int, bytes] = {}
_SERVER_HDR = b"Server: seaweedfs-tpu\r\n"
_DATE_CACHE: tuple[int, bytes] = (0, b"")


def _status_line(code: int) -> bytes:
    line = _STATUS_LINES.get(code)
    if line is None:
        import http as _http
        try:
            phrase = _http.HTTPStatus(code).phrase
        except ValueError:
            phrase = ""
        line = _STATUS_LINES[code] = \
            f"HTTP/1.1 {code} {phrase}\r\n".encode("latin-1")
    return line


def _date_header() -> bytes:
    global _DATE_CACHE
    now = int(time.time())
    cached_at, hdr = _DATE_CACHE
    if cached_at != now:
        from email.utils import formatdate
        hdr = f"Date: {formatdate(now, usegmt=True)}\r\n".encode("latin-1")
        _DATE_CACHE = (now, hdr)
    return hdr


def _sendmsg_all(sock, parts: list) -> None:
    """Gather-write every buffer in `parts` (writev under the hood);
    falls back to sendall per part on partial sends or where sendmsg is
    unavailable."""
    total = sum(len(p) for p in parts)
    try:
        sent = sock.sendmsg(parts)
    except AttributeError:      # platform without sendmsg
        for p in parts:
            sock.sendall(p)
        return
    if sent >= total:
        return
    # rare partial gather: resume with sendall of each remainder
    for p in parts:
        if sent >= len(p):
            sent -= len(p)
            continue
        sock.sendall(memoryview(p)[sent:] if sent else p)
        sent = 0


def _trace_skip(path: str) -> bool:
    """Request paths whose spans would drown real traffic in the ring
    buffer (scrapers poll these): context still propagates, recording is
    skipped.  Exact match for the scrape endpoints — a filer user file
    like /metrics-archive/day.csv must still trace."""
    return path in ("/metrics", "/status") or path.startswith("/debug/")


_MAX_LINE = 65536          # request line / single header cap
_MAX_HEADERS = 128


class _BadRequest(Exception):
    pass


def _http_fastpath():
    """The C extension when the native HTTP serving loop should run:
    built, not killed (`WEED_FASTPATH_HTTP=0`, checked per connection so
    tests can flip it live), and new enough to carry the HTTP entry
    points — a stale prebuilt .so without them silently keeps the
    Python loop instead of crashing mid-accept."""
    if os.environ.get("WEED_FASTPATH_HTTP", "1") == "0":
        return None
    from seaweedfs_tpu import native
    fp = native.fastpath()
    if fp is not None and hasattr(fp, "http_read_request"):
        return fp
    return None


class _NativeReader:
    """BufferedReader shim over the C fastpath connection buffer:
    readline()/read() delegate to the extension, so the Python body
    readers (BodyReader/ChunkedBodyReader) framing through this object
    can never desync from the bytes the C parser has already
    buffered."""

    __slots__ = ("_fp", "_ctx")

    def __init__(self, fp, ctx):
        self._fp = fp
        self._ctx = ctx

    def readline(self, limit: int = -1) -> bytes:
        return self._fp.http_readline(self._ctx, limit)

    def read(self, n: int = -1) -> bytes:
        return self._fp.http_read(self._ctx, n)

    def close(self) -> None:
        pass  # the capsule owns the buffer; the socket owns the fd


class HttpServer:
    """Routes are (method, path_prefix) -> handler; longest prefix wins,
    and `exact=True` routes match only the full path (they sort ahead of
    an equal-length prefix).  A fallback handler (prefix "") catches
    file-id style paths.

    The serving loop is persistent-connection native: one thread per
    connection runs readline-parse -> dispatch -> gather-write until the
    peer closes (or sends Connection: close), so a pooled client's
    request costs no accept/handshake and pipelined requests drain
    back-to-back.  Every request runs inside a trace scope: the incoming
    `X-Trace-Id` header is adopted (minted when absent), echoed on the
    response, and propagated by the outgoing client helpers below.
    Attaching a `tracing.Tracer` to `.tracer` additionally records one
    span per request into that server's /debug/traces ring."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        # (method, prefix, handler, exact, stream_body)
        self.routes: list[tuple[str, str, Handler, bool, bool]] = []
        self.tracer: "tracing.Tracer | None" = None
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        # the old BaseServer backlog of 5 reset connections under modest
        # burst concurrency (40 parallel uploads)
        self._sock.listen(128)
        self.host = host
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # additional listening sockets sharing this route table — the
        # process-sharded volume workers serve the SAME handlers on the
        # cluster-shared SO_REUSEPORT socket and their private port
        self._extra_socks: list[socket.socket] = []
        # live connections, closed on stop() so clients holding pooled
        # keep-alive sockets see a real FIN instead of a dead peer
        self._conns: set[socket.socket] = set()
        self._conns_lock = locks.Lock("HttpServer._conns_lock")
        # combined parse -> route -> serve hook for the native loop:
        # when set, called as fast_lane(method, target, headers, remote)
        # for body-less GET/HEAD requests before the generic parse +
        # dispatch; returning None falls through to the normal path.
        # The volume server installs its hot-GET needle lane here.
        self.fast_lane: "Callable[[str, str, CIDict, str], Response | None] | None" = None

    def route(self, method: str, prefix: str, handler: Handler,
              exact: bool = False, stream_body: bool = False) -> None:
        """stream_body=True: matched requests get their body as a
        Request.body_stream reader instead of a materialized buffer —
        the handler owns consumption (streaming uploads)."""
        self.routes.append((method, prefix, handler, exact, stream_body))
        self.routes.sort(key=lambda r: (len(r[1]), r[3]), reverse=True)

    def _match(self, method: str, path: str
               ) -> "tuple[Optional[Handler], bool]":
        """-> (handler, stream_body) — ONE matcher for both the
        handler lookup and the body-streaming decision, so the two can
        never route to different entries."""
        for m, prefix, h, exact, stream in self.routes:
            if m not in (method, "*"):
                continue
            if path == prefix if exact else path.startswith(prefix):
                return h, stream
        return None, False

    def start(self) -> int:
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name="http-accept")
        self._thread.start()
        return self.port

    def add_listener(self, sock: socket.socket) -> None:
        """Serve this route table on an ALREADY bound+listening socket
        too (a second accept loop).  The caller owns binding policy —
        this is how a volume worker joins the cluster-shared
        SO_REUSEPORT data port next to its private one."""
        self._extra_socks.append(sock)
        threading.Thread(target=self._accept_loop, args=(sock,),
                         daemon=True, name="http-accept-extra").start()

    def serve_socket(self, conn: socket.socket, addr=None) -> None:
        """Adopt an externally-accepted connection into the serving loop
        (the accept-and-pass worker fallback: the supervisor accepts on
        the shared port and hands connected fds to workers over
        socket.send_fds)."""
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError as e:
            LOG.debug("nodelay on adopted socket failed: %s", e)
        with self._conns_lock:
            self._conns.add(conn)
        threading.Thread(target=self._serve_conn,
                         args=(conn, addr or ("", 0)),
                         daemon=True).start()

    def stop(self) -> None:
        self._stop.set()
        # shutdown() BEFORE close(): a thread blocked in accept()/recv()
        # holds a reference to the open file description, so close()
        # alone neither wakes it nor releases the port — shutdown wakes
        # the blocked syscall and flushes a FIN to keep-alive peers
        for s in [self._sock] + self._extra_socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        with self._conns_lock:
            conns = list(self._conns)
            self._conns.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    # -- accept / serve loops ----------------------------------------------
    def _accept_loop(self, sock: "socket.socket | None" = None) -> None:
        from .retry import RetryPolicy
        listener = sock if sock is not None else self._sock
        backoff = RetryPolicy(base_delay=0.05, max_delay=1.0)
        failures = 0
        while not self._stop.is_set():
            try:
                conn, addr = listener.accept()
                failures = 0
            except OSError as e:
                if self._stop.is_set():
                    return
                # transient accept failures (ECONNABORTED mid-handshake,
                # EMFILE under fd pressure) must not kill the listener —
                # the old ThreadingHTTPServer survived these too.  Only
                # a closed listening socket (EBADF/EINVAL) is terminal.
                import errno
                if e.errno in (errno.EBADF, errno.EINVAL):
                    return
                failures += 1
                LOG.warning("accept failed (%d consecutive): %s",
                            failures, e)
                # jittered, growing pause: under EMFILE a tight retry
                # burns the CPU the serving threads need to free fds
                time.sleep(backoff.backoff(min(failures, 6)))
                continue
            # Nagle + delayed-ACK adds a uniform ~40ms to every
            # request/response exchange; the data path cannot afford it
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._conns_lock:
                self._conns.add(conn)
            threading.Thread(target=self._serve_conn, args=(conn, addr),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket, addr) -> None:
        """Per-connection entry: the native C loop when the fastpath
        extension carries the HTTP entry points (kill switch:
        WEED_FASTPATH_HTTP=0), else the pure-Python loop.  Both produce
        byte-identical responses — pinned by tests/test_http_native.py."""
        fp = _http_fastpath()
        if fp is not None:
            self._serve_conn_native(conn, addr, fp)
        else:
            self._serve_conn_py(conn, addr)

    def _serve_conn_py(self, conn: socket.socket, addr) -> None:
        rf = conn.makefile("rb", buffering=64 << 10)
        try:
            while not self._stop.is_set():
                try:
                    req, close = self._read_request(rf, conn, addr)
                except _BadRequest as e:
                    self._emit(conn, "GET",
                               Response.error(str(e) or "bad request", 400),
                               close=True)
                    return
                if req is None:       # clean EOF between requests
                    return
                resp = self._dispatch(req)
                unread = req.body_stream is not None \
                    and not req.body_stream.done
                if unread:
                    # handler answered without consuming the streamed
                    # body (early error): cheaply complete the framing
                    # so keep-alive survives, else close after replying
                    try:
                        unread = not req.body_stream.drain(1 << 20)
                    except (_BadRequest, OSError, ConnectionError):
                        unread = True
                    if unread:
                        close = True
                try:
                    if faults.ACTIVE and self._serve_fault(conn, req,
                                                           resp):
                        return        # injected mid-body reset
                    try:
                        self._emit(conn, req.method, resp, close=close)
                    except (BrokenPipeError, ConnectionResetError,
                            OSError):
                        return
                finally:
                    if isinstance(resp.body, FileRegion):
                        resp.body.close()
                if unread:
                    # the client may still be mid-send: flush a FIN and
                    # drain a bounded slice of the abandoned body so the
                    # queued response isn't RST away (same discipline as
                    # _reply_error_and_drain on the frame path)
                    try:
                        conn.shutdown(socket.SHUT_WR)
                        conn.settimeout(1.0)  # weedlint: disable=WL060
                        drained = 0
                        while drained < (8 << 20):
                            piece = conn.recv(64 << 10)
                            if not piece:
                                break
                            drained += len(piece)
                    except OSError:
                        pass
                    return
                if close:
                    return
                # keep-alive: drop request/response refs before parking
                # in readline — an idle conn must not pin a multi-MB
                # body until the peer's next request
                req = resp = None  # noqa: F841
        except (ConnectionError, OSError, ValueError):
            pass
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                rf.close()
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass

    def _serve_conn_native(self, conn: socket.socket, addr, fp) -> None:
        """The C serving loop: one fp.http_read_request call per request
        head, one fp.http_write_response per response, with the GIL
        released around every recv/send.  Control flow mirrors
        _serve_conn_py exactly — same dispatch, faults gate, unread-body
        drain, and teardown — and chunked/streamed bodies ride the
        Python readers over _NativeReader, so StreamBody/FileRegion/
        sendfile serving is untouched."""
        ctx = fp.conn_new(conn.fileno())
        rf = _NativeReader(fp, ctx)
        remote = addr[0] if addr else ""
        try:
            while not self._stop.is_set():
                try:
                    tup = fp.http_read_request(ctx, CIDict, _MAX_LINE,
                                               _MAX_HEADERS)
                except ValueError as e:
                    # the C parser raises _BadRequest's exact messages
                    self._emit_native(
                        fp, ctx, conn, "GET",
                        Response.error(str(e) or "bad request", 400),
                        close=True)
                    return
                if tup is None:       # clean EOF between requests
                    return
                method, target, version, headers = tup
                # combined parse -> route -> serve fast lane (volume hot
                # GETs): body-less, no Expect handshake, no fault plans
                # pending — anything else takes the generic path below
                fl = self.fast_lane
                if (fl is not None and not faults.ACTIVE
                        and method in ("GET", "HEAD")
                        and "content-length" not in headers
                        and "transfer-encoding" not in headers
                        and "expect" not in headers):
                    resp = fl(method, target, headers, remote)
                    if resp is not None:
                        close = self._should_close(version, headers)
                        try:
                            try:
                                self._emit_native(fp, ctx, conn, method,
                                                  resp, close)
                            except (BrokenPipeError,
                                    ConnectionResetError, OSError):
                                return
                        finally:
                            if isinstance(resp.body, FileRegion):
                                resp.body.close()
                        if close:
                            return
                        resp = None  # noqa: F841
                        continue
                try:
                    req, close = self._finish_request_native(
                        fp, ctx, rf, conn, addr, method, target, version,
                        headers)
                except _BadRequest as e:
                    self._emit_native(
                        fp, ctx, conn, "GET",
                        Response.error(str(e) or "bad request", 400),
                        close=True)
                    return
                resp = self._dispatch(req)
                unread = req.body_stream is not None \
                    and not req.body_stream.done
                if unread:
                    try:
                        unread = not req.body_stream.drain(1 << 20)
                    except (_BadRequest, OSError, ConnectionError):
                        unread = True
                    if unread:
                        close = True
                try:
                    if faults.ACTIVE and self._serve_fault(conn, req,
                                                           resp):
                        return        # injected mid-body reset
                    try:
                        self._emit_native(fp, ctx, conn, req.method,
                                          resp, close)
                    except (BrokenPipeError, ConnectionResetError,
                            OSError):
                        return
                finally:
                    if isinstance(resp.body, FileRegion):
                        resp.body.close()
                if unread:
                    # same FIN + bounded-drain discipline as the Python
                    # loop (see _serve_conn_py)
                    try:
                        conn.shutdown(socket.SHUT_WR)
                        conn.settimeout(1.0)  # weedlint: disable=WL060
                        drained = 0
                        while drained < (8 << 20):
                            piece = conn.recv(64 << 10)
                            if not piece:
                                break
                            drained += len(piece)
                    except OSError:
                        pass
                    return
                if close:
                    return
                # keep-alive: drop refs before parking in the C recv
                req = resp = None  # noqa: F841
        except (ConnectionError, OSError, ValueError):
            pass
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _finish_request_native(self, fp, ctx, rf, conn, addr, method,
                               target, version, headers
                               ) -> "tuple[Request, bool]":
        """Body framing + Request construction for a C-parsed head —
        the second half of _read_request, sharing its exact semantics
        (Expect handshake, route match, chunked/stream readers)."""
        if headers.get("Expect", "").lower() == "100-continue":
            conn.sendall(b"HTTP/1.1 100 Continue\r\n\r\n")
        if (target.startswith("/") and not target.startswith("//")
                and "?" not in target and "#" not in target):
            # urlsplit is pure overhead here: a rootful target with no
            # query and no fragment IS the path (urlsplit can't find a
            # scheme or netloc in it, and parse_qs("") is {}) — pinned
            # against urlsplit by the parity corpus
            path: str = target
            query: dict[str, list[str]] = {}
        else:
            parsed = urllib.parse.urlsplit(target)
            path = parsed.path
            query = urllib.parse.parse_qs(parsed.query,
                                          keep_blank_values=True)
        handler, streams = self._match(method, path)
        body = b""
        body_stream = None
        content_length = 0
        te = headers.get("Transfer-Encoding", "").lower()
        if "chunked" in te:
            content_length = -1
            if streams:
                body_stream = ChunkedBodyReader(rf)
            else:
                body = self._read_chunked(rf)
        else:
            try:
                length = int(headers.get("Content-Length") or 0)
            except ValueError:
                raise _BadRequest("bad Content-Length") from None
            content_length = length
            if length:
                if streams:
                    body_stream = BodyReader(rf, length)
                elif length > 0:
                    try:
                        body = fp.http_read_body(ctx, length)
                    except ValueError:
                        raise _BadRequest("truncated body") from None
                else:
                    # negative Content-Length reads to EOF, matching
                    # BufferedReader.read(negative) in the Python loop
                    body = rf.read(length)
        req = Request(
            method=method, path=path, query=query,
            headers=headers, body=body, remote_addr=addr[0],
            body_stream=body_stream, content_length=content_length,
            handler=handler)
        return req, self._should_close(version, headers)

    @classmethod
    def _emit_native(cls, fp, ctx, conn, method: str, resp: Response,
                     close: bool) -> None:
        """_emit's native twin: the SAME _build_head bytes (parity by
        construction) pushed through one gathered writev; streaming
        shapes delegate to the shared region/stream emitters."""
        head = cls._build_head(resp, close)
        body = resp.body
        if method == "HEAD" or not _body_len(body):
            fp.http_write_response(ctx, head, b"")
            return
        if isinstance(body, FileRegion):
            cls._emit_region(conn, head, body)
            return
        if isinstance(body, StreamBody):
            cls._emit_stream(conn, head, body)
            return
        fp.http_write_response(ctx, head, body)

    def _read_request(self, rf, conn, addr
                      ) -> "tuple[Request | None, bool]":
        """Parse one request off the buffered reader -> (request,
        connection-should-close).  None on clean EOF."""
        line = rf.readline(_MAX_LINE + 2)
        if not line:
            return None, True
        if line in (b"\r\n", b"\n"):
            # stray CRLF between pipelined requests (RFC 7230 §3.5)
            line = rf.readline(_MAX_LINE + 2)
            if not line:
                return None, True
        if len(line) > _MAX_LINE:
            raise _BadRequest("request line too long")
        try:
            method_b, target_b, version_b = line.split(None, 2)
            version = version_b.strip()
        except ValueError:
            raise _BadRequest("malformed request line") from None
        headers = CIDict()
        # +1: the loop also consumes the blank terminator line, so a
        # request with exactly _MAX_HEADERS headers must get one extra
        # iteration to reach its break
        for _ in range(_MAX_HEADERS + 1):
            h = rf.readline(_MAX_LINE + 2)
            if h in (b"\r\n", b"\n", b""):
                break
            if len(h) > _MAX_LINE:
                raise _BadRequest("header line too long")
            k, sep, v = h.partition(b":")
            if not sep:
                raise _BadRequest("malformed header")
            # bytes-level strip for the NAME too (it used to be
            # str.strip after decode, which also ate unicode whitespace
            # like latin-1 0x85/0xA0 — the C parser strips ASCII
            # whitespace only, and the two must agree byte for byte)
            headers[k.strip().decode("latin-1")] = \
                v.strip().decode("latin-1")
        else:
            raise _BadRequest("too many headers")
        if headers.get("Expect", "").lower() == "100-continue":
            conn.sendall(b"HTTP/1.1 100 Continue\r\n\r\n")
        target = target_b.decode("latin-1")
        parsed = urllib.parse.urlsplit(target)
        method = method_b.decode("latin-1")
        # streaming routes take their body as a reader; everything else
        # keeps the historical buffer-before-dispatch behavior.  The
        # matched handler rides on the request so dispatch never
        # re-scans (or diverges from) the route table.
        handler, streams = self._match(method, parsed.path)
        body = b""
        body_stream = None
        content_length = 0
        te = headers.get("Transfer-Encoding", "").lower()
        if "chunked" in te:
            content_length = -1
            if streams:
                body_stream = ChunkedBodyReader(rf)
            else:
                body = self._read_chunked(rf)
        else:
            try:
                length = int(headers.get("Content-Length") or 0)
            except ValueError:
                raise _BadRequest("bad Content-Length") from None
            content_length = length
            if length:
                if streams:
                    body_stream = BodyReader(rf, length)
                else:
                    body = rf.read(length)
                    if len(body) < length:
                        raise _BadRequest("truncated body")
        req = Request(
            method=method, path=parsed.path,
            query=urllib.parse.parse_qs(parsed.query,
                                        keep_blank_values=True),
            headers=headers, body=body, remote_addr=addr[0],
            body_stream=body_stream, content_length=content_length,
            handler=handler)
        return req, self._should_close(version, headers)

    @staticmethod
    def _should_close(version: bytes, headers: CIDict) -> bool:
        """Keep-alive decision, shared by the Python and native loops."""
        conn_hdr = headers.get("Connection", "").lower()
        return (conn_hdr == "close"
                or (version == b"HTTP/1.0" and conn_hdr != "keep-alive"))

    @staticmethod
    def _read_chunked(rf) -> bytes:
        """Chunked request body (aws CLI streams uploads this way),
        capped at ChunkedBodyReader.MATERIALIZE_CAP like the TCP frame
        path's MAX_FRAME_BODY — an unbounded chunk stream must not be
        able to OOM the server pre-dispatch.  ONE decoder serves both
        the buffered and the streamed paths."""
        return ChunkedBodyReader(rf).read_all()

    def _dispatch(self, req: Request) -> Response:
        handler = req.handler
        if not tracing.enabled():
            # WEED_TRACE=0: no minting, no scope, no span — the
            # uninstrumented baseline the bench prices tracing against
            if handler is None:
                return Response.error("not found", 404)
            try:
                return handler(req)
            except _BadRequest as e:
                # a streamed body failing mid-handler (client hung up,
                # oversized chunked frame) is the CLIENT's fault: answer
                # 400 like the parse-time reads always did, never a
                # budget-burning 500
                return Response.error(str(e) or "bad request", 400)
            except Exception as e:
                return Response.error(f"{type(e).__name__}: {e}")
        t0 = time.time()            # span start: wall, for alignment
        p0 = time.perf_counter()    # span duration: monotonic (WL120)
        # clamp both ids: they are client-controlled and ride internal
        # protocols with bounded slots (the TCP frame trace slot is a
        # u8 length)
        tid = tracing.clamp_id(req.headers.get(tracing.TRACE_HEADER,
                                               "")) \
            or tracing.new_trace_id()
        # the caller's span id arrives as X-Span-Id and becomes this
        # request span's parent; our own span id is the ambient parent
        # for every downstream hop made while serving it
        parent = tracing.clamp_id(req.headers.get(tracing.SPAN_HEADER,
                                                  ""))
        sid = tracing.new_span_id()
        tags: dict = {}
        with tracing.trace_scope(tid, sid, tags):
            if handler is None:
                resp = Response.error("not found", 404)
            else:
                try:
                    resp = handler(req)
                except _BadRequest as e:
                    # client-side streamed-body failure: 400, not 500
                    # (see the untraced branch above)
                    resp = Response.error(str(e) or "bad request", 400)
                except Exception as e:
                    resp = Response.error(f"{type(e).__name__}: {e}")
        resp.headers.setdefault(tracing.TRACE_HEADER, tid)
        tracer = self.tracer
        if tracer is not None and not _trace_skip(req.path):
            tracer.record(f"{req.method} {req.path}", tid,
                          t0, time.perf_counter() - p0,
                          status=("ok" if resp.status < 400
                                  else f"http {resp.status}"),
                          span_id=sid, parent_id=parent,
                          **tracing.copy_tags(tags))
        return resp

    def _serve_fault(self, conn, req: Request, resp: Response) -> bool:
        """Serve-side chaos (util/faults.py ``http.serve``): a 'reset'
        plan advertises the full Content-Length, sends half the body and
        slams the connection — the torn-response shape clients must
        survive.  Returns True when the connection was killed."""
        p = faults.hit("http.serve", f"{self.host}:{self.port} {req.path}")
        if p is None or p.mode != "reset":
            return False
        head = self._build_head(resp, close=True)
        body = _body_bytes(resp.body)   # streamed shapes materialize here
        try:
            conn.sendall(bytes(head) + body[:len(body) // 2])
            conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        return True

    @staticmethod
    def _build_head(resp: Response, close: bool) -> bytearray:
        head = bytearray(_status_line(resp.status))
        head += _SERVER_HDR
        head += _date_header()
        head += b"Content-Type: "
        head += resp.content_type.encode("latin-1")
        head += b"\r\n"
        # a handler may override Content-Length (HEAD replies advertise
        # the real size with an empty body)
        explicit_cl = resp.headers.pop("Content-Length", None)
        head += b"Content-Length: "
        head += (explicit_cl
                 or str(_body_len(resp.body))).encode("latin-1")
        head += b"\r\n"
        for k, v in resp.headers.items():
            head += f"{k}: {v}\r\n".encode("latin-1")
        if close:
            head += b"Connection: close\r\n"
        head += b"\r\n"
        return head

    @classmethod
    def _emit(cls, conn, method: str, resp: Response, close: bool) -> None:
        """Prebuilt status line + cached Date + ONE gather-write of head
        and body (see _sendmsg_all).  Streaming shapes send the head
        first, then the pieces / the sendfile'd file region."""
        head = cls._build_head(resp, close)
        body = resp.body
        if method == "HEAD" or not _body_len(body):
            conn.sendall(bytes(head))
            return
        if isinstance(body, FileRegion):
            cls._emit_region(conn, head, body)
            return
        if isinstance(body, StreamBody):
            cls._emit_stream(conn, head, body)
            return
        _sendmsg_all(conn, [bytes(head), body])

    @staticmethod
    def _emit_region(conn, head: bytearray, region: FileRegion) -> None:
        """Zero-copy: os.sendfile straight from the (dup'ed) volume fd
        to the socket.  Any sendfile failure resumes from the verified
        in-memory fallback at the exact byte it stopped at — the client
        always sees the advertised Content-Length or a hard close."""
        conn.sendall(bytes(head))
        sent = 0
        if region.fd >= 0 and hasattr(os, "sendfile"):
            try:
                while sent < region.count:
                    n = os.sendfile(conn.fileno(), region.fd,
                                    region.offset + sent,
                                    region.count - sent)
                    if n == 0:
                        break
                    sent += n
            except OSError as e:
                import errno
                if e.errno in (errno.EPIPE, errno.ECONNRESET):
                    raise    # peer is gone; nothing to resume
                LOG.debug("sendfile failed at +%d/%d, resuming from "
                          "memory: %s", sent, region.count, e)
        if sent < region.count:
            conn.sendall(memoryview(region.fallback)[sent:])

    @staticmethod
    def _emit_stream(conn, head: bytearray, body: StreamBody) -> None:
        conn.sendall(bytes(head))
        sent = 0
        try:
            for piece in body.it:
                if piece:
                    conn.sendall(piece)
                    sent += len(piece)
        except (OSError, ConnectionError):
            raise
        except Exception as e:
            # producer failure mid-body: the head (with Content-Length)
            # is already on the wire, so the only honest move is a hard
            # close — the client sees a truncated body, never garbage
            LOG.warning("streaming body failed after %d/%d bytes: %s",
                        sent, body.length, e)
            raise ConnectionError(
                f"stream body aborted mid-send: {e}") from e
        if sent != body.length:
            raise ConnectionError(
                f"stream body produced {sent} of {body.length} bytes")


# -- client helpers ---------------------------------------------------------

def _pool_size_default() -> int:
    try:
        return max(1, int(os.environ.get("WEED_HTTP_POOL", "8")))
    except ValueError:
        return 8


def _pool_wait_default() -> float:
    try:
        return float(os.environ.get("WEED_HTTP_POOL_WAIT", "0.5"))
    except ValueError:
        return 0.5


class _Conn(object):
    """One pooled keep-alive connection (http.client under the hood)."""

    __slots__ = ("hc", "overflow")

    def __init__(self, host: str, port: int, timeout: float):
        import http.client

        class _NodelayConn(http.client.HTTPConnection):
            def connect(self):
                super().connect()
                self.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)

        self.hc = _NodelayConn(host, port, timeout=timeout)
        self.overflow = False

    def set_timeout(self, timeout: float) -> None:
        self.hc.timeout = timeout
        if self.hc.sock is not None:
            self.hc.sock.settimeout(timeout)

    def close(self) -> None:
        try:
            self.hc.close()
        except OSError:
            pass


class ConnectionPool:
    """Process-wide bounded keep-alive pools, one per (host, port).

    urllib opens a fresh TCP connection per request; on the small-file
    hot path (the reference's 15.7k req/s benchmark) connection setup
    dominates.  The pool is SHARED across threads — the previous
    thread-local design held one socket per (thread, host), so a
    100-thread server fanning out to one replica kept 100 upstream
    sockets.  Here at most `size` connections exist per host; an
    exhausted pool blocks briefly for a returned connection, then
    overflows with a throwaway connection (closed on release) so bursts
    degrade gracefully instead of deadlocking.

    Stats (created/reused/overflow) let benchmarks assert the no-churn
    property: a 1k-write run opens O(pool size) upstream connections.
    """

    def __init__(self, size: "int | None" = None,
                 wait: "float | None" = None):
        self.size = size if size is not None else _pool_size_default()
        self.wait = wait if wait is not None else _pool_wait_default()
        self._lock = locks.Lock("ConnectionPool._lock")
        self._cv = locks.Condition(self._lock, name="ConnectionPool._cv")
        self._idle: dict[tuple, list[_Conn]] = {}
        self._in_use: dict[tuple, int] = {}
        self.stats = {"created": 0, "reused": 0, "overflow": 0,
                      "waited": 0}

    # -- checkout / checkin ------------------------------------------------
    def _acquire(self, key: tuple, timeout: float,
                 fresh: bool = False,
                 no_reuse: bool = False) -> tuple[_Conn, bool]:
        """-> (conn, reused).  Blocks up to `self.wait` when the host is
        at capacity, then overflows.  `fresh=True` skips the idle stack
        — the stale-socket retry must get a genuinely NEW connection,
        not the next idle socket that may be just as stale (every idle
        conn to a restarted peer is).  `no_reuse=True` also skips the
        idle stack but leaves it intact: a non-seekable streamed body
        must never ride a reused socket whose staleness would force an
        (impossible) resend."""
        host, port = key
        deadline = None
        with self._cv:
            if fresh:
                # the sibling idle conns are suspect for the same
                # reason the failed one was: drop them now instead of
                # failing one request per stale socket
                for conn in self._idle.pop(key, []):
                    conn.close()
            while True:
                idle = None if no_reuse else self._idle.get(key)
                if idle:
                    conn = idle.pop()
                    self._in_use[key] = self._in_use.get(key, 0) + 1
                    self.stats["reused"] += 1
                    return conn, True
                if self._in_use.get(key, 0) < self.size:
                    self._in_use[key] = self._in_use.get(key, 0) + 1
                    self.stats["created"] += 1
                    break   # create outside the lock
                if deadline is None:
                    deadline = time.time() + self.wait
                    self.stats["waited"] += 1
                remaining = deadline - time.time()
                if remaining <= 0:
                    # overflow: a throwaway connection, not counted
                    # against the pool and closed on release
                    self.stats["overflow"] += 1
                    conn = _Conn(host, port, timeout)
                    conn.overflow = True
                    return conn, False
                self._cv.wait(remaining)
        return _Conn(host, port, timeout), False

    def _release(self, key: tuple, conn: _Conn, discard: bool) -> None:
        if conn.overflow:
            conn.close()
            return
        with self._cv:
            self._in_use[key] = max(0, self._in_use.get(key, 0) - 1)
            if not discard:
                self._idle.setdefault(key, []).append(conn)
            self._cv.notify()
        if discard:
            conn.close()

    def idle_count(self, host: str, port: int) -> int:
        with self._lock:
            return len(self._idle.get((host, port), []))

    def close_idle(self) -> None:
        with self._cv:
            idle, self._idle = self._idle, {}
        for conns in idle.values():
            for c in conns:
                c.close()

    # -- request -----------------------------------------------------------
    def request(self, url: str, method: str, body, headers: dict,
                timeout: float, follow_redirects: int = 3
                ) -> tuple[int, bytes, dict]:
        import http.client

        parsed = urllib.parse.urlsplit(url)
        if parsed.scheme == "https":
            raise NotImplementedError(
                "https is not supported by the pooled client; terminate "
                "TLS in front (the reference uses mTLS on gRPC, plain "
                "HTTP on the data path)")
        key = (parsed.hostname, parsed.port)
        if faults.ACTIVE:
            # client-side chaos: connect refusal / reset surface as the
            # REAL exception types so callers' failover paths run
            # organically (faults.py)
            p = faults.hit("http.request",
                           f"{parsed.hostname}:{parsed.port}")
            if p is not None:
                if p.mode == "refuse":
                    raise ConnectionRefusedError(
                        f"injected fault #{p.rule_id}: connect refused "
                        f"{parsed.netloc}")
                raise ConnectionResetError(
                    f"injected fault #{p.rule_id}: reset by "
                    f"{parsed.netloc}")
        path = parsed.path + (f"?{parsed.query}" if parsed.query else "")
        # a file-like body that can't rewind must go out on a socket
        # that can't be stale: skip idle reuse so a send failure is a
        # REAL failure (raised), never a silent half-consumed resend
        one_shot_body = hasattr(body, "read") \
            and not hasattr(body, "seek")
        for attempt in (0, 1):
            conn, reused = self._acquire(key, timeout,
                                         fresh=attempt == 1,
                                         no_reuse=one_shot_body)
            conn.set_timeout(timeout)
            try:
                if attempt and hasattr(body, "seek"):
                    body.seek(0)  # streamed file body: rewind for resend
                conn.hc.request(method, path, body=body, headers=headers)
                resp = conn.hc.getresponse()
                data = resp.read()
            except (http.client.HTTPException, ConnectionError, OSError):
                self._release(key, conn, discard=True)
                # retry ONLY a reused keep-alive socket that may simply
                # have gone stale; a fresh connection's failure (refused,
                # timeout) is real — re-sending could double-apply a POST
                if attempt or not reused:
                    raise
                continue
            except BaseException:
                # anything else (bad header ValueError, a streaming body
                # raising mid-send, KeyboardInterrupt) must still give
                # the slot back or the host pool pins at capacity with
                # zero requests in flight
                self._release(key, conn, discard=True)
                raise
            # one-shot-body conns never COME from the idle stack, so
            # returning them there would grow it one socket per
            # streamed upload, unbounded — close instead
            discard = bool(resp.will_close) or one_shot_body
            self._release(key, conn, discard=discard)
            resp_headers = dict(resp.getheaders())
            if resp.status in (301, 302, 307, 308) and follow_redirects \
                    and method in ("GET", "HEAD"):
                # only safe methods auto-follow: replaying a POST body at
                # a redirect target could turn a misrouted read into a
                # duplicate write
                loc = resp_headers.get("Location", "")
                if loc:
                    if loc.startswith("/"):
                        loc = f"http://{parsed.netloc}{loc}"
                    return self.request(loc, method, body, headers,
                                        timeout, follow_redirects - 1)
            return resp.status, data, resp_headers
        raise OSError("unreachable")

    def request_stream(self, url: str, method: str, headers: dict,
                       timeout: float, chunk: int = 1 << 16
                       ) -> tuple[int, object, dict]:
        """GET/HEAD whose 2xx body comes back as a chunk ITERATOR
        instead of one buffered bytes — the proxy hop of a gateway
        (S3 object GET -> filer) must not double-buffer what both ends
        already stream.  The pooled connection stays checked out until
        the iterator is exhausted (returned to the pool) or closed
        early (discarded — a half-read keep-alive socket would poison
        the next request).  Non-2xx and bodyless responses are
        materialized and behave exactly like request()."""
        import http.client

        parsed = urllib.parse.urlsplit(url)
        if parsed.scheme == "https":
            raise NotImplementedError(
                "https is not supported by the pooled client")
        key = (parsed.hostname, parsed.port)
        if faults.ACTIVE:
            p = faults.hit("http.request",
                           f"{parsed.hostname}:{parsed.port}")
            if p is not None:
                if p.mode == "refuse":
                    raise ConnectionRefusedError(
                        f"injected fault #{p.rule_id}: connect refused "
                        f"{parsed.netloc}")
                raise ConnectionResetError(
                    f"injected fault #{p.rule_id}: reset by "
                    f"{parsed.netloc}")
        path = parsed.path + (f"?{parsed.query}" if parsed.query else "")
        for attempt in (0, 1):
            conn, reused = self._acquire(key, timeout,
                                         fresh=attempt == 1)
            conn.set_timeout(timeout)
            try:
                conn.hc.request(method, path, headers=headers)
                resp = conn.hc.getresponse()
            except (http.client.HTTPException, ConnectionError, OSError):
                self._release(key, conn, discard=True)
                if attempt or not reused:
                    raise   # same stale-keep-alive retry as request()
                continue
            except BaseException:
                self._release(key, conn, discard=True)
                raise
            resp_headers = dict(resp.getheaders())
            if not (200 <= resp.status < 300) or method == "HEAD":
                # error/redirect bodies are small XML/JSON: buffer them
                # so every existing error path keeps working on bytes
                try:
                    data = resp.read()
                except (http.client.HTTPException, ConnectionError,
                        OSError):
                    self._release(key, conn, discard=True)
                    raise
                self._release(key, conn,
                              discard=bool(resp.will_close))
                if resp.status in (301, 302, 307, 308) \
                        and method in ("GET", "HEAD"):
                    loc = resp_headers.get("Location", "")
                    if loc:
                        if loc.startswith("/"):
                            loc = f"http://{parsed.netloc}{loc}"
                        return self.request_stream(loc, method, headers,
                                                   timeout, chunk)
                return resp.status, data, resp_headers

            def body_iter(conn=conn, resp=resp, key=key):
                done = False
                try:
                    while True:
                        piece = resp.read(chunk)
                        if not piece:
                            done = True
                            return
                        yield piece
                except (http.client.HTTPException, ConnectionError,
                        OSError):
                    raise
                finally:
                    # exhausted cleanly -> back to the idle stack;
                    # abandoned/error -> the socket still carries
                    # unread body bytes and must not be reused
                    self._release(
                        key, conn,
                        discard=not done or bool(resp.will_close))

            return resp.status, body_iter(), resp_headers
        raise OSError("unreachable")


_POOL = ConnectionPool()


def connection_pool() -> ConnectionPool:
    """The process-wide client pool (benchmarks read .stats off it)."""
    return _POOL


def reset_connection_pool(size: "int | None" = None,
                          wait: "float | None" = None) -> ConnectionPool:
    """Swap in a fresh pool (tests; picks up env knobs again)."""
    global _POOL
    old = _POOL
    _POOL = ConnectionPool(size=size, wait=wait)
    old.close_idle()
    return _POOL


def http_request(url: str, method: str = "GET", body: bytes | None = None,
                 headers: dict | None = None,
                 timeout: "float | None" = None
                 ) -> tuple[int, bytes, dict]:
    """-> (status, body, headers); non-2xx does NOT raise.  Keep-alive
    pooled per host (bounded by WEED_HTTP_POOL).  Propagates the ambient
    trace id (X-Trace-Id) so multi-hop requests correlate across
    servers.  ``timeout=None`` takes WEED_HTTP_TIMEOUT (util/retry.py)
    — one knob for the fleet, not a constant per call site."""
    if timeout is None:
        from .retry import default_http_timeout
        timeout = default_http_timeout()
    if not url.startswith("http"):
        url = "http://" + url
    headers = dict(headers or {})
    if tracing.enabled():
        tid = tracing.current_trace_id()
        if tid:
            headers.setdefault(tracing.TRACE_HEADER, tid)
            sid = tracing.current_span_id()
            if sid:
                # name the calling span as the remote span's parent —
                # how the cross-server tree links up
                headers.setdefault(tracing.SPAN_HEADER, sid)
    return _POOL.request(url, method, body, headers, timeout)


def http_request_stream(url: str, method: str = "GET",
                        headers: dict | None = None,
                        timeout: "float | None" = None
                        ) -> tuple[int, object, dict]:
    """Streaming sibling of http_request: 2xx GET bodies come back as
    a chunk iterator (wrap in StreamBody to serve), everything else as
    bytes.  Same trace propagation and default-timeout semantics."""
    if timeout is None:
        from .retry import default_http_timeout
        timeout = default_http_timeout()
    if not url.startswith("http"):
        url = "http://" + url
    headers = dict(headers or {})
    if tracing.enabled():
        tid = tracing.current_trace_id()
        if tid:
            headers.setdefault(tracing.TRACE_HEADER, tid)
            sid = tracing.current_span_id()
            if sid:
                headers.setdefault(tracing.SPAN_HEADER, sid)
    return _POOL.request_stream(url, method, headers, timeout)


def http_get_json(url: str, timeout: "float | None" = None) -> dict:
    status, body, _ = http_request(url, timeout=timeout)
    out = json.loads(body) if body else {}
    if status >= 400:
        raise RuntimeError(out.get("error", f"HTTP {status}"))
    return out
