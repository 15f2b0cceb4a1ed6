"""Volume server — serves blobs over HTTP, admin/EC ops over gRPC, and
heartbeats to the master.

Capability-equivalent to weed/server/volume_server.go + handlers +
volume_grpc_*.go:
- HTTP data path: GET/HEAD/POST/DELETE /<vid>,<fid> with cookie checks,
  replica fan-out on write (topology/store_replicate.go:23-175), EC
  fallback on read, 302 redirect when the volume lives elsewhere
  (volume_server_handlers_read.go:31).
- gRPC `VolumeServer` service: volume lifecycle (allocate/delete/mount/
  readonly), vacuum check/compact/commit, batch delete, CopyFile streaming,
  and the 9 EC RPCs (volume_grpc_erasure_coding.go): ShardsGenerate /
  ShardsRebuild / ShardsCopy / ShardsDelete / ShardsMount / ShardsUnmount /
  ShardRead / BlobDelete / ShardsToVolume.
- Heartbeat: bidi stream to the master every pulse with the full volume +
  EC-shard snapshot (volume_grpc_client_to_master.go:48-213); accepts
  volume_size_limit back.
- Degraded EC reads fetch missing shard ranges from peers found via master
  LookupEcVolume, cached with a staleness window (store_ec.go:227-268).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

from ..pb.rpc import POOL, RpcError, RpcServer, from_b64, to_b64
from ..pb.rpc import tls_enabled as rpc_tls_enabled
from ..storage import ec as ec_pkg
from ..storage.ec.layout import DEFAULT_GEOMETRY, to_ext
from ..storage.needle import Needle
from ..storage.store import Store
from ..storage.ttl import TTL
from ..storage.types import FileId
from ..storage.volume import NotFoundError, volume_file_name
from ..util import tracing
from .hb_delta import HeartbeatDeltaEncoder
from ..util.http import (FileRegion, HttpServer, Request, Response,
                         _BadRequest, _body_len, http_request,
                         parse_byte_range)

from ..util.weedlog import logger

LOG = logger(__name__)

PULSE_SECONDS = 5
EC_LOCATION_STALENESS = 11.0  # the freshest staleness tier (store_ec.go:227)
# cached "volume is nowhere" answers: long enough to absorb a miss
# burst, short enough that a just-heartbeated volume becomes reachable
# within one pulse
NEGATIVE_LOOKUP_TTL = 1.0


# what a frame-port file copy may fetch: a volume's EC files
# (wide stripes reach .ec23 and beyond)
_EC_FILE_EXT = re.compile(r"\.(ec[0-9]{2,3}|ecx|ecj|vif)")


def sendfile_enabled() -> bool:
    """WEED_SENDFILE=0 turns zero-copy serving off fleet-wide — the
    byte-identical fallback knob (PR 12 workers=1 precedent)."""
    return os.environ.get("WEED_SENDFILE", "1") != "0" \
        and hasattr(os, "sendfile")


def _sendfile_min() -> int:
    """Needles below this serve from memory: a sendfile syscall tax on
    1KB smallfile reads would cost more than the copy it saves."""
    try:
        return int(os.environ.get("WEED_SENDFILE_MIN", str(64 << 10)))
    except ValueError:
        return 64 << 10


def _maybe_resize_image(data: bytes, mime: str, width: str, height: str,
                        mode: str) -> tuple[bytes, str]:
    """On-the-fly image resize on GET ?width=&height=[&mode=fit|fill]
    (weed/images/resizing.go, volume_server_handlers_read.go:267-292).
    Non-images or decode failures pass through untouched."""
    try:
        import io

        from PIL import Image
        img = Image.open(io.BytesIO(data))
        # decompression-bomb guard: a tiny stored blob can declare a huge
        # pixel canvas; decoding it would exhaust server memory on GET
        if img.width * img.height > 64_000_000:
            return data, mime
        fmt = img.format or "PNG"
        w = int(width) if width else img.width
        h = int(height) if height else img.height
        if mode == "fill":
            img = img.resize((w, h))
        else:  # fit: preserve aspect ratio within the box
            img.thumbnail((w, h))
        out = io.BytesIO()
        img.save(out, format=fmt)
        return out.getvalue(), f"image/{fmt.lower()}"
    except Exception:
        return data, mime


class VolumeServer:
    def __init__(self, master_grpc: str, directories: list[str],
                 host: str = "127.0.0.1", port: int = 0, grpc_port: int = 0,
                 public_url: str = "", data_center: str = "", rack: str = "",
                 max_volume_counts: list[int] | None = None,
                 pulse_seconds: float = PULSE_SECONDS,
                 jwt_signing_key: str = "", tcp_port: int = 0,
                 worker=None):
        # worker: a WorkerContext (volume_server/workers.py) when this
        # server is one partition of a process-sharded logical node —
        # requests for vids outside the partition forward to the owning
        # sibling, and /status+/metrics proxy to the supervisor's merge
        self._worker = worker
        # master_grpc may be a comma-separated list; heartbeats rotate
        # through it and re-home to whatever leader the replies announce
        self._masters = [m.strip() for m in master_grpc.split(",")
                         if m.strip()]
        self.master_grpc = self._masters[0]
        self.data_center = data_center
        self.rack = rack
        self.jwt_signing_key = jwt_signing_key
        from ..stats import ServerMetrics
        from ..util import profiling
        self.metrics = ServerMetrics()
        self.tracer = tracing.Tracer("volume")
        profiling.sampler()  # always-on process sampler (WEED_PROFILE)
        # hot-needle LRU in front of the read paths (HTTP + TCP frames);
        # writes/deletes of a needle evict its entry, populates are
        # offset-guarded (volume_server/needle_cache.py)
        from .needle_cache import HotNeedleCache
        self.needle_cache = HotNeedleCache()
        # workload heat sketches (util/sketch.py): every read/write on
        # every serving loop (HTTP, TCP frame, worker shard) folds in
        # here; /heat serves the snapshot the master federates
        from ..util.sketch import HeatTracker
        self.heat = HeatTracker()
        self._heat_gauges = HeatTracker.register_metrics(
            self.metrics.registry)
        self.pulse_seconds = pulse_seconds
        self.store = Store(directories, max_volume_counts)
        # a disk fault that degrades a volume to read-only must reach
        # the master NOW, not a pulse later — one heartbeat is the
        # acceptance window for the master to stop assigning there
        self.store.set_on_degrade(self._on_volume_degraded)
        self.http = HttpServer(host, port)
        self.rpc = RpcServer(host, grpc_port)
        self.volume_size_limit = 0
        self._stop = threading.Event()
        self._hb_thread: threading.Thread | None = None
        self._hb_wake = threading.Event()
        self._hb_gen = 0        # bumped by heartbeat_now callers
        self._hb_acked_gen = 0  # generation of the last acked payload
        self._hb_inflight: list[int] = []  # gens of yielded payloads, FIFO
        # workers stream to the SUPERVISOR, which merges full snapshots
        # (_rpc_worker_heartbeat stores the latest payload wholesale) —
        # delta-encode only the hop to a real master
        self._hb_delta = HeartbeatDeltaEncoder(
            enabled=False if worker is not None else None)
        # volume.server.leave: stop heartbeating (master unregisters us)
        # while data service stays up for drains (VolumeServerLeave RPC)
        self._leaving = False
        # vid -> (ts, {shard_id: [grpc addresses]})
        self._ec_locations: dict[int, tuple[float, dict[int, list[str]]]] = {}
        # vid -> (ts, [location dicts]) — replica urls for write fan-out
        self._vol_locations: dict[int, tuple[float, list[dict]]] = {}
        self.http.tracer = self.tracer
        self.rpc.tracer = self.tracer
        self._register_http()
        self._register_rpc()
        self._public_url = public_url
        from .tcp import TcpDataServer
        self.tcp = TcpDataServer(self, host=host, port=tcp_port)
        # persistent replica fan-out pool: the previous design spawned
        # one thread PER WRITE PER REPLICA — thread creation cost on
        # every replicated write, and each thread's fresh TCP connection
        # churned a socket per request.  Executor workers persist, so
        # their per-thread frame connections (operation._tcp_sock) and
        # the shared HTTP pool stay warm across writes.
        try:
            workers = max(2, int(os.environ.get("WEED_FANOUT_WORKERS",
                                                "8")))
        except ValueError:
            workers = 8
        self._fanout = ThreadPoolExecutor(max_workers=workers,
                                          thread_name_prefix="vs-fanout")

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        self.http.start()
        self.rpc.start()
        self.tcp.start()
        self.store.ip = self.http.host
        self.store.port = self.http.port
        self.store.public_url = self._public_url or self.http.address
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True)
        self._hb_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.http.stop()
        self.rpc.stop()
        self.tcp.stop()
        self._fanout.shutdown(wait=False)
        self.store.close()

    @property
    def url(self) -> str:
        return self.http.address

    @property
    def grpc_address(self) -> str:
        return self.rpc.address

    # -- heartbeat (volume_grpc_client_to_master.go:90-213) ----------------
    def _heartbeat_payload(self) -> dict:
        hb = self.store.collect_heartbeat()
        return {
            "ip": self.http.host, "port": self.http.port,
            "grpc_port": self.rpc.port, "tcp_port": self.tcp.port,
            "public_url": self.store.public_url,
            "data_center": self.data_center, "rack": self.rack,
            "max_volume_count": hb.max_volume_count,
            "max_file_key": hb.max_file_key,
            "volumes": [vars(v) for v in hb.volumes],
            "ec_shards": [{"id": e["id"], "collection": e["collection"],
                           "ec_index_bits": int(e["ec_index_bits"])}
                          for e in hb.ec_shards],
        }

    def _heartbeat_loop(self) -> None:
        target_idx = 0
        while not self._stop.is_set() and not self._leaving:
            try:
                client = POOL.client(self.master_grpc, "Seaweed")
                # fresh connection: the master may have swept us, so the
                # first payload must be a full snapshot
                self._hb_delta.reset()

                def requests():
                    while not self._stop.is_set() and not self._leaving:
                        # stamp which generation this payload reflects so
                        # heartbeat_now can wait for a POST-mutation ack
                        self._hb_inflight.append(self._hb_gen)
                        yield self._hb_delta.encode(
                            self._heartbeat_payload())
                        self._hb_wake.wait(self.pulse_seconds)
                        self._hb_wake.clear()

                for reply in client.stream("SendHeartbeat", requests()):
                    if self._hb_inflight:
                        self._hb_acked_gen = self._hb_inflight.pop(0)
                    self._hb_delta.note_reply(reply)
                    if reply.get("resync"):
                        self._hb_wake.set()  # re-register this pulse
                    if reply.get("volume_size_limit"):
                        self.volume_size_limit = reply["volume_size_limit"]
                    leader = reply.get("leader", "")
                    if leader and leader != self.master_grpc \
                            and self._leader_reachable(leader):
                        # re-home to the announced leader
                        # (volume_grpc_client_to_master.go leader chase)
                        self.master_grpc = leader
                        self._hb_inflight.clear()
                        break
                    if self._stop.is_set():
                        break
            except RpcError:
                self._hb_inflight.clear()
                # rotate to the next configured master
                target_idx = (target_idx + 1) % len(self._masters)
                self.master_grpc = self._masters[target_idx]
            self._stop.wait(1.0)

    def _leader_reachable(self, leader: str) -> bool:
        """Guard against re-home flapping: an announced leader address may
        be an unreachable alias (e.g. the master's 127.0.0.1 view of
        itself seen from another machine) — only switch if it answers."""
        if leader in self._masters:
            return True
        try:
            POOL.client(leader, "Seaweed").call("GetMasterConfiguration",
                                                {}, timeout=2.0)
            return True
        except RpcError:
            return False

    def _on_volume_degraded(self, vid: int) -> None:
        """A write-path IO fault flipped volume `vid` read-only
        (storage/volume.py _degrade): push the state to the master
        immediately so the very next Assign excludes it."""
        LOG.warning("volume %d degraded; pushing immediate heartbeat",
                    vid)
        self._hb_wake.set()

    def heartbeat_now(self, timeout: float = 5.0) -> None:
        """Push a fresh snapshot through the PERSISTENT stream and wait for
        the master to ack a payload built AFTER this call (the reference's
        New/DeletedVolumesChan delta trigger).  A separate one-shot stream
        would be wrong: the master unregisters a node when its heartbeat
        stream ends."""
        self._hb_gen += 1
        want = self._hb_gen
        self._hb_wake.set()
        deadline = time.time() + timeout
        while self._hb_acked_gen < want and time.time() < deadline:
            self._hb_wake.set()
            time.sleep(0.01)

    # -- HTTP data path ----------------------------------------------------
    def _register_http(self) -> None:
        self.http.route("GET", "/status", self._http_status)
        self.http.route("GET", "/metrics", self._http_metrics)
        self.http.route("GET", "/heat", self._http_heat)
        from ..util import locks, profiling
        self._traces_handler = tracing.traces_http_handler(self.tracer)
        self._profile_handler = profiling.profile_http_handler()
        self.http.route("GET", "/debug/traces", self._http_debug_traces)
        self.http.route("GET", "/debug/profile",
                        self._http_debug_profile)
        self.http.route("GET", "/debug/lockdep",
                        lambda req: Response.json(locks.debug_snapshot()),
                        exact=True)
        if self._worker is not None:
            # the supervisor's heartbeat_now pulls a fresh partition
            # snapshot through this before pushing the merged payload
            self.http.route("POST", "/heartbeat_now",
                            self._http_heartbeat_now, exact=True)
        # keep THE bound method the route table holds: the fast lane
        # recognizes the data route by identity, and `self._http_data`
        # builds a fresh bound-method object on every attribute access
        self._data_route = self._http_data
        self.http.route("*", "/", self._data_route)
        # native-loop fast lane: hot body-less GET/HEADs skip the
        # generic parse + dispatch (util/http.py _serve_conn_native)
        self.http.fast_lane = self._http_fast_lane

    def _http_fast_lane(self, method: str, target: str, headers,
                        remote: str) -> "Response | None":
        """Combined parse -> route -> serve lane for the native HTTP
        loop: the volume GET/HEAD hot path with the wire work already
        done in C.  Returns None to fall back to the generic loop —
        anything that needs urlsplit (query strings), tracing scopes, or
        a non-data route takes the normal path, so responses stay
        byte-identical by construction.  The JWT gate (write-only) and
        the needle-cache probe stay in Python inside _read_needle."""
        if tracing.enabled() or "?" in target or "#" in target \
                or not target.startswith("/") or target.startswith("//"):
            return None
        handler, _streams = self.http._match(method, target)
        if handler is not self._data_route:
            return None     # /status, /metrics, /debug/*: generic path
        req = Request(method=method, path=target, query={},
                      headers=headers, body=b"", remote_addr=remote,
                      handler=handler)
        # exactly _dispatch's untraced wrapping around the same handler:
        # error accounting and heat recording happen inside _http_data
        try:
            return self._http_data(req)
        except _BadRequest as e:
            return Response.error(str(e) or "bad request", 400)
        except Exception as e:
            return Response.error(f"{type(e).__name__}: {e}")

    def _http_heartbeat_now(self, req: Request) -> Response:
        self.heartbeat_now(timeout=3.0)
        return Response.json({"ok": True})

    # -- worker-partition plumbing (volume_server/workers.py) -------------
    def _owns_vid(self, vid: int) -> bool:
        return self._worker is None or self._worker.owns(vid)

    def _forward_to_owner(self, req: Request, fid: FileId) -> Response:
        """Wrong-worker HTTP request: proxy it to the owning sibling's
        private port, marked so it can never bounce twice.  The shared
        SO_REUSEPORT socket load-balances CONNECTIONS, not vids — this
        is the correctness backstop for clients without the per-vid
        routing map."""
        target = self._worker.peer_http_addr(fid.volume_id)
        qs = urllib.parse.urlencode(
            [(k, v) for k, vals in req.query.items() for v in vals])
        url = f"http://{target}{req.path}" + (f"?{qs}" if qs else "")
        headers = {"X-Weed-Worker-Forward": "1"}
        for h in ("Content-Encoding", "Authorization",
                  "Accept-Encoding", "If-None-Match"):
            if h in req.headers:
                headers[h] = req.headers[h]
        try:
            status, body, rhdrs = http_request(
                url, method=req.method, body=req.body or None,
                headers=headers)
        except (OSError, ConnectionError) as e:
            self.metrics.volume_errors.inc("forward")
            return Response.error(f"worker forward failed: {e}", 502)
        drop = {"content-length", "date", "server", "connection",
                "transfer-encoding", "content-type"}
        return Response(
            status, body,
            content_type=rhdrs.get("Content-Type",
                                   "application/octet-stream"),
            headers={k: v for k, v in rhdrs.items()
                     if k.lower() not in drop})

    def _proxy_supervisor(self, req: Request, path: str) -> Response:
        """/status and /metrics on a worker answer for the whole logical
        node (the supervisor merges every partition); ?worker_local=1
        asks for just this partition."""
        try:
            status, body, rhdrs = http_request(
                f"http://{self._worker.supervisor_admin}{path}",
                timeout=10.0)
        except (OSError, ConnectionError) as e:
            LOG.warning("supervisor merge proxy failed, serving "
                        "partition-local %s: %s", path, e)
            return None  # caller serves its local view
        return Response(status, body,
                        content_type=rhdrs.get("Content-Type",
                                               "text/plain"))

    def _proxy_supervisor_debug(self, req: Request, path: str,
                                timeout: float = 10.0) \
            -> "Response | None":
        """Sharded mode: /debug/* on a worker answers for the WHOLE
        logical node through the supervisor's merge (which re-fetches
        each partition with worker_local=1), keeping the query string
        and the X-Profile-* headers intact.  None -> serve the local
        partition (supervisor unreachable, or worker_local asked)."""
        qs = urllib.parse.urlencode(
            [(k, v) for k, vals in req.query.items() for v in vals
             if k != "worker_local"])
        url = f"http://{self._worker.supervisor_admin}{path}" \
            + (f"?{qs}" if qs else "")
        try:
            status, body, rhdrs = http_request(url, timeout=timeout)
        except (OSError, ConnectionError) as e:
            LOG.warning("supervisor debug proxy failed, serving "
                        "partition-local %s: %s", path, e)
            return None
        keep = {k: v for k, v in rhdrs.items()
                if k.lower().startswith("x-profile-")}
        return Response(status, body,
                        content_type=rhdrs.get("Content-Type",
                                               "text/plain"),
                        headers=keep)

    def _http_debug_traces(self, req: Request) -> Response:
        if self._worker is not None and not req.qs("worker_local"):
            merged = self._proxy_supervisor_debug(req, "/debug/traces")
            if merged is not None:
                return merged
        return self._traces_handler(req)

    def _http_debug_profile(self, req: Request) -> Response:
        if self._worker is not None and not req.qs("worker_local"):
            try:
                seconds = float(req.qs("seconds", "1") or 1)
            except ValueError:
                seconds = 1.0
            merged = self._proxy_supervisor_debug(
                req, "/debug/profile", timeout=max(10.0, seconds + 15))
            if merged is not None:
                return merged
        return self._profile_handler(req)

    def _http_heat(self, req: Request) -> Response:
        """This server's heat sketches (util/sketch.py snapshot).  On a
        worker the bare path answers for the whole logical node via the
        supervisor's merge; ?worker_local=1 serves just this partition.
        ?freq=0 drops the count-min matrix (the bulky part) for callers
        that only want the top-K tables."""
        if self._worker is not None and not req.qs("worker_local"):
            merged = self._proxy_supervisor(req, "/heat")
            if merged is not None:
                return merged
        return Response.json(
            self.heat.snapshot(include_freq=req.qs("freq") != "0"))

    def _http_metrics(self, req: Request) -> Response:
        if self._worker is not None and not req.qs("worker_local"):
            merged = self._proxy_supervisor(req, "/metrics")
            if merged is not None:
                return merged
        self.heat.fill_metrics(self._heat_gauges)
        total = sum(len(loc.volumes) for loc in self.store.locations)
        self.metrics.volume_count.set(value=total)
        self.metrics.needle_cache_bytes.set(
            value=float(self.needle_cache.stats["bytes"]))
        # the process-global codec families ride along: per-backend EC
        # encode/decode latency + bytes (ops/codec.py codec_metrics)
        from ..ops.codec import codec_metrics
        from ..stats import metrics_response
        return metrics_response(
            req, lambda exemplars=False:
            self.metrics.render(exemplars=exemplars)
            + codec_metrics().registry.render(exemplars=exemplars))

    def _check_jwt(self, req: Request, fid: FileId) -> "Response | None":
        """Write gate (volume_server_handlers_write.go:41): when a signing
        key is configured, writes/deletes need a master-issued token."""
        if not self.jwt_signing_key:
            return None
        from ..security import JwtError, verify_fid_jwt
        token = req.qs("jwt")
        auth = req.headers.get("Authorization", "")
        if not token and auth.startswith("BEARER "):
            token = auth[7:]
        if not token and auth.startswith("Bearer "):
            token = auth[7:]
        try:
            verify_fid_jwt(self.jwt_signing_key, token, str(fid))
        except JwtError as e:
            return Response.error(f"jwt: {e}", 401)
        return None

    def _http_status(self, req: Request) -> Response:
        if self._worker is not None and not req.qs("worker_local"):
            merged = self._proxy_supervisor(req, "/status")
            if merged is not None:
                return merged
        hb = self.store.collect_heartbeat()
        from ..parallel.mesh_codec import ec_backend_status
        return Response.json({"Version": "seaweedfs-tpu",
                              "Volumes": [vars(v) for v in hb.volumes],
                              "NeedleCache": self.needle_cache.stats,
                              "Ec": ec_backend_status()})

    def _parse_fid_path(self, path: str) -> FileId:
        # /3,01637037d6 (volume_server_handlers_read.go:43 parsing)
        part = path.lstrip("/").split("/")[-1]
        # strip a .ext the client may append
        if "." in part:
            part = part.split(".", 1)[0]
        return FileId.parse(part)

    _HTTP_KINDS = {"GET": "read", "HEAD": "read", "POST": "write",
                   "PUT": "write", "DELETE": "delete"}

    def _http_data(self, req: Request) -> Response:
        try:
            fid = self._parse_fid_path(req.path)
        except Exception:
            return Response.error("invalid fid path", 400)
        kind = self._HTTP_KINDS.get(req.method)
        if kind is None:
            return Response.error("method not allowed", 405)
        if self._worker is not None \
                and not self._worker.owns(fid.volume_id) \
                and not req.headers.get("X-Weed-Worker-Forward"):
            return self._forward_to_owner(req, fid)
        try:
            if kind == "read":
                resp = self._read_needle(fid, req)
            elif kind == "write":
                resp = self._write_needle(fid, req)
            else:
                resp = self._delete_needle(fid, req)
        except Exception:
            # a raised handler exception becomes a 500 one layer up
            # (HttpServer._dispatch) — it must burn the error budget
            # like any other server fault
            self.metrics.volume_errors.inc(kind)
            raise
        if resp.status >= 500:
            # server-fault accounting for the SLO availability burn;
            # 4xx (not-found, cookie mismatch, bad jwt) is the user's
            # problem and must not eat the error budget
            self.metrics.volume_errors.inc(kind)
        self.heat.record(
            kind, volume=fid.volume_id, key=str(fid),
            nbytes=(_body_len(resp.body) if kind == "read"
                    else len(req.body or b"")),
            error=resp.status >= 500)
        return resp

    def _read_needle(self, fid: FileId, req: Request) -> Response:
        t0 = time.perf_counter()
        self.metrics.volume_requests.inc("read")
        v = self.store.find_volume(fid.volume_id)
        if v is not None:
            # hot-needle LRU first (HTTP needs the full metadata, so
            # data_only entries populated by the TCP path don't count)
            ce = self.needle_cache.get(fid.volume_id, fid.key, fid.cookie,
                                       need_metadata=True)
            if ce is not None:
                self.metrics.needle_cache_ops.inc("hit")
                return self._serve_needle(
                    req, ce.data, ce.etag, ce.name, ce.mime,
                    ce.is_compressed, t0)
            self.metrics.needle_cache_ops.inc("miss")
        try:
            if v is not None:
                # zero-copy: n.data stays a memoryview over the pread
                # buffer all the way to the socket
                n = v.read_needle(fid.key, fid.cookie, zero_copy=True)
            elif self.store.find_ec_volume(fid.volume_id) is not None:
                self._ensure_ec_remote_reader(fid.volume_id)
                n = self.store.read_ec_needle(fid.volume_id, fid.key,
                                              fid.cookie)
            else:
                return self._redirect_or_404(fid)
        except NotFoundError:
            return Response.error("not found", 404)
        except ec_pkg.EcNotFoundError:
            return Response.error("not found", 404)
        if v is not None and not n.has_ttl() \
                and self.needle_cache.admissible(len(n.data)) \
                and getattr(n, "volume_offset", None) is not None:
            from .needle_cache import CachedNeedle
            self.needle_cache.put_guarded(
                fid.volume_id, fid.key,
                CachedNeedle(cookie=n.cookie, data=bytes(n.data),
                             offset=n.volume_offset, etag=n.etag(),
                             mime=bytes(n.mime), name=bytes(n.name),
                             is_compressed=n.is_compressed(),
                             data_only=False),
                lambda: v.needle_offset(fid.key))
        return self._serve_needle(req, n.data, n.etag(), n.name, n.mime,
                                  n.is_compressed(), t0,
                                  volume=v, fid=fid,
                                  volume_offset=getattr(
                                      n, "volume_offset", None))

    def _serve_needle(self, req: Request, data, etag: str, name: bytes,
                      mime_b: bytes, compressed: bool, t0: float,
                      volume=None, fid: "FileId | None" = None,
                      volume_offset: "int | None" = None) -> Response:
        """Response assembly shared by the cache-hit and disk paths.
        `data` may be bytes or a memoryview (zero-copy serving); the
        negotiation/resize branches materialize bytes only when they
        must transform the payload.  Single-range requests answer 206
        on identity bytes; big uncompressed disk reads go out through
        os.sendfile from the .dat fd (volume/fid/volume_offset plumb
        the disk-read provenance — cache hits and EC reads serve from
        memory)."""
        headers = {"Etag": f'"{etag}"'}
        if name:
            headers["X-File-Name"] = bytes(name).decode(errors="replace")
        mime = (bytes(mime_b).decode(errors="replace")
                if mime_b else "application/octet-stream")
        gzip_verbatim = False
        if compressed:
            # negotiate like volume_server_handlers_read.go:208-215:
            # gzip-accepting clients get the stored bytes verbatim (zero
            # recompute), everyone else gets them decompressed.  Resize
            # requests always decode — the image transform must see the
            # content, never the gzip envelope
            from ..util.compression import accepts_gzip, decompress
            resizing = bool(req.qs("width") or req.qs("height"))
            headers["Vary"] = "Accept-Encoding"  # caches key on encoding
            if accepts_gzip(req.headers.get("Accept-Encoding", "")) \
                    and not resizing:
                headers["Content-Encoding"] = "gzip"
                # RFC 9110: distinct representations need distinct
                # validators — If-None-Match does not key on encoding,
                # so the gzip body must not share the identity ETag
                headers["Etag"] = f'"{etag}-gzip"'
                gzip_verbatim = True
            else:
                data = decompress(bytes(data))
        else:
            resizing = bool(req.qs("width") or req.qs("height"))
        if resizing:
            data, mime = _maybe_resize_image(
                data, mime, req.qs("width"), req.qs("height"),
                req.qs("mode"))
        # single-range serving on identity bytes (the HTTP fallback of
        # the ranged chunk-read fast path).  The gzip-verbatim branch
        # keeps today's ignore-Range behavior: ranges into a stored
        # gzip stream would index the wrong representation.
        status, range_start = 200, 0
        rng = req.headers.get("Range", "")
        if rng.startswith("bytes=") and not gzip_verbatim \
                and not resizing and len(data) > 0:
            parsed = parse_byte_range(rng[6:], len(data))
            if parsed is None:
                self.metrics.volume_latency.observe(
                    "read", value=time.perf_counter() - t0,
                    trace_id=tracing.current_trace_id())
                return Response(416, b"", headers={
                    "Content-Range": f"bytes */{len(data)}"})
            if parsed != (0, len(data)):
                start, stop = parsed
                headers["Content-Range"] = \
                    f"bytes {start}-{stop - 1}/{len(data)}"
                status, range_start = 206, start
                data = data[start:stop]
        headers["Accept-Ranges"] = "bytes"
        body = data
        if volume is not None and fid is not None \
                and volume_offset is not None \
                and not compressed and not resizing \
                and isinstance(data, memoryview) \
                and len(data) >= _sendfile_min() \
                and req.method == "GET" and sendfile_enabled():
            from ..util import faults
            if not faults.ACTIVE:
                # zero-copy eligible: an uncompressed, CRC-verified
                # disk read with no transform and no fault hooks in
                # play.  The dup'ed fd is taken under the volume lock
                # while the needle still lives at the read offset, so
                # a racing vacuum can't redirect the send; the
                # verified memoryview rides along as the fallback.
                dup_fd = volume.data_fd_for_sendfile(fid.key,
                                                     volume_offset)
                if dup_fd is not None:
                    body = FileRegion(
                        dup_fd,
                        volume.needle_data_offset(volume_offset)
                        + range_start,
                        len(data), data)
        self.metrics.volume_latency.observe(
            "read", value=time.perf_counter() - t0,
            trace_id=tracing.current_trace_id())
        return Response(status, body, content_type=mime, headers=headers)

    def _redirect_or_404(self, fid: FileId) -> Response:
        # short TTL, positive AND negative: a burst of misses costs one
        # master gRPC call per second instead of one per request, while
        # a volume mid-move (vacuum swap, EC conversion) still gets a
        # fresh answer within a second — an 11s-stale redirect target
        # would bounce readers between dead locations for longer than
        # any client retry window
        locs = self._lookup_locations(fid.volume_id, negative_ok=True,
                                      max_age=NEGATIVE_LOOKUP_TTL)
        locs = [l for l in locs if l["url"] != self.url]
        if not locs:
            return Response.error("volume not found", 404)
        return Response(302, b"", headers={
            "Location": f"http://{locs[0]['public_url']}/{fid}"})

    def _write_needle(self, fid: FileId, req: Request) -> Response:
        t0 = time.perf_counter()
        denied = self._check_jwt(req, fid)
        if denied is not None:
            return denied
        v = self.store.find_volume(fid.volume_id)
        if v is None:
            return Response.error(f"volume {fid.volume_id} not local", 404)
        n = Needle(id=fid.key, cookie=fid.cookie, data=req.body)
        if req.qs("name"):
            n.set_name(req.qs("name").encode())
        if req.qs("mime"):
            n.set_mime(req.qs("mime").encode())
        if req.qs("ttl"):
            n.set_ttl(TTL.parse(req.qs("ttl")))
        if req.headers.get("Content-Encoding", "").lower() == "gzip" \
                or req.qs("compressed"):
            # client uploaded pre-gzipped content (upload_content.go
            # sets the header); the flag drives read-side negotiation
            n.set_is_compressed()
        if req.qs("fsync"):
            # durable writes ride the group-commit worker: N concurrent
            # fsync writers share one fsync per batch (volume_write.go:233)
            size = v.write_needle_durable(n).result(timeout=30)
        else:
            size = self.store.write_volume_needle(fid.volume_id, n)
        # evict AFTER the store mutation landed (needle_cache coherence)
        self.needle_cache.invalidate(fid.volume_id, fid.key)
        if req.qs("type") != "replicate":
            err = self._replicate(fid, req, "POST", req.body)
            if err:
                return Response.error(f"replication failed: {err}", 500)
        self.metrics.volume_requests.inc("write")
        self.metrics.volume_latency.observe(
            "write", value=time.perf_counter() - t0,
            trace_id=tracing.current_trace_id())
        return Response.json({"name": req.qs("name"), "size": size,
                              "eTag": n.etag()}, status=201)

    def _delete_needle(self, fid: FileId, req: Request) -> Response:
        denied = self._check_jwt(req, fid)
        if denied is not None:
            return denied
        self.metrics.volume_requests.inc("delete")
        if self.store.has_volume(fid.volume_id):
            size = self.store.delete_volume_needle(fid.volume_id, fid.key,
                                                   fid.cookie)
            self.needle_cache.invalidate(fid.volume_id, fid.key)
        elif self.store.find_ec_volume(fid.volume_id) is not None:
            vol = self.store.find_ec_volume(fid.volume_id)
            # same cookie gate as the normal-volume path: read the needle
            # header to validate before tombstoning
            try:
                self._ensure_ec_remote_reader(fid.volume_id)
                n = vol.read_needle(fid.key)
            except ec_pkg.EcNotFoundError:
                return Response.json({"size": 0}, status=202)
            if n.cookie != fid.cookie:
                return Response.error("cookie mismatch", 400)
            vol.delete_needle(fid.key)
            size = 0
        else:
            return Response.error("volume not local", 404)
        if req.qs("type") != "replicate":
            err = self._replicate(fid, req, "DELETE", None)
            if err:
                return Response.error(f"replication failed: {err}", 500)
        return Response.json({"size": size}, status=202)

    # -- raw-TCP data fast path (volume_server/tcp.py frames) --------------
    def tcp_write(self, fid_str: str, body, jwt: str,
                  replicate: bool = False, compressed: bool = False,
                  ttl: str = "") -> tuple[int, str]:
        """The HTTP write handler's semantics — jwt gate, replication
        fan-out — minus what a frame cannot express (name/mime/fsync
        params; durable group-commit writes stay HTTP-only).  The
        extended frame ('X') carries replicate/compressed/ttl, so
        replication fan-out and filer ttl'd or pre-gzipped chunk
        uploads ride frames too.  Skipping the Request/Response
        wrapping and its twelve per-op query-string parses is what
        the frame saves on every 1KB write.
        -> (size, etag); every avoidable per-op allocation matters
        here: the jwt check reuses the parsed needle key, and the
        fan-out work is built only when replicas actually exist."""
        t0 = time.perf_counter()
        fid = FileId.parse(fid_str)
        if self._worker is not None \
                and not self._worker.owns(fid.volume_id):
            # wrong-worker frame: hand the WHOLE op to the owner (it
            # runs the jwt gate and, when replicate is unset, the
            # replica fan-out).  Ownership is vid%N-deterministic, so
            # this can never bounce twice.
            from .. import operation
            out = operation.upload_data_tcp(
                self._worker.peer_tcp_addr(fid.volume_id), fid_str,
                body, jwt=jwt, replicate=replicate,
                compressed=compressed, ttl=ttl)
            return out["size"], out["eTag"]
        if self.jwt_signing_key:
            from ..security import JwtError, verify_fid_jwt
            try:
                # hot path: the wire fid verbatim (clients echo the
                # master's canonical form, so no re-format needed)
                verify_fid_jwt(self.jwt_signing_key, jwt, fid_str,
                               key=fid.key)
            except JwtError:
                try:
                    # cold path: a NON-canonical wire fid (upper-case
                    # hex, zero-padded vid) must still match a token
                    # minted for the canonical form, like the HTTP gate
                    verify_fid_jwt(self.jwt_signing_key, jwt, str(fid),
                                   key=fid.key)
                except JwtError as e:
                    raise ValueError(f"jwt: {e}") from None
        n = Needle(id=fid.key, cookie=fid.cookie, data=body)
        if ttl:
            n.set_ttl(TTL.parse(ttl))
        if compressed:
            n.set_is_compressed()
        try:
            size = self.store.write_volume_needle(fid.volume_id, n)
        except NotFoundError:
            raise ValueError(f"volume {fid.volume_id} not local") from None
        except Exception:
            # server-fault accounting mirrors _http_data: a disk/storage
            # failure on the frame path must burn the SLO error budget
            # like its HTTP twin would (not-local/jwt are client-class)
            self.metrics.volume_errors.inc("write")
            self.heat.record("write", volume=fid.volume_id, key=fid_str,
                             nbytes=len(body), error=True)
            raise
        self.needle_cache.invalidate(fid.volume_id, fid.key)
        if not replicate:
            err = self._fan_out(
                fid, "POST", body,
                lambda: "type=replicate"
                + (f"&jwt={urllib.parse.quote(jwt, safe='')}" if jwt
                   else "")
                + (f"&ttl={urllib.parse.quote(ttl, safe='')}" if ttl
                   else "")
                + ("&compressed=1" if compressed else ""),
                jwt=jwt, ttl=ttl, compressed=compressed, tcp_ok=True)
            if err:
                # the HTTP handler answers this with a 500 — same burn
                self.metrics.volume_errors.inc("write")
                raise ValueError(f"replication failed: {err}")
        self.metrics.volume_requests.inc("write")
        self.metrics.volume_latency.observe(
            "write", value=time.perf_counter() - t0,
            trace_id=tracing.current_trace_id())
        self.heat.record("write", volume=fid.volume_id, key=fid_str,
                         nbytes=len(body))
        return size, n.etag()

    def tcp_read(self, fid_str: str) -> bytes:
        fid = FileId.parse(fid_str)
        if self._worker is not None \
                and not self._worker.owns(fid.volume_id):
            from .. import operation
            return operation.read_file_tcp(
                self._worker.peer_tcp_addr(fid.volume_id), fid_str)
        # hot path: plain volume read with no Request/Response wrapping —
        # 1KB reads are dispatch-bound, and the TCP frame protocol has no
        # use for headers/mime/resize anyway
        v = self.store.find_volume(fid.volume_id)
        if v is not None:
            t0 = time.perf_counter()
            self.metrics.volume_requests.inc("read")
            ce = self.needle_cache.get(fid.volume_id, fid.key, fid.cookie)
            if ce is not None:
                self.metrics.needle_cache_ops.inc("hit")
                self.metrics.volume_latency.observe(
                    "read", value=time.perf_counter() - t0,
                    trace_id=tracing.current_trace_id())
                self.heat.record("read", volume=fid.volume_id,
                                 key=fid_str, nbytes=len(ce.data))
                return ce.data
            self.metrics.needle_cache_ops.inc("miss")
            offset = v.needle_offset(fid.key)
            meta: dict = {}
            try:
                data = v.read_needle_data(fid.key, fid.cookie, meta=meta)
            except NotFoundError:
                raise ValueError("not found") from None
            except Exception:
                # disk/CRC faults on the frame read path burn the SLO
                # error budget like a 500 from _http_data (404 doesn't)
                self.metrics.volume_errors.inc("read")
                raise
            if offset is not None and not meta.get("ttl") \
                    and self.needle_cache.admissible(len(data)):
                # data_only entry: the frame path never parses metadata;
                # an HTTP read of the same needle repopulates with it.
                # The offset guard keeps a populate racing an overwrite
                # from installing stale bytes (needle_cache.py).
                from .needle_cache import CachedNeedle
                self.needle_cache.put_guarded(
                    fid.volume_id, fid.key,
                    CachedNeedle(cookie=fid.cookie, data=data,
                                 offset=offset),
                    lambda: v.needle_offset(fid.key))
            self.metrics.volume_latency.observe(
                "read", value=time.perf_counter() - t0,
                trace_id=tracing.current_trace_id())
            self.heat.record("read", volume=fid.volume_id, key=fid_str,
                             nbytes=len(data))
            return data
        from ..util.http import CIDict, FileRegion, _body_bytes, _body_len
        req = Request(method="GET", path="", query={},
                      headers=CIDict(), body=b"")
        resp = self._read_needle(fid, req)  # EC / redirect cases
        # a volume mounted mid-request can route the synthetic GET down
        # the local disk path, which may answer with a sendfile
        # FileRegion — the frame reply needs real bytes, and the
        # region's dup'ed fd must not leak
        if isinstance(resp.body, FileRegion):
            resp.body.close()
        if resp.status >= 500:
            self.metrics.volume_errors.inc("read")
        self.heat.record("read", volume=fid.volume_id, key=fid_str,
                         nbytes=_body_len(resp.body),
                         error=resp.status >= 500)
        if resp.status >= 300:
            raise ValueError(
                _body_bytes(resp.body).decode(errors="replace"))
        return _body_bytes(resp.body)

    def tcp_read_range(self, fid_str: str, offset: int,
                       length: int) -> bytes:
        """The 'G' frame: exactly [offset, offset+length) of a plain
        needle's data — sub-chunk Range requests move only the bytes
        they need off this server.  Anything the ranged fast path can't
        serve (EC volumes, rich/compressed needles, remote volumes)
        raises, and the client falls back to a whole-chunk 'R'/HTTP
        read."""
        from .tcp import MAX_FRAME_BODY
        fid = FileId.parse(fid_str)
        if length > MAX_FRAME_BODY:
            # bounds the reply allocation the same way request bodies
            # are bounded — a ranged read never needs more than a chunk
            raise ValueError(
                f"ranged read of {length} exceeds cap {MAX_FRAME_BODY}")
        if self._worker is not None \
                and not self._worker.owns(fid.volume_id):
            from .. import operation
            return operation.read_range_tcp(
                self._worker.peer_tcp_addr(fid.volume_id), fid_str,
                offset, length)
        v = self.store.find_volume(fid.volume_id)
        if v is None:
            raise ValueError(
                f"volume {fid.volume_id} not local; ranged reads "
                "serve plain local volumes only")
        t0 = time.perf_counter()
        self.metrics.volume_requests.inc("read")
        # cache slice ONLY for entries KNOWN plain (HTTP-populated,
        # metadata-bearing, uncompressed): a data_only entry may hold a
        # compressed needle's STORED gzip bytes with no flag to say so
        # — slicing those would answer status-0 garbage instead of the
        # error the client's whole-chunk fallback keys off.  Bounds
        # behave exactly like the disk path (start past the data is an
        # error, never an empty success).
        ce = self.needle_cache.get(fid.volume_id, fid.key, fid.cookie,
                                   need_metadata=True)
        if ce is not None and not ce.is_compressed:
            self.metrics.needle_cache_ops.inc("hit")
            if offset >= len(ce.data):
                raise ValueError(
                    f"range start {offset} beyond needle data "
                    f"{len(ce.data)}")
            piece = ce.data[offset:offset + length]
        else:
            self.metrics.needle_cache_ops.inc("miss")
            try:
                piece = v.read_needle_range(fid.key, fid.cookie,
                                            offset, length)
            except NotFoundError:
                raise ValueError("not found") from None
            except OSError:
                # disk faults on the ranged path burn the SLO error
                # budget like every other read-path 500
                self.metrics.volume_errors.inc("read")
                raise
        self.metrics.volume_latency.observe(
            "read", value=time.perf_counter() - t0,
            trace_id=tracing.current_trace_id())
        self.heat.record("read", volume=fid.volume_id, key=fid_str,
                         nbytes=len(piece))
        return piece

    def tcp_delete(self, fid_str: str, jwt: str) -> dict:
        from ..util.http import CIDict
        fid = FileId.parse(fid_str)
        if self._worker is not None \
                and not self._worker.owns(fid.volume_id):
            from .. import operation
            return operation.delete_file_tcp(
                self._worker.peer_tcp_addr(fid.volume_id), fid_str,
                jwt=jwt)
        req = Request(method="DELETE", path="",
                      query={"jwt": [jwt]} if jwt else {},
                      headers=CIDict(), body=b"")
        resp = self._delete_needle(fid, req)
        self.heat.record("delete", volume=fid.volume_id, key=fid_str,
                         error=resp.status >= 500)
        if resp.status >= 300:
            raise ValueError(resp.body.decode(errors="replace"))
        return json.loads(resp.body)

    def _lookup_locations(self, vid: int, negative_ok: bool = False,
                          max_age: float = EC_LOCATION_STALENESS
                          ) -> list[dict]:
        """Master LookupVolume behind a TTL cache.  `max_age` bounds how
        stale a served entry may be (the redirect path passes the short
        window); empty results are additionally capped at
        NEGATIVE_LOOKUP_TTL and served ONLY to callers that opt in — the
        write fan-out must re-ask rather than skip a replica because of
        a momentarily stale miss."""
        now = time.time()
        cached = self._vol_locations.get(vid)
        if cached is not None:
            ts, locs = cached
            ttl = min(max_age,
                      max_age if locs else NEGATIVE_LOOKUP_TTL)
            if now - ts < ttl and (locs or negative_ok):
                return locs
        try:
            client = POOL.client(self.master_grpc, "Seaweed")
            out = client.call("LookupVolume",
                              {"volume_or_file_ids": [str(vid)]})
            locs = out["volume_id_locations"][str(vid)]["locations"]
        except (RpcError, KeyError):
            locs = []  # not registered yet (e.g. pre-heartbeat tests)
        self._vol_locations[vid] = (now, locs)
        return locs

    def _replica_locations(self, vid: int) -> list[dict]:
        """Write-path lookup: never trusts a cached negative — see
        _lookup_locations."""
        return self._lookup_locations(vid, negative_ok=False)

    def _replicate(self, fid: FileId, req: Request, method: str,
                   body: bytes | None) -> str:
        """Synchronous fan-out to the other replicas
        (topology/store_replicate.go DistributedOperation:160)."""
        qs = "type=replicate"
        for arg in ("name", "mime", "ttl", "jwt"):
            if req.qs(arg):
                qs += f"&{arg}={urllib.parse.quote(req.qs(arg), safe='')}"
        compressed = req.headers.get("Content-Encoding",
                                     "").lower() == "gzip" \
            or bool(req.qs("compressed"))
        if compressed:
            qs += "&compressed=1"  # replicas must keep the needle flag
        jwt = req.qs("jwt")
        auth = req.headers.get("Authorization", "")
        if not jwt and auth[:7] in ("BEARER ", "Bearer "):
            jwt = auth[7:]
            qs += f"&jwt={urllib.parse.quote(jwt, safe='')}"
        # name/mime have no frame slot: such writes replicate over HTTP
        tcp_ok = method == "POST" and not req.qs("name") \
            and not req.qs("mime")
        return self._fan_out(fid, method, body, qs, jwt=jwt,
                             ttl=req.qs("ttl"), compressed=compressed,
                             tcp_ok=tcp_ok)

    def _fan_out(self, fid: FileId, method: str, body, qs,
                 jwt: str = "", ttl: str = "", compressed: bool = False,
                 tcp_ok: bool = False) -> str:
        """The shared replica fan-out (HTTP and TCP write paths), run on
        the persistent executor — no thread construction per write.
        Transport errors count as replication failures — a DOWN replica
        must fail the write loudly, never silently skip it.  `qs` may be
        a zero-arg callable so hot callers defer the query-string build
        to the (rare) replicated case."""
        locs = [l for l in self._replica_locations(fid.volume_id)
                if l["url"] != self.url]
        if not locs:
            return ""
        if callable(qs):
            # stay lazy until a send actually takes the HTTP branch (the
            # frame fast path never needs the query string) — memoized
            # so multi-replica HTTP fan-out builds it once; a racing
            # duplicate build is harmless (pure string work)
            build, cache = qs, []

            def qs_lazy():
                if not cache:
                    cache.append(build())
                return cache[0]
            qs = qs_lazy
        if len(locs) == 1:
            # one replica: send inline — a queue hop + future wait buys
            # nothing when there is no parallelism to gain
            err = self._send_replica(locs[0], fid, method, body, qs,
                                     jwt, ttl, compressed, tcp_ok)
            return err or ""
        # the persistent executor's workers have no thread-local context:
        # wrap the task so each replica send runs under THIS request's
        # ambient trace (regression: fan-out spans must share the root's
        # trace id instead of minting unrelated ones)
        send = tracing.propagate(self._send_replica)
        futs = [self._fanout.submit(send, loc, fid, method,
                                    body, qs, jwt, ttl, compressed,
                                    tcp_ok)
                for loc in locs]
        errors = [e for e in (f.result() for f in futs) if e]
        return "; ".join(errors)

    def _send_replica(self, loc: dict, fid: FileId, method: str, body,
                      qs, jwt: str, ttl: str, compressed: bool,
                      tcp_ok: bool) -> "str | None":
        """One replica send: frame fast path when the replica advertises
        a TCP port (the replicate flag stops it fanning out again), HTTP
        through the shared pool otherwise.  A dead TCP port falls back
        to HTTP (and is negative-cached); a server-side rejection is
        real and fails the write."""
        t0 = time.perf_counter()
        from .. import operation
        tcp = loc.get("tcp_url", "")
        if tcp_ok and tcp and not operation.tcp_dead(tcp):
            try:
                operation.upload_data_tcp(tcp, str(fid), body, jwt=jwt,
                                          replicate=True, ttl=ttl,
                                          compressed=compressed)
                self.metrics.replica_fanout_ops.inc("tcp", "ok")
                self.metrics.replica_fanout_latency.observe(
                    "tcp", value=time.perf_counter() - t0)
                return None
            except (OSError, ConnectionError):
                operation.mark_tcp_dead(tcp)   # fall through to HTTP
            except RuntimeError as e:
                self.metrics.replica_fanout_ops.inc("tcp", "error")
                return f"{loc['url']}: {e}"
        if callable(qs):
            qs = qs()   # HTTP branch: the query string is finally needed
        try:
            status, _, _ = http_request(
                f"http://{loc['url']}/{fid}?{qs}", method=method,
                body=body)
        except (OSError, ConnectionError) as e:
            self.metrics.replica_fanout_ops.inc("http", "error")
            return f"{loc['url']}: {e}"
        if status >= 300:
            self.metrics.replica_fanout_ops.inc("http", "error")
            return f"{loc['url']}: HTTP {status}"
        self.metrics.replica_fanout_ops.inc("http", "ok")
        self.metrics.replica_fanout_latency.observe(
            "http", value=time.perf_counter() - t0)
        return None

    # -- EC remote shard plumbing -----------------------------------------
    def _ec_shard_locations(self, vid: int) -> dict[int, list[str]]:
        now = time.time()
        cached = self._ec_locations.get(vid)
        if cached and now - cached[0] < EC_LOCATION_STALENESS:
            return cached[1]
        client = POOL.client(self.master_grpc, "Seaweed")
        out = client.call("LookupEcVolume", {"volume_id": vid})
        locs = {int(e["shard_id"]):
                [f"{l['url'].split(':')[0]}:{l['grpc_port']}"
                 for l in e["locations"] if l.get("grpc_port")]
                for e in out.get("shard_id_locations", [])}
        self._ec_locations[vid] = (now, locs)
        return locs

    def _ensure_ec_remote_reader(self, vid: int) -> None:
        vol = self.store.find_ec_volume(vid)
        if vol is None or vol.remote_reader is not None:
            return

        def remote_reader(vid2: int, shard_id: int, offset: int,
                          size: int) -> bytes | None:
            try:
                locations = self._ec_shard_locations(vid2).get(shard_id, [])
            except RpcError:
                return None
            for addr in locations:
                if addr == self.grpc_address:
                    continue
                try:
                    client = POOL.client(addr, "VolumeServer")
                    chunks = [r["data"] for r in client.stream(
                        "VolumeEcShardRead",
                        iter([{"volume_id": vid2, "shard_id": shard_id,
                               "offset": offset, "size": size}]))]
                    data = b"".join(chunks)
                    if len(data) == size:
                        return data
                except RpcError:
                    continue
            return None

        vol.remote_reader = remote_reader

    # -- gRPC admin service ------------------------------------------------
    def _register_rpc(self) -> None:
        self.rpc.add_service(
            "VolumeServer",
            unary={
                "AllocateVolume": self._rpc_allocate_volume,
                "VolumeDelete": self._rpc_volume_delete,
                "VolumeConfigureReplication":
                    self._rpc_configure_replication,
                "VolumeMarkReadonly": self._rpc_mark_readonly,
                "VolumeMarkWritable": self._rpc_mark_writable,
                "VolumeMount": self._rpc_volume_mount,
                "VolumeUnmount": self._rpc_volume_unmount,
                "VacuumVolumeCheck": self._rpc_vacuum_check,
                "VacuumVolumeCompact": self._rpc_vacuum_compact,
                "VacuumVolumeCommit": self._rpc_vacuum_commit,
                "VacuumVolumeCleanup": lambda req: {},
                "BatchDelete": self._rpc_batch_delete,
                "ReadVolumeFileStatus": self._rpc_volume_file_status,
                "VolumeServerStatus": self._rpc_server_status,
                "Ping": lambda req: {"ok": True},
                "VolumeServerLeave": self._rpc_server_leave,
                "VolumeCopy": self._rpc_volume_copy,
                "VolumeTierMoveDatToRemote": self._rpc_tier_move_to,
                "VolumeTierMoveDatFromRemote": self._rpc_tier_move_from,
                "VolumeEcShardsGenerate": self._rpc_ec_generate,
                "VolumeEcShardsRebuild": self._rpc_ec_rebuild,
                "VolumeEcShardsCopy": self._rpc_ec_copy,
                "VolumeEcShardsDelete": self._rpc_ec_delete,
                "VolumeEcShardsMount": self._rpc_ec_mount,
                "VolumeEcShardsUnmount": self._rpc_ec_unmount,
                "VolumeEcBlobDelete": self._rpc_ec_blob_delete,
                "VolumeEcShardsToVolume": self._rpc_ec_to_volume,
                "VolumeEcGeometry": self._rpc_ec_geometry,
                "VolumeNeedleDigest": self._rpc_needle_digest,
                "VolumeSyncFrom": self._rpc_volume_sync_from,
            },
            stream={
                "VolumeEcShardRead": self._rpc_ec_shard_read,
                "CopyFile": self._rpc_copy_file,
                "Query": self._rpc_query,
                "VolumeTailSender": self._rpc_volume_tail,
            })

    def _rpc_needle_digest(self, req: dict) -> dict:
        """Offset-free digest of the volume's live needles (the
        anti-entropy scrub's comparison unit, storage/scrub.py).
        deep=True re-reads every record with CRC verification — the
        bit-rot scan — and reports unreadable keys."""
        from ..storage import scrub
        return scrub.volume_digest(self._find_volume(req),
                                   deep=bool(req.get("deep")))

    def _rpc_volume_sync_from(self, req: dict) -> dict:
        """Reconcile this replica from an authoritative peer by tailing
        its VolumeTailSender stream (the repair planner's divergence
        fix): missing needles are written, divergent or bit-rotten ones
        overwritten, tombstones re-applied.  `only_keys` scopes the
        apply to those needle ids — the planner's bit-rot repair,
        which must touch nothing but the unreadable records."""
        from ..storage import scrub
        vid = int(req["volume_id"])
        v = self._find_volume(req)
        only = {int(k) for k in req.get("only_keys", [])} or None
        src = POOL.client(req["source_data_node"], "VolumeServer")
        applied = 0
        for r in src.stream("VolumeTailSender", iter([{
                "volume_id": vid,
                "since_ns": int(req.get("since_ns", 0))}])):
            if only is not None and int(r["needle_id"]) not in only:
                continue
            changed = scrub.apply_tail_record(
                v, int(r["needle_id"]), int(r["cookie"]),
                from_b64(r["needle_blob"]),
                is_delete=bool(r.get("is_delete")),
                is_compressed=bool(r.get("is_compressed")))
            if changed:
                applied += 1
                self.needle_cache.invalidate(vid, int(r["needle_id"]))
        if applied:
            # reconciled content changes the heartbeat counters; tell
            # the master now, not a pulse later
            self._hb_wake.set()
        return {"applied": applied}

    def _rpc_volume_tail(self, requests):
        """Stream needles appended after since_ns — the incremental
        backup/replica-catchup feed (volume_grpc_tail.go VolumeTailSender,
        operation/tail_volume.go)."""
        for req in requests:
            vid = int(req["volume_id"])
            since_ns = int(req.get("since_ns", 0))
            v = self.store.find_volume(vid)
            if v is None:
                raise RpcError(f"volume {vid} not found")
            for offset, n, body_len in v.scan_needles():
                try:
                    full = Needle.read_from(
                        v.data_backend, offset, n.size, v.version)
                except Exception as e:
                    # tail keeps streaming past one bad record, but the
                    # corruption itself must be visible to an operator
                    LOG.debug("tail skipping needle at offset %s in "
                              "volume %s: %s", offset, vid, e)
                    continue
                # append_at_ns lives in the record TRAILER (v3), so the
                # filter runs after the full read, not on the header scan
                if full.append_at_ns and full.append_at_ns <= since_ns:
                    continue
                yield {"needle_id": full.id, "cookie": full.cookie,
                       "append_at_ns": full.append_at_ns,
                       "is_delete": full.size == 0 and not full.data,
                       "is_compressed": full.is_compressed(),
                       "needle_blob": to_b64(bytes(full.data))}

    def _rpc_query(self, requests):
        """SQL-ish scan over JSON/CSV needles (S3 Select analogue,
        server/volume_grpc_query.go:12 + query/json/query_json.go).

        req: {"from": {"file_ids": [...]}, "selections": [fields],
              "where": {"field", "op" (=,!=,<,<=,>,>=,contains), "value"},
              "input_format": "json"|"csv"}"""
        import json as _json

        OPS = {"=", "!=", "contains", "<", "<=", ">", ">="}

        def matches(row: dict, where: dict) -> bool:
            if not where:
                return True
            field, op, want = (where.get("field"), where.get("op", "="),
                               where.get("value"))
            got = row.get(field)
            if op == "=":
                return got == want
            if op == "!=":
                return got != want
            if op == "contains":
                return isinstance(got, str) and str(want) in got
            try:
                got_n, want_n = float(got), float(want)
            except (TypeError, ValueError):
                return False
            return {"<": got_n < want_n, "<=": got_n <= want_n,
                    ">": got_n > want_n, ">=": got_n >= want_n}[op]

        for req in requests:
            selections = req.get("selections") or []
            where = req.get("where") or {}
            if where and where.get("op", "=") not in OPS:
                raise RpcError(
                    f"unsupported where.op {where.get('op')!r}; "
                    f"supported: {sorted(OPS)}")
            fmt = req.get("input_format", "json")
            for fid_s in req.get("from", {}).get("file_ids", []):
                try:
                    fid = FileId.parse(fid_s)
                    n = self._read_needle_any(fid)
                    raw = bytes(n.data)
                    if n.is_compressed():
                        # JSON/CSV are compressable types, so scanned
                        # needles are often stored gzipped — the parser
                        # must see the content, not the envelope
                        from ..util.compression import decompress
                        raw = decompress(raw)
                except Exception as e:
                    # malformed fid / missing needle / corrupt stored
                    # bytes: skip this one, keep scanning the rest
                    LOG.debug("query skipping %s: %s", fid_s, e)
                    continue
                text = raw.decode(errors="replace")
                rows: list = []
                if fmt == "json":
                    for line in text.splitlines():
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rows.append(_json.loads(line))
                        except ValueError:
                            continue
                else:  # csv with header row
                    import csv as _csv
                    import io as _io
                    rows = list(_csv.DictReader(_io.StringIO(text)))
                for row in rows:
                    if not isinstance(row, dict) or not matches(row, where):
                        continue
                    if selections:
                        row = {k: row.get(k) for k in selections}
                    yield {"record": row}

    # volume lifecycle
    def _rpc_allocate_volume(self, req: dict) -> dict:
        if not self._owns_vid(int(req["volume_id"])):
            # defense in depth: the supervisor routes by vid%N, so a
            # misrouted allocate means a partition-count mismatch —
            # creating the volume HERE would strand it invisibly
            raise RpcError(
                f"volume {req['volume_id']} belongs to worker "
                f"{self._worker.owner_of(int(req['volume_id']))}, "
                f"not {self._worker.index}")
        self.store.add_volume(
            int(req["volume_id"]), req.get("collection", ""),
            replica_placement=req.get("replication") or "000",
            ttl=req.get("ttl", ""))
        return {}

    def _rpc_volume_delete(self, req: dict) -> dict:
        self.store.delete_volume(int(req["volume_id"]))
        # coarse but rare: a recreated vid must never serve the old
        # volume's cached needles
        self.needle_cache.clear()
        # the repair loop's trim guard reads the master's topology:
        # this deletion must be visible there NOW, or a second trim of
        # the same volume still counts the removed copy
        self._hb_wake.set()
        return {}

    def _find_volume(self, req: dict):
        v = self.store.find_volume(int(req["volume_id"]))
        if v is None:
            raise RpcError(f"volume {req['volume_id']} not found")
        return v

    def _rpc_configure_replication(self, req: dict) -> dict:
        """Rewrite the superblock's replica-placement byte
        (volume_grpc_admin.go VolumeConfigureReplication)."""
        import dataclasses

        from ..storage.super_block import ReplicaPlacement
        v = self._find_volume(req)
        rp = ReplicaPlacement.parse(req["replication"])
        # replace() keeps every other superblock field (notably `extra`,
        # whose length the needle offsets depend on)
        v.super_block = dataclasses.replace(v.super_block,
                                            replica_placement=rp)
        v.data_backend.write_at(v.super_block.to_bytes(), 0)
        return {}

    def _rpc_mark_readonly(self, req: dict) -> dict:
        self._find_volume(req).read_only = True
        # nudge an immediate heartbeat so the master stops routing writes
        # here NOW, not a pulse later (the reference's delta channels give
        # the same promptness) — ec.encode freezes volumes via this RPC
        self._hb_wake.set()
        return {}

    def _rpc_mark_writable(self, req: dict) -> dict:
        self._find_volume(req).read_only = False
        self._hb_wake.set()
        return {}

    def _rpc_volume_mount(self, req: dict) -> dict:
        vid = int(req["volume_id"])
        for loc in self.store.locations:
            loc.load_existing_volumes()
            if vid in loc.volumes:
                return {}
        raise RpcError(f"volume {vid} files not found")

    def _rpc_volume_unmount(self, req: dict) -> dict:
        for loc in self.store.locations:
            loc.unload_volume(int(req["volume_id"]))
        # the .dat may be replaced while unmounted (volume copy/move)
        self.needle_cache.clear()
        return {}

    def _rpc_server_leave(self, req: dict) -> dict:
        """Stop heartbeating so the master unregisters this server and
        routes no new writes here; the data path stays up so an operator
        can still drain/copy volumes off (volume_grpc_admin.go
        VolumeServerLeave + shell command_volume_server_leave.go)."""
        self._leaving = True
        self._hb_wake.set()
        return {}

    def _rpc_volume_copy(self, req: dict) -> dict:
        """Pull a whole volume (.dat/.idx) from another server and mount it
        (volume_grpc_copy.go VolumeCopy)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        if self.store.has_volume(vid):
            raise RpcError(f"volume {vid} already exists here")
        loc = self.store.locations[0]
        base = volume_file_name(loc.directory, collection, vid)
        src = POOL.client(req["source_data_node"], "VolumeServer")
        # stream into .tmp files; only rename the pair once BOTH completed,
        # so a dead source never leaves a loadable truncated volume
        try:
            for ext in (".dat", ".idx"):
                with open(base + ext + ".tmp", "wb") as f:
                    for r in src.stream("CopyFile", iter([{
                            "volume_id": vid, "collection": collection,
                            "ext": ext}])):
                        f.write(r["file_content"])
        except Exception:
            for ext in (".dat", ".idx"):
                if os.path.exists(base + ext + ".tmp"):
                    os.remove(base + ext + ".tmp")
            raise
        for ext in (".dat", ".idx"):
            os.replace(base + ext + ".tmp", base + ext)
        loc.load_existing_volumes()
        if not self.store.has_volume(vid):
            raise RpcError(f"volume {vid} failed to load after copy")
        # the repair loop's MTTR depends on the master learning about
        # the new replica immediately, not a pulse later
        self._hb_wake.set()
        return {"last_append_at_ns": 0}

    # vacuum
    def _rpc_vacuum_check(self, req: dict) -> dict:
        v = self._find_volume(req)
        if v.read_only:
            # frozen (ec.encode snapshot in flight) or degraded (dying
            # disk): report clean so the master's sweep skips it — a
            # compact would swap .dat/.idx under the encoder's by-path
            # reads, or write .cpd to a disk that just failed
            return {"garbage_ratio": 0.0}
        return {"garbage_ratio": v.garbage_level()}

    def _rpc_vacuum_compact(self, req: dict) -> dict:
        v = self._find_volume(req)
        if v.read_only:
            raise RpcError(f"volume {v.id} is read-only "
                           f"(frozen/degraded); refusing compact")
        reclaimed = v.vacuum()
        return {"reclaimed_bytes": reclaimed}

    def _rpc_vacuum_commit(self, req: dict) -> dict:
        v = self._find_volume(req)
        return {"volume_size": v.content_size()}

    def _rpc_batch_delete(self, req: dict) -> dict:
        results = []
        for fid_s in req.get("file_ids", []):
            try:
                fid = FileId.parse(fid_s)
                size = self.store.delete_volume_needle(
                    fid.volume_id, fid.key,
                    None if req.get("skip_cookie_check") else fid.cookie)
                self.needle_cache.invalidate(fid.volume_id, fid.key)
                results.append({"file_id": fid_s, "status": 202,
                                "size": size})
            except Exception as e:
                results.append({"file_id": fid_s, "status": 500,
                                "error": str(e)})
        return {"results": results}

    def _rpc_volume_file_status(self, req: dict) -> dict:
        v = self._find_volume(req)
        return {
            "volume_id": v.id, "collection": v.collection,
            "dat_file_size": v.content_size(),
            "idx_file_size": v.nm.index_file_size(),
            "file_count": v.nm.file_count(),
            "compaction_revision": v.super_block.compaction_revision,
        }

    def _rpc_server_status(self, req: dict) -> dict:
        hb = self.store.collect_heartbeat()
        return {"volumes": [vars(v) for v in hb.volumes],
                "ec_shards": [{"id": e["id"],
                               "ec_index_bits": int(e["ec_index_bits"])}
                              for e in hb.ec_shards]}

    # -- tiering (volume_grpc_tier.go) -------------------------------------
    def _rpc_tier_move_to(self, req: dict) -> dict:
        """Push a sealed volume's .dat to remote storage and reopen it
        through the remote backend (VolumeTierMoveDatToRemote)."""
        from ..remote_storage import new_remote_storage
        from ..storage.tier import upload_volume_dat
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            raise RpcError(f"volume {vid} not found")
        if not v.read_only:
            raise RpcError(f"volume {vid} must be readonly before tiering")
        kind = req.get("destination_backend", "local")
        cfg = req.get("backend_config") or {}
        remote = new_remote_storage(kind, **cfg)
        v.sync()
        base = v.base_path
        collection = v.collection
        self.store.unload_volume(vid)
        upload_volume_dat(base, remote, kind, cfg,
                          keep_local=bool(req.get("keep_local_dat_file")))
        for loc in self.store.locations:
            loc.load_existing_volumes()
        if not self.store.has_volume(vid):
            raise RpcError(f"volume {vid} failed to reopen tiered")
        return {}

    def _rpc_tier_move_from(self, req: dict) -> dict:
        """Pull a tiered .dat back to local disk
        (VolumeTierMoveDatFromRemote)."""
        from ..storage.tier import untier_volume_dat
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            raise RpcError(f"volume {vid} not found")
        base = v.base_path
        self.store.unload_volume(vid)
        untier_volume_dat(base)
        for loc in self.store.locations:
            loc.load_existing_volumes()
        return {}

    # -- EC RPCs (volume_grpc_erasure_coding.go) ---------------------------
    def _base_path(self, vid: int, collection: str) -> str:
        import glob as _glob
        for loc in self.store.locations:
            base = volume_file_name(loc.directory, collection, vid)
            # geometry-independent probe: any shard file counts (wide
            # stripes reach .ec23 and beyond)
            if (os.path.exists(base + ".dat")
                    or os.path.exists(base + ".ecx")
                    or _glob.glob(base + ".ec[0-9][0-9]")):
                return base
        # fall back to the first location (for incoming copies)
        return volume_file_name(self.store.locations[0].directory,
                                collection, vid)

    def _rpc_ec_generate(self, req: dict) -> dict:
        """VolumeEcShardsGenerate (volume_grpc_erasure_coding.go:38): freeze
        the volume, write .ecx + shards + .vif via the TPU codec."""
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            raise RpcError(f"volume {vid} not found")
        # freeze + drain BEFORE snapshotting: the encoder reads .idx and
        # .dat by path outside the volume lock, so a straggler write
        # already past the orchestration's mark-readonly would otherwise
        # append AFTER the .ecx snapshot — an acked needle the EC volume
        # then doesn't index (the soak's lost-write sibling of the
        # stat/append race)
        v.freeze_writes()
        v.sync()
        # swap-point forensics: record the (map size, dat size) pair
        # this encode froze, under the orchestrator's trace id — if the
        # soak's SizeMismatchError needle maps to this window, the
        # ec.encode flow is the culprit (ROADMAP open item)
        LOG.info("ec encode volume %d trace=%s starting: map=%d needles "
                 "dat=%d bytes", vid,
                 tracing.current_trace_id() or "-", v.nm.file_count(),
                 v.content_size())
        geo = DEFAULT_GEOMETRY
        if req.get("data_shards") or req.get("code_kind"):
            # wide stripes RS(28,4)/RS(16,8) and the clay/lrc families
            # (BASELINE targets beyond the reference's fixed RS(10,4))
            from ..storage.ec.layout import EcGeometry
            geo = EcGeometry(
                data_shards=int(req.get("data_shards") or 10),
                parity_shards=int(req.get("parity_shards", 4)),
                code_kind=req.get("code_kind") or "rs",
                lrc_locals=int(req.get("lrc_locals", 0)))
        ec_pkg.encode_volume_to_ec(v.base_path, version=v.version, geo=geo)
        return {}

    def _rpc_ec_rebuild(self, req: dict) -> dict:
        """VolumeEcShardsRebuild: regenerate `shard_ids` from the local
        shards (every absent shard when not given), reading the set the
        shared planner names (storage/ec/plan.py).  The span carries
        `plan_kind`, `read_shards` (how many) and `bytes_read`."""
        base = self._base_path(int(req["volume_id"]),
                               req.get("collection", ""))
        stats: dict = {}
        shard_ids = req.get("shard_ids")
        rebuilt = ec_pkg.rebuild_ec_files(
            base, stats=stats,
            shard_ids=None if shard_ids is None
            else [int(s) for s in shard_ids])
        if stats:
            tracing.tag("plan_kind", stats["plan_kind"])
            tracing.add("read_shards", len(stats["read_shards"]))
            tracing.add("bytes_read", stats["bytes_read"])
        # stats surface the clay/LRC repair-IO advantage to operators
        # (bytes_read, plan_kind) — see storage/ec/codes.py — both in the
        # RPC reply (shell ec.rebuild prints it) and /metrics counters
        if rebuilt and stats.get("plan_kind"):
            self.metrics.ec_rebuilds.inc(stats["plan_kind"])
            self.metrics.ec_rebuild_bytes_read.inc(
                stats["plan_kind"], value=float(stats.get("bytes_read",
                                                          0)))
        return {"rebuilt_shard_ids": rebuilt, "rebuild_stats": stats}

    def _rpc_ec_copy(self, req: dict) -> dict:
        """Copy shard files from the source server via CopyFile streams
        (volume_grpc_erasure_coding.go:117-180).  The span's tags split
        its time: `recv_s` (waiting on the stream), `frame_s` (parsing
        each message's envelope), `write_s` (file writes and the final
        renames), `bytes` received and `raw_bytes`, the part of them that
        came raw (all of it: the chunks are `bytes` values).

        With `repair_planes_of` (the lost shard of a single clay loss,
        set by the shell's ec.rebuild) only each helper's repair planes
        are copied, to a plane file (`_ec_copy_planes`).

        With `source_tcp` (the source's frame port, set by the shell)
        each file comes over that port (`_pull_file`), not CopyFile."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        base = self._base_path(vid, collection)
        src = POOL.client(req["source_data_node"], "VolumeServer")
        if req.get("repair_planes_of") is not None:
            return self._ec_copy_planes(req, base, src)
        exts = [to_ext(int(s)) for s in req.get("shard_ids", [])]
        if req.get("copy_ecx_files", True):
            exts += [".ecx", ".ecj", ".vif"]
        for ext in exts:
            # stream to a .tmp and rename on success: constant memory for
            # multi-GB shards, and never a partial file under the real name
            tmp = base + ext + ".tmp"
            try:
                with open(tmp, "wb") as f:
                    self._pull_file(req, src, {
                        "volume_id": vid, "collection": collection,
                        "ext": ext}, f)
            except RpcError:
                if os.path.exists(tmp):
                    os.remove(tmp)
                if ext == ".ecj":  # journal may not exist yet
                    continue
                raise
            with tracing.stage("write"):
                os.replace(tmp, base + ext)
        return {}

    def _ec_copy_planes(self, req: dict, base: str, src) -> dict:
        """The plane copy of a clay repair: of each shard in `shard_ids`
        (a helper), the layers the repair of shard `repair_planes_of`
        reads, streamed by the source's CopyFile into a .tmp and renamed
        to its plane file (codes.plane_file), which no shard scan
        matches.  A stream whose length is not the shard size / q (a
        source that ignored the field sends the whole shard) fails the
        RPC, naming the source, and leaves no file of this call behind.
        The span carries `plane_layers` (beta) beside `bytes`."""
        import glob

        from ..ops import clay_matrix
        from ..storage.ec.codes import plane_file
        lost = int(req["repair_planes_of"])
        geo = ec_pkg.geometry_from_vif(base)
        if geo.code_kind != "clay":
            raise RpcError(f"repair planes of a {geo.code_kind} volume")
        code = clay_matrix.code(geo.data_shards, geo.parity_shards)
        tracing.tag("plane_layers", code.beta)
        local = sorted(glob.glob(glob.escape(base) + ".ec[0-9][0-9]"))
        if not local:
            raise RpcError(f"no shard of {base} here to size the repair "
                           f"planes by")
        want = os.path.getsize(local[0]) // code.q
        done: list[str] = []
        tmp = ""
        try:
            for s in req.get("shard_ids", []):
                dest = plane_file(base, int(s), lost)
                tmp = dest + ".tmp"
                with open(tmp, "wb") as f:
                    got = self._pull_file(req, src, {
                        "volume_id": req["volume_id"],
                        "collection": req.get("collection", ""),
                        "ext": to_ext(int(s)),
                        "repair_planes_of": lost}, f, limit=want)
                if got != want:
                    raise RpcError(
                        f"repair planes of shard {s} for shard {lost} from "
                        f"{req['source_data_node']}: "
                        f"{'over ' if got > want else ''}{got} bytes, "
                        f"want {want} (shard size / q {code.q})")
                with tracing.stage("write"):
                    os.replace(tmp, dest)
                done.append(dest)
        except BaseException:
            for p in done + [tmp]:
                if os.path.exists(p):
                    os.remove(p)
            raise
        return {}

    def _pull_file(self, req: dict, src, fields: dict, f,
                   limit: "int | None" = None) -> int:
        """One file of a VolumeEcShardsCopy into the open file `f`; ->
        bytes received, stopping once more than `limit` have arrived
        (none written past it).  Over the source's frame port when the
        request names one (`source_tcp`) and gRPC runs without TLS: the
        bytes leave by sendfile and land by recv_into, outside the
        interpreter lock (tcp.fetch_file).  A source that does not know
        the file copy, or whose port refuses the connection, is asked
        over CopyFile instead, as is every source under TLS."""
        from .tcp import CopyRefused, fetch_file
        addr = req.get("source_tcp", "")
        if addr and not rpc_tls_enabled():
            try:
                return fetch_file(addr, fields, f, limit)
            except CopyRefused as e:
                if not str(e).startswith("unknown op"):
                    raise RpcError(f"{fields['ext']} from {addr}: {e}") \
                        from None
            except ConnectionRefusedError:
                pass
            except OSError as e:
                raise RpcError(f"{fields['ext']} from {addr}: "
                               f"{type(e).__name__}: {e}") from None
        got = 0
        for r in src.stream("CopyFile", iter([fields])):
            data = r["file_content"]
            got += len(data)
            tracing.add("bytes", len(data))
            if limit is not None and got > limit:
                break
            with tracing.stage("write"):
                f.write(data)
        return got

    def _rpc_ec_delete(self, req: dict) -> dict:
        """VolumeEcShardsDelete: remove shard files, and the index files
        once no shard remains.  With `repair_planes_of` it removes only
        the plane files of `shard_ids` copied for that loss (ec.rebuild's
        clean-up after a failed plane repair)."""
        vid = int(req["volume_id"])
        base = self._base_path(vid, req.get("collection", ""))
        if req.get("repair_planes_of") is not None:
            from ..storage.ec.codes import plane_file
            for s in req.get("shard_ids", []):
                p = plane_file(base, int(s), int(req["repair_planes_of"]))
                if os.path.exists(p):
                    os.remove(p)
            return {}
        for s in req.get("shard_ids", []):
            p = base + to_ext(int(s))
            if os.path.exists(p):
                os.remove(p)
        # drop index files when no shards remain (volume_grpc_erasure_coding.go:205)
        total = ec_pkg.geometry_from_vif(base).total_shards
        if not any(os.path.exists(base + to_ext(s))
                   for s in range(total)):
            for ext in (".ecx", ".ecj", ".vif"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
        return {}

    def _rpc_ec_mount(self, req: dict) -> dict:
        self.store.mount_ec_shards(
            int(req["volume_id"]), req.get("collection", ""),
            [int(s) for s in req.get("shard_ids", [])])
        self._hb_wake.set()  # rebuilt/moved shards register this pulse
        return {}

    def _rpc_ec_unmount(self, req: dict) -> dict:
        self.store.unmount_ec_shards(
            int(req["volume_id"]),
            [int(s) for s in req.get("shard_ids", [])])
        return {}

    def _rpc_ec_blob_delete(self, req: dict) -> dict:
        vol = self.store.find_ec_volume(int(req["volume_id"]))
        if vol is not None:
            vol.delete_needle(int(req["file_key"]))
        return {}

    def _rpc_ec_to_volume(self, req: dict) -> dict:
        """Decode shards back into a normal volume and mount it
        (VolumeEcShardsToVolume)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        base = self._base_path(vid, collection)
        total = ec_pkg.geometry_from_vif(base).total_shards
        ec_pkg.decode_ec_to_volume(base)
        self.store.unmount_ec_shards(vid, list(range(total)))
        for loc in self.store.locations:
            loc.load_existing_volumes()
        v = self.store.find_volume(vid)
        if v is not None:
            # the decode just swapped a live volume into place: log the
            # (map size, dat size) pair it came up with (soak forensics)
            LOG.info("ec decode volume %d trace=%s mounted: map=%d "
                     "needles dat=%d bytes", vid,
                     tracing.current_trace_id() or "-",
                     v.nm.file_count(), v.content_size())
        return {}

    def _rpc_ec_geometry(self, req: dict) -> dict:
        """The stripe geometry recorded in .vif (wide-stripe support —
        maintenance tools must not assume 10+4).  Fails rather than guess
        when the .vif is absent/incomplete so callers probe another
        holder instead of shrinking a wide stripe to 14."""
        base = self._base_path(int(req["volume_id"]),
                               req.get("collection", ""))
        info = ec_pkg.load_volume_info(base)
        if "data_shards" not in info:
            raise RpcError(f"no geometry in .vif for volume "
                           f"{req['volume_id']} at {base}")
        return {"data_shards": info["data_shards"],
                "parity_shards": info["parity_shards"],
                "total_shards": info["data_shards"]
                + info["parity_shards"],
                "code_kind": info.get("code_kind", "rs"),
                "lrc_locals": info.get("lrc_locals", 0)}

    def _rpc_ec_shard_read(self, requests):
        """Stream shard bytes (VolumeEcShardRead volume_server.proto:82)."""
        for req in requests:
            vol = self.store.find_ec_volume(int(req["volume_id"]))
            if vol is None:
                raise RpcError(f"ec volume {req['volume_id']} not found")
            shard = vol.shards.get(int(req["shard_id"]))
            if shard is None:
                raise RpcError(f"shard {req['shard_id']} not local")
            offset, remaining = int(req["offset"]), int(req["size"])
            while remaining > 0:
                chunk = shard.read_at(min(remaining, 1 << 20), offset)
                if not chunk:
                    break
                yield {"data": chunk}
                offset += len(chunk)
                remaining -= len(chunk)

    def _read_needle_any(self, fid: FileId) -> Needle:
        """Needle from the normal volume OR its EC-encoded remnant (the
        same fallback the HTTP read path uses)."""
        if self.store.has_volume(fid.volume_id):
            return self.store.read_volume_needle(fid.volume_id, fid.key,
                                                 fid.cookie)
        if self.store.find_ec_volume(fid.volume_id) is not None:
            self._ensure_ec_remote_reader(fid.volume_id)
            return self.store.read_ec_needle(fid.volume_id, fid.key,
                                             fid.cookie)
        raise NotFoundError(f"volume {fid.volume_id} not found")

    def _rpc_copy_file(self, requests):
        """Stream any volume/shard file (CopyFile volume_server.proto:60).
        The span's tags split its time: `read_s` (disk), `frame_s`
        (building each message's envelope, serialized on this thread),
        `bytes` sent and `raw_bytes`, the part of them sent raw (all of
        it: each chunk is yielded as a `bytes` value).

        With `repair_planes_of` (a lost shard of a clay volume) it streams
        of the shard file `ext` only the layers that shard's repair reads
        (`_copy_planes`)."""
        for req in requests:
            base = self._base_path(int(req["volume_id"]),
                                   req.get("collection", ""))
            path = base + req["ext"]
            if not os.path.exists(path):
                raise RpcError(f"{path} not found")
            if req.get("repair_planes_of") is not None:
                yield from self._copy_planes(base, path, req)
                continue
            with open(path, "rb") as f:
                while True:
                    with tracing.stage("read"):
                        chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    tracing.add("bytes", len(chunk))
                    yield {"file_content": chunk}

    def _copy_planes(self, base: str, path: str, req: dict):
        """CopyFile's plane branch: window by window, the beta layers of
        the helper shard at `path` that the repair of shard
        `repair_planes_of` reads, in rebuild_clay's order
        (codes.iter_repair_planes: a memmap and a take), several windows
        to a message of at most 1 MiB.  The span carries `plane_layers`
        (beta) beside `bytes`."""
        from ..ops import clay_matrix
        from ..storage.ec.codes import iter_repair_planes
        geo = ec_pkg.geometry_from_vif(base)
        lost = int(req["repair_planes_of"])
        ext = req["ext"]
        if geo.code_kind != "clay":
            raise RpcError(f"repair planes of a {geo.code_kind} volume")
        if not (0 <= lost < geo.total_shards) or ext == to_ext(lost) \
                or not ext.startswith(".ec") or not ext[3:].isdigit():
            raise RpcError(f"no repair planes of {ext} for shard {lost}")
        tracing.tag("plane_layers", clay_matrix.code(
            geo.data_shards, geo.parity_shards).beta)
        planes = iter_repair_planes(path, geo, lost)
        while True:
            with tracing.stage("read"):
                chunk = next(planes, None)
            if chunk is None:
                break
            tracing.add("bytes", chunk.nbytes)
            yield {"file_content": memoryview(chunk.reshape(-1))}

    def tcp_copy_file(self, req: dict, sock, start) -> None:
        """CopyFile over the frame port ('C', volume_server/tcp.py) for
        VolumeEcShardsCopy: the EC files of a volume only, and not while
        gRPC runs under TLS (the frame port has none).  Calls `start()`
        once the request checks out, then sends the file: whole by
        os.sendfile, or with `repair_planes_of` the chunks of
        `_copy_planes`.  With the request's `trace_id` the copy is a
        `VolumeServer/CopyFile` span under `parent_span_id`, tagged
        `transport: tcp`, carrying `send_s`, `frame_s`, `bytes`,
        `raw_bytes` (and the plane gather's `read_s`)."""
        tid = tracing.clamp_id(str(req.get("trace_id", "")))
        if not (tid and tracing.enabled()):
            self._send_copy(req, sock, start)
            return
        sid = tracing.new_span_id()
        tags: dict = {}
        t0 = time.time()            # span start: wall
        p0 = time.perf_counter()    # duration: monotonic
        status = "ok"
        try:
            with tracing.trace_scope(tid, sid, tags):
                self._send_copy(req, sock, start)
        except BaseException:
            status = "error"
            raise
        finally:
            if self.tracer is not None:
                self.tracer.record(
                    "VolumeServer/CopyFile", tid, t0,
                    time.perf_counter() - p0, status=status,
                    slow_log=False, span_id=sid,
                    parent_id=tracing.clamp_id(
                        str(req.get("parent_span_id", ""))),
                    transport="tcp", **tracing.copy_tags(tags))

    def _send_copy(self, req: dict, sock, start) -> None:
        from .tcp import (COPY_CHUNK, end_copy, send_copy_chunk,
                          sendfile_copy_chunks)
        if rpc_tls_enabled():
            raise ValueError("no file copy over the frame port under "
                             "TLS: use CopyFile")
        ext = str(req.get("ext", ""))
        collection = str(req.get("collection", ""))
        if not _EC_FILE_EXT.fullmatch(ext) or "/" in collection:
            raise ValueError(f"no frame-port copy of {collection!r} "
                             f"{ext!r}: EC files of a volume only")
        base = self._base_path(int(req["volume_id"]), collection)
        path = base + ext
        if not os.path.exists(path):
            raise ValueError(f"{path} not found")
        if req.get("repair_planes_of") is None:
            with open(path, "rb") as f:
                start()
                if sendfile_enabled():
                    sendfile_copy_chunks(sock, f)
                    return
                while chunk := f.read(COPY_CHUNK):
                    send_copy_chunk(sock, chunk)
                    tracing.add("bytes", len(chunk))
                    tracing.add("raw_bytes", len(chunk))
                end_copy(sock)
            return
        # a generator: its checks run at the first next(), before start()
        chunks = self._copy_planes(base, path, req)
        msg = next(chunks, None)
        start()
        while msg is not None:
            send_copy_chunk(sock, msg["file_content"])
            tracing.add("raw_bytes", len(msg["file_content"]))
            msg = next(chunks, None)
        end_copy(sock)
