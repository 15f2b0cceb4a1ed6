"""Process-sharded volume data plane — N worker processes behind one
logical volume server (ISSUE 12).

One Python process serves every small-file op on one core, with the
GIL as its wall, where the Go reference spreads over every core.  The
unlock is horizontal: shard the serving plane across real OS processes so each
worker owns a core, and keep the cluster's view of the node unchanged.

Architecture
------------
- ``ShardedVolumeServer`` (the supervisor) lives in the parent process.
  It owns the logical gRPC address (routing per-volume admin RPCs to
  the owning worker), a small admin HTTP server that merges worker
  ``/status`` + ``/metrics`` pages (re-using the PR 9 federation
  relabeler per worker), the worker process table (spawn, readiness,
  crash respawn), and ONE merged heartbeat stream to the master — the
  master sees a single DataNode whose volume list is the union of the
  workers' partitions.
- Workers are REAL subprocesses started with ``subprocess`` (exec, not
  ``os.fork`` — forking a threaded server replays every held lock into
  the child; weedlint WL110 enforces the discipline).  Each worker runs
  a full ``VolumeServer`` whose "master" is the supervisor's gRPC
  surface: the existing heartbeat loop, lookup TTL caches and fan-out
  machinery work unmodified, with the supervisor aggregating heartbeats
  and proxying lookups to the real master (rewriting the logical node's
  location to the owning worker so replica fan-out stays worker-true).
- Partitioning is by volume id: worker ``i`` of ``N`` owns every vid
  with ``vid % N == i`` and roots its Store in a private
  ``<dir>/workers/<i>`` subdirectory — disjoint volume/needle-cache/
  store state by construction, no cross-process locking on the hot
  path.  ``rebalance_partitions`` moves volume files between worker
  subdirectories when ``N`` changes (and adopts files from a previous
  single-process layout).
- The public HTTP data port is SHARED: every worker binds it with
  SO_REUSEPORT and the kernel load-balances connections.  Where
  SO_REUSEPORT is unavailable (or WEED_VOLUME_REUSEPORT=0), the
  supervisor falls back to accept-and-pass: it accepts on the shared
  port and hands connected fds to workers round-robin over a unix
  socket via ``socket.send_fds``.
- A request landing on the wrong worker is forwarded to the owner over
  the worker's private HTTP/TCP port (volume_server/server.py worker
  hooks).  The TCP fast path rarely needs the forward: each worker has
  its own frame port and the merged heartbeat stamps every volume with
  its owner's ``tcp_port``, so master lookups/assigns hand clients a
  vid-accurate frame address (operation's per-vid _TCP_ROUTE and the
  wdclient vid map pick it up for free).

``WEED_VOLUME_WORKERS`` picks the worker count for the CLI: unset/``1``
keeps today's single-process server byte-identical; ``0``/``auto``
means one worker per core.
"""

from __future__ import annotations

import itertools
import json
import os
import re
import signal
import socket
import struct
import subprocess
import sys
import threading
from ..util import locks
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError
from dataclasses import dataclass, field

from ..pb.rpc import POOL, RpcError, RpcServer
from ..util.http import HttpServer, Request, Response, http_request
from ..util.weedlog import logger
from .hb_delta import HeartbeatDeltaEncoder

LOG = logger(__name__)

PULSE_SECONDS = 5

# files that belong to one volume id: <base>.<ext> with base parsed by
# parse_volume_base_name; .ecNN covers wide stripes up to 99 shards
_VOLUME_FILE_RE = re.compile(
    r"^(?P<base>.+?)\.(?P<ext>dat|idx|tier|vif|ecx|ecj|cpd|cpx|ec\d{2})$")


def resolve_worker_count(value: "str | int | None") -> int:
    """WEED_VOLUME_WORKERS semantics: unset/1 -> 1 (byte-identical
    single process), 0/'auto' -> one worker per core, N -> N."""
    if value is None:
        value = os.environ.get("WEED_VOLUME_WORKERS", "1")
    try:
        n = int(value)
    except (TypeError, ValueError):
        n = 0 if str(value).strip().lower() == "auto" else 1
    if n <= 0:
        n = os.cpu_count() or 1
    return max(1, n)


def reuseport_available() -> bool:
    if os.environ.get("WEED_VOLUME_REUSEPORT", "1") == "0":
        return False
    return hasattr(socket, "SO_REUSEPORT")


def worker_partition_dir(directory: str, index: int) -> str:
    return os.path.join(directory, "workers", str(index))


def rebalance_partitions(directories: list[str], count: int) -> int:
    """Move volume files into the worker subdirectory their vid hashes
    to (vid % count) — run by the supervisor BEFORE spawning workers,
    so a worker-count change (or a previous single-process layout in
    the bare directory) never strands volumes where no worker looks.
    Returns the number of files moved."""
    moved = 0
    for directory in directories:
        sources = [directory]
        workers_root = os.path.join(directory, "workers")
        if os.path.isdir(workers_root):
            for name in sorted(os.listdir(workers_root)):
                sub = os.path.join(workers_root, name)
                if name.isdigit() and os.path.isdir(sub):
                    sources.append(sub)
        for src in sources:
            for fname in sorted(os.listdir(src)):
                m = _VOLUME_FILE_RE.match(fname)
                if m is None:
                    continue
                from ..storage.volume import parse_volume_base_name
                try:
                    _, vid = parse_volume_base_name(m.group("base"))
                except ValueError:
                    continue
                dst_dir = worker_partition_dir(directory, vid % count)
                if os.path.abspath(src) == os.path.abspath(dst_dir):
                    continue
                os.makedirs(dst_dir, exist_ok=True)
                os.replace(os.path.join(src, fname),
                           os.path.join(dst_dir, fname))
                moved += 1
    return moved


@dataclass
class WorkerContext:
    """What one worker knows about its siblings — carried in the spawn
    config, duck-typed by volume_server/server.py's worker hooks."""
    index: int
    count: int
    shared_port: int
    host: str = "127.0.0.1"
    peer_http: dict = field(default_factory=dict)   # index -> http port
    peer_tcp: dict = field(default_factory=dict)    # index -> tcp port
    supervisor_admin: str = ""                      # host:port (merge)
    reuseport: bool = True
    supervisor_uds: str = ""                        # fd-pass fallback

    def owns(self, vid: int) -> bool:
        return vid % self.count == self.index

    def owner_of(self, vid: int) -> int:
        return vid % self.count

    def peer_http_addr(self, vid: int) -> str:
        return f"{self.host}:{self.peer_http[self.owner_of(vid)]}"

    def peer_tcp_addr(self, vid: int) -> str:
        return f"{self.host}:{self.peer_tcp[self.owner_of(vid)]}"


def _free_port(host: str) -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _PortShim:
    """Duck-type for `vs.tcp.port` style access on the supervisor (the
    SimCluster fault verbs key on it)."""

    def __init__(self, port: int = 0):
        self.port = port


class ShardedVolumeServer:
    """Supervisor for N volume-server worker processes presenting ONE
    logical volume server to the cluster.  Constructor-compatible with
    VolumeServer so SimCluster and the CLI swap it in transparently."""

    def __init__(self, master_grpc: str, directories: list[str],
                 host: str = "127.0.0.1", port: int = 0,
                 grpc_port: int = 0, public_url: str = "",
                 data_center: str = "", rack: str = "",
                 max_volume_counts: "list[int] | None" = None,
                 pulse_seconds: float = PULSE_SECONDS,
                 jwt_signing_key: str = "", workers: int = 2,
                 reuseport: "bool | None" = None):
        self._masters = [m.strip() for m in master_grpc.split(",")
                         if m.strip()]
        self.master_grpc = self._masters[0]
        self.host = host
        self.directories = [os.path.abspath(d) for d in directories]
        self.data_center = data_center
        self.rack = rack
        self.jwt_signing_key = jwt_signing_key
        self.pulse_seconds = pulse_seconds
        self.workers = max(2, int(workers))
        self._public_url = public_url
        self._max_volume_counts = max_volume_counts \
            or [7] * len(self.directories)
        self.reuseport = reuseport_available() if reuseport is None \
            else bool(reuseport)
        self.rpc = RpcServer(host, grpc_port)
        self.http = HttpServer(host, 0)   # admin: merged status/metrics
        self._register_rpc()
        self._register_http()
        # shared data port: reserve it with a bound-but-never-listening
        # SO_REUSEPORT socket so the number survives until every worker
        # has joined the reuseport group (no free_port()-style race); in
        # fallback mode this same socket becomes the accept-and-pass
        # listener
        self._shared_sock = socket.socket(socket.AF_INET,
                                          socket.SOCK_STREAM)
        self._shared_sock.setsockopt(socket.SOL_SOCKET,
                                     socket.SO_REUSEADDR, 1)
        if self.reuseport:
            self._shared_sock.setsockopt(socket.SOL_SOCKET,
                                         socket.SO_REUSEPORT, 1)
        self._shared_sock.bind((host, port))
        self.shared_port = self._shared_sock.getsockname()[1]
        # worker table
        self._worker_ports: dict[int, dict] = {}
        self._procs: dict[int, subprocess.Popen] = {}
        self._worker_hb: dict[int, dict] = {}
        self._hb_port_to_idx: dict[int, int] = {}
        self.restarts: dict[int, int] = {}
        self._cfg_paths: dict[int, str] = {}
        # fd-pass fallback state
        self._uds_path = ""
        self._uds_sock: "socket.socket | None" = None
        self._fd_conns: dict[int, socket.socket] = {}
        self._fd_lock = locks.Lock("ShardedVolumeServer._fd_lock")
        self._fd_rr = itertools.count()
        # merged heartbeat stream state (mirrors VolumeServer's)
        self.volume_size_limit = 0
        self._stop = threading.Event()
        self._leaving = False
        self._hb_wake = threading.Event()
        self._hb_gen = 0
        self._hb_acked_gen = 0
        self._hb_inflight: list[int] = []
        self._hb_delta = HeartbeatDeltaEncoder()
        self._threads: list[threading.Thread] = []
        self._monitor_thread: "threading.Thread | None" = None
        self.tcp = _PortShim()
        # persistent admin fan-out pool: the merged /debug/profile must
        # sample every worker CONCURRENTLY (N sequential fetches would
        # multiply the profile window by N), and per-call executors are
        # the churn PR 5 removed from the data plane
        # >= one thread per worker: the merged profile's windows must
        # overlap, and a pool smaller than the worker count would
        # serialize the tail into a DIFFERENT (later) sampling window
        self._admin_pool = ThreadPoolExecutor(
            max_workers=max(8, self.workers),
            thread_name_prefix="vsup-admin")

    # -- addresses ---------------------------------------------------------
    @property
    def url(self) -> str:
        return f"{self.host}:{self.shared_port}"

    @property
    def grpc_address(self) -> str:
        return self.rpc.address

    @property
    def admin_address(self) -> str:
        return self.http.address

    def worker_http_addr(self, i: int) -> str:
        return f"{self.host}:{self._worker_ports[i]['http']}"

    def worker_tcp_addr(self, i: int) -> str:
        return f"{self.host}:{self._worker_ports[i]['tcp']}"

    def worker_grpc_addr(self, i: int) -> str:
        return f"{self.host}:{self._worker_ports[i]['grpc']}"

    def owner_of(self, vid: int) -> int:
        return vid % self.workers

    # -- lifecycle ---------------------------------------------------------
    def start(self, ready_timeout: float = 60.0) -> None:
        rebalance_partitions(self.directories, self.workers)
        self.rpc.start()
        self.http.start()
        for i in range(self.workers):
            self._worker_ports[i] = {
                "http": _free_port(self.host),
                "grpc": _free_port(self.host),
                "tcp": _free_port(self.host),
            }
            self._hb_port_to_idx[self._worker_ports[i]["http"]] = i
        self.tcp = _PortShim(self._worker_ports[0]["tcp"])
        if not self.reuseport:
            self._start_fd_pass()
        LOG.warning("volume workers (%d) run EC on the CPU codec with "
                    "JAX_PLATFORMS=cpu; EC on an accelerator needs a "
                    "single-process volume server (WEED_VOLUME_WORKERS=1)",
                    self.workers)
        for i in range(self.workers):
            self._spawn_worker(i)
        self._wait_ready(ready_timeout)
        t = threading.Thread(target=self._heartbeat_loop, daemon=True,
                             name="vsup-heartbeat")
        t.start()
        self._threads.append(t)
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, daemon=True, name="vsup-monitor")
        self._monitor_thread.start()
        self._threads.append(self._monitor_thread)

    def stop(self) -> None:
        self._stop.set()
        # join the monitor BEFORE signalling workers: a respawn racing
        # the SIGTERM sweep would install a brand-new subprocess that
        # nothing ever terminates (the monitor also re-checks _stop
        # after each spawn and kills its own late respawn)
        monitor = getattr(self, "_monitor_thread", None)
        if monitor is not None and monitor.is_alive():
            monitor.join(timeout=5.0)
        for sock in ([self._shared_sock] if self._shared_sock else []):
            try:
                sock.close()
            except OSError as e:
                LOG.debug("shared socket close failed: %s", e)
        if self._uds_sock is not None:
            try:
                self._uds_sock.close()
            except OSError as e:
                LOG.debug("uds close failed: %s", e)
        for i, proc in list(self._procs.items()):
            if proc.poll() is None:
                try:
                    proc.send_signal(signal.SIGTERM)
                except OSError as e:
                    LOG.debug("worker %d SIGTERM failed: %s", i, e)
        deadline = time.time() + 5.0
        for i, proc in list(self._procs.items()):
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                LOG.warning("worker %d ignored SIGTERM; killing", i)
                proc.kill()
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired as e:
                    LOG.warning("worker %d unkillable: %s", i, e)
        self.rpc.stop()
        self.http.stop()
        self._admin_pool.shutdown(wait=False)

    # -- worker processes --------------------------------------------------
    def _worker_config(self, i: int) -> dict:
        ports = self._worker_ports[i]
        per_dir = []
        for total in self._max_volume_counts:
            base = max(1, total // self.workers)
            extra = 1 if i < (total - base * self.workers) else 0
            per_dir.append(base + extra)
        return {
            "supervisor_grpc": self.grpc_address,
            "supervisor_admin": self.admin_address,
            "directories": self.directories,
            "host": self.host,
            "index": i,
            "workers": self.workers,
            "shared_port": self.shared_port,
            "http_port": ports["http"],
            "grpc_port": ports["grpc"],
            "tcp_port": ports["tcp"],
            "peer_http": {str(j): p["http"]
                          for j, p in self._worker_ports.items()},
            "peer_tcp": {str(j): p["tcp"]
                         for j, p in self._worker_ports.items()},
            "data_center": self.data_center,
            "rack": self.rack,
            "jwt_signing_key": self.jwt_signing_key,
            "pulse_seconds": self.pulse_seconds,
            "max_volume_counts": per_dir,
            "reuseport": self.reuseport,
            "supervisor_uds": self._uds_path,
        }

    def _spawn_worker(self, i: int) -> None:
        state_dir = os.path.join(self.directories[0], "workers")
        os.makedirs(state_dir, exist_ok=True)
        cfg_path = os.path.join(state_dir, f"worker{i}.json")
        with open(cfg_path, "w", encoding="utf-8") as f:
            json.dump(self._worker_config(i), f)
        self._cfg_paths[i] = cfg_path
        env = dict(os.environ)
        # one process per chip: N workers cannot share one TPU, and a
        # worker that lost the race for it would fall back to the CPU
        # in silence.  Workers never open the accelerator; their EC
        # runs on the CPU codec, which start() logs and /status shows.
        env["JAX_PLATFORMS"] = "cpu"
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep \
            + env.get("PYTHONPATH", "")
        log_path = os.path.join(state_dir, f"worker{i}.log")
        with open(log_path, "ab") as log_f:
            self._procs[i] = subprocess.Popen(
                [sys.executable, "-m",
                 "seaweedfs_tpu.volume_server.workers",
                 "--config", cfg_path],
                env=env, stdout=log_f, stderr=subprocess.STDOUT)
        LOG.info("spawned volume worker %d/%d pid=%d (http=%d tcp=%d)",
                 i, self.workers, self._procs[i].pid,
                 self._worker_ports[i]["http"],
                 self._worker_ports[i]["tcp"])

    def _worker_ready(self, i: int) -> bool:
        try:
            status, _, _ = http_request(
                f"http://{self.worker_http_addr(i)}/status"
                "?worker_local=1", timeout=2.0)
            return status == 200
        except (OSError, ConnectionError):
            return False

    def _wait_ready(self, timeout: float) -> None:
        deadline = time.time() + timeout
        pending = set(range(self.workers))
        while pending and time.time() < deadline:
            for i in list(pending):
                proc = self._procs.get(i)
                if proc is not None and proc.poll() is not None:
                    raise RuntimeError(
                        f"volume worker {i} exited with "
                        f"{proc.returncode} during startup (log: "
                        f"{self.directories[0]}/workers/worker{i}.log)")
                if self._worker_ready(i):
                    pending.discard(i)
            if pending:
                time.sleep(0.05)
        if pending:
            raise TimeoutError(
                f"volume workers {sorted(pending)} never became ready")
        # the FIRST merged full-sync must carry every partition: a
        # payload missing a worker would register the node with half
        # its volumes and the next full sync would unregister the rest
        # cluster-wide.  Workers pulse immediately after start, so
        # this converges in milliseconds — a miss is a startup failure,
        # not something to shrug past.
        deadline = time.time() + timeout
        while len(self._worker_hb) < self.workers:
            if time.time() >= deadline:
                missing = sorted(set(range(self.workers))
                                 - set(self._worker_hb))
                raise TimeoutError(
                    f"volume workers {missing} never delivered their "
                    "first heartbeat to the supervisor")
            time.sleep(0.02)

    def _monitor_loop(self) -> None:
        """Crash supervision: a dead worker is respawned on the SAME
        ports (routing maps, fd-pass registrations and the master's
        per-volume tcp routing all stay valid)."""
        while not self._stop.wait(0.25):
            for i, proc in list(self._procs.items()):
                if proc.poll() is None or self._stop.is_set():
                    continue
                self.restarts[i] = self.restarts.get(i, 0) + 1
                LOG.warning("volume worker %d died (exit %s); "
                            "respawning (restart #%d)", i,
                            proc.returncode, self.restarts[i])
                with self._fd_lock:
                    dead = self._fd_conns.pop(i, None)
                if dead is not None:
                    try:
                        dead.close()
                    except OSError as e:
                        LOG.debug("dead worker uds close: %s", e)
                # the last heartbeat payload is KEPT during the respawn
                # window: a merged full-sync missing this partition
                # would make the master unregister (and publish
                # deleted_vids for) every volume the worker still has
                # on disk — a few seconds of stale advertisement beats
                # cluster-wide lookup churn; the respawned worker's
                # first pulse replaces it
                self._spawn_worker(i)
                if self._stop.is_set():
                    # stop() raced the respawn: this process is OURS to
                    # reap, nothing else knows it exists
                    self._procs[i].terminate()
                    return
                try:
                    self._wait_worker(i, timeout=30.0)
                except (TimeoutError, RuntimeError) as e:
                    LOG.warning("worker %d respawn not ready yet: %s",
                                i, e)
                # the respawned worker's volumes must re-register with
                # the master promptly
                self._hb_wake.set()
                # record the respawn in the cluster's durable event
                # timeline (observability v3) — best effort, the
                # monitor must keep supervising through a dead master
                try:
                    POOL.client(self.master_grpc, "Seaweed").call(
                        "ClusterEventAppend", {
                            "type": "worker.respawn",
                            "severity": "warning",
                            "message": f"volume worker {i} of "
                                       f"{self.url} respawned "
                                       f"(restart #{self.restarts[i]}, "
                                       f"exit {proc.returncode})",
                            "fields": {"server": self.url, "worker": i,
                                       "restarts": self.restarts[i],
                                       "exit_code": proc.returncode
                                       if proc.returncode is not None
                                       else -1}},
                        timeout=5)
                except RpcError as e:
                    LOG.debug("worker.respawn event emit failed: %s", e)

    def _wait_worker(self, i: int, timeout: float) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self._stop.is_set():
                return   # shutting down; stop() reaps the process
            if self._worker_ready(i):
                return
            proc = self._procs.get(i)
            if proc is not None and proc.poll() is not None:
                raise RuntimeError(
                    f"worker {i} exited {proc.returncode} while "
                    "restarting")
            time.sleep(0.05)
        raise TimeoutError(f"worker {i} not ready after {timeout}s")

    # -- test/ops verbs ----------------------------------------------------
    def kill_worker(self, i: int, sig: int = signal.SIGKILL) -> int:
        """Hard-kill one worker (crash drill).  Returns the pid killed;
        the monitor loop respawns it on the same ports."""
        proc = self._procs[i]
        pid = proc.pid
        proc.send_signal(sig)
        return pid

    def wait_worker_restarted(self, i: int, old_pid: int,
                              timeout: float = 30.0) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            proc = self._procs.get(i)
            if proc is not None and proc.pid != old_pid \
                    and proc.poll() is None and self._worker_ready(i):
                return
            time.sleep(0.05)
        raise TimeoutError(f"worker {i} did not restart in {timeout}s")

    def status(self) -> dict:
        return {
            "workers": self.workers,
            "shared_port": self.shared_port,
            "reuseport": self.reuseport,
            "fallback": "" if self.reuseport else "send_fds",
            "restarts": dict(self.restarts),
            "pids": {i: p.pid for i, p in self._procs.items()
                     if p.poll() is None},
            "ports": {i: dict(p) for i, p in self._worker_ports.items()},
        }

    # -- accept-and-pass fallback (no SO_REUSEPORT) ------------------------
    def _start_fd_pass(self) -> None:
        self._uds_path = os.path.join(self.directories[0], "workers",
                                      "sup.sock")
        os.makedirs(os.path.dirname(self._uds_path), exist_ok=True)
        if os.path.exists(self._uds_path):
            os.remove(self._uds_path)
        self._uds_sock = socket.socket(socket.AF_UNIX,
                                       socket.SOCK_STREAM)
        self._uds_sock.bind(self._uds_path)
        self._uds_sock.listen(self.workers + 2)
        self._shared_sock.listen(128)
        t = threading.Thread(target=self._uds_registrar, daemon=True,
                             name="vsup-uds")
        t.start()
        self._threads.append(t)
        t = threading.Thread(target=self._fd_pass_accept_loop,
                             daemon=True, name="vsup-accept")
        t.start()
        self._threads.append(t)

    def _uds_registrar(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._uds_sock.accept()
                idx = struct.unpack("<B", conn.recv(1))[0]
            except (OSError, struct.error):
                if self._stop.is_set():
                    return
                continue
            with self._fd_lock:
                old = self._fd_conns.pop(idx, None)
                self._fd_conns[idx] = conn
            if old is not None:
                try:
                    old.close()
                except OSError as e:
                    LOG.debug("stale worker uds close: %s", e)
            LOG.info("worker %d registered for accept-and-pass", idx)

    def _fd_pass_accept_loop(self) -> None:
        """The supervisor accepts on the shared port and passes each
        connected fd to a worker round-robin (socket.send_fds) — the
        kernel-less cousin of SO_REUSEPORT distribution.  Wrong-worker
        requests forward exactly as in reuseport mode."""
        from ..util.retry import RetryPolicy
        backoff = RetryPolicy(base_delay=0.05, max_delay=1.0)
        failures = 0
        while not self._stop.is_set():
            try:
                conn, _ = self._shared_sock.accept()
                failures = 0
            except OSError as e:
                if self._stop.is_set():
                    return
                # transient accept failures (EMFILE, ECONNABORTED)
                # must not kill the logical node's ONLY data-port
                # listener; only a closed socket is terminal
                import errno
                if e.errno in (errno.EBADF, errno.EINVAL):
                    return
                failures += 1
                LOG.warning("shared-port accept failed (%d "
                            "consecutive): %s", failures, e)
                time.sleep(backoff.backoff(min(failures, 6)))
                continue
            passed = False
            for _ in range(self.workers):
                idx = next(self._fd_rr) % self.workers
                with self._fd_lock:
                    uds = self._fd_conns.get(idx)
                if uds is None:
                    continue
                try:
                    socket.send_fds(uds, [b"c"], [conn.fileno()])
                    passed = True
                    break
                except OSError as e:
                    LOG.debug("fd pass to worker %d failed: %s", idx, e)
                    with self._fd_lock:
                        self._fd_conns.pop(idx, None)
            if not passed:
                LOG.warning("no worker available for accepted "
                            "connection; dropping")
            try:
                conn.close()   # the worker holds its own duplicate now
            except OSError as e:
                LOG.debug("post-pass close failed: %s", e)

    # -- worker-facing Seaweed service (heartbeat fan-in, lookup proxy) ----
    def _register_rpc(self) -> None:
        self.rpc.add_service(
            "Seaweed",
            unary={
                "LookupVolume": self._rpc_lookup_volume,
                "LookupEcVolume": self._rpc_lookup_ec_volume,
                "GetMasterConfiguration": self._rpc_master_config,
            },
            stream={"SendHeartbeat": self._rpc_worker_heartbeat})
        route = self._route_unary
        self.rpc.add_service(
            "VolumeServer",
            unary={
                "AllocateVolume": route("AllocateVolume"),
                "VolumeDelete": route("VolumeDelete"),
                "VolumeConfigureReplication":
                    route("VolumeConfigureReplication"),
                "VolumeMarkReadonly": route("VolumeMarkReadonly"),
                "VolumeMarkWritable": route("VolumeMarkWritable"),
                "VolumeMount": route("VolumeMount"),
                "VolumeUnmount": route("VolumeUnmount"),
                "VacuumVolumeCheck": route("VacuumVolumeCheck"),
                "VacuumVolumeCompact": route("VacuumVolumeCompact"),
                "VacuumVolumeCommit": route("VacuumVolumeCommit"),
                "VacuumVolumeCleanup": route("VacuumVolumeCleanup"),
                "BatchDelete": self._rpc_batch_delete,
                "ReadVolumeFileStatus": route("ReadVolumeFileStatus"),
                "VolumeServerStatus": self._rpc_server_status,
                "Ping": lambda req: {"ok": True},
                "VolumeServerLeave": self._rpc_server_leave,
                "VolumeCopy": route("VolumeCopy"),
                "VolumeTierMoveDatToRemote":
                    route("VolumeTierMoveDatToRemote"),
                "VolumeTierMoveDatFromRemote":
                    route("VolumeTierMoveDatFromRemote"),
                "VolumeEcShardsGenerate": route("VolumeEcShardsGenerate"),
                "VolumeEcShardsRebuild": route("VolumeEcShardsRebuild"),
                "VolumeEcShardsCopy": route("VolumeEcShardsCopy"),
                "VolumeEcShardsDelete": route("VolumeEcShardsDelete"),
                "VolumeEcShardsMount": route("VolumeEcShardsMount"),
                "VolumeEcShardsUnmount": route("VolumeEcShardsUnmount"),
                "VolumeEcBlobDelete": route("VolumeEcBlobDelete"),
                "VolumeEcShardsToVolume": route("VolumeEcShardsToVolume"),
                "VolumeEcGeometry": route("VolumeEcGeometry"),
                "VolumeNeedleDigest": route("VolumeNeedleDigest"),
                "VolumeSyncFrom": route("VolumeSyncFrom"),
            },
            stream={
                "VolumeEcShardRead": self._route_stream("VolumeEcShardRead"),
                "CopyFile": self._route_stream("CopyFile"),
                "VolumeTailSender": self._route_stream("VolumeTailSender"),
                "Query": self._rpc_query,
            })

    def _worker_client(self, vid: int):
        return POOL.client(self.worker_grpc_addr(self.owner_of(vid)),
                           "VolumeServer")

    def _route_unary(self, method: str):
        def handler(req: dict) -> dict:
            vid = int(req.get("volume_id", 0))
            return self._worker_client(vid).call(method, req)
        return handler

    def _route_stream(self, method: str):
        def handler(requests):
            first = next(iter(requests), None)
            if first is None:
                return
            vid = int(first.get("volume_id", 0))
            yield from self._worker_client(vid).stream(
                method, itertools.chain([first], requests))
        return handler

    def _rpc_query(self, requests):
        """Query scans by file id, so one request may span partitions:
        split the fid list per owning worker and concatenate."""
        for req in requests:
            fids = req.get("from", {}).get("file_ids", [])
            by_worker: dict[int, list[str]] = {}
            for fid_s in fids:
                try:
                    vid = int(str(fid_s).split(",", 1)[0])
                except ValueError:
                    continue
                by_worker.setdefault(self.owner_of(vid), []).append(fid_s)
            for idx, sub in sorted(by_worker.items()):
                sub_req = dict(req)
                sub_req["from"] = dict(req.get("from", {}),
                                       file_ids=sub)
                client = POOL.client(self.worker_grpc_addr(idx),
                                     "VolumeServer")
                yield from client.stream("Query", iter([sub_req]))

    def _rpc_batch_delete(self, req: dict) -> dict:
        by_worker: dict[int, list[str]] = {}
        for fid_s in req.get("file_ids", []):
            try:
                vid = int(str(fid_s).split(",", 1)[0])
            except ValueError:
                by_worker.setdefault(0, []).append(fid_s)
                continue
            by_worker.setdefault(self.owner_of(vid), []).append(fid_s)
        results_by_fid: dict[str, dict] = {}
        for idx, sub in sorted(by_worker.items()):
            client = POOL.client(self.worker_grpc_addr(idx),
                                 "VolumeServer")
            sub_req = dict(req, file_ids=sub)
            for r in client.call("BatchDelete", sub_req)["results"]:
                results_by_fid[r["file_id"]] = r
        return {"results": [results_by_fid[f]
                            for f in req.get("file_ids", [])
                            if f in results_by_fid]}

    def _rpc_server_status(self, req: dict) -> dict:
        volumes: list = []
        ec_shards: list = []
        for i in range(self.workers):
            client = POOL.client(self.worker_grpc_addr(i),
                                 "VolumeServer")
            try:
                out = client.call("VolumeServerStatus", req)
            except RpcError as e:
                LOG.warning("worker %d status failed: %s", i, e)
                continue
            volumes.extend(out.get("volumes", []))
            ec_shards.extend(out.get("ec_shards", []))
        return {"volumes": volumes, "ec_shards": ec_shards}

    def _rpc_server_leave(self, req: dict) -> dict:
        self._leaving = True
        self._hb_wake.set()
        return {}

    def _rpc_master_config(self, req: dict) -> dict:
        return POOL.client(self.master_grpc, "Seaweed").call(
            "GetMasterConfiguration", req)

    def _rpc_lookup_volume(self, req: dict) -> dict:
        """Proxy to the real master, then rewrite the LOGICAL node's
        location to the owning worker's private addresses: a worker's
        replica fan-out must target its sibling directly (its own url
        filters out naturally when it IS the owner), never bounce a
        write back through the shared port."""
        out = POOL.client(self.master_grpc, "Seaweed").call(
            "LookupVolume", req)
        logical = self.url
        for id_s, entry in out.get("volume_id_locations", {}).items():
            try:
                vid = int(str(id_s).split(",", 1)[0])
            except ValueError:
                continue
            owner = self.owner_of(vid)
            for loc in entry.get("locations", []):
                if loc.get("url") != logical:
                    continue
                loc["url"] = self.worker_http_addr(owner)
                loc["public_url"] = loc["url"]
                loc["tcp_url"] = self.worker_tcp_addr(owner)
        return out

    def _rpc_lookup_ec_volume(self, req: dict) -> dict:
        return POOL.client(self.master_grpc, "Seaweed").call(
            "LookupEcVolume", req)

    def _rpc_worker_heartbeat(self, requests):
        idx: "int | None" = None
        for hb in requests:
            if idx is None:
                idx = self._hb_port_to_idx.get(int(hb.get("port", 0)))
                if idx is None:
                    raise RpcError(
                        f"unknown worker heartbeat port {hb.get('port')}")
            self._worker_hb[idx] = hb
            # bubble the delta up: the merged stream pushes promptly so
            # a degraded volume still reaches the master within ~one
            # pulse end-to-end
            self._hb_wake.set()
            yield {"volume_size_limit": self.volume_size_limit,
                   "leader": ""}

    # -- merged heartbeat to the real master -------------------------------
    def _merged_payload(self) -> dict:
        volumes: list = []
        ec_shards: list = []
        max_vc = 0
        max_key = 0
        for i in sorted(self._worker_hb):
            hb = self._worker_hb[i]
            tcp_port = self._worker_ports[i]["tcp"]
            for v in hb.get("volumes", []):
                v = dict(v)
                # per-volume worker routing: lookups/assigns hand
                # clients the OWNER's frame port, not a node-level one
                v["tcp_port"] = tcp_port
                volumes.append(v)
            ec_shards.extend(hb.get("ec_shards", []))
            max_vc += int(hb.get("max_volume_count", 0))
            max_key = max(max_key, int(hb.get("max_file_key", 0)))
        return {
            "ip": self.host, "port": self.shared_port,
            "grpc_port": self.rpc.port,
            "tcp_port": self._worker_ports[0]["tcp"]
            if self._worker_ports else 0,
            "public_url": self._public_url or self.url,
            "data_center": self.data_center, "rack": self.rack,
            "max_volume_count": max_vc, "max_file_key": max_key,
            "volumes": volumes, "ec_shards": ec_shards,
        }

    def _heartbeat_loop(self) -> None:
        target_idx = 0
        while not self._stop.is_set() and not self._leaving:
            try:
                client = POOL.client(self.master_grpc, "Seaweed")
                # new connection → first payload must be a full snapshot
                self._hb_delta.reset()

                def requests():
                    while not self._stop.is_set() and not self._leaving:
                        self._hb_inflight.append(self._hb_gen)
                        yield self._hb_delta.encode(self._merged_payload())
                        self._hb_wake.wait(self.pulse_seconds)
                        self._hb_wake.clear()

                for reply in client.stream("SendHeartbeat", requests()):
                    if self._hb_inflight:
                        self._hb_acked_gen = self._hb_inflight.pop(0)
                    self._hb_delta.note_reply(reply)
                    if reply.get("resync"):
                        self._hb_wake.set()  # re-register this pulse
                    if reply.get("volume_size_limit"):
                        self.volume_size_limit = \
                            reply["volume_size_limit"]
                    leader = reply.get("leader", "")
                    if leader and leader != self.master_grpc \
                            and self._leader_reachable(leader):
                        self.master_grpc = leader
                        self._hb_inflight.clear()
                        break
                    if self._stop.is_set():
                        break
            except RpcError:
                self._hb_inflight.clear()
                target_idx = (target_idx + 1) % len(self._masters)
                self.master_grpc = self._masters[target_idx]
            self._stop.wait(1.0)

    def _leader_reachable(self, leader: str) -> bool:
        if leader in self._masters:
            return True
        try:
            POOL.client(leader, "Seaweed").call(
                "GetMasterConfiguration", {}, timeout=2.0)
            return True
        except RpcError:
            return False

    def heartbeat_now(self, timeout: float = 5.0) -> None:
        """Wait for the master to ack a merged payload built after this
        call — but first pull a FRESH snapshot from every worker, so the
        merged payload reflects mutations the caller just made through
        the data plane."""
        for i in range(self.workers):
            try:
                status, body, _ = http_request(
                    f"http://{self.worker_http_addr(i)}/heartbeat_now"
                    "?worker_local=1", method="POST", body=b"",
                    timeout=timeout)
                if status != 200:
                    LOG.debug("worker %d heartbeat_now: HTTP %d", i,
                              status)
            except (OSError, ConnectionError) as e:
                LOG.debug("worker %d heartbeat_now failed: %s", i, e)
        self._hb_gen += 1
        want = self._hb_gen
        self._hb_wake.set()
        deadline = time.time() + timeout
        while self._hb_acked_gen < want and time.time() < deadline:
            self._hb_wake.set()
            time.sleep(0.01)

    # -- admin HTTP (merged observability) ---------------------------------
    def _register_http(self) -> None:
        self.http.route("GET", "/status", self._http_status, exact=True)
        self.http.route("GET", "/metrics", self._http_metrics,
                        exact=True)
        self.http.route("GET", "/workers", self._http_workers,
                        exact=True)
        self.http.route("GET", "/heat", self._http_heat, exact=True)
        # debug parity (ISSUE 14): tracing/profiling must not go dark
        # behind the supervisor — merged by default, one partition via
        # ?worker=<i>
        self.http.route("GET", "/debug/traces",
                        self._http_debug_traces, exact=True)
        self.http.route("GET", "/debug/profile",
                        self._http_debug_profile, exact=True)

    def _fetch_worker(self, i: int, path: str, qs: str = "",
                      timeout: float = 5.0) -> tuple:
        url = f"http://{self.worker_http_addr(i)}{path}?worker_local=1"
        if qs:
            url += "&" + qs
        return http_request(url, timeout=timeout)

    def _http_status(self, req: Request) -> Response:
        merged = {"Version": "seaweedfs-tpu", "Volumes": [],
                  "Workers": self.status(), "NeedleCache": []}
        for i in range(self.workers):
            try:
                status, body, _ = self._fetch_worker(i, "/status")
                if status != 200:
                    raise OSError(f"HTTP {status}")
                d = json.loads(body)
            except (OSError, ConnectionError, ValueError) as e:
                merged.setdefault("Errors", {})[str(i)] = str(e)
                continue
            merged["Volumes"].extend(d.get("Volumes", []))
            merged["NeedleCache"].append(d.get("NeedleCache", {}))
            merged.setdefault("Ec", []).append(d.get("Ec"))
        return Response.json(merged)

    def _http_metrics(self, req: Request) -> Response:
        """Merged exposition: each worker's page relabeled with
        worker="<i>" via the PR 9 federation relabeler, family metadata
        emitted once."""
        from ..master.observe import relabel_exposition
        lines: list[str] = []
        meta: dict[str, list] = {}
        up: dict[int, int] = {}
        for i in range(self.workers):
            try:
                status, body, _ = self._fetch_worker(i, "/metrics")
                if status != 200:
                    raise OSError(f"HTTP {status}")
                up[i] = 1
            except (OSError, ConnectionError) as e:
                LOG.debug("worker %d metrics fetch failed: %s", i, e)
                up[i] = 0
                continue
            sample_lines, fam_meta = relabel_exposition(
                body.decode(errors="replace"), f"worker{i}")
            lines.extend(sample_lines)
            for fam, m in fam_meta.items():
                meta.setdefault(fam, m)
        out: list[str] = []
        emitted: set[str] = set()
        for line in lines:
            fam = line.split("{", 1)[0].rstrip()
            base = fam
            for suffix in ("_bucket", "_sum", "_count", "_total"):
                if base.endswith(suffix):
                    base = base[: -len(suffix)]
                    break
            for fam_name in (fam, base):
                if fam_name in meta and fam_name not in emitted:
                    out.extend(meta[fam_name])
                    emitted.add(fam_name)
            out.append(line)
        out.append("# HELP seaweedfs_volume_worker_up worker process "
                   "answering its admin scrape")
        out.append("# TYPE seaweedfs_volume_worker_up gauge")
        for i, v in sorted(up.items()):
            out.append(f'seaweedfs_volume_worker_up{{worker="{i}"}} {v}')
        # crash supervision is only trustworthy if respawns are
        # countable: the alert plane reads this next to worker_up
        out.append("# HELP seaweedfs_volume_worker_respawn_total "
                   "worker processes respawned by the supervisor")
        out.append("# TYPE seaweedfs_volume_worker_respawn_total "
                   "counter")
        for i in range(self.workers):
            out.append(f'seaweedfs_volume_worker_respawn_total'
                       f'{{worker="{i}"}} {self.restarts.get(i, 0)}')
        return Response(200, ("\n".join(out) + "\n").encode(),
                        content_type="text/plain; version=0.0.4")

    def _http_workers(self, req: Request) -> Response:
        return Response.json(self.status())

    def _http_heat(self, req: Request) -> Response:
        """Merged heat for the logical node: every partition's sketches
        folded through util/sketch.merge_snapshots — the same merge the
        master applies across servers, so worker -> supervisor ->
        master grouping is associative by construction."""
        from ..util.sketch import merge_snapshots
        qs = "freq=0" if req.qs("freq") == "0" else ""
        snaps: list[dict] = []
        errors: dict[str, str] = {}
        for i in range(self.workers):
            try:
                status, body, _ = self._fetch_worker(i, "/heat", qs=qs)
                if status != 200:
                    raise OSError(f"HTTP {status}")
                snaps.append(json.loads(body))
            except (OSError, ConnectionError, ValueError) as e:
                errors[str(i)] = str(e)
        merged = merge_snapshots(snaps)
        merged["workers"] = {"up": len(snaps), "of": self.workers}
        if errors:
            merged["workers"]["errors"] = errors
        return Response.json(merged)

    # -- debug parity: traces + profile through the supervisor -------------
    @staticmethod
    def _passthrough_qs(req: Request) -> str:
        return urllib.parse.urlencode(
            [(k, v) for k, vals in req.query.items() for v in vals
             if k not in ("worker", "worker_local")])

    def _select_worker(self, req: Request) -> "int | None":
        sel = req.qs("worker")
        if sel == "":
            return None
        try:
            i = int(sel)
        except ValueError:
            raise ValueError(f"?worker= must be 0..{self.workers - 1}")
        if not 0 <= i < self.workers:
            raise ValueError(f"?worker= must be 0..{self.workers - 1}")
        return i

    def _http_debug_traces(self, req: Request) -> Response:
        """Merged span rings (every span stamped with its worker), or
        one partition's raw page via ?worker=<i>."""
        qs = self._passthrough_qs(req)
        try:
            sel = self._select_worker(req)
        except ValueError as e:
            return Response.error(str(e), 400)
        if sel is not None:
            status, body, _ = self._fetch_worker(sel, "/debug/traces",
                                                 qs)
            return Response(status, body, content_type="application/json")
        merged = {"spans": [], "workers": {}}
        for i in range(self.workers):
            try:
                status, body, _ = self._fetch_worker(i, "/debug/traces",
                                                     qs)
                if status != 200:
                    raise OSError(f"HTTP {status}")
                d = json.loads(body)
            except (OSError, ConnectionError, ValueError) as e:
                merged["workers"][str(i)] = {"error": str(e)}
                continue
            spans = d.get("spans", [])
            for s in spans:
                s["worker"] = i
            merged["spans"].extend(spans)
            merged["workers"][str(i)] = {"span_count": len(spans)}
        merged["span_count"] = len(merged["spans"])
        return Response.json(merged)

    def _http_debug_profile(self, req: Request) -> Response:
        """Merged collapsed-stack profile: every worker sampled
        CONCURRENTLY for the same window, stacks prefixed with
        worker<i>; so a flamegraph shows the partition split.
        ?worker=<i> passes one partition's page through untouched."""
        try:
            seconds = float(req.qs("seconds", "1") or 1)
        except ValueError:
            return Response.error("seconds must be a number", 400)
        timeout = max(10.0, seconds + 10.0)
        qs = self._passthrough_qs(req)
        try:
            sel = self._select_worker(req)
        except ValueError as e:
            return Response.error(str(e), 400)
        if sel is not None:
            status, body, rhdrs = self._fetch_worker(
                sel, "/debug/profile", qs, timeout=timeout)
            keep = {k: v for k, v in rhdrs.items()
                    if k.lower().startswith("x-profile-")}
            return Response(status, body, content_type="text/plain",
                            headers=keep)
        futs = {i: self._admin_pool.submit(
                    self._fetch_worker, i, "/debug/profile", qs,
                    timeout)
                for i in range(self.workers)}
        lines: list[str] = []
        samples = 0
        errors: dict[str, str] = {}
        for i, fut in futs.items():
            try:
                status, body, rhdrs = fut.result(timeout=timeout + 5)
                if status != 200:
                    raise OSError(f"HTTP {status}")
            # FutureTimeoutError is NOT a TimeoutError subclass until
            # 3.11 — without it a slow worker 500s the whole merge
            except (OSError, ConnectionError, TimeoutError,
                    FutureTimeoutError) as e:
                errors[str(i)] = str(e)
                continue
            try:
                samples += int(rhdrs.get("X-Profile-Samples", "0"))
            except ValueError:
                pass
            for line in body.decode(errors="replace").splitlines():
                stack, _, count = line.rpartition(" ")
                if stack and count.isdigit():
                    lines.append(f"worker{i};{stack} {count}")
        headers = {"X-Profile-Samples": str(samples),
                   "X-Profile-Workers": str(self.workers)}
        if errors:
            headers["X-Profile-Errors"] = json.dumps(errors)
        return Response(200, ("\n".join(lines) + "\n").encode(),
                        content_type="text/plain", headers=headers)


# -- worker process entrypoint ----------------------------------------------

def _bind_shared_reuseport(host: str, port: int) -> socket.socket:
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    sock.bind((host, port))
    sock.listen(128)
    return sock


def _fd_receive_loop(vs, ctx: WorkerContext,
                     stop: threading.Event) -> None:
    """Accept-and-pass client side: register with the supervisor over
    its unix socket, then adopt every fd it sends into the worker's
    HTTP serving loop."""
    while not stop.is_set():
        try:
            with socket.socket(socket.AF_UNIX,
                               socket.SOCK_STREAM) as uds:
                uds.connect(ctx.supervisor_uds)
                uds.sendall(struct.pack("<B", ctx.index))
                while not stop.is_set():
                    msg, fds, _flags, _addr = socket.recv_fds(uds, 16,
                                                              8)
                    if not msg and not fds:
                        raise ConnectionError("supervisor closed uds")
                    for fd in fds:
                        # ownership transfers: serve_socket's conn
                        # thread closes the adopted socket when the
                        # peer is done
                        conn = socket.socket(fileno=fd)  # weedlint: disable=WL040
                        vs.http.serve_socket(conn)
        except OSError as e:
            LOG.debug("fd receive loop reconnecting: %s", e)
            if stop.wait(0.2):
                return


def run_worker(cfg: dict) -> int:
    """One worker process: a full VolumeServer over this partition's
    private directories, homed on the supervisor as its 'master'."""
    from .server import VolumeServer
    ctx = WorkerContext(
        index=int(cfg["index"]), count=int(cfg["workers"]),
        shared_port=int(cfg["shared_port"]), host=cfg["host"],
        peer_http={int(k): int(v)
                   for k, v in cfg.get("peer_http", {}).items()},
        peer_tcp={int(k): int(v)
                  for k, v in cfg.get("peer_tcp", {}).items()},
        supervisor_admin=cfg.get("supervisor_admin", ""),
        reuseport=bool(cfg.get("reuseport", True)),
        supervisor_uds=cfg.get("supervisor_uds", ""))
    dirs = [worker_partition_dir(d, ctx.index)
            for d in cfg["directories"]]
    for d in dirs:
        os.makedirs(d, exist_ok=True)
    vs = VolumeServer(
        cfg["supervisor_grpc"], dirs, host=cfg["host"],
        port=int(cfg["http_port"]), grpc_port=int(cfg["grpc_port"]),
        tcp_port=int(cfg["tcp_port"]),
        data_center=cfg.get("data_center", ""),
        rack=cfg.get("rack", ""),
        max_volume_counts=[int(c)
                           for c in cfg.get("max_volume_counts", [7])],
        pulse_seconds=float(cfg.get("pulse_seconds", PULSE_SECONDS)),
        jwt_signing_key=cfg.get("jwt_signing_key", ""),
        worker=ctx)
    vs.start()
    stop = threading.Event()
    shared_sock = None
    if ctx.reuseport:
        shared_sock = _bind_shared_reuseport(ctx.host, ctx.shared_port)
        vs.http.add_listener(shared_sock)
    else:
        threading.Thread(target=_fd_receive_loop, args=(vs, ctx, stop),
                         daemon=True, name="vs-fd-receive").start()
    woke = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: woke.set())
        except (ValueError, OSError) as e:
            LOG.debug("signal handler install failed: %s", e)
    LOG.info("volume worker %d/%d serving: shared=%s private http=%s "
             "tcp=%d grpc=%s", ctx.index, ctx.count,
             f"{ctx.host}:{ctx.shared_port}"
             + ("" if ctx.reuseport else " (fd-pass)"),
             vs.url, vs.tcp.port, vs.grpc_address)
    woke.wait()
    stop.set()
    vs.stop()
    return 0


def main(argv: "list[str] | None" = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="seaweedfs-tpu volume worker (internal; spawned by "
                    "ShardedVolumeServer)")
    ap.add_argument("--config", required=True,
                    help="path to the supervisor-written worker config")
    args = ap.parse_args(argv)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    return run_worker(cfg)


if __name__ == "__main__":
    sys.exit(main())
