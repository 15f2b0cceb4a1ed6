"""CLI — `python -m seaweedfs_tpu <command>` (the reference's `weed` binary,
weed/command/command.go:10-43).

Implemented commands: master, volume, filer, s3, server (all-in-one),
shell (interactive + -c one-shot), upload, download, delete, benchmark,
scaffold, version.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def _wait_forever():
    """Block until SIGINT/SIGTERM, then return so the caller runs its
    orderly .stop() chain and exits 0 (the real-process cluster gate
    asserts that clean-shutdown contract)."""
    woke = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: woke.set())
        except (ValueError, OSError):  # non-main thread / platform quirk
            pass
    try:
        woke.wait()
    except KeyboardInterrupt:
        pass
    finally:
        # restore defaults so a SECOND signal can still kill a shutdown
        # that wedges in the callers' .stop() chain
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                signal.signal(sig, signal.SIG_DFL)
            except (ValueError, OSError):
                pass


def cmd_master(args) -> int:
    from ..master import MasterServer
    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    m = MasterServer(host=args.ip, port=args.port, grpc_port=args.grpc_port,
                     volume_size_limit_mb=args.volume_size_limit_mb,
                     default_replication=args.default_replication,
                     jwt_signing_key=resolve_jwt_key(args.jwt_key),
                     peers=peers,
                     event_dir=getattr(args, "event_dir", "") or None)
    m.start()
    print(f"master http {m.address} grpc {m.grpc_address}")
    _wait_forever()
    m.stop()
    return 0


def cmd_volume(args) -> int:
    from ..volume_server.workers import resolve_worker_count
    workers = resolve_worker_count(getattr(args, "workers", None))
    if workers > 1:
        # process-sharded data plane: N workers share the data port
        # behind one logical server (volume_server/workers.py)
        from ..volume_server.workers import ShardedVolumeServer
        vs = ShardedVolumeServer(
            args.mserver, args.dir.split(","), host=args.ip,
            port=args.port, grpc_port=args.grpc_port,
            data_center=args.data_center, rack=args.rack,
            max_volume_counts=[int(c) for c in args.max.split(",")],
            jwt_signing_key=resolve_jwt_key(args.jwt_key),
            workers=workers)
        vs.start()
        print(f"volume server http {vs.url} grpc {vs.grpc_address} "
              f"({workers} workers, "
              f"{'reuseport' if vs.reuseport else 'accept-and-pass'})")
        _wait_forever()
        vs.stop()
        return 0
    from ..volume_server import VolumeServer
    vs = VolumeServer(args.mserver, args.dir.split(","),
                      host=args.ip, port=args.port,
                      grpc_port=args.grpc_port,
                      data_center=args.data_center, rack=args.rack,
                      max_volume_counts=[int(c) for c in
                                         args.max.split(",")],
                      jwt_signing_key=resolve_jwt_key(args.jwt_key))
    vs.start()
    print(f"volume server http {vs.url} grpc {vs.grpc_address}")
    _wait_forever()
    vs.stop()
    return 0


def cmd_filer(args) -> int:
    from ..filer import FilerServer
    f = FilerServer(args.master, host=args.ip, port=args.port,
                    grpc_port=args.grpc_port,
                    store_kind=args.store, store_path=args.store_path,
                    collection=args.collection,
                    replication=args.default_replication,
                    encrypt_data=args.encrypt_volume_data)
    f.start()
    print(f"filer http {f.address} grpc {f.grpc_address}")
    _wait_forever()
    f.stop()
    return 0


def cmd_s3(args) -> int:
    from ..s3 import IdentityAccessManagement, S3ApiServer
    if args.config:
        with open(args.config) as fh:
            iam = IdentityAccessManagement.from_config(json.load(fh))
    else:
        iam = IdentityAccessManagement()
    from ..pb import ServerAddress
    filer = ServerAddress.parse(args.filer)
    audit = None
    if args.auditLog:
        from ..s3.audit import AuditLog
        audit = AuditLog(args.auditLog)
    s3 = S3ApiServer(filer.url, filer.grpc, host=args.ip, port=args.port,
                     iam=iam, audit_log=audit)
    s3.start()
    print(f"s3 api {s3.address}"
          + (f" (audit log: {args.auditLog})" if audit else ""))
    _wait_forever()
    s3.stop()
    if audit:
        audit.close()
    return 0


def cmd_server(args) -> int:
    """All-in-one master + volume + filer (+ s3) (command/server.go)."""
    from ..filer import FilerServer
    from ..master import MasterServer
    from ..s3 import S3ApiServer
    from ..volume_server import VolumeServer
    # gRPC rides the http port + 10000 convention (pb/server_address.go)
    m = MasterServer(host=args.ip, port=args.master_port,
                     grpc_port=args.master_port + 10000,
                     jwt_signing_key=resolve_jwt_key(args.jwt_key))
    m.start()
    vs = VolumeServer(m.grpc_address, args.dir.split(","), host=args.ip,
                      port=args.volume_port,
                      max_volume_counts=[int(c) for c in
                                         args.max.split(",")],
                      jwt_signing_key=resolve_jwt_key(args.jwt_key))
    vs.start()
    store_path = args.filer_store_path
    if store_path is None:
        # default the metadata DB into the data dir so two all-in-one
        # servers in one cwd don't silently share a store
        store_path = os.path.join(args.dir.split(",")[0], "filer.db")
    f = FilerServer(m.grpc_address, host=args.ip, port=args.filer_port,
                    grpc_port=args.filer_port + 10000,
                    store_kind=args.filer_store,
                    store_path=store_path,
                    encrypt_data=getattr(args, "encrypt_volume_data",
                                         False))
    f.start()
    parts = [f"master {m.address} (grpc {m.grpc_address})",
             f"volume {vs.url}", f"filer {f.address}"]
    s3srv = None
    if args.s3:
        audit = None
        if getattr(args, "s3_audit_log", ""):
            from ..s3.audit import AuditLog
            audit = AuditLog(args.s3_audit_log)
        s3srv = S3ApiServer(f.address, f.grpc_address, host=args.ip,
                            port=args.s3_port, audit_log=audit)
        s3srv.start()
        parts.append(f"s3 {s3srv.address}")
    print("server started: " + ", ".join(parts))
    _wait_forever()
    if s3srv:
        s3srv.stop()
    f.stop()
    vs.stop()
    m.stop()
    return 0


def cmd_shell(args) -> int:
    from ..shell import CommandEnv, ShellError, run_command
    env = CommandEnv(args.master)
    if args.command:
        try:
            print(run_command(env, args.command))
            return 0
        except ShellError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
    print("seaweedfs-tpu shell; `help` lists commands, `exit` quits")
    while True:
        try:
            line = input("> ").strip()
        except (EOFError, KeyboardInterrupt):
            break
        if line in ("exit", "quit"):
            break
        if not line:
            continue
        try:
            print(run_command(env, line))
        except ShellError as e:
            print(f"error: {e}")
        except Exception as e:
            print(f"error: {type(e).__name__}: {e}")
    env.unlock()
    return 0


def cmd_upload(args) -> int:
    from .. import operation
    for path in args.files:
        with open(path, "rb") as fh:
            data = fh.read()
        record = {"fileName": path, "size": len(data)}
        compressed = False
        if args.cipher:
            # blob uploads have no filer entry to hold the key, so it is
            # printed for the caller to keep (download -cipherKey).
            # No gzip under -cipher: the needle flag can't be set on an
            # opaque sealed box, and download has no chunk record
            from ..util import cipher as cipher_mod
            data, record["cipherKey"] = cipher_mod.seal(data)
        else:
            # auto-gzip compressible files like the reference's upload
            # path; the needle flag drives read-side negotiation
            from ..util import compression
            data, compressed = compression.maybe_gzip(
                data, ext=os.path.splitext(path)[1])
        fid = operation.assign_and_upload(
            args.master, data, replication=args.replication,
            collection=args.collection, ttl=args.ttl,
            compressed=compressed)
        record["fid"] = fid
        print(json.dumps(record))
    return 0


def cmd_download(args) -> int:
    from .. import operation
    if args.cipher_key and len(args.fids) > 1:
        # upload -cipher mints a DISTINCT key per file; one key cannot
        # open several fids, so fail before writing anything
        print("-cipherKey opens exactly one fid (each upload -cipher "
              "record carries its own key)", file=sys.stderr)
        return 1
    if args.output and len(args.fids) > 1:
        print("-o names one output file; downloading several fids into "
              "it would keep only the last", file=sys.stderr)
        return 1
    for fid in args.fids:
        # stored=False: no chunk record here — the volume server decodes
        # compressed needles by its own flag
        data = operation.read_file(args.master, fid, stored=False)
        if args.cipher_key:
            from ..util import cipher as cipher_mod
            try:
                data = cipher_mod.maybe_decrypt(data, args.cipher_key)
            except cipher_mod.CipherError as e:
                print(f"{fid}: {e}", file=sys.stderr)
                return 1
        out = args.output or fid.replace(",", "_")
        with open(out, "wb") as fh:
            fh.write(data)
        print(f"{fid} -> {out} ({len(data)} bytes)")
    return 0


def cmd_delete(args) -> int:
    from .. import operation
    for fid in args.fids:
        operation.delete_file(args.master, fid)
        print(f"deleted {fid}")
    return 0


def cmd_benchmark(args) -> int:
    from .benchmark import run_benchmark, run_benchmark_mp
    if args.p > 1:
        run_benchmark_mp(args.master, n_files=args.n,
                         file_size=args.size, processes=args.p,
                         collection=args.collection,
                         write_only=args.write_only)
    else:
        run_benchmark(args.master, n_files=args.n, file_size=args.size,
                      concurrency=args.c, collection=args.collection,
                      write_only=args.write_only)
    return 0


def cmd_backup(args) -> int:
    """Incremental volume backup (command/backup.go): pull needles
    appended since the last run via VolumeTailSender into a local copy."""
    from .. import operation
    from ..pb.rpc import POOL, from_b64
    from ..shell.commands import iter_data_nodes, node_grpc
    from ..storage.needle import Needle
    from ..storage.volume import Volume
    vid = args.volumeId
    locs = operation.lookup_volume(args.master, vid)
    if not locs:
        print(f"volume {vid} not found", file=sys.stderr)
        return 1
    # find the holder's gRPC address from the master topology
    topo = POOL.client(args.master, "Seaweed").call("VolumeList")["topology"]
    holder_grpc = None
    for _, _, dn in iter_data_nodes(topo):
        if any(v["id"] == vid for v in dn["volumes"]) \
                and dn["id"] == locs[0]["url"]:
            holder_grpc = node_grpc(dn)
    if holder_grpc is None:
        print(f"no gRPC address for volume {vid} holder", file=sys.stderr)
        return 1
    os.makedirs(args.dir, exist_ok=True)
    ts_path = os.path.join(args.dir, f"{vid}.last_ts")
    since = 0
    if os.path.exists(ts_path):
        with open(ts_path) as fh:
            since = int(fh.read().strip() or 0)
    v = Volume(args.dir, args.collection, vid)
    client = POOL.client(holder_grpc, "VolumeServer")
    pulled = 0
    last_ts = since
    for r in client.stream("VolumeTailSender",
                           iter([{"volume_id": vid,
                                  "since_ns": since}])):
        n = Needle(id=int(r["needle_id"]), cookie=int(r["cookie"]),
                   data=from_b64(r["needle_blob"]))
        if r.get("is_delete"):
            v.delete_needle(n.id)
        else:
            v.write_needle(n)
        pulled += 1
        last_ts = max(last_ts, int(r.get("append_at_ns", 0)))
    v.close()
    with open(ts_path, "w") as fh:
        fh.write(str(last_ts))
    print(json.dumps({"volume_id": vid, "needles_pulled": pulled,
                      "backup_dir": args.dir}))
    return 0


def cmd_webdav(args) -> int:
    from ..pb import ServerAddress
    from ..webdav import WebDavServer
    filer = ServerAddress.parse(args.filer)
    dav = WebDavServer(filer.url, filer.grpc, host=args.ip,
                       port=args.port, root=args.root)
    dav.start()
    print(f"webdav {dav.address} -> filer {filer.url}")
    _wait_forever()
    dav.stop()
    return 0


def cmd_iam(args) -> int:
    from ..pb import ServerAddress
    from ..s3 import IdentityAccessManagement
    from ..s3.iam import IamApiServer
    filer = ServerAddress.parse(args.filer)
    srv = IamApiServer(IdentityAccessManagement(), filer.grpc,
                       host=args.ip, port=args.port)
    srv.start()
    print(f"iam api {srv.address}")
    _wait_forever()
    srv.stop()
    return 0


def cmd_msg_broker(args) -> int:
    from ..messaging import MessageBroker
    from ..pb import ServerAddress
    filer = ServerAddress.parse(args.filer)
    broker = MessageBroker(filer.grpc, host=args.ip, grpc_port=args.port)
    broker.start()
    print(f"message broker grpc {broker.grpc_address}")
    _wait_forever()
    broker.stop()
    return 0


def cmd_filer_sync(args) -> int:
    from ..pb import ServerAddress
    from ..replication.filer_sync import FilerSync
    a = ServerAddress.parse(args.a)
    b = ServerAddress.parse(args.b)
    sync = FilerSync(a.grpc, args.a_master, b.grpc, args.b_master,
                     path_prefix=args.path)
    sync.start()
    print(f"filer.sync {a.url} <-> {b.url} (prefix {args.path})")
    _wait_forever()
    sync.stop()
    return 0


def cmd_master_follower(args) -> int:
    """Read-only master follower (command/master_follower.go): serves
    lookups from a KeepConnected-fed vid cache, proxies writes."""
    from ..master import MasterServer
    m = MasterServer(host=args.ip, port=args.port,
                     grpc_port=args.grpc_port, follow=args.masters)
    m.start()
    print(f"master.follower http {m.address} grpc {m.grpc_address} "
          f"following {args.masters}")
    _wait_forever()
    m.stop()
    return 0


def cmd_filer_meta_backup(args) -> int:
    """Continuous filer metadata backup (command/filer_meta_backup.go):
    subscribe to the metadata stream and append every event to a JSONL
    file; -restore replays a backup into the filer."""
    from ..pb import ServerAddress
    from ..pb.rpc import POOL, RpcError
    addr = ServerAddress.parse(args.filer)
    client = POOL.client(addr.grpc, "SeaweedFiler")
    if args.restore:
        n = 0
        with open(args.o) as f:
            for line in f:
                ev = json.loads(line)
                entry = ev.get("new_entry")
                if entry:
                    client.call("CreateEntry", {"entry": entry})
                    n += 1
                elif ev.get("old_entry"):
                    old = ev["old_entry"]
                    d, _, name = old["full_path"].rpartition("/")
                    try:
                        client.call("DeleteEntry", {
                            "directory": d or "/", "name": name,
                            "is_recursive": True,
                            "ignore_recursive_error": True})
                    except RpcError:
                        pass
        print(f"restored {n} entries from {args.o}")
        return 0
    since = 0
    if os.path.exists(args.o):
        with open(args.o) as f:
            for line in f:
                try:
                    since = max(since, json.loads(line).get("ts_ns", 0))
                except ValueError:
                    pass
    print(f"backing up {addr.grpc} metadata (prefix {args.path}) "
          f"to {args.o} since_ns={since}")
    try:
        with open(args.o, "a") as f:
            for msg in client.stream(
                    "SubscribeMetadata",
                    iter([{"since_ns": since,
                           "path_prefix": args.path}])):
                if "ping" in msg:
                    f.flush()
                    continue
                f.write(json.dumps(msg, separators=(",", ":")) + "\n")
                f.flush()
    except (KeyboardInterrupt, RpcError):
        pass    # filer went away / operator interrupt: exit cleanly
    return 0


def cmd_filer_remote_sync(args) -> int:
    """Continuously push local changes under remote mounts back to their
    remotes (command/filer_remote_sync.go; the -gateway variant of the
    reference maps to the same push loop over /buckets mounts)."""
    import time as _time

    from ..pb import ServerAddress
    from ..shell.command_remote import load_remote_mounts
    addr = ServerAddress.parse(args.filer)

    print(f"filer.remote.sync watching {args.dir or 'all mounts'} "
          f"every {args.interval}s")
    try:
        while True:
            for mount in load_remote_mounts(addr.grpc, args.master,
                                            only_dir=args.dir):
                try:
                    pushed = mount.sync_to_remote()
                    if pushed:
                        print(f"pushed {pushed} objects from "
                              f"{mount.mount_dir}")
                except Exception as e:
                    print(f"sync {mount.mount_dir} failed: {e}")
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


def cmd_mount(args) -> int:
    """FUSE-mount the filer namespace (weed mount, command/mount.go) via
    the ctypes libfuse2 adapter."""
    from ..mount.fuse_adapter import mount_and_serve
    from ..pb import ServerAddress
    addr = ServerAddress.parse(args.filer)
    print(f"mounting {addr.grpc} at {args.dir} (ctrl-c to unmount)")
    return mount_and_serve(addr.grpc, args.master, args.dir,
                           foreground=True,
                           encrypt_data=args.encrypt_volume_data)


def cmd_ftp(args) -> int:
    """FTP gateway over the filer (beyond the reference: its ftpd is an
    unimplemented stub, weed/ftpd/ftp_server.go)."""
    from ..ftpd import FtpServer
    from ..pb import ServerAddress
    filer = ServerAddress.parse(args.filer)
    users = {args.user: args.password} if args.user else None
    if users is None and args.ip not in ("127.0.0.1", "localhost", "::1"):
        print("WARNING: ftp gateway bound to a routable address with NO "
              "credentials configured — ANY client gets full read/write "
              "over the filer namespace.  Pass -user/-password (and "
              "-tls.cert/-tls.key for FTPS).", file=sys.stderr)
    srv = FtpServer(filer.url, filer.grpc, host=args.ip, port=args.port,
                    users=users, tls_cert=args.tls_cert,
                    tls_key=args.tls_key)
    srv.start()
    print(f"ftp gateway {srv.address}"
          + (" (FTPS available)" if args.tls_cert else ""))
    _wait_forever()
    srv.stop()
    return 0


def cmd_scaffold(args) -> int:
    """Print sample configs (command/scaffold.go): TOML templates for
    the layered config system (util/config.py), plus the legacy JSON
    samples via -output json."""
    if getattr(args, "output", "toml") == "json":
        samples = {
            "s3": {"identities": [{
                "name": "admin",
                "credentials": [{"accessKey": "ACCESS_KEY",
                                 "secretKey": "SECRET_KEY"}],
                "actions": ["Admin"]}]},
            "filer": {"store": "sqlite", "store_path": "./filer.db"},
            "security": {"jwt_signing_key": "", "white_list": []},
        }
        print(json.dumps(samples.get(args.config, samples), indent=2))
        return 0
    from ..util.config import scaffold as toml_scaffold
    print(toml_scaffold(args.config))
    return 0


def resolve_jwt_key(explicit: str) -> str:
    """Flag > WEED_JWT_SIGNING_KEY env > security.toml [jwt.signing] key
    (util/config.py layering: env overrides apply on top of the file;
    reference util/config.go + viper env)."""
    if explicit:
        return explicit
    from ..util.config import load_config
    return str(load_config("security").get("jwt.signing.key") or "")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="seaweedfs_tpu",
        description="TPU-native distributed object store "
                    "(SeaweedFS-capability framework)")
    sub = p.add_subparsers(dest="command", required=True)

    m = sub.add_parser("master", help="start a master server")
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-grpc_port", dest="grpc_port", type=int, default=19333)
    m.add_argument("-volumeSizeLimitMB", dest="volume_size_limit_mb",
                   type=int, default=30 * 1024)
    m.add_argument("-defaultReplication", dest="default_replication",
                   default="000")
    m.add_argument("-jwtKey", dest="jwt_key", default="",
                   help="HS256 signing key gating volume writes")
    m.add_argument("-peers", default="",
                   help="comma-separated master gRPC addresses for HA")
    m.add_argument("-eventDir", dest="event_dir", default="",
                   help="directory for the durable cluster event "
                        "timeline journal (default: WEED_EVENT_DIR "
                        "env, else ring-only)")
    m.set_defaults(fn=cmd_master)

    v = sub.add_parser("volume", help="start a volume server")
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-grpc_port", dest="grpc_port", type=int, default=18080)
    v.add_argument("-dir", default="./data")
    v.add_argument("-max", default="7")
    v.add_argument("-mserver", default="127.0.0.1:19333")
    v.add_argument("-dataCenter", dest="data_center", default="")
    v.add_argument("-rack", dest="rack", default="")
    v.add_argument("-jwtKey", dest="jwt_key", default="",
                   help="HS256 signing key (must match the master's)")
    v.add_argument("-workers", default=None,
                   help="worker processes sharing the data port "
                        "(default WEED_VOLUME_WORKERS; 1 = single "
                        "process, 0/auto = one per core)")
    v.set_defaults(fn=cmd_volume)

    f = sub.add_parser("filer", help="start a filer server")
    f.add_argument("-ip", default="127.0.0.1")
    f.add_argument("-port", type=int, default=8888)
    f.add_argument("-grpc_port", dest="grpc_port", type=int, default=18888)
    f.add_argument("-master", default="127.0.0.1:19333")
    f.add_argument("-store", default="sqlite")
    f.add_argument("-store_path", dest="store_path", default="./filer.db")
    f.add_argument("-collection", default="")
    f.add_argument("-encryptVolumeData", dest="encrypt_volume_data",
                   action="store_true",
                   help="seal chunk data with per-chunk AES256-GCM keys "
                        "before upload; volume servers hold only "
                        "ciphertext (keys live in filer metadata)")
    f.add_argument("-defaultReplication", dest="default_replication",
                   default="")
    f.set_defaults(fn=cmd_filer)

    s = sub.add_parser("s3", help="start an S3 gateway")
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-port", type=int, default=8333)
    s.add_argument("-filer", default="127.0.0.1:8888.18888")
    s.add_argument("-config", default="")
    s.add_argument("-auditLog", default="",
                   help="append one JSON line per request to this file "
                        "(the reference's -auditLogConfig access log)")
    s.set_defaults(fn=cmd_s3)

    srv = sub.add_parser("server", help="master + volume + filer (+ s3)")
    srv.add_argument("-ip", default="127.0.0.1")
    srv.add_argument("-master.port", dest="master_port", type=int,
                     default=9333)
    srv.add_argument("-volume.port", dest="volume_port", type=int,
                     default=8080)
    srv.add_argument("-filer.port", dest="filer_port", type=int,
                     default=8888)
    srv.add_argument("-s3", action="store_true")
    srv.add_argument("-filer.encryptVolumeData",
                     dest="encrypt_volume_data", action="store_true",
                     help="embedded filer seals chunks with per-chunk "
                          "AES256-GCM keys")
    srv.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    srv.add_argument("-s3.auditLog", dest="s3_audit_log", default="",
                     help="S3 access log (JSON lines) for the embedded "
                          "gateway")
    srv.add_argument("-dir", default="./data")
    srv.add_argument("-max", default="7")
    srv.add_argument("-filer.store", dest="filer_store", default="sqlite")
    srv.add_argument("-filer.store_path", dest="filer_store_path",
                     default=None,
                     help="default: <dir>/filer.db")
    srv.add_argument("-jwtKey", dest="jwt_key", default="")
    srv.set_defaults(fn=cmd_server)

    sh = sub.add_parser("shell", help="maintenance shell")
    sh.add_argument("-master", default="127.0.0.1:19333",
                    help="master gRPC address")
    sh.add_argument("-c", dest="command", default="",
                    help="run one command and exit")
    sh.set_defaults(fn=cmd_shell)

    up = sub.add_parser("upload", help="upload files")
    up.add_argument("-master", default="127.0.0.1:19333")
    up.add_argument("-replication", default="")
    up.add_argument("-collection", default="")
    up.add_argument("-ttl", default="")
    up.add_argument("-cipher", action="store_true",
                    help="AES256-GCM encrypt before upload; the key is "
                         "printed in the JSON record (keep it — there "
                         "is no filer entry to hold it)")
    up.add_argument("files", nargs="+")
    up.set_defaults(fn=cmd_upload)

    dl = sub.add_parser("download", help="download files by fid")
    dl.add_argument("-master", default="127.0.0.1:19333")
    dl.add_argument("-cipherKey", dest="cipher_key", default="",
                    help="base64 key from `upload -cipher`")
    dl.add_argument("-o", dest="output", default="")
    dl.add_argument("fids", nargs="+")
    dl.set_defaults(fn=cmd_download)

    rm = sub.add_parser("delete", help="delete files by fid")
    rm.add_argument("-master", default="127.0.0.1:19333")
    rm.add_argument("fids", nargs="+")
    rm.set_defaults(fn=cmd_delete)

    b = sub.add_parser("benchmark",
                       help="load-test a cluster (command/benchmark.go)")
    b.add_argument("-master", default="127.0.0.1:19333")
    b.add_argument("-n", type=int, default=10000)
    b.add_argument("-size", type=int, default=1024)
    b.add_argument("-c", type=int, default=16,
                   help="threads (single-process mode)")
    b.add_argument("-p", type=int, default=1,
                   help="worker processes (>1 switches to multiprocess "
                        "mode and ignores -c)")
    b.add_argument("-collection", default="")
    b.add_argument("-writeOnly", dest="write_only", action="store_true")
    b.set_defaults(fn=cmd_benchmark)

    bk = sub.add_parser("backup",
                        help="incremental local backup of one volume")
    bk.add_argument("-master", default="127.0.0.1:19333")
    bk.add_argument("-volumeId", type=int, required=True)
    bk.add_argument("-collection", default="")
    bk.add_argument("-dir", default="./backup")
    bk.set_defaults(fn=cmd_backup)

    from .volume_tools import cmd_compact, cmd_export, cmd_fix
    fx = sub.add_parser("fix",
                        help="rebuild a volume's .idx from its .dat "
                             "(offline; no server needed)")
    fx.add_argument("-dir", default=".")
    fx.add_argument("-collection", default="")
    fx.add_argument("-volumeId", type=int, required=True)
    fx.set_defaults(fn=cmd_fix)

    cp = sub.add_parser("compact",
                        help="offline vacuum of one volume")
    cp.add_argument("-dir", default=".")
    cp.add_argument("-collection", default="")
    cp.add_argument("-volumeId", type=int, required=True)
    cp.add_argument("-preallocate", type=int, default=0)
    cp.set_defaults(fn=cmd_compact)

    ex = sub.add_parser("export",
                        help="export a volume's live files to a tar")
    ex.add_argument("-dir", default=".")
    ex.add_argument("-collection", default="")
    ex.add_argument("-volumeId", type=int, required=True)
    ex.add_argument("-o", default="export.tar", help="output tar path")
    ex.add_argument("-newer", default="",
                    help="only files modified after YYYY-MM-DDTHH:MM:SS")
    ex.add_argument("-limit", type=int, default=0)
    ex.set_defaults(fn=cmd_export)

    dav = sub.add_parser("webdav", help="start a WebDAV gateway")
    dav.add_argument("-ip", default="127.0.0.1")
    dav.add_argument("-port", type=int, default=7333)
    dav.add_argument("-filer", default="127.0.0.1:8888.18888")
    dav.add_argument("-root", default="/")
    dav.set_defaults(fn=cmd_webdav)

    iam = sub.add_parser("iam", help="start the IAM API")
    iam.add_argument("-ip", default="127.0.0.1")
    iam.add_argument("-port", type=int, default=8111)
    iam.add_argument("-filer", default="127.0.0.1:8888.18888")
    iam.set_defaults(fn=cmd_iam)

    br = sub.add_parser("msg.broker", help="start a message broker")
    br.add_argument("-ip", default="127.0.0.1")
    br.add_argument("-port", type=int, default=17777)
    br.add_argument("-filer", default="127.0.0.1:8888.18888")
    br.set_defaults(fn=cmd_msg_broker)

    fsync = sub.add_parser("filer.sync",
                           help="bidirectional sync between two filers")
    fsync.add_argument("-a", required=True,
                       help="filer A host:port[.grpcPort]")
    fsync.add_argument("-b", required=True)
    fsync.add_argument("-a.master", dest="a_master",
                       default="127.0.0.1:19333")
    fsync.add_argument("-b.master", dest="b_master",
                       default="127.0.0.1:19333")
    fsync.add_argument("-path", default="/")
    fsync.set_defaults(fn=cmd_filer_sync)

    from .filer_tools import (cmd_filer_backup, cmd_filer_cat,
                              cmd_filer_copy, cmd_filer_meta_tail,
                              cmd_filer_remote_gateway,
                              cmd_filer_replicate)
    fcp = sub.add_parser("filer.copy",
                         help="parallel local-tree upload to the filer")
    fcp.add_argument("sources", nargs="+",
                     help="local files or directories")
    fcp.add_argument("dest", help="http://filer:port/dest/dir/")
    fcp.add_argument("-concurrency", type=int, default=8)
    fcp.add_argument("-include", default="",
                     help="only file names matching this glob")
    fcp.add_argument("-verbose", action="store_true")
    fcp.set_defaults(fn=cmd_filer_copy)

    fct = sub.add_parser("filer.cat",
                         help="print one filer file to stdout")
    fct.add_argument("path", help="http://filer:port/path/to/file")
    fct.set_defaults(fn=cmd_filer_cat)

    fmt_ = sub.add_parser("filer.meta.tail",
                          help="tail filer metadata events as JSON lines")
    fmt_.add_argument("-filer", default="127.0.0.1:8888.18888")
    fmt_.add_argument("-pathPrefix", default="/")
    fmt_.add_argument("-pattern", default="",
                      help="glob on the entry file name")
    fmt_.add_argument("-timeAgo", type=float, default=0,
                      help="start this many seconds in the past")
    fmt_.add_argument("-limit", type=int, default=0,
                      help="exit after N events (0 = forever)")
    fmt_.add_argument("-until-ping", dest="until_ping",
                      action="store_true",
                      help="exit once caught up with the live tail")
    fmt_.set_defaults(fn=cmd_filer_meta_tail)

    def _backup_flags(p):
        p.add_argument("-filer", default="127.0.0.1:8888.18888")
        p.add_argument("-master", default="",
                       help="chunk-read master (defaults to the "
                            "filer's configured master)")
        p.add_argument("-path", default="/")
        p.add_argument("-targetDir", default="",
                       help="replicate into this local directory")
        p.add_argument("-targetS3Endpoint", default="")
        p.add_argument("-targetS3Bucket", default="")
        p.add_argument("-targetS3AccessKey", default="")
        p.add_argument("-targetS3SecretKey", default="")
        p.add_argument("-interval", type=float, default=2.0)
        p.add_argument("-once", action="store_true",
                       help="drain available events and exit")
        p.add_argument("-maxEvents", type=int, default=0)

    fbk = sub.add_parser("filer.backup",
                         help="continuous one-way backup of a filer "
                              "path into a local dir or S3 sink")
    _backup_flags(fbk)
    fbk.set_defaults(fn=cmd_filer_backup)

    frp = sub.add_parser("filer.replicate",
                         help="standalone replicator daemon (sink from "
                              "flags or replication.toml)")
    _backup_flags(frp)
    frp.set_defaults(fn=cmd_filer_replicate)

    frg = sub.add_parser("filer.remote.gateway",
                         help="bind local buckets to a configured "
                              "remote and push changes")
    frg.add_argument("-filer", default="127.0.0.1:8888.18888")
    frg.add_argument("-master", default="")
    frg.add_argument("-dir", default="/buckets")
    frg.add_argument("-createBucketAt", required=True,
                     help="configured remote name")
    frg.add_argument("-interval", type=float, default=2.0)
    frg.add_argument("-rounds", type=int, default=0,
                     help="exit after N rounds (0 = forever)")
    frg.set_defaults(fn=cmd_filer_remote_gateway)

    mf = sub.add_parser("master.follower",
                        help="read-only master follower "
                             "(lookup offload)")
    mf.add_argument("-ip", default="127.0.0.1")
    mf.add_argument("-port", type=int, default=9433)
    mf.add_argument("-grpc_port", type=int, default=0)
    mf.add_argument("-masters", default="127.0.0.1:19333",
                    help="comma-separated master gRPC addresses")
    mf.set_defaults(fn=cmd_master_follower)

    mb = sub.add_parser("filer.meta.backup",
                        help="continuous filer metadata backup "
                             "(JSONL; -restore replays)")
    mb.add_argument("-filer", default="127.0.0.1:8888.18888")
    mb.add_argument("-o", default="filer_meta_backup.jsonl")
    mb.add_argument("-path", default="/")
    mb.add_argument("-restore", action="store_true")
    mb.set_defaults(fn=cmd_filer_meta_backup)

    rs = sub.add_parser("filer.remote.sync",
                        help="push local changes under remote mounts "
                             "to the cloud")
    rs.add_argument("-filer", default="127.0.0.1:8888.18888")
    rs.add_argument("-master", default="127.0.0.1:19333")
    rs.add_argument("-dir", default="",
                    help="one mount dir (default: all mounts)")
    rs.add_argument("-interval", type=float, default=5.0)
    rs.set_defaults(fn=cmd_filer_remote_sync)

    mnt = sub.add_parser("mount",
                         help="FUSE-mount the filer namespace")
    mnt.add_argument("-filer", default="127.0.0.1:8888.18888")
    mnt.add_argument("-master", default="127.0.0.1:19333")
    mnt.add_argument("-dir", required=True)
    mnt.add_argument("-encryptVolumeData", dest="encrypt_volume_data",
                     action="store_true",
                     help="seal chunks written through this mount "
                          "(reads always honor cipher_key)")
    mnt.set_defaults(fn=cmd_mount)

    ftp = sub.add_parser("ftp", help="start an FTP gateway")
    ftp.add_argument("-ip", default="127.0.0.1")
    ftp.add_argument("-port", type=int, default=8021)
    ftp.add_argument("-filer", default="127.0.0.1:8888.18888")
    ftp.add_argument("-user", default="",
                     help="require this login (default: OPEN ACCESS — "
                          "safe only on loopback)")
    ftp.add_argument("-password", default="")
    ftp.add_argument("-tls.cert", dest="tls_cert", default="",
                     help="server certificate: enables AUTH TLS (FTPS)")
    ftp.add_argument("-tls.key", dest="tls_key", default="")
    ftp.set_defaults(fn=cmd_ftp)

    sc = sub.add_parser("scaffold", help="print sample configs")
    sc.add_argument("-config", default="")
    sc.add_argument("-output", default="toml", choices=["toml", "json"])
    sc.set_defaults(fn=cmd_scaffold)

    ver = sub.add_parser("version")
    ver.set_defaults(fn=lambda a: print("seaweedfs-tpu 0.1 "
                                        "(capability target SeaweedFS 2.96)")
                     or 0)
    return p


def main(argv: list[str] | None = None) -> int:
    import sys as _sys
    argv = list(_sys.argv[1:] if argv is None else argv)
    from ..util.compile_cache import place_compile_cache
    place_compile_cache()
    # global verbosity: bare -v or glog-style -v=N; a following token is
    # NEVER consumed (so `master -v 100` can't silently swallow an
    # argument meant for the subcommand)
    verbosity = 0
    for i, a in enumerate(list(argv)):
        if a == "-v":
            verbosity = 1
            argv.pop(i)
            break
        if a.startswith("-v=") and a[3:].isdigit():
            verbosity = int(a[3:])
            argv.pop(i)
            break
    # global mTLS: -tls.dir <dir> expects ca.crt/cluster.crt/cluster.key
    # (security/tls.py generate_cluster_certs layout; the reference wires
    # the same through security.toml [grpc.*])
    tls_set = False
    for i, a in enumerate(list(argv)):
        if a == "-tls.dir" and i + 1 < len(argv):
            tls_dir = argv[i + 1]
            del argv[i:i + 2]
            from ..pb import rpc as rpc_mod
            from ..security.tls import TlsConfig
            rpc_mod.set_tls(TlsConfig(
                os.path.join(tls_dir, "ca.crt"),
                os.path.join(tls_dir, "cluster.crt"),
                os.path.join(tls_dir, "cluster.key")))
            tls_set = True
            break
    if not tls_set:
        # security.toml [grpc] ca/cert/key (+ WEED_GRPC_* env overrides)
        from ..util.config import load_config
        sec = load_config("security")
        if sec.get("grpc.ca"):
            from ..pb import rpc as rpc_mod
            from ..security.tls import TlsConfig
            rpc_mod.set_tls(TlsConfig(str(sec["grpc.ca"]),
                                      str(sec.get("grpc.cert") or ""),
                                      str(sec.get("grpc.key") or "")))
    # global EC backend pin on every verb: -ec.backend
    # native|numpy|pallas|jax|auto.  Sets WEED_EC_BACKEND, which
    # overrides the platform rule (pallas on a TPU, native on a CPU)
    for i, a in enumerate(list(argv)):
        if a == "-ec.backend" and i + 1 < len(argv):
            value = argv[i + 1]
            del argv[i:i + 2]
            from ..ops.codec import validate_ec_backend_pin
            prior = os.environ.get("WEED_EC_BACKEND")
            os.environ["WEED_EC_BACKEND"] = value
            try:
                # fail loudly pre-serve: bad name, then bad host
                validate_ec_backend_pin()
            except (ValueError, RuntimeError):
                # don't leave a bad pin behind for in-process callers
                if prior is None:
                    del os.environ["WEED_EC_BACKEND"]
                else:
                    os.environ["WEED_EC_BACKEND"] = prior
                raise
            break
    # global profiling hooks on every verb (reference
    # util/grace/pprof.go:11-55): -cpuprofile FILE / -memprofile FILE
    prof_args = {}
    for flag, key in (("-cpuprofile", "cpuprofile"),
                      ("-memprofile", "memprofile")):
        for i, a in enumerate(list(argv)):
            if a == flag and i + 1 < len(argv):
                prof_args[key] = argv[i + 1]
                del argv[i:i + 2]
                break
    if prof_args:
        from ..util.profiling import setup_profiling
        setup_profiling(**prof_args)
    from ..util import weedlog
    weedlog.setup(verbosity)
    args = build_parser().parse_args(argv)
    return args.fn(args) or 0
