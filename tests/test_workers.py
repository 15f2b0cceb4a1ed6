"""Worker-partition coherence matrix (ISSUE 12): the process-sharded
volume data plane must be indistinguishable from a single-process
server to every client.

- write lands on its vid's owner; a read through the WRONG worker's
  private HTTP or TCP port forwards to the owner and returns the bytes;
- the master sees ONE logical DataNode whose volume list is the union
  of the partitions, with per-volume tcp routing to the owning worker;
- a SIGKILL'd worker respawns on the same ports with ZERO acked loss;
- the SO_REUSEPORT-unavailable fallback (supervisor accept-and-pass
  over socket.send_fds) serves the same traffic;
- volume_workers=1 keeps the plain in-process VolumeServer —
  byte-identical behavior to today.
"""

import json
import os

import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.pb.rpc import POOL
from seaweedfs_tpu.testing import SimCluster
from seaweedfs_tpu.util.http import http_request
from seaweedfs_tpu.volume_server import VolumeServer
from seaweedfs_tpu.volume_server.workers import (ShardedVolumeServer,
                                                 worker_partition_dir)


@pytest.fixture(scope="module")
def sharded():
    """One 2-worker sharded cluster shared by the read-path tests
    (worker subprocess boots are the expensive part)."""
    c = SimCluster(masters=1, volume_servers=1, volume_workers=2,
                   pulse_seconds=0.4).start()
    yield c
    c.stop()


def _upload_some(c, n, tag=b"blob"):
    fids = []
    for i in range(n):
        fids.append(c.upload(tag + b"-%d" % i))
    return fids


def test_partition_write_read_any_worker(sharded):
    """Write through the normal flow, then read every fid through BOTH
    workers' private ports AND the shared port — wrong-worker requests
    must forward, not 404."""
    c = sharded
    vs = c.volume_servers[0]
    assert isinstance(vs, ShardedVolumeServer)
    fids = _upload_some(c, 12, b"coh")
    vids = {int(f.split(",")[0]) for f in fids}
    assert len(vids) > 1, "need volumes in both partitions"
    for i, fid in enumerate(fids):
        want = b"coh-%d" % i
        for addr in (vs.worker_http_addr(0), vs.worker_http_addr(1),
                     vs.url):
            status, body, _ = http_request(f"http://{addr}/{fid}")
            assert status == 200, (addr, fid, status, body)
            assert body == want


def test_wrong_worker_tcp_forward(sharded):
    """The frame path forwards too: a read sent to the non-owner's tcp
    port returns the needle via the owner."""
    c = sharded
    vs = c.volume_servers[0]
    fids = _upload_some(c, 6, b"tcp")
    for i, fid in enumerate(fids):
        vid = int(fid.split(",", 1)[0])
        wrong = (vid + 1) % vs.workers
        got = operation.read_file_tcp(vs.worker_tcp_addr(wrong), fid)
        assert got == b"tcp-%d" % i


def test_heartbeat_aggregation_single_logical_node(sharded):
    """The master must see ONE DataNode: union volume list, summed
    capacity, and per-volume tcp routing to the owning worker."""
    c = sharded
    vs = c.volume_servers[0]
    c.sync_heartbeats()
    m = c.masters[0]
    nodes = m.topo.data_nodes()
    assert len(nodes) == 1
    dn = nodes[0]
    assert dn.id == vs.url          # the SHARED data address
    assert dn.grpc_port == vs.rpc.port
    assert dn.max_volumes == c.max_volumes  # summed worker capacity
    assert dn.volumes, "no volumes registered"
    for vid in dn.volumes:
        owner = vid % vs.workers
        assert dn.volume_tcp_ports[vid] == \
            vs.status()["ports"][owner]["tcp"], \
            f"vid {vid} routed to the wrong worker"
    # lookups hand clients the OWNER's frame port
    for vid in list(dn.volumes)[:4]:
        locs = operation.lookup_volume(c.master_grpc, vid)
        assert locs and locs[0]["tcp_url"] == vs.worker_tcp_addr(
            vid % vs.workers)
        assert locs[0]["url"] == vs.url


def test_merged_status_and_metrics(sharded):
    """/status and /metrics on the shared port answer for the WHOLE
    logical node (supervisor merge), per-partition views stay reachable
    with ?worker_local=1."""
    c = sharded
    vs = c.volume_servers[0]
    status, body, _ = http_request(f"http://{vs.url}/status")
    assert status == 200
    merged = json.loads(body)
    assert merged["Workers"]["workers"] == 2
    # one process per chip: workers never open the accelerator, and
    # each says which codec its EC work runs on
    assert len(merged["Ec"]) == 2
    for ec in merged["Ec"]:
        assert ec["pin"] == "auto"
        if ec["opened"]:
            assert ec["platform"] == "cpu"
            assert ec["backend"] in ("mesh", "native", "jax")
    for proc in vs._procs.values():
        with open(f"/proc/{proc.pid}/environ", "rb") as f:
            assert b"JAX_PLATFORMS=cpu" in f.read().split(b"\0")
    status, body, _ = http_request(
        f"http://{vs.worker_http_addr(0)}/status?worker_local=1")
    local = json.loads(body)
    assert len(local["Volumes"]) < len(merged["Volumes"])
    # every vid in the merged view belongs to exactly one partition
    merged_vids = sorted(v["id"] for v in merged["Volumes"])
    assert len(merged_vids) == len(set(merged_vids))
    status, body, _ = http_request(f"http://{vs.url}/metrics")
    assert status == 200
    text = body.decode()
    assert 'seaweedfs_volume_worker_up{worker="0"} 1' in text
    assert 'seaweedfs_volume_worker_up{worker="1"} 1' in text


def test_worker_crash_respawn_zero_acked_loss(tmp_path):
    """SIGKILL one worker mid-life: the supervisor respawns it on the
    same ports and every previously-acked write reads back."""
    import time

    with SimCluster(masters=1, volume_servers=1, volume_workers=2,
                    pulse_seconds=0.4,
                    base_dir=str(tmp_path / "crash")) as c:
        vs = c.volume_servers[0]
        fids = _upload_some(c, 30, b"acked")
        pid = c.kill_volume_worker(0, 1)
        c.wait_volume_worker(0, 1, pid)
        assert vs.restarts.get(1) == 1
        for i, fid in enumerate(fids):
            assert c.read(fid) == b"acked-%d" % i, f"lost {fid}"
        # the respawned partition still takes NEW writes
        fid = c.upload(b"post-crash")
        assert c.read(fid) == b"post-crash"
        # the respawn is COUNTABLE (ISSUE 14): merged metrics carry
        # seaweedfs_volume_worker_respawn_total next to worker_up
        status, body, _ = http_request(f"http://{vs.url}/metrics")
        assert status == 200
        text = body.decode()
        assert 'seaweedfs_volume_worker_respawn_total{worker="1"} 1' \
            in text
        assert 'seaweedfs_volume_worker_respawn_total{worker="0"} 0' \
            in text
        # ... and recorded in the master's durable event timeline (the
        # monitor emits it async right after respawn readiness)
        m = c.masters[0]
        deadline = time.time() + 10
        evs = []
        while time.time() < deadline:
            evs = m.events.query(types=["worker.respawn"])
            if evs:
                break
            time.sleep(0.1)
        assert evs, "worker.respawn event never reached the timeline"
        assert evs[-1]["worker"] == 1 and evs[-1]["server"] == vs.url


def test_sharded_debug_traces_and_profile_parity(sharded):
    """ISSUE 14 satellite: /debug/traces and /debug/profile on the
    shared port answer for the WHOLE logical node (supervisor merge,
    every worker represented), with ?worker= selecting one partition —
    tracing/profiling must not go dark at WEED_VOLUME_WORKERS>1."""
    c = sharded
    vs = c.volume_servers[0]
    fids = _upload_some(c, 6, b"dbg")
    # hit BOTH private ports so both workers' span rings are non-empty
    # (wrong-worker forwards record a span on the receiving worker too)
    for fid in fids:
        for w in (0, 1):
            status, _, _ = http_request(
                f"http://{vs.worker_http_addr(w)}/{fid}")
            assert status == 200
    status, body, _ = http_request(f"http://{vs.url}/debug/traces")
    assert status == 200
    merged = json.loads(body)
    assert merged["span_count"] == len(merged["spans"]) > 0
    assert {s["worker"] for s in merged["spans"]} == {0, 1}
    # one partition, raw page (no worker stamps)
    one = json.loads(http_request(
        f"http://{vs.url}/debug/traces?worker=0")[1])
    assert "spans" in one and all("worker" not in s
                                  for s in one["spans"])
    status, _, _ = http_request(f"http://{vs.url}/debug/traces?worker=9")
    assert status == 400
    # merged profile: concurrent windows, stacks prefixed worker<i>;
    status, body, headers = http_request(
        f"http://{vs.url}/debug/profile?seconds=0.6", timeout=30)
    assert status == 200
    assert int(headers["X-Profile-Samples"]) > 0
    assert headers["X-Profile-Workers"] == "2"
    text = body.decode()
    prefixes = {line.split(";", 1)[0] for line in text.splitlines()}
    assert {"worker0", "worker1"} <= prefixes
    for line in text.splitlines():
        stack, _, count = line.rpartition(" ")
        assert stack and count.isdigit()
    # ?worker= passes one partition's page through, headers intact
    status, body, headers = http_request(
        f"http://{vs.url}/debug/profile?seconds=0.3&worker=1",
        timeout=30)
    assert status == 200 and "X-Profile-Samples" in headers
    assert not any(line.startswith("worker1;")
                   for line in body.decode().splitlines())


def test_reuseport_unavailable_fallback(tmp_path, monkeypatch):
    """WEED_VOLUME_REUSEPORT=0 forces the accept-and-pass path: the
    supervisor accepts on the shared port and passes fds to workers
    over socket.send_fds — same traffic, same answers."""
    monkeypatch.setenv("WEED_VOLUME_REUSEPORT", "0")
    with SimCluster(masters=1, volume_servers=1, volume_workers=2,
                    pulse_seconds=0.4,
                    base_dir=str(tmp_path / "fb")) as c:
        vs = c.volume_servers[0]
        assert vs.status()["fallback"] == "send_fds"
        fids = _upload_some(c, 8, b"fb")
        for i, fid in enumerate(fids):
            assert c.read(fid) == b"fb-%d" % i
        # shared-port requests flow through the fd pass
        status, _, _ = http_request(f"http://{vs.url}/status")
        assert status == 200
        status, body, _ = http_request(f"http://{vs.url}/{fids[0]}")
        assert status == 200 and body == b"fb-0"


def test_workers_one_is_plain_volume_server():
    """volume_workers=1 (the default) must construct the unchanged
    in-process VolumeServer — byte-identical single-process behavior."""
    c = SimCluster(masters=1, volume_servers=1)
    try:
        vs = c._make_vs(0)
        assert type(vs) is VolumeServer
    finally:
        # never started; nothing to stop beyond constructed servers
        vs.store.close()
        for m in c.masters:
            m.stop()


def test_bulk_streams_pass_raw_bytes_through_the_front(sharded):
    """CopyFile and VolumeEcShardRead routed through the supervisor's
    gRPC port re-yield the owning worker's messages: the raw bytes of
    their envelopes arrive unchanged, as the files on disk hold them."""
    c = sharded
    vs = c.volume_servers[0]
    fid = c.upload(os.urandom(300_000))
    vid = int(fid.split(",")[0])
    base = os.path.join(worker_partition_dir(vs.directories[0],
                                             vs.owner_of(vid)), str(vid))
    front = POOL.client(vs.rpc.address, "VolumeServer")
    got = [r["file_content"] for r in front.stream("CopyFile", iter([{
        "volume_id": vid, "collection": "", "ext": ".dat"}]))]
    assert all(type(b) is bytes for b in got)
    with open(base + ".dat", "rb") as f:
        assert b"".join(got) == f.read()
    # seal the volume on its worker, then read a shard back through the
    # front (the last sharded test: the volume stays read-only)
    front.call("VolumeEcShardsGenerate", {"volume_id": vid,
                                          "collection": ""})
    front.call("VolumeEcShardsMount", {"volume_id": vid, "collection": "",
                                       "shard_ids": list(range(14))})
    with open(base + ".ec03", "rb") as f:
        shard = f.read()
    got = [r["data"] for r in front.stream("VolumeEcShardRead", iter([{
        "volume_id": vid, "shard_id": 3, "offset": 100,
        "size": len(shard) - 100}]))]
    assert all(type(b) is bytes for b in got)
    assert b"".join(got) == shard[100:]
