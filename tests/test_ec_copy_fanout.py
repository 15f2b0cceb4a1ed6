"""ec.encode and ec.rebuild run their per-node shard copies at once:
every destination's (encode) or source's (rebuild) VolumeEcShardsCopy is
in flight before any of them goes on, and the verb's output counts them
in `copy_fanout`."""

import json
import os
import threading

import pytest

from seaweedfs_tpu import operation, shell
from seaweedfs_tpu.shell.command_ec import (_most_at_once,
                                          collect_ec_shard_map, plan_rebuild)
from seaweedfs_tpu.storage.ec.layout import EcGeometry
from seaweedfs_tpu.testing import SimCluster
from seaweedfs_tpu.volume_server.server import VolumeServer

# long enough for a loaded host to bring every copy to the barrier; a
# serial verb holds its first copy there until it breaks
BARRIER_S = 30
GEO = {"rs": EcGeometry(10, 4), "clay": EcGeometry(10, 4, code_kind="clay")}


def _gate_copies(monkeypatch) -> dict:
    """While gate["barrier"] is set, each VolumeEcShardsCopy waits there
    before copying (the handler is bound when a server starts, so this
    runs before the cluster is built)."""
    gate: dict = {}
    orig = VolumeServer._rpc_ec_copy

    def gated(self, req):
        barrier = gate.get("barrier")
        if barrier is not None:
            barrier.wait()
        return orig(self, req)
    monkeypatch.setattr(VolumeServer, "_rpc_ec_copy", gated)
    return gate


def _lose(c, env, vid: int, shard: int) -> None:
    holder = next(vs for vs in c.volume_servers
                  if any(os.path.exists(os.path.join(
                      d.directory, f"{vid}.ec{shard:02d}"))
                      for d in vs.store.locations))
    client = env.volume_server(holder.grpc_address)
    client.call("VolumeEcShardsUnmount", {"volume_id": vid,
                                         "shard_ids": [shard]})
    client.call("VolumeEcShardsDelete", {"volume_id": vid, "collection": "",
                                        "shard_ids": [shard]})
    c.sync_heartbeats()


@pytest.mark.parametrize("verb", ["encode", "rebuild"])
@pytest.mark.parametrize("kind", ["rs", "clay"])
def test_per_node_copies_run_at_once(tmp_path, monkeypatch, kind, verb):
    """A barrier of one party per node the verb copies to (encode: the
    three destinations of four servers) or from (rebuild: the sources
    of the rebuilder's plan; a single clay loss copies repair planes)
    opens only if every copy is in flight at once."""
    gate = _gate_copies(monkeypatch)
    flags = "-kind clay" if kind == "clay" else ""
    with SimCluster(volume_servers=4, base_dir=str(tmp_path)) as c:
        fids = [operation.assign_and_upload(c.master_grpc,
                                            os.urandom(2500 + 83 * i))
                for i in range(6)]
        vid = int(fids[0].split(",")[0])
        blobs = {f: c.read(f) for f in fids if int(f.split(",")[0]) == vid}
        env = shell.CommandEnv(c.master_grpc)
        shell.run_command(env, "lock")
        if verb == "encode":
            gate["barrier"] = threading.Barrier(3, timeout=BARRIER_S)
        out = json.loads(shell.run_command(
            env, f"ec.encode -volumeId {vid} {flags}".strip()))["encoded"][0]
        assert out["copy_fanout"] == 3
        assert len(out["distribution"]) == 4
        c.sync_heartbeats()
        if verb == "rebuild":
            lost = 7
            _lose(c, env, vid, lost)
            shard_map = collect_ec_shard_map(env.topology())[vid]
            _, plan, copies = plan_rebuild(GEO[kind], [lost], shard_map)
            assert plan.kind == ("clay-plane" if kind == "clay"
                                 else "rs-full")
            assert len(copies) >= 2
            gate["barrier"] = threading.Barrier(len(copies),
                                                timeout=BARRIER_S)
            out = json.loads(shell.run_command(
                env, f"ec.rebuild -volumeId {vid}"))["rebuilt"][0]
            assert out["rebuilt"] == [lost]
            assert out["copy_fanout"] == len(copies)
            assert out["copied"] == sorted(s for t in copies.values()
                                           for s in t)
            c.sync_heartbeats()
        assert not gate["barrier"].broken
        for fid, payload in blobs.items():
            assert c.read(fid) == payload


@pytest.mark.parametrize("spans, most", [
    ([], 0),
    ([(0.0, 1.0)], 1),
    ([(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)], 1),     # one after another
    ([(0.0, 2.0), (1.0, 3.0), (2.5, 4.0)], 2),
    ([(0.0, 5.0), (0.1, 4.0), (0.2, 3.0)], 3),
])
def test_copy_fanout_counts_the_copies_that_overlapped(spans, most):
    """`copy_fanout` is measured from the copies' start and end times:
    a serial verb reads 1 whatever its plan's node count."""
    assert _most_at_once(spans) == most
