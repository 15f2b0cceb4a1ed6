"""The system under test: an in-process SimCluster (a master and the
configuration's volume servers), and the maintenance moves the drivers
make through its own RPCs: lose shards, restore a volume."""

from __future__ import annotations

import glob
import os
import time

from seaweedfs_tpu.shell.command_ec import collect_ec_shard_map
from seaweedfs_tpu.shell.commands import iter_data_nodes
from seaweedfs_tpu.testing import SimCluster


def make(run, volume_servers: int) -> SimCluster:
    """A cluster not yet started: its servers' directories exist, so a
    fixture can be written into them first.  It is kept in
    run.state["cluster"], from where the harness stops it."""
    c = SimCluster(volume_servers=volume_servers,
                   base_dir=os.path.join(run.scratch, "cluster"))
    run.state["cluster"] = c
    return c


def server_dir(cluster: SimCluster, i: int) -> str:
    return os.path.join(cluster.base_dir, f"vol{i}")


def server_index(cluster: SimCluster, directory: str) -> int:
    return next(i for i in range(len(cluster.volume_servers))
                if server_dir(cluster, i) == directory)


def base_name(collection: str, vid: int) -> str:
    return f"{collection}_{vid}" if collection else str(vid)


def shard_paths(cluster: SimCluster, collection: str, vid: int
                ) -> dict[int, str]:
    """{shard id: path} of every shard file on every server."""
    out = {}
    for i in range(len(cluster.volume_servers)):
        for p in glob.glob(os.path.join(server_dir(cluster, i),
                                        base_name(collection, vid)
                                        + ".ec[0-9][0-9]")):
            out[int(p[-2:])] = p
    return out


def seen(env, vid: int) -> tuple[set[int], bool]:
    """(shard ids of `vid` mounted somewhere, whether the plain volume
    is), as the master's topology has them."""
    topo = env.topology()
    held = collect_ec_shard_map(topo).get(vid, {})
    plain = any(v["id"] == vid for _, _, dn in iter_data_nodes(topo)
                for v in dn["volumes"])
    return {s for ids in held.values() for s in ids}, plain


def settle(cluster: SimCluster, env, vid: int, shards: set[int],
           volume: bool, timeout: float = 20.0) -> None:
    """Push heartbeats until the master sees exactly `shards` mounted
    and the plain volume present or not."""
    deadline = time.monotonic() + timeout
    while True:
        cluster.sync_heartbeats()
        now = seen(env, vid)
        if now == (shards, volume):
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"master sees shards {sorted(now[0])} and volume={now[1]},"
                f" wanted {sorted(shards)} and volume={volume}")
        time.sleep(0.05)


def drop_shards(cluster: SimCluster, env, collection: str, vid: int,
                shards: list[int]) -> None:
    """Lose shards through the unmount and delete RPCs of the servers
    that hold them, as a failed disk leaves a volume."""
    held = shard_paths(cluster, collection, vid)
    for i, vs in enumerate(cluster.volume_servers):
        mine = [s for s in shards if s in held
                and os.path.dirname(held[s]) == server_dir(cluster, i)]
        if not mine:
            continue
        client = env.volume_server(vs.grpc_address)
        client.call("VolumeEcShardsUnmount",
                    {"volume_id": vid, "shard_ids": mine})
        client.call("VolumeEcShardsDelete",
                    {"volume_id": vid, "collection": collection,
                     "shard_ids": mine})


def spans(cluster: SimCluster, trace_id: str) -> list[dict]:
    """The program's RPC spans of one trace id, from every server's
    ring (read after each verb, before the ring can rotate)."""
    out = []
    for vs in cluster.volume_servers:
        out += vs.tracer.snapshot(trace_id=trace_id)
    return out
