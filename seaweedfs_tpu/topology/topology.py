"""Topology — the master's in-memory model of the whole cluster.

Capability-equivalent to weed/topology/topology.go:23-257 + topology_ec.go:
- DataCenter/Rack/DataNode tree rooted here
- per-(collection, rp, ttl, disk) VolumeLayout map
- heartbeat ingestion: full sync + incremental volume/EC deltas
- EC shard location map vid -> {shard id -> [DataNode]}
- max volume id tracking (the raft state machine value,
  topology/cluster_commands.go) and pick_for_write

Serialization: to_dict()/from_topology_dict() produce the same shape the
shell's `volume.list` works from, so balancing/repair commands are unit-
testable on saved cluster state exactly like the reference (SURVEY §4).
"""

from __future__ import annotations

import random
import threading
import time as _time
from typing import Optional

from ..storage.ec.shard_bits import ShardBits
from ..storage.super_block import ReplicaPlacement
from ..storage.ttl import TTL
from ..storage.volume import VolumeInfo
from .node import DataCenter, DataNode, Node, Rack
from .volume_layout import VolumeGrowOption, VolumeLayout


class Topology:
    def __init__(self, volume_size_limit: int = 30 * 1024 * 1024 * 1024,
                 pulse_seconds: int = 5, seed: int | None = None):
        self.root = Node("topo")
        self.volume_size_limit = volume_size_limit
        self.pulse_seconds = pulse_seconds
        self.layouts: dict[tuple[str, str, str, str], VolumeLayout] = {}
        # vid -> shard_id -> [DataNode]  (topology_ec.go EcShardLocations)
        self.ec_shard_map: dict[int, dict[int, list[DataNode]]] = {}
        self.ec_collections: dict[int, str] = {}
        self.max_volume_id = 0
        # multi-master: HA swaps in a raft-replicated allocator (ha.py
        # reserve_vid — the reference's MaxVolumeIdCommand)
        self.vid_allocator = None
        # location-change hook (master lookup cache invalidation):
        # called with the set of volume ids whose replica locations may
        # have changed, or None for "everything" (node unregister).
        # Invoked OUTSIDE self._lock wherever possible; the callback
        # must be non-blocking and take no locks (the master's bumps
        # plain version counters)
        self.on_locations_changed = None
        self._lock = threading.RLock()
        self._rng = random.Random(seed)

    def _notify_locations(self, vids: "set[int] | None") -> None:
        cb = self.on_locations_changed
        if cb is not None and (vids is None or vids):
            cb(vids)

    # -- tree helpers ------------------------------------------------------
    def get_or_create_data_center(self, dc_id: str) -> DataCenter:
        return self.root.get_or_create(dc_id, DataCenter)  # type: ignore[return-value]

    def get_or_create_data_node(self, dc_id: str, rack_id: str,
                                node_id: str, **kw) -> DataNode:
        dc = self.get_or_create_data_center(dc_id or "DefaultDataCenter")
        rack = dc.get_or_create_rack(rack_id or "DefaultRack")
        return rack.get_or_create_data_node(node_id, **kw)

    def data_nodes(self) -> list[DataNode]:
        return list(self.root.data_nodes())

    def find_data_node(self, node_id: str) -> Optional[DataNode]:
        for dn in self.root.data_nodes():
            if dn.id == node_id:
                return dn
        return None

    # -- layouts -----------------------------------------------------------
    def get_volume_layout(self, collection: str, rp: ReplicaPlacement,
                          ttl_str: str = "", disk_type: str = "hdd"
                          ) -> VolumeLayout:
        key = (collection, str(rp), ttl_str, disk_type)
        with self._lock:
            if key not in self.layouts:
                self.layouts[key] = VolumeLayout(
                    rp, ttl_str, disk_type, self.volume_size_limit)
            return self.layouts[key]

    def _layout_for_info(self, v: VolumeInfo) -> VolumeLayout:
        rp = ReplicaPlacement.from_byte(v.replica_placement)
        ttl_str = str(TTL.from_uint32(v.ttl)) if v.ttl else ""
        return self.get_volume_layout(v.collection, rp, ttl_str)

    # -- volume registration (topology.go RegisterVolumeLayout:118) --------
    def register_volume(self, v: VolumeInfo, dn: DataNode) -> None:
        with self._lock:
            self.max_volume_id = max(self.max_volume_id, v.id)
            dn.add_or_update_volume(v)
            self._layout_for_info(v).register_volume(v, dn)
        self._notify_locations({v.id})

    def unregister_volume(self, v: VolumeInfo, dn: DataNode) -> None:
        with self._lock:
            dn.delete_volume_by_id(v.id)
            self._layout_for_info(v).unregister_volume(v, dn)
        self._notify_locations({v.id})

    # -- heartbeat ingestion (master_grpc_server.go:21-183) ----------------
    def sync_data_node(self, dn: DataNode, volumes: list[VolumeInfo],
                       ec_shards: dict[int, ShardBits] | None = None) -> None:
        """Full registration sync for one server."""
        with self._lock:
            new, deleted = dn.update_volumes(volumes)
            for v in deleted:
                self._layout_for_info(v).unregister_volume(v, dn)
            for v in volumes:
                self.max_volume_id = max(self.max_volume_id, v.id)
                self._layout_for_info(v).register_volume(v, dn)
            if ec_shards is not None:
                self.sync_ec_shards(dn, ec_shards)
        self._notify_locations({v.id for v in volumes} |
                               {v.id for v in deleted})

    def sync_ec_shards(self, dn: DataNode,
                       shards: dict[int, ShardBits],
                       collections: dict[int, str] | None = None) -> None:
        """Full EC shard sync for one server (RegisterEcShards
        topology_ec.go)."""
        touched: set[int] = set(shards)
        with self._lock:
            dn.update_ec_shards(shards)
            # rebuild this node's entries in the global map
            for vid, by_shard in list(self.ec_shard_map.items()):
                for sid, nodes in list(by_shard.items()):
                    if dn in nodes and not (
                            vid in shards and shards[vid].has_shard_id(sid)):
                        nodes.remove(dn)
                        touched.add(vid)
                    if not nodes:
                        del by_shard[sid]
                if not by_shard:
                    del self.ec_shard_map[vid]
                    self.ec_collections.pop(vid, None)
            for vid, bits in shards.items():
                self.max_volume_id = max(self.max_volume_id, vid)
                by_shard = self.ec_shard_map.setdefault(vid, {})
                if collections and vid in collections:
                    self.ec_collections[vid] = collections[vid]
                for sid in bits.shard_ids():
                    nodes = by_shard.setdefault(sid, [])
                    if dn not in nodes:
                        nodes.append(dn)
        self._notify_locations(touched)

    def unregister_data_node(self, dn: DataNode) -> None:
        """Server died: drop from layouts + EC map, unlink from tree
        (topology.go UnRegisterDataNode:200)."""
        with self._lock:
            for v in list(dn.volumes.values()):
                self._layout_for_info(v).set_volume_unavailable(v.id, dn)
            self.sync_ec_shards(dn, {})
            dn.is_active = False
            if dn.parent:
                dn.parent.unlink_child(dn.id)
        # everything the node hosted moved/vanished — cheaper to drop
        # the whole location cache than enumerate under churn
        self._notify_locations(None)

    # -- lookups -----------------------------------------------------------
    def lookup(self, collection: str, vid: int) -> list[DataNode]:
        """Volume replica locations (topology.go Lookup:92)."""
        with self._lock:
            for (coll, _, _, _), layout in self.layouts.items():
                if collection and coll != collection:
                    continue
                locs = layout.lookup(vid)
                if locs:
                    return locs
        return []

    def lookup_ec_shards(self, vid: int) -> dict[int, list[DataNode]]:
        return {sid: list(nodes)
                for sid, nodes in self.ec_shard_map.get(vid, {}).items()}

    # -- id assignment -----------------------------------------------------
    def next_volume_id(self) -> int:
        """The raft-replicated MaxVolumeIdCommand counter
        (topology/cluster_commands.go).  The allocator is called OUTSIDE
        the topology lock — it may block on a raft quorum round-trip whose
        apply path itself takes this lock."""
        alloc = self.vid_allocator
        if alloc is not None:
            return alloc()
        with self._lock:
            self.max_volume_id += 1
            return self.max_volume_id

    def pick_for_write(self, option: VolumeGrowOption
                       ) -> tuple[int, list[DataNode]]:
        layout = self.get_volume_layout(
            option.collection, option.replica_placement, option.ttl_str,
            option.disk_type)
        return layout.pick_for_write(option, self._rng)

    def has_writable_volume(self, option: VolumeGrowOption) -> bool:
        layout = self.get_volume_layout(
            option.collection, option.replica_placement, option.ttl_str,
            option.disk_type)
        return layout.active_volume_count(option) > 0

    # -- serialization (the `volume.list` shape, shell tests' input) -------
    def to_dict(self) -> dict:
        out: dict = {"max_volume_id": self.max_volume_id,
                     "ec_collections": {str(vid): coll for vid, coll
                                        in self.ec_collections.items()},
                     "data_centers": []}
        for dc in self.root.children.values():
            dcd = {"id": dc.id, "racks": []}
            for rack in dc.children.values():
                rd = {"id": rack.id, "data_nodes": []}
                for dn in rack.children.values():
                    assert isinstance(dn, DataNode)
                    rd["data_nodes"].append({
                        "id": dn.id, "ip": dn.ip, "port": dn.port,
                        "grpc_port": dn.grpc_port,
                        "tcp_port": dn.tcp_port,
                        "public_url": dn.public_url,
                        "max_volumes": dn.max_volumes,
                        # mid-churn guard: a node swept between this
                        # snapshot and plan execution flips inactive;
                        # planners must not copy from/to it
                        "is_active": dn.is_active,
                        "last_seen_age_s": round(
                            max(0.0, _time.time() - dn.last_seen), 3),
                        "volumes": [vars(v) for v in dn.volumes.values()],
                        "ec_shards": {str(vid): int(bits)
                                      for vid, bits in dn.ec_shards.items()},
                    })
                dcd["racks"].append(rd)
            out["data_centers"].append(dcd)
        return out


def from_topology_dict(d: dict, **topo_kw) -> Topology:
    """Rebuild a Topology from to_dict() output — the fake-topology seam
    the shell/balancer tests run on (command_volume_list_test.go pattern)."""
    topo = Topology(**topo_kw)
    for dcd in d.get("data_centers", []):
        for rd in dcd.get("racks", []):
            for nd in rd.get("data_nodes", []):
                dn = topo.get_or_create_data_node(
                    dcd["id"], rd["id"], nd["id"], ip=nd.get("ip", ""),
                    port=nd.get("port", 0),
                    grpc_port=nd.get("grpc_port", 0),
                    tcp_port=nd.get("tcp_port", 0),
                    public_url=nd.get("public_url", ""),
                    max_volumes=nd.get("max_volumes", 7))
                volumes = [VolumeInfo(**v) for v in nd.get("volumes", [])]
                shards = {int(vid): ShardBits(bits)
                          for vid, bits in nd.get("ec_shards", {}).items()}
                topo.sync_data_node(dn, volumes, shards)
    topo.max_volume_id = max(topo.max_volume_id,
                             d.get("max_volume_id", 0))
    return topo
