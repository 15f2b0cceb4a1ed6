"""EC maintenance commands — capability-equivalent to
weed/shell/command_ec_encode.go / _rebuild.go / _balance.go / _decode.go.

ec.encode is the SURVEY §3.5 north-star flow: freeze -> TPU-encode ->
spread shards -> drop source replicas.  Planning (which volumes, which
servers get which shards) is pure over the topology dump for unit testing;
execution drives the VolumeServer EC RPCs.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

from ..pb.rpc import RpcError
from ..storage.ec.layout import TOTAL_SHARDS_COUNT, EcGeometry
from ..storage.ec.plan import RepairPlan, repair_plan
from ..storage.ec.shard_bits import ShardBits
from ..util import tracing
from ..util.weedlog import logger
from .commands import (CommandEnv, ShellError, command, iter_data_nodes,
                       node_grpc, node_tcp, parse_flags)

LOG = logger(__name__)


# -- planning (pure) -------------------------------------------------------

def collect_volume_ids_for_ec_encode(topo: dict, volume_size_limit: int,
                                     full_percent: float = 95.0,
                                     quiet_seconds: float = 3600.0,
                                     now: float | None = None,
                                     collection: str = "") -> list[int]:
    """Full + quiet volumes (collectVolumeIdsForEcEncode
    command_ec_encode.go:267)."""
    now = time.time() if now is None else now
    vids = set()
    for _, _, dn in iter_data_nodes(topo):
        for v in dn["volumes"]:
            if collection and v.get("collection", "") != collection:
                continue
            if v.get("size", 0) < volume_size_limit * full_percent / 100.0:
                continue
            if now - v.get("modified_at_second", 0) < quiet_seconds:
                continue
            vids.add(v["id"])
    return sorted(vids)


def plan_shard_distribution(topo: dict, vid: int, source_id: str,
                            n_total: int = TOTAL_SHARDS_COUNT
                            ) -> dict[str, list[int]]:
    """node_id -> shard ids, most-free-slots first, round-robin
    (balancedEcDistribution command_ec_encode.go:249)."""
    nodes = []
    for _, _, dn in iter_data_nodes(topo):
        free = (dn.get("max_volumes", 7) - len(dn["volumes"])
                - sum(ShardBits(int(b)).shard_id_count()
                      for b in dn.get("ec_shards", {}).values())
                / TOTAL_SHARDS_COUNT)
        nodes.append((free, dn["id"]))
    if not nodes:
        raise ShellError("no data nodes")
    nodes.sort(reverse=True)
    out: dict[str, list[int]] = {nid: [] for _, nid in nodes}
    order = [nid for _, nid in nodes]
    for shard in range(n_total):
        out[order[shard % len(order)]].append(shard)
    return {nid: shards for nid, shards in out.items() if shards}


def plan_rebuild(geo: EcGeometry, missing: list[int],
                 shard_map: dict[str, list[int]]
                 ) -> tuple[str, RepairPlan, dict[str, list[int]]]:
    """(rebuilder, its repair plan, {holder: shards it copies the
    rebuilder}).  The rebuilder is the holder of the most of its plan's
    read set (storage/ec/plan.py, shards it holds preferred), ties
    broken by the most shards of the volume, then the lowest id; only
    the part of the read set it lacks is copied.  Raises ValueError when
    the survivors cannot rebuild `missing`."""
    present = sorted({s for ids in shard_map.values() for s in ids})
    plans = {nid: repair_plan(geo, missing, present, prefer=ids)
             for nid, ids in shard_map.items()}
    rebuilder = min(shard_map, key=lambda nid: (
        -len(set(plans[nid].read_shards) & set(shard_map[nid])),
        -len(shard_map[nid]), nid))
    need = set(plans[rebuilder].read_shards) - set(shard_map[rebuilder])
    copies: dict[str, list[int]] = {}
    for nid, ids in shard_map.items():
        take = [s for s in ids if s in need]
        if nid != rebuilder and take:
            copies[nid] = take
            need -= set(take)
    return rebuilder, plans[rebuilder], copies


def collect_ec_shard_map(topo: dict) -> dict[int, dict[str, list[int]]]:
    """vid -> node_id -> shard ids present."""
    out: dict[int, dict[str, list[int]]] = {}
    for _, _, dn in iter_data_nodes(topo):
        for vid_s, bits in dn.get("ec_shards", {}).items():
            vid = int(vid_s)
            ids = ShardBits(int(bits)).shard_ids()
            if ids:
                out.setdefault(vid, {})[dn["id"]] = ids
    return out


def plan_ec_balance(topo: dict) -> list[dict]:
    """Move shards from over-loaded holders to nodes with none of that
    volume's shards, evening the per-node count (command_ec_balance.go)."""
    all_nodes = [dn["id"] for _, _, dn in iter_data_nodes(topo)]
    grpc = {dn["id"]: node_grpc(dn) for _, _, dn in iter_data_nodes(topo)}
    moves = []
    for vid, holders in sorted(collect_ec_shard_map(topo).items()):
        counts = {nid: len(holders.get(nid, [])) for nid in all_nodes}
        target = -(-TOTAL_SHARDS_COUNT // max(len(all_nodes), 1))  # ceil
        for _ in range(TOTAL_SHARDS_COUNT):
            src = max(counts, key=counts.get)
            dst = min(counts, key=counts.get)
            if counts[src] <= target or counts[src] - counts[dst] <= 1:
                break
            shard = sorted(holders[src])[-1]
            moves.append({"volume_id": vid, "shard_id": shard,
                          "from": src, "from_grpc": grpc[src],
                          "to": dst, "to_grpc": grpc[dst]})
            holders[src].remove(shard)
            holders.setdefault(dst, []).append(shard)
            counts[src] -= 1
            counts[dst] += 1
    return moves


# -- execution helpers -----------------------------------------------------

def _volume_locations(env: CommandEnv, vid: int) -> list[dict]:
    out = env.master().call("LookupVolume",
                            {"volume_or_file_ids": [str(vid)]})
    return out["volume_id_locations"][str(vid)]["locations"]


def _grpc_of_location(topo: dict, url: str) -> str:
    for _, _, dn in iter_data_nodes(topo):
        if dn["id"] == url or f"{dn['ip']}:{dn['port']}" == url:
            return node_grpc(dn)
    raise ShellError(f"no grpc address for {url}")


def _run_per_node(what: str, jobs: dict) -> None:
    """Run each node's job (node id -> callable) at once, one worker a
    node, each under the caller's trace, so the RPCs it makes carry the
    verb's trace id and parent span (parallelCopyEcShardsFromSource
    command_ec_encode.go:190).  Every job ends before this returns; the
    first failure in the jobs' order is then raised, the others logged."""
    if not jobs:
        return
    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        futures = {nid: pool.submit(tracing.propagate(job))
                   for nid, job in jobs.items()}
    errors = [(nid, e) for nid, f in futures.items()
              if (e := f.exception()) is not None]
    for nid, e in errors[1:]:
        LOG.warning("%s: %s failed too: %s", what, nid, e)
    if errors:
        raise errors[0][1]


def _most_at_once(spans: list) -> int:
    """The most of `spans` ((start, end) pairs) open at one time: the
    verbs' `copy_fanout`, the copies that ran together."""
    return max((sum(s <= t < e for s, e in spans) for t, _ in spans),
               default=0)


def _tcp_by_id(topo: dict) -> dict:
    """{node id: its frame port "host:port"} of the nodes that have one;
    a VolumeEcShardsCopy names its source's as `source_tcp`."""
    return {dn["id"]: node_tcp(dn) for _, _, dn in iter_data_nodes(topo)
            if dn.get("tcp_port")}


def do_ec_encode(env: CommandEnv, vid: int, collection: str = "",
                 data_shards: int = 0, parity_shards: int = 0,
                 kind: str = "", lrc_locals: int = 0) -> dict:
    """Full doEcEncode flow (command_ec_encode.go:95-188).

    `kind` selects the code family beyond the reference's fixed RS:
    "clay" (MSR, 1/q repair IO) or "lrc" (local groups; `lrc_locals`
    local parities within parity_shards) — see storage/ec/codes.py.

    The whole flow runs under ONE trace id (minted here, propagated as
    x-trace-id metadata on every RPC): the freeze → generate → spread →
    delete sequence swaps live volume state on several servers, and a
    failure part-way through is a prime suspect for the soak
    SizeMismatchError — the id ties this orchestration to the
    volume-side swap logs.

    The spread runs every node's copy and mount at once; `copy_fanout`
    in the output is the most copies that overlapped."""
    tid = tracing.current_trace_id() or tracing.new_trace_id()
    with tracing.trace_scope(tid):
        try:
            return _do_ec_encode_traced(env, vid, tid, collection,
                                        data_shards, parity_shards,
                                        kind, lrc_locals)
        except Exception as e:
            # the failure path IS the interesting path: replicas may be
            # frozen readonly with shards half-spread — name the trace
            # so an operator (and the soak test's logs) can walk it
            LOG.warning("ec.encode volume %d trace=%s FAILED mid-flow: "
                        "%s (replicas may be readonly with partial "
                        "shards)", vid, tid, e)
            raise


def _do_ec_encode_traced(env: CommandEnv, vid: int, tid: str,
                         collection: str, data_shards: int,
                         parity_shards: int, kind: str,
                         lrc_locals: int) -> dict:
    topo = env.topology()
    locations = _volume_locations(env, vid)
    if not locations:
        raise ShellError(f"volume {vid} not found")
    src_grpc = _grpc_of_location(topo, locations[0]["url"])
    # freeze every replica
    for loc in locations:
        env.volume_server(_grpc_of_location(topo, loc["url"])).call(
            "VolumeMarkReadonly", {"volume_id": vid})
    # generate shards on one replica (the TPU hot loop)
    gen_req = {"volume_id": vid, "collection": collection}
    n_total = TOTAL_SHARDS_COUNT
    if data_shards or parity_shards or kind:
        gen_req["data_shards"] = data_shards or 10
        gen_req["parity_shards"] = parity_shards or 4
        n_total = gen_req["data_shards"] + gen_req["parity_shards"]
    if kind:
        gen_req["code_kind"] = kind
        gen_req["lrc_locals"] = lrc_locals
    env.volume_server(src_grpc).call("VolumeEcShardsGenerate", gen_req,
                                     timeout=3600)
    # spread + mount
    plan = plan_shard_distribution(topo, vid, locations[0]["url"],
                                   n_total=n_total)
    grpc_by_id = {dn["id"]: node_grpc(dn)
                  for _, _, dn in iter_data_nodes(topo)}
    src_id = None
    for _, _, dn in iter_data_nodes(topo):
        if f"{dn['ip']}:{dn['port']}" == locations[0]["url"] \
                or dn["id"] == locations[0]["url"]:
            src_id = dn["id"]
    src_tcp = _tcp_by_id(topo).get(src_id, "")
    copying: list = []

    def place(node_id: str, shard_ids: list[int]) -> None:
        target = env.volume_server(grpc_by_id[node_id])
        if node_id != src_id:
            t0 = time.perf_counter()
            target.call("VolumeEcShardsCopy", {
                "volume_id": vid, "collection": collection,
                "shard_ids": shard_ids, "copy_ecx_files": True,
                "source_data_node": src_grpc, "source_tcp": src_tcp},
                timeout=3600)
            copying.append((t0, time.perf_counter()))
        target.call("VolumeEcShardsMount",
                    {"volume_id": vid, "collection": collection,
                     "shard_ids": shard_ids})
    # every node's copy + mount at once; the source's job is its mount
    _run_per_node(f"ec.encode volume {vid}",
                  {nid: partial(place, nid, ids)
                   for nid, ids in plan.items()})
    # drop non-local shard files from the source, delete original volume
    src = env.volume_server(src_grpc)
    keep = set(plan.get(src_id, []))
    drop = [s for s in range(n_total) if s not in keep]
    if drop:
        src.call("VolumeEcShardsUnmount", {"volume_id": vid,
                                           "shard_ids": drop})
        src.call("VolumeEcShardsDelete", {"volume_id": vid,
                                          "collection": collection,
                                          "shard_ids": drop})
    for loc in locations:
        env.volume_server(_grpc_of_location(topo, loc["url"])).call(
            "VolumeDelete", {"volume_id": vid})
    return {"volume_id": vid, "distribution": plan,
            "copy_fanout": _most_at_once(copying)}


def _ec_geometry(env: CommandEnv, vid: int, collection: str,
                 holders: list[str], grpc_by_id: dict) -> EcGeometry:
    """The stripe geometry and code of an EC volume, from a holder's .vif
    (wide stripes and the clay/LRC families: not the fixed 10+4)."""
    for nid in holders:
        try:
            g = env.volume_server(grpc_by_id[nid]).call(
                "VolumeEcGeometry",
                {"volume_id": vid, "collection": collection})
        except RpcError:
            continue
        return EcGeometry(data_shards=g["data_shards"],
                          parity_shards=g["parity_shards"],
                          code_kind=g.get("code_kind", "rs"),
                          lrc_locals=g.get("lrc_locals", 0))
    return EcGeometry()


def do_ec_rebuild(env: CommandEnv, vid: int, collection: str = "") -> dict:
    """Pick a rebuilder, copy it the part of the repair's read set it
    lacks, rebuild + mount the missing shards (command_ec_rebuild.go:
    58-230).

    The read set comes from the planner the rebuilder's
    VolumeEcShardsRebuild reads by (storage/ec/plan.py): k survivors for
    RS, the d helpers for clay, the lost shard's local group for a
    single LRC loss.  The rebuilder is the server holding the most of
    that set (ties: the most shards of the volume, then the lowest id),
    so the copy is as small as the placement allows; it regenerates only
    the missing shards and then deletes its temporary copies.

    A single clay loss (plan "clay-plane") copies of each remote helper
    only the beta repair planes its repair reads, a quarter of the shard
    for clay(10,4) (`repair_planes_of` on VolumeEcShardsCopy); the
    rebuild reads and removes those plane files, and a failed copy or
    rebuild has them removed.  Every other plan copies whole shards.

    The copies from the sources run at once (`copy_fanout` in the
    output); the rebuild, and a failure's clean-up, wait for all."""
    topo = env.topology()
    shard_map = collect_ec_shard_map(topo).get(vid, {})
    present = sorted({s for ids in shard_map.values() for s in ids})
    grpc_by_id = {dn["id"]: node_grpc(dn)
                  for _, _, dn in iter_data_nodes(topo)}
    geo = _ec_geometry(env, vid, collection, list(shard_map), grpc_by_id)
    missing = [s for s in range(geo.total_shards) if s not in present]
    if not missing:
        return {"volume_id": vid, "rebuilt": [], "copied": [],
                "copy_fanout": 0}
    try:
        rebuilder_id, plan, copies = plan_rebuild(geo, missing, shard_map)
    except ValueError as e:
        raise ShellError(f"ec volume {vid}: {e}") from None
    rebuilder = env.volume_server(grpc_by_id[rebuilder_id])
    # a single clay loss copies only the helpers' repair planes
    planes = {"repair_planes_of": missing[0]} \
        if plan.kind == "clay-plane" else {}

    tcp_by_id = _tcp_by_id(topo)
    copying: list = []

    def copy(node_id: str, take: list[int]) -> None:
        t0 = time.perf_counter()
        rebuilder.call("VolumeEcShardsCopy", {
            "volume_id": vid, "collection": collection,
            "shard_ids": take, "copy_ecx_files": False,
            "source_data_node": grpc_by_id[node_id],
            "source_tcp": tcp_by_id.get(node_id, ""), **planes},
            timeout=3600)
        copying.append((t0, time.perf_counter()))
    try:
        _run_per_node(f"ec.rebuild volume {vid}",
                      {nid: partial(copy, nid, take)
                       for nid, take in copies.items()})
        out = rebuilder.call("VolumeEcShardsRebuild",
                             {"volume_id": vid, "collection": collection,
                              "shard_ids": missing}, timeout=3600)
    except RpcError:
        if planes:
            # the rebuild removes the plane files it read; after a
            # failure (every copy has ended) they would lie there unused
            try:
                rebuilder.call("VolumeEcShardsDelete", {
                    "volume_id": vid, "collection": collection,
                    "shard_ids": sorted(s for t in copies.values()
                                        for s in t), **planes})
            except RpcError as e:
                LOG.warning("ec.rebuild volume %d: plane files left on "
                            "%s: %s", vid, rebuilder_id, e)
        raise
    rebuilt = out.get("rebuilt_shard_ids", [])
    copied = sorted(s for take in copies.values() for s in take)
    rebuilder.call("VolumeEcShardsMount",
                   {"volume_id": vid, "collection": collection,
                    "shard_ids": rebuilt})
    # drop the temp copies that still live elsewhere
    stale = [s for s in copied if s not in rebuilt]
    if stale and not planes:
        rebuilder.call("VolumeEcShardsDelete",
                       {"volume_id": vid, "collection": collection,
                        "shard_ids": stale})
    return {"volume_id": vid, "rebuilt": rebuilt,
            "rebuilder": rebuilder_id, "copied": copied,
            "copy_fanout": _most_at_once(copying),
            # repair-IO accounting (bytes_read, plan_kind, read_shards,
            # executor): operators see the clay/LRC reduced-read plans
            # in the verb output, mirrored by the /metrics counters
            "rebuild_stats": out.get("rebuild_stats", {})}


# -- commands --------------------------------------------------------------

@command("ec.encode", "erasure-code volumes: -volumeId N | -collection c "
                      "-fullPercent p -quietFor s [-dataShards k "
                      "-parityShards m] [-kind rs|clay|lrc "
                      "-lrcLocals l]")
def cmd_ec_encode(env: CommandEnv, args: list[str]) -> str:
    flags = parse_flags(args)
    env.confirm_is_locked()
    if "volumeId" in flags:
        vids = [int(flags["volumeId"])]
    else:
        cfg = env.master().call("GetMasterConfiguration")
        limit = cfg.get("volume_size_limit_m_b", 30 * 1024) * 1024 * 1024
        vids = collect_volume_ids_for_ec_encode(
            env.topology(), limit,
            full_percent=float(flags.get("fullPercent", 95)),
            quiet_seconds=float(flags.get("quietFor", 3600)),
            collection=flags.get("collection", ""))
    results = [do_ec_encode(env, vid, flags.get("collection", ""),
                            data_shards=int(flags.get("dataShards", 0)),
                            parity_shards=int(flags.get("parityShards",
                                                        0)),
                            kind=flags.get("kind", ""),
                            lrc_locals=int(flags.get("lrcLocals", 0)))
               for vid in vids]
    return json.dumps({"encoded": results})


@command("ec.rebuild", "rebuild missing ec shards (-volumeId N | all)")
def cmd_ec_rebuild(env: CommandEnv, args: list[str]) -> str:
    flags = parse_flags(args)
    env.confirm_is_locked()
    if "volumeId" in flags:
        vids = [int(flags["volumeId"])]
    else:
        vids = sorted(collect_ec_shard_map(env.topology()))
    return json.dumps({"rebuilt": [
        do_ec_rebuild(env, vid, flags.get("collection", ""))
        for vid in vids]})


@command("ec.balance", "even ec shards across servers (-force applies)")
def cmd_ec_balance(env: CommandEnv, args: list[str]) -> str:
    flags = parse_flags(args)
    moves = plan_ec_balance(env.topology())
    if flags.get("force") != "true":
        return json.dumps({"planned_moves": moves})
    env.confirm_is_locked()
    for mv in moves:
        dst = env.volume_server(mv["to_grpc"])
        dst.call("VolumeEcShardsCopy", {
            "volume_id": mv["volume_id"], "shard_ids": [mv["shard_id"]],
            "copy_ecx_files": True, "source_data_node": mv["from_grpc"]},
            timeout=3600)
        dst.call("VolumeEcShardsMount",
                 {"volume_id": mv["volume_id"], "collection": "",
                  "shard_ids": [mv["shard_id"]]})
        src = env.volume_server(mv["from_grpc"])
        src.call("VolumeEcShardsUnmount",
                 {"volume_id": mv["volume_id"],
                  "shard_ids": [mv["shard_id"]]})
        src.call("VolumeEcShardsDelete",
                 {"volume_id": mv["volume_id"], "collection": "",
                  "shard_ids": [mv["shard_id"]]})
    return json.dumps({"moved": len(moves)})


@command("ec.decode", "decode an ec volume back to a normal volume: -volumeId N")
def cmd_ec_decode(env: CommandEnv, args: list[str]) -> str:
    flags = parse_flags(args)
    env.confirm_is_locked()
    vid = int(flags["volumeId"])
    collection = flags.get("collection", "")
    topo = env.topology()
    shard_map = collect_ec_shard_map(topo).get(vid, {})
    if not shard_map:
        raise ShellError(f"ec volume {vid} not found")
    grpc_by_id = {dn["id"]: node_grpc(dn)
                  for _, _, dn in iter_data_nodes(topo)}
    # gather all shards onto the node with the most
    target_id = max(shard_map, key=lambda nid: len(shard_map[nid]))
    target = env.volume_server(grpc_by_id[target_id])
    local = set(shard_map[target_id])
    for node_id, ids in shard_map.items():
        if node_id == target_id:
            continue
        need = [s for s in ids if s not in local]
        if need:
            target.call("VolumeEcShardsCopy", {
                "volume_id": vid, "collection": collection,
                "shard_ids": need, "copy_ecx_files": False,
                "source_data_node": grpc_by_id[node_id]}, timeout=3600)
            local |= set(need)
    target.call("VolumeEcShardsToVolume",
                {"volume_id": vid, "collection": collection}, timeout=3600)
    # remove ec shards everywhere else
    for node_id, ids in shard_map.items():
        vs = env.volume_server(grpc_by_id[node_id])
        if node_id != target_id:
            vs.call("VolumeEcShardsUnmount",
                    {"volume_id": vid, "shard_ids": ids})
            vs.call("VolumeEcShardsDelete",
                    {"volume_id": vid, "collection": collection,
                     "shard_ids": ids})
    return json.dumps({"volume_id": vid, "decoded_on": target_id})
