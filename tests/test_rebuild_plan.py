"""The repair read-set planner that ec.rebuild's shell side and the
volume server's VolumeEcShardsRebuild share (storage/ec/plan.py): which
server rebuilds, which shards it is copied, which it reads, for RS, clay
and LRC."""

import contextlib
import json
import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import lrc
from seaweedfs_tpu.shell.command_ec import plan_rebuild
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec.layout import EcGeometry

SMALL = dict(large_block_size=16 * 1024, small_block_size=1024)
RS = EcGeometry(10, 4, **SMALL)
CLAY = EcGeometry(10, 4, code_kind="clay", **SMALL)
LRC = EcGeometry(12, 4, code_kind="lrc", lrc_locals=2, **SMALL)


def round_robin(geo, lost=(), servers=4):
    """ec.encode's spread over `servers` nodes (shard s on node
    s % servers), less the lost shards."""
    return {f"n{i}": [s for s in range(geo.total_shards)
                      if s % servers == i and s not in lost]
            for i in range(servers)}


@pytest.mark.parametrize("lost", [0, 7, 12, 13])
def test_lrc_single_loss_copies_only_the_missing_part_of_its_group(lost):
    held = round_robin(LRC, [lost])
    rebuilder, plan, copies = plan_rebuild(LRC, [lost], held)
    assert plan.kind == "local"
    group = lrc.LrcGeometry(12, 2, 2)
    g = lost // 6 if lost < 12 else lost - 12
    want = [s for s in group.group_members(g) + [12 + g] if s != lost]
    assert sorted(plan.read_shards) == want
    copied = sorted(s for take in copies.values() for s in take)
    # 4/4/4/4 placement: the rebuilder holds 2 of the 6, 4 are copied
    assert len(copied) == 4
    assert set(copied) | (set(want) & set(held[rebuilder])) == set(want)
    for nid, take in copies.items():
        assert set(take) <= set(held[nid])


@pytest.mark.parametrize("lost", [0, 7, 13])
def test_rs_copies_k_minus_what_the_rebuilder_holds(lost):
    held = round_robin(RS, [lost])
    rebuilder, plan, copies = plan_rebuild(RS, [lost], held)
    local = set(held[rebuilder])
    assert len(local) == 4 and local <= set(plan.read_shards)
    assert len(plan.read_shards) == 10
    assert sum(len(t) for t in copies.values()) == 10 - len(local)


@pytest.mark.parametrize("lost", [0, 7, 13])
def test_clay_copy_set_is_every_survivor_the_rebuilder_lacks(lost):
    """Clay's single-loss read set is all 13 helpers: the copy is what
    the verb copied before the planner, 9 shards to a holder of 4."""
    held = round_robin(CLAY, [lost])
    rebuilder, plan, copies = plan_rebuild(CLAY, [lost], held)
    assert plan.kind == "clay-plane"
    assert len(held[rebuilder]) == 4
    copied = sorted(s for take in copies.values() for s in take)
    assert copied == sorted(s for s in range(14)
                            if s != lost and s not in held[rebuilder])
    assert len(copied) == 9


def test_rebuilder_ties_go_to_most_shards_then_lowest_id():
    held = {"b": [1, 2], "a": [3, 4], "c": [5, 6, 7]}
    geo = EcGeometry(6, 2)          # RS(6,2): shard 0 lost, 7 survive
    rebuilder, plan, _ = plan_rebuild(geo, [0], held)
    assert rebuilder == "c" and len(plan.read_shards) == 6
    rebuilder, _, _ = plan_rebuild(geo, [0], {"b": [1, 2], "a": [3, 4],
                                              "c": [5, 6], "d": [7]})
    assert rebuilder == "a"


def test_unrecoverable_is_refused():
    with pytest.raises(ValueError):
        plan_rebuild(RS, list(range(5)), round_robin(RS, range(5)))


def _sealed(tmp_path, geo):
    """A striped volume of seeded bytes, every shard and its .vif."""
    os.makedirs(tmp_path / "sealed")
    base = str(tmp_path / "sealed" / "5")
    size = geo.large_row_size() + 2 * geo.small_row_size() + 321
    payload = np.random.default_rng(5).integers(0, 256, size, np.uint8)
    payload.tofile(base + ".dat")
    ec.write_ec_files(base, geo)
    extra = {"lrc_construction": lrc.CONSTRUCTION} \
        if geo.code_kind == "lrc" else {}
    ec.save_volume_info(base, 3, dat_size=size,
                        data_shards=geo.data_shards,
                        parity_shards=geo.parity_shards,
                        large_block_size=geo.large_block_size,
                        small_block_size=geo.small_block_size,
                        code_kind=geo.code_kind,
                        lrc_locals=geo.lrc_locals, **extra)
    return base


@pytest.mark.parametrize("geo,lost", [
    (RS, [0]), (RS, [7]), (RS, [0, 13]),
    (CLAY, [3]), (CLAY, [0, 13]),
    (LRC, [0]), (LRC, [13]), (LRC, [14]), (LRC, [0, 1, 7, 10]),
], ids=["rs-0", "rs-7", "rs-2loss", "clay-3", "clay-2loss", "lrc-0",
        "lrc-13", "lrc-global", "lrc-4loss"])
def test_shell_and_server_share_the_read_set(tmp_path, geo, lost):
    """The shell's plan over the round-robin placement names the read
    set; a rebuilder holding its own shards and the copies reads that
    very set, and regenerates only the lost shards, byte for byte."""
    sealed = _sealed(tmp_path, geo)
    held = round_robin(geo, lost)
    rebuilder, plan, copies = plan_rebuild(geo, lost, held)
    os.makedirs(tmp_path / "rebuilder")
    base = str(tmp_path / "rebuilder" / "5")
    shutil.copyfile(sealed + ".vif", base + ".vif")
    on_disk = set(held[rebuilder]) | {s for t in copies.values() for s in t}
    for s in on_disk:
        shutil.copyfile(sealed + ec.to_ext(s), base + ec.to_ext(s))
    stats: dict = {}
    assert ec.rebuild_ec_files(base, stats=stats, shard_ids=lost) == lost
    assert sorted(stats["read_shards"]) == sorted(plan.read_shards)
    assert stats["plan_kind"].startswith(plan.kind)
    for s in lost:
        with open(base + ec.to_ext(s), "rb") as a, \
                open(sealed + ec.to_ext(s), "rb") as b:
            assert a.read() == b.read(), f"shard {s}"
    assert {s for s in range(geo.total_shards)
            if os.path.exists(base + ec.to_ext(s))} == on_disk | set(lost)


@contextlib.contextmanager
def _sealed_cluster(tmp_path, flags):
    """A four-server SimCluster with one volume of six blobs sealed by
    `ec.encode {flags}`: (cluster, env, vid, {fid: payload})."""
    from seaweedfs_tpu import operation, shell
    from seaweedfs_tpu.testing import SimCluster

    with SimCluster(volume_servers=4, base_dir=str(tmp_path)) as c:
        blobs = {}
        for i in range(6):
            fid = operation.assign_and_upload(c.master_grpc,
                                              os.urandom(3000 + 91 * i))
            blobs[fid] = None
        vid = int(next(iter(blobs)).split(",")[0])
        blobs = {fid: c.read(fid) for fid in blobs
                 if int(fid.split(",")[0]) == vid}
        env = shell.CommandEnv(c.master_grpc)
        shell.run_command(env, "lock")
        shell.run_command(env, f"ec.encode -volumeId {vid} {flags}".strip())
        c.sync_heartbeats()
        yield c, env, vid, blobs


def _lose(c, env, vid, lost):
    for shard in lost:
        holder = next(vs for vs in c.volume_servers
                      if any(os.path.exists(os.path.join(
                          d.directory, f"{vid}.ec{shard:02d}"))
                          for d in vs.store.locations))
        client = env.volume_server(holder.grpc_address)
        client.call("VolumeEcShardsUnmount",
                    {"volume_id": vid, "shard_ids": [shard]})
        client.call("VolumeEcShardsDelete", {"volume_id": vid,
                                             "collection": "",
                                             "shard_ids": [shard]})
    c.sync_heartbeats()


def _spy_copies(monkeypatch) -> list[dict]:
    """Every VolumeEcShardsCopy request the servers built after this
    call receive (the handler is bound when a server starts)."""
    from seaweedfs_tpu.volume_server.server import VolumeServer
    seen: list[dict] = []
    orig = VolumeServer._rpc_ec_copy

    def spy(self, req):
        seen.append(dict(req))
        return orig(self, req)
    monkeypatch.setattr(VolumeServer, "_rpc_ec_copy", spy)
    return seen


def _copy_bytes(c) -> int:
    return sum(sp.get("bytes", 0) for vs in c.volume_servers
               for sp in vs.tracer.snapshot()
               if sp["name"] == "VolumeServer/VolumeEcShardsCopy")


@pytest.mark.parametrize("kind,flags,copies", [
    ("rs", "", 6),
    ("lrc", "-kind lrc -dataShards 12 -parityShards 4 -lrcLocals 2", 4),
    ("clay", "-kind clay", 9),
])
def test_ec_rebuild_verb_copies_only_the_plan(tmp_path, kind, flags,
                                              copies, monkeypatch):
    """`ec.rebuild` of one lost shard through the shell verb and the
    volume-server RPCs on four servers: it copies only the part of the
    read set the rebuilder lacks (LRC 4 of its 6-shard group, RS k minus
    the rebuilder's 4, clay every survivor it lacks), regenerates only
    the lost shard, removes its copies, mounts no shard twice, and every
    blob reads back.  RS and LRC copy whole shards; clay, the helpers'
    repair planes alone, a quarter of each."""
    from seaweedfs_tpu import shell
    from seaweedfs_tpu.shell.command_ec import collect_ec_shard_map

    requests = _spy_copies(monkeypatch)
    with _sealed_cluster(tmp_path, flags) as (c, env, vid, blobs):
        n = 16 if kind == "lrc" else 14
        lost = 0
        _lose(c, env, vid, [lost])
        shard_size = os.path.getsize(next(
            os.path.join(d.directory, f"{vid}.ec01")
            for vs in c.volume_servers for d in vs.store.locations
            if os.path.exists(os.path.join(d.directory, f"{vid}.ec01"))))
        before = collect_ec_shard_map(env.topology())[vid]
        del requests[:]
        sealed_bytes = _copy_bytes(c)
        out = json.loads(shell.run_command(
            env, f"ec.rebuild -volumeId {vid}"))["rebuilt"][0]
        assert out["rebuilt"] == [lost]
        assert len(out["copied"]) == copies
        assert not set(out["copied"]) & set(before[out["rebuilder"]])
        if kind == "rs":
            assert copies == 10 - len(before[out["rebuilder"]])
        planes = kind == "clay"
        assert [r.get("repair_planes_of") for r in requests] \
            == [lost if planes else None] * len(requests)
        assert _copy_bytes(c) - sealed_bytes \
            == copies * shard_size // (4 if planes else 1)
        stats = out["rebuild_stats"]
        assert stats["plan_kind"] == {"rs": "rs-full", "lrc": "local",
                                      "clay": "clay-plane"}[kind]
        if planes:
            assert stats["copy"] == "planes"
            assert stats["helpers_from_planes"] == copies
        (span,) = [sp for vs in c.volume_servers
                   for sp in vs.tracer.snapshot()
                   if sp["name"] == "VolumeServer/VolumeEcShardsRebuild"]
        assert span["plan_kind"] == stats["plan_kind"]
        assert span["read_shards"] == len(stats["read_shards"])
        assert span["bytes_read"] == stats["bytes_read"] > 0
        # the test host's eight virtual devices give RS the MeshCodec
        assert stats["executor"] in ("native", "numpy", "jax", "mesh")
        c.sync_heartbeats()
        after = collect_ec_shard_map(env.topology())[vid]
        mounted = sorted(s for ids in after.values() for s in ids)
        assert mounted == list(range(n)), "a shard mounted twice or lost"
        files = [s for vs in c.volume_servers for d in vs.store.locations
                 for s in range(n) if os.path.exists(
                     os.path.join(d.directory, f"{vid}.ec{s:02d}"))]
        assert sorted(files) == list(range(n)), "a temporary copy stayed"
        assert not [p for vs in c.volume_servers
                    for d in vs.store.locations
                    for p in os.listdir(d.directory) if ".planes" in p]
        for fid, payload in blobs.items():
            assert c.read(fid) == payload


def test_two_clay_losses_copy_whole_shards(tmp_path, monkeypatch):
    """Two lost clay shards decode from k whole survivors
    ("clay-decode"): the copy requests carry no plane field and move
    whole shards."""
    from seaweedfs_tpu import shell

    requests = _spy_copies(monkeypatch)
    with _sealed_cluster(tmp_path, "-kind clay") as (c, env, vid, blobs):
        shards = {}
        for vs in c.volume_servers:
            for d in vs.store.locations:
                for s in (0, 13):
                    p = os.path.join(d.directory, f"{vid}.ec{s:02d}")
                    if os.path.exists(p):
                        with open(p, "rb") as f:
                            shards[s] = f.read()
        _lose(c, env, vid, [0, 13])
        del requests[:]
        sealed_bytes = _copy_bytes(c)
        out = json.loads(shell.run_command(
            env, f"ec.rebuild -volumeId {vid}"))["rebuilt"][0]
        assert out["rebuilt"] == [0, 13]
        assert out["rebuild_stats"]["plan_kind"] == "clay-decode"
        assert out["rebuild_stats"]["copy"] == "whole"
        assert requests and all("repair_planes_of" not in r
                                for r in requests)
        assert _copy_bytes(c) - sealed_bytes \
            == len(out["copied"]) * len(shards[0])
        for s, want in shards.items():
            p = next(os.path.join(d.directory, f"{vid}.ec{s:02d}")
                     for vs in c.volume_servers for d in vs.store.locations
                     if os.path.exists(os.path.join(d.directory,
                                                    f"{vid}.ec{s:02d}")))
            with open(p, "rb") as f:
                assert f.read() == want
        c.sync_heartbeats()
        for fid, payload in blobs.items():
            assert c.read(fid) == payload
