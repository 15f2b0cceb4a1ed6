"""The plane copy of a single clay loss: `ec.rebuild` copies of each
remote helper only the beta repair planes (a quarter of a clay(10,4)
shard) into a plane file, the rebuild reads and removes those files,
and CopyFile without the field streams whole files as it always did."""

import glob
import json
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu import operation, shell
from seaweedfs_tpu.ops import clay_matrix
from seaweedfs_tpu.pb.rpc import RpcError
from seaweedfs_tpu.shell.command_ec import do_ec_rebuild
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.testing import SimCluster
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.volume_server.server import VolumeServer

COPY = "VolumeServer/VolumeEcShardsCopy"
COPY_FILE = "VolumeServer/CopyFile"


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """A clay(10,4) volume sealed over four servers, and every shard's
    sealed bytes."""
    with SimCluster(volume_servers=4,
                    base_dir=str(tmp_path_factory.mktemp("clay"))) as c:
        fids = [operation.assign_and_upload(c.master_grpc,
                                            os.urandom(2000 + 77 * i))
                for i in range(6)]
        vid = int(fids[0].split(",")[0])
        blobs = {f: c.read(f) for f in fids if int(f.split(",")[0]) == vid}
        env = shell.CommandEnv(c.master_grpc)
        shell.run_command(env, "lock")
        shell.run_command(env, f"ec.encode -volumeId {vid} -kind clay")
        c.sync_heartbeats()
        homes = _shard_paths(c, vid)
        assert sorted(homes) == list(range(14))
        shards = {s: _read(p) for s, p in homes.items()}
        yield _Sealed(c, env, vid, shards, blobs, homes)


class _Sealed:
    def __init__(self, c, env, vid, shards, blobs, homes):
        self.c, self.env, self.vid = c, env, vid
        self.shards, self.blobs, self.homes = shards, blobs, homes

    def rearm(self):
        """Every shard back where the seal put it: a rebuilt shard stays
        on its rebuilder, which would otherwise gather the helpers."""
        c, env, vid = self.c, self.env, self.vid
        now = _shard_paths(c, vid)
        for s, home in self.homes.items():
            if now.get(s) == home:
                continue
            if s in now:
                _holder_call(c, env, now[s], "VolumeEcShardsUnmount",
                             {"volume_id": vid, "shard_ids": [s]})
                _holder_call(c, env, now[s], "VolumeEcShardsDelete",
                             {"volume_id": vid, "collection": "",
                              "shard_ids": [s]})
            with open(home, "wb") as f:
                f.write(self.shards[s])
            _holder_call(c, env, home, "VolumeEcShardsMount",
                         {"volume_id": vid, "collection": "",
                          "shard_ids": [s]})
        c.sync_heartbeats()
        return c, env, vid, self.shards, self.blobs


def _holder_call(c, env, path: str, method: str, req: dict) -> dict:
    holder = next(vs for vs in c.volume_servers
                  if any(os.path.dirname(path) == d.directory
                         for d in vs.store.locations))
    return env.volume_server(holder.grpc_address).call(method, req)


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _dirs(c) -> list[str]:
    return [d.directory for vs in c.volume_servers
            for d in vs.store.locations]


def _shard_paths(c, vid: int) -> dict[int, str]:
    return {int(p[-2:]): p for d in _dirs(c)
            for p in glob.glob(os.path.join(d, f"{vid}.ec[0-9][0-9]"))}


def _leftovers(c, vid: int) -> list[str]:
    """Plane files and temporary copies on any server."""
    return sorted(p for d in _dirs(c)
                  for pat in (f"{vid}.*.planes*", f"{vid}.*.tmp")
                  for p in glob.glob(os.path.join(d, pat)))


def _lose(c, env, vid: int, lost: int) -> None:
    path = _shard_paths(c, vid)[lost]
    _holder_call(c, env, path, "VolumeEcShardsUnmount",
                 {"volume_id": vid, "shard_ids": [lost]})
    _holder_call(c, env, path, "VolumeEcShardsDelete",
                 {"volume_id": vid, "collection": "", "shard_ids": [lost]})
    c.sync_heartbeats()


def _spans(c, tid: str, name: str) -> list[dict]:
    return [sp for vs in c.volume_servers
            for sp in vs.tracer.snapshot(trace_id=tid) if sp["name"] == name]


@pytest.mark.parametrize("lost", range(14))
def test_single_clay_loss_copies_only_the_repair_planes(sealed, lost):
    c, env, vid, shards, blobs = sealed.rearm()
    _lose(c, env, vid, lost)
    tid = tracing.new_trace_id()
    with tracing.trace_scope(tid):
        out = do_ec_rebuild(env, vid)
    assert out["rebuilt"] == [lost]
    assert _read(_shard_paths(c, vid)[lost]) == shards[lost]
    size = len(shards[lost])
    copies = _spans(c, tid, COPY)
    # the seal's placement 4, 4, 3, 3: the rebuilder holds 3 or 4
    # helpers and is copied the planes of the other 9 or 10
    assert len(out["copied"]) in (9, 10) and copies
    assert sum(sp["bytes"] for sp in copies) \
        == len(out["copied"]) * size // 4
    assert {sp["plane_layers"] for sp in copies + _spans(c, tid, COPY_FILE)} \
        == {64}
    stats = out["rebuild_stats"]
    assert stats["plan_kind"] == "clay-plane"
    assert stats["copy"] == "planes"
    assert stats["helpers_from_planes"] == len(out["copied"])
    assert stats["bytes_read"] == 13 * size // 4
    assert _leftovers(c, vid) == []
    c.sync_heartbeats()
    for fid, payload in blobs.items():
        assert c.read(fid) == payload


def test_a_source_that_ignores_the_field_fails_the_copy(sealed,
                                                        monkeypatch):
    """A source streaming the whole shard for a plane request: the copy
    fails naming the source, leaves no temporary, plane or partial
    shard file, and ec.rebuild reports the error; with a sound source
    the next ec.rebuild repairs the shard."""
    c, env, vid, shards, _ = sealed.rearm()
    lost = 5
    _lose(c, env, vid, lost)

    def whole_shard(self, base, path, req):
        with open(path, "rb") as f:
            while chunk := f.read(1 << 20):
                yield {"file_content": chunk}
    monkeypatch.setattr(VolumeServer, "_copy_planes", whole_shard)
    sources = {vs.grpc_address for vs in c.volume_servers}
    with pytest.raises(RpcError) as err:
        shell.run_command(env, f"ec.rebuild -volumeId {vid}")
    assert "repair planes" in str(err.value)
    assert any(s in str(err.value) for s in sources)
    assert _leftovers(c, vid) == []
    assert lost not in _shard_paths(c, vid)
    monkeypatch.undo()
    out = json.loads(shell.run_command(
        env, f"ec.rebuild -volumeId {vid}"))["rebuilt"][0]
    assert out["rebuilt"] == [lost]
    assert _read(_shard_paths(c, vid)[lost]) == shards[lost]
    assert _leftovers(c, vid) == []


def test_a_failed_source_waits_for_the_other_copies(sealed, monkeypatch):
    """The copies run at once: one source streams whole shards for a
    plane request while the others' streams are held until that copy
    has failed.  ec.rebuild raises the error naming the failing source
    only once the late copies have ended, then removes every plane file,
    theirs too, and a clean ec.rebuild repairs the shard."""
    c, env, vid, shards, _ = sealed.rearm()
    lost = 5
    _lose(c, env, vid, lost)
    failed = threading.Event()
    lock = threading.Lock()
    roles: dict[str, str] = {}
    late: list[bool] = []
    planes = VolumeServer._copy_planes
    copy_planes = VolumeServer._ec_copy_planes

    def source(self, base, path, req):
        with lock:
            role = roles.setdefault(self.grpc_address,
                                    "late" if roles else "fail")
        if role == "fail":
            with open(path, "rb") as f:
                while chunk := f.read(1 << 20):
                    yield {"file_content": chunk}
            return
        late.append(failed.wait(timeout=30))
        yield from planes(self, base, path, req)

    def rebuilder(self, req, base, src):
        try:
            return copy_planes(self, req, base, src)
        except RpcError:
            failed.set()
            raise
    monkeypatch.setattr(VolumeServer, "_copy_planes", source)
    monkeypatch.setattr(VolumeServer, "_ec_copy_planes", rebuilder)
    with pytest.raises(RpcError) as err:
        shell.run_command(env, f"ec.rebuild -volumeId {vid}")
    (failing,) = [a for a, r in roles.items() if r == "fail"]
    assert "repair planes" in str(err.value) and failing in str(err.value)
    assert len(roles) >= 2 and late and all(late)
    assert _leftovers(c, vid) == []
    assert lost not in _shard_paths(c, vid)
    monkeypatch.undo()
    out = json.loads(shell.run_command(
        env, f"ec.rebuild -volumeId {vid}"))["rebuilt"][0]
    assert out["rebuilt"] == [lost]
    assert out["copy_fanout"] == len(roles)
    assert _read(_shard_paths(c, vid)[lost]) == shards[lost]
    assert _leftovers(c, vid) == []


SMALL = 4096          # win_a = 16 B a layer: 1,200 windows make 2 messages


@pytest.fixture
def helper_shard(sealed):
    """A random clay(10,4) shard of 1,200 small windows, as shard 3 of a
    volume of its own on the first server."""
    c, env = sealed.c, sealed.env
    vs = c.volume_servers[0]
    vid = 9001
    base = os.path.join(vs.store.locations[0].directory, str(vid))
    data = np.random.default_rng(3).integers(0, 256, 1200 * SMALL,
                                             dtype=np.uint8)
    data.tofile(base + ".ec03")
    ec.save_volume_info(base, 3, dat_size=10 * len(data), data_shards=10,
                        parity_shards=4, large_block_size=SMALL * 1024,
                        small_block_size=SMALL, code_kind="clay")
    yield env.volume_server(vs.grpc_address), vid, data
    for ext in (".ec03", ".vif"):
        os.remove(base + ext)


def test_copy_file_without_the_field_streams_the_whole_file(helper_shard):
    client, vid, data = helper_shard
    got = [r["file_content"] for r in client.stream(
        "CopyFile", iter([{"volume_id": vid, "ext": ".ec03"}]))]
    raw = data.tobytes()
    assert got == [raw[i:i + (1 << 20)] for i in range(0, len(raw), 1 << 20)]


@pytest.mark.parametrize("lost", [0, 7, 13])
def test_copy_file_with_the_field_streams_the_plane_layers(helper_shard,
                                                           lost):
    client, vid, data = helper_shard
    got = [r["file_content"] for r in client.stream(
        "CopyFile", iter([{"volume_id": vid, "ext": ".ec03",
                           "repair_planes_of": lost}]))]
    _, plane, _ = clay_matrix.repair_flat(10, 4, lost)
    want = data.reshape(-1, 256, SMALL // 256)[:, list(plane)]
    assert b"".join(got) == want.tobytes()
    assert len(b"".join(got)) == len(data) // 4
    # whole windows, up to 1 MiB a message
    assert [len(g) for g in got] == [1024 * 64 * 16, 176 * 64 * 16]


def test_copy_file_refuses_planes_of_the_lost_shard_itself(helper_shard):
    client, vid, _ = helper_shard
    with pytest.raises(RpcError):
        list(client.stream("CopyFile", iter([{
            "volume_id": vid, "ext": ".ec03", "repair_planes_of": 3}])))
