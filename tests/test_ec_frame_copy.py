"""The EC shard copy over the source's raw-TCP frame port ('C',
volume_server/tcp.py): whole files by sendfile, a clay helper's repair
planes, the refusals, the cut stream, and the fall-back to CopyFile
where the port cannot serve the copy."""

import io
import os

import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.ops import clay_matrix
from seaweedfs_tpu.pb.rpc import POOL, RpcClient, RpcError
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.testing import SimCluster
from seaweedfs_tpu.volume_server import server as server_mod
from seaweedfs_tpu.volume_server import tcp

SMALL = 4096                # win_a = 16 B a layer
VID = 9001


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    """Two servers; the first holds a random clay(10,4) shard 3 of
    1,200 small windows (4.9 MB) of volume VID, with its .vif."""
    with SimCluster(volume_servers=2,
                    base_dir=str(tmp_path_factory.mktemp("frame"))) as c:
        vs = c.volume_servers[0]
        base = os.path.join(vs.store.locations[0].directory, str(VID))
        data = np.random.default_rng(5).integers(0, 256, 1200 * SMALL,
                                                 dtype=np.uint8)
        data.tofile(base + ".ec03")
        ec.save_volume_info(base, 3, dat_size=10 * len(data),
                            data_shards=10, parity_shards=4,
                            large_block_size=SMALL * 1024,
                            small_block_size=SMALL, code_kind="clay")
        yield c, f"127.0.0.1:{vs.tcp.port}", data


@pytest.fixture(params=["native", "python"])
def serve_loop(request, monkeypatch):
    """Both of the frame port's serve loops: the C one when it builds
    here, and the Python one."""
    if request.param == "native":
        if native.fastpath() is None:
            pytest.skip("no C frame loop on this host")
    else:
        monkeypatch.setattr(native, "fastpath", lambda: None)
    return request.param


@pytest.mark.parametrize("sendfile", ["1", "0"])
def test_whole_file_arrives_in_chunks(source, serve_loop, sendfile,
                                      monkeypatch):
    """Chunks of COPY_CHUNK and a short last one, by sendfile or, with
    WEED_SENDFILE=0, by read and send; the bytes are the file's."""
    _, addr, data = source
    monkeypatch.setenv("WEED_SENDFILE", sendfile)
    monkeypatch.setattr(tcp, "COPY_CHUNK", 256 << 10)
    out = io.BytesIO()
    assert tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03"},
                          out) == len(data)
    assert out.getvalue() == data.tobytes()


@pytest.mark.parametrize("lost", [0, 7, 13])
def test_plane_request_sends_the_repair_layers(source, serve_loop, lost):
    _, addr, data = source
    out = io.BytesIO()
    got = tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03",
                                "repair_planes_of": lost}, out)
    _, plane, _ = clay_matrix.repair_flat(10, 4, lost)
    want = data.reshape(-1, 256, SMALL // 256)[:, list(plane)]
    assert got == len(data) // 4
    assert out.getvalue() == want.tobytes()


@pytest.mark.parametrize("req, why", [
    ({"ext": ".dat"}, "EC files of a volume only"),
    ({"ext": "/../../etc/passwd"}, "EC files of a volume only"),
    ({"ext": ".ec03", "collection": "../x"}, "EC files of a volume only"),
    ({"ext": ".ec05"}, "not found"),
    ({"ext": ".ec03", "repair_planes_of": 3}, "no repair planes"),
])
def test_refusals_write_nothing_and_keep_the_port(source, serve_loop,
                                                   req, why):
    _, addr, data = source
    out = io.BytesIO()
    with pytest.raises(tcp.CopyRefused, match=why):
        tcp.fetch_file(addr, {"volume_id": VID, **req}, out)
    assert out.getvalue() == b""
    # the port serves the next copy
    assert tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03"},
                          io.BytesIO()) == len(data)


def test_limit_stops_the_copy_before_writing_past_it(source, monkeypatch):
    _, addr, data = source
    monkeypatch.setattr(tcp, "COPY_CHUNK", 256 << 10)
    out = io.BytesIO()
    got = tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03"}, out,
                         limit=len(data) // 4)
    assert len(data) // 4 < got < len(data)
    assert len(out.getvalue()) <= len(data) // 4
    assert out.getvalue() == data.tobytes()[:len(out.getvalue())]


def test_a_copy_cut_after_it_started_is_not_taken_for_whole(source,
                                                            monkeypatch):
    _, addr, _ = source

    def one_chunk_then_fail(sock, f):
        tcp.send_copy_chunk(sock, f.read(1000))
        raise OSError("disk gone")
    monkeypatch.setattr(tcp, "sendfile_copy_chunks", one_chunk_then_fail)
    with pytest.raises(ConnectionError):
        tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03"},
                       io.BytesIO())


def test_no_frame_port_copy_under_tls(source, monkeypatch):
    """Under TLS the source refuses the copy, and a destination asks
    over CopyFile without trying the port."""
    c, addr, data = source
    monkeypatch.setattr(server_mod, "rpc_tls_enabled", lambda: True)
    with pytest.raises(tcp.CopyRefused, match="TLS"):
        tcp.fetch_file(addr, {"volume_id": VID, "ext": ".ec03"},
                       io.BytesIO())
    tried = []
    monkeypatch.setattr(tcp, "fetch_file",
                        lambda *a, **k: tried.append(a))
    streams = _spy_streams(monkeypatch)
    _copy_to_second(c, addr)
    assert tried == [] and streams == ["CopyFile"]
    assert _copied(c) == data.tobytes()


@pytest.mark.parametrize("port", ["old", "closed"])
def test_a_source_port_without_the_copy_falls_back_to_copyfile(
        source, monkeypatch, port):
    """A source whose port predates the copy ('unknown op') or refuses
    the connection: VolumeEcShardsCopy pulls over CopyFile."""
    c, addr, data = source
    if port == "old":
        monkeypatch.setattr(
            tcp.TcpDataServer, "_serve_copy",
            lambda self, conn, body: tcp.write_reply(
                conn, 1, b"unknown op 'C'"))
    else:
        addr = "127.0.0.1:1"
    streams = _spy_streams(monkeypatch)
    _copy_to_second(c, addr)
    assert streams == ["CopyFile"]
    assert _copied(c) == data.tobytes()


def test_other_refusals_fail_the_copy_naming_the_port(source):
    c, addr, _ = source
    with pytest.raises(RpcError, match=f".ec04 from {addr}: .*not found"):
        POOL.client(c.volume_servers[1].grpc_address, "VolumeServer").call(
            "VolumeEcShardsCopy", {
                "volume_id": VID, "shard_ids": [4],
                "copy_ecx_files": False,
                "source_data_node": c.volume_servers[0].grpc_address,
                "source_tcp": addr})


def _spy_streams(monkeypatch) -> list[str]:
    """The names of the gRPC streams this process opens from now on."""
    opened = []
    stream = RpcClient.stream

    def spy(self, method, requests, timeout=None):
        opened.append(method)
        return stream(self, method, requests, timeout)
    monkeypatch.setattr(RpcClient, "stream", spy)
    return opened


def _copy_to_second(c, addr: str) -> None:
    path = _second_path(c)
    if os.path.exists(path):
        os.remove(path)
    POOL.client(c.volume_servers[1].grpc_address, "VolumeServer").call(
        "VolumeEcShardsCopy", {
            "volume_id": VID, "shard_ids": [3], "copy_ecx_files": False,
            "source_data_node": c.volume_servers[0].grpc_address,
            "source_tcp": addr})


def _second_path(c) -> str:
    return os.path.join(c.volume_servers[1].store.locations[0].directory,
                        f"{VID}.ec03")


def _copied(c) -> bytes:
    with open(_second_path(c), "rb") as f:
        return f.read()
