"""Stage timings inside a span (util/tracing.stage / add): where the
tags land, how they cross threads, what they cost with tracing off; the
stages of the EC shard copy on the volume-server RPC spans, and the
codec registry's per-stage series on the encode and degraded-read
paths."""

import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.master import MasterServer
from seaweedfs_tpu.ops.codec import RSCodec, codec_metrics
from seaweedfs_tpu.pb.rpc import POOL, RpcServer
from seaweedfs_tpu.shell import CommandEnv
from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec.layout import EcGeometry
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.volume_server import VolumeServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("gather", "pack", "wait", "unpack", "write", "cpu")


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# -- the primitive ---------------------------------------------------------

def test_stage_and_add_sum_onto_innermost_span():
    t = tracing.Tracer("t")
    with t.span("outer"):
        with tracing.stage("read"):
            _spin(0.002)
        with t.span("inner"):
            with tracing.stage("read"):
                _spin(0.001)
            with tracing.stage("read"):
                _spin(0.001)
            tracing.add("bytes", 10)
            tracing.add("bytes", 5)
        tracing.add("bytes", 1)
    inner, outer = t.snapshot()
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["bytes"] == 15 and outer["bytes"] == 1
    assert 0.002 <= inner["read_s"] <= inner["duration_ms"] / 1e3
    assert 0.002 <= outer["read_s"] < outer["duration_ms"] / 1e3
    # outside any span, stages and counts go nowhere
    with tracing.stage("read"):
        pass
    tracing.add("bytes", 1)
    assert len(t.snapshot()) == 2


def test_stage_crosses_propagate_into_a_worker():
    t = tracing.Tracer("t")

    def work():
        with tracing.stage("write"):
            _spin(0.001)
        tracing.add("bytes", 7)

    with ThreadPoolExecutor(2) as pool, t.span("rpc"):
        futures = [pool.submit(tracing.propagate(work)) for _ in range(4)]
        for f in futures:
            f.result(timeout=10)
        # without propagate the worker has no span to add to
        pool.submit(work).result(timeout=10)
    (span,) = t.snapshot()
    assert span["bytes"] == 28
    assert span["write_s"] >= 0.004


def test_stage_tags_are_summed_under_a_lock():
    """Several threads summing into one span lose no update."""
    t = tracing.Tracer("t")
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(8) as pool, t.span("rpc"):
            def work():
                for _ in range(2000):
                    tracing.add("bytes", 1)
            futures = [pool.submit(tracing.propagate(work))
                       for _ in range(8)]
            for f in futures:
                f.result(timeout=30)
    finally:
        sys.setswitchinterval(switch)
    assert t.snapshot()[0]["bytes"] == 16000


def test_stage_records_no_tags_with_tracing_off():
    t = tracing.Tracer("t")
    seen = []
    tracing.set_enabled(False)
    try:
        with t.span("rpc"):
            with tracing.stage("read"):
                pass
            with tracing.stage("read", observe=seen.append):
                _spin(0.001)
            tracing.add("bytes", 3)
    finally:
        tracing.set_enabled(True)
    (span,) = t.snapshot()
    assert "read_s" not in span and "bytes" not in span
    # a metrics counter behind a stage counts whether tracing is on or not
    assert len(seen) == 1 and seen[0] >= 0.001


def test_tracing_module_leaves_jax_unloaded():
    code = (
        "import sys\n"
        "from seaweedfs_tpu.util import tracing\n"
        "t = tracing.Tracer('t')\n"
        "with t.span('s'):\n"
        "    with tracing.stage('read'):\n"
        "        pass\n"
        "    tracing.add('bytes', 1)\n"
        "assert 'jax' not in sys.modules, 'tracing loaded jax'\n"
        "print(t.snapshot()[0]['bytes'])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=60,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


def test_stage_is_a_profiler_annotation_once_jax_is_loaded(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.stage("probe.stage_annotation"):
            _spin(0.001)
    finally:
        jax.profiler.stop_trace()
    blobs = [open(os.path.join(d, f), "rb").read()
             for d, _, files in os.walk(tmp_path) for f in files
             if f.endswith(".xplane.pb")]
    assert blobs and any(b"weed.probe.stage_annotation" in b
                         for b in blobs)


# -- RPC framing -----------------------------------------------------------

def test_stream_framing_lands_on_the_rpc_spans():
    """A streamed response is serialized on the handler thread, after the
    handler's generator yields it: that JSON time is the server span's
    frame_s.  The consumer's span gets recv_s and its parse's frame_s."""
    server = RpcServer()
    server.tracer = tracing.Tracer("server")
    payload = "x" * (1 << 20)

    def chunks(requests):
        for req in requests:
            for _ in range(int(req["n"])):
                yield {"data": payload}     # no framing in the handler

    server.add_service("T", stream={"Chunks": chunks})
    server.start()
    client_tracer = tracing.Tracer("client")
    try:
        client = POOL.client(server.address, "T")
        with client_tracer.span("consume"):
            got = [len(r["data"]) for r in client.stream(
                "Chunks", iter([{"n": 4}]))]
    finally:
        server.stop()
    assert got == [1 << 20] * 4
    deadline = time.time() + 5
    while not server.tracer.snapshot() and time.time() < deadline:
        time.sleep(0.01)
    (span,) = server.tracer.snapshot()
    assert span["name"] == "T/Chunks"
    assert 0 < span["frame_s"] <= span["duration_ms"] / 1e3
    (consumer,) = client_tracer.snapshot()
    assert 0 < consumer["recv_s"] <= consumer["duration_ms"] / 1e3
    assert 0 < consumer["frame_s"] <= consumer["duration_ms"] / 1e3


@pytest.fixture()
def cluster(tmp_path):
    master = MasterServer(seed=5)
    master.start()
    servers = []
    for i in range(4):
        d = tmp_path / f"vol{i}"
        d.mkdir()
        vs = VolumeServer(master.grpc_address, [str(d)],
                          pulse_seconds=0.5, max_volume_counts=[30])
        vs.start()
        servers.append(vs)
    deadline = time.time() + 10
    while time.time() < deadline and len(master.topo.data_nodes()) < 4:
        time.sleep(0.05)
    yield master, servers, CommandEnv(master.grpc_address)
    for vs in servers:
        vs.stop()
    master.stop()


def _volume_files(directory: str, vid: int) -> list[str]:
    return [os.path.join(directory, f) for f in os.listdir(directory)
            if f.split(".")[0] == str(vid)
            and f.split(".")[1] not in ("dat", "idx")]


def _copy_spans(servers, tid: str) -> list[dict]:
    """The VolumeEcShardsCopy spans of one trace, each checked, as the
    CopyFile spans under them, for its stage tags and for bytes that all
    came raw; the bytes sent are the bytes copied.  Every file crossed
    the source's frame port: a CopyFile span tagged `transport: tcp`,
    its bytes sent by sendfile."""
    copies, sends = [], []
    for vs in servers:
        for s in vs.tracer.snapshot(trace_id=tid):
            if s["name"] == "VolumeServer/VolumeEcShardsCopy":
                for tag in ("recv_s", "frame_s", "write_s"):
                    assert 0 < s[tag] <= s["duration_ms"] / 1e3, (tag, s)
                # every shard byte came raw, none base64 in JSON
                assert s["raw_bytes"] == s["bytes"], s
                copies.append(s)
            elif s["name"] == "VolumeServer/CopyFile" \
                    and s["status"] == "ok":
                assert s["transport"] == "tcp", s
                for tag in ("send_s", "frame_s"):
                    assert 0 < s[tag] <= s["duration_ms"] / 1e3, (tag, s)
                assert s["raw_bytes"] == s["bytes"], s
                sends.append(s)
    # each file a copy pulled is one CopyFile under it
    assert {s["parent_id"] for s in sends} <= {s["span_id"] for s in copies}
    assert len(sends) >= len(copies)
    assert sum(s["bytes"] for s in sends) == sum(s["bytes"] for s in copies)
    return copies


def _seal(master, servers, env) -> tuple[int, dict]:
    vid = None
    for i in range(12):
        fid = operation.assign_and_upload(master.grpc_address,
                                          os.urandom(40_000 + i))
        vid = vid or int(fid.split(",")[0])
    for vs in servers:
        vs.heartbeat_now()
    tid = tracing.new_trace_id()
    with tracing.trace_scope(tid):
        out = do_ec_encode(env, vid)
    return vid, {"tid": tid, **out}


def test_ec_encode_copy_spans_carry_bytes_and_stages(cluster):
    """The destinations copy at once, each under the verb's trace: every
    copy is found by the trace id, and carries what it pulled."""
    master, servers, env = cluster
    vid, out = _seal(master, servers, env)
    copies = _copy_spans(servers, out["tid"])
    assert len(copies) == out["copy_fanout"] == 3
    copied = 0
    for vs in servers:
        mine = [s for s in vs.tracer.snapshot(trace_id=out["tid"])
                if s["name"] == "VolumeServer/VolumeEcShardsCopy"]
        if mine:
            # the files this server pulled are the EC files it now holds
            want = sum(os.path.getsize(p) for p in _volume_files(
                vs.store.locations[0].directory, vid))
            assert sum(s["bytes"] for s in mine) == want > 0
            copied += want
    assert sum(s["bytes"] for s in copies) == copied


def test_ec_rebuild_copy_spans_carry_bytes_and_stages(cluster):
    """The sources' copies to the rebuilder run at once, each under the
    verb's trace id and span: every copy names the verb's span as its
    parent, and the bytes sent are the whole shards copied."""
    master, servers, env = cluster
    vid, _ = _seal(master, servers, env)
    lost = 0
    holder = next(vs for vs in servers if os.path.exists(os.path.join(
        vs.store.locations[0].directory, f"{vid}.ec00")))
    size = os.path.getsize(os.path.join(holder.store.locations[0].directory,
                                        f"{vid}.ec00"))
    client = env.volume_server(holder.grpc_address)
    client.call("VolumeEcShardsUnmount", {"volume_id": vid,
                                         "shard_ids": [lost]})
    client.call("VolumeEcShardsDelete", {"volume_id": vid, "collection": "",
                                        "shard_ids": [lost]})
    for vs in servers:
        vs.heartbeat_now()
    tid, sid = tracing.new_trace_id(), tracing.new_span_id()
    with tracing.trace_scope(tid, sid):
        out = do_ec_rebuild(env, vid)
    assert out["rebuilt"] == [lost]
    copies = _copy_spans(servers, tid)
    assert len(copies) == out["copy_fanout"] >= 2
    assert {s["parent_id"] for s in copies} == {sid}
    assert sum(s["bytes"] for s in copies) == len(out["copied"]) * size


# -- codec registry stages -------------------------------------------------

GEO = EcGeometry(data_shards=10, parity_shards=4,
                 large_block_size=16 * 1024, small_block_size=1024)


def _stage_values(backend: str, op: str) -> dict[str, float]:
    m = codec_metrics()
    return {s: m.stages[s].value(backend, op) for s in STAGES}


def _make_volume(directory: str, vid: int = 7) -> dict:
    rng = np.random.default_rng(3)
    v = Volume(directory, "", vid)
    needles = {}
    for i in range(1, 31):
        data = rng.integers(0, 256, int(rng.integers(1, 6000)),
                            dtype=np.uint8).tobytes()
        n = Needle(id=i, cookie=int(rng.integers(1 << 31)), data=data)
        v.write_needle(n)
        needles[i] = (n.cookie, data)
    v.close()
    return needles


def test_jax_codec_encode_and_degraded_read_raise_every_stage(tmp_path):
    codec = RSCodec(GEO.data_shards, GEO.parity_shards, backend="jax")
    needles = _make_volume(str(tmp_path))
    enc0 = _stage_values("rs_jax", "encode")
    ec.encode_volume_to_ec(str(tmp_path / "7"), version=3, geo=GEO,
                           codec=codec)
    enc1 = _stage_values("rs_jax", "encode")
    assert all(enc1[s] > enc0[s] for s in STAGES), (enc0, enc1)

    lost = {0, 7, 10, 13}
    ev = ec.EcVolume(str(tmp_path), "", 7, GEO, codec)
    for s in range(GEO.total_shards):
        if s not in lost:
            ev.add_shard(s)
    rec0 = _stage_values("rs_jax", "reconstruct")
    t = tracing.Tracer("volume")
    for nid, (cookie, data) in needles.items():
        with t.span(f"GET {nid}"):
            assert ev.read_needle(nid, cookie).data == data
    ev.close()
    rec1 = _stage_values("rs_jax", "reconstruct")
    assert all(rec1[s] > rec0[s] for s in STAGES if s != "write")
    assert rec1["write"] == rec0["write"]
    spans = t.snapshot()
    for tag in ("ec.locate_s", "ec.interval.local_s", "needle.parse_s"):
        assert all(s[tag] > 0 for s in spans), tag
    degraded = [s for s in spans if "ec.reconstruct_s" in s]
    assert degraded and all(
        s["codec.pack_s"] + s["codec.wait_s"] + s["codec.unpack_s"]
        <= s["ec.reconstruct_s"] <= s["duration_ms"] / 1e3
        for s in degraded)


def test_synchronous_reconstruct_stages_within_its_op_seconds():
    codec = RSCodec(4, 2, backend="jax")
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (4, 4096), dtype=np.uint8)
    parity = codec.encode(data)
    shards = [None, data[1], data[2], data[3], parity[0], None]
    codec.reconstruct(shards)           # compile outside the measurement
    m = codec_metrics()
    label = ("rs_jax", "reconstruct")
    before = _stage_values(*label)
    op0 = m.seconds._sums[label]
    out = codec.reconstruct(shards)
    op = m.seconds._sums[label] - op0
    after = _stage_values(*label)
    assert np.array_equal(out[0], data[0])
    spent = {s: after[s] - before[s] for s in STAGES}
    assert all(spent[s] > 0 for s in ("pack", "wait", "unpack"))
    assert spent["pack"] + spent["wait"] + spent["unpack"] <= op


def test_cpu_codecs_record_no_device_stages():
    codec = RSCodec(4, 2, backend="numpy")
    before = _stage_values("rs_numpy", "encode")
    codec.encode(np.zeros((4, 1024), dtype=np.uint8))
    assert _stage_values("rs_numpy", "encode") == before


def test_stage_series_in_the_exposition():
    text = codec_metrics().registry.render()
    for s in STAGES:
        assert f"# TYPE seaweedfs_codec_{s}_seconds_total counter" in text


def test_lrc_encode_and_rebuild_raise_every_stage(tmp_path, monkeypatch):
    """LRC's encode and its rebuild, on RS's executor, record the
    gather, pack, wait, unpack and write stages under backend "lrc", as
    the RS encoder does under its own label."""
    from seaweedfs_tpu.storage.ec import codes
    monkeypatch.setenv("WEED_EC_BACKEND", "jax")
    monkeypatch.setattr(codes, "_multi_device", lambda: False)  # one chip
    geo = EcGeometry(data_shards=12, parity_shards=4, code_kind="lrc",
                     lrc_locals=2, large_block_size=16 * 1024,
                     small_block_size=1024)
    _make_volume(str(tmp_path))
    base = str(tmp_path / "7")
    enc0 = _stage_values("lrc", "encode")
    ec.encode_volume_to_ec(base, version=3, geo=geo)
    enc1 = _stage_values("lrc", "encode")
    assert all(enc1[s] > enc0[s] for s in STAGES if s != "cpu"), (enc0, enc1)
    os.remove(base + ec.to_ext(0))
    rec0 = _stage_values("lrc", "reconstruct")
    stats: dict = {}
    assert ec.rebuild_ec_files(base, stats=stats) == [0]
    rec1 = _stage_values("lrc", "reconstruct")
    assert all(rec1[s] > rec0[s] for s in STAGES if s != "cpu"), (rec0, rec1)
    assert stats["executor"] == "jax" and stats["plan_kind"] == "local"
