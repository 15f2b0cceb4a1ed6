"""Production Clay + LRC erasure codes (storage/ec/codes.py): shard-file
round-trips, the measured repair-IO advantage, degraded reads, and the
shell verb flow — VERDICT r2 #3 (BASELINE's beyond-RS code families)."""

import json
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops import clay_matrix, gf256, lrc, rs_matrix
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec.layout import EcGeometry

rng = np.random.default_rng(21)

CLAY_GEO = EcGeometry(data_shards=10, parity_shards=4,
                      large_block_size=16 * 1024, small_block_size=1024,
                      code_kind="clay")
LRC_GEO = EcGeometry(data_shards=10, parity_shards=4,
                     large_block_size=16 * 1024, small_block_size=1024,
                     code_kind="lrc", lrc_locals=2)
RS_GEO = EcGeometry(data_shards=10, parity_shards=4,
                    large_block_size=16 * 1024, small_block_size=1024)


def make_ec_volume(tmp_path, geo, vid=7, size=None):
    """A raw .dat striped into shard files + .vif under `geo`.  The .dat
    begins with a valid super block, as every real volume's does."""
    from seaweedfs_tpu.storage.super_block import SuperBlock
    os.makedirs(tmp_path, exist_ok=True)
    if size is None:
        size = geo.large_row_size() + 3 * geo.small_row_size() + 777
    base = str(tmp_path / str(vid))
    sb = SuperBlock().to_bytes()
    payload = rng.integers(0, 256, size, dtype=np.uint8)
    payload[:len(sb)] = np.frombuffer(sb, np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(payload.tobytes())
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
    return base, payload


def read_shards(base, geo):
    out = {}
    for i in range(geo.total_shards):
        with open(base + ec.to_ext(i), "rb") as f:
            out[i] = f.read()
    return out


def test_clay_data_shards_identical_to_rs(tmp_path):
    """Clay is systematic: data shard files are byte-identical to RS's,
    so locate math and normal reads never consult the kind."""
    b1, _ = make_ec_volume(tmp_path / "clay", CLAY_GEO)
    b2, _ = make_ec_volume(tmp_path / "rs", RS_GEO)
    # same rng stream -> different payloads; re-make with equal payload
    payload = rng.integers(0, 256, 40 * 1024, dtype=np.uint8)
    for base, geo in ((str(tmp_path / "c2"), CLAY_GEO),
                      (str(tmp_path / "r2"), RS_GEO)):
        with open(base + ".dat", "wb") as f:
            f.write(payload.tobytes())
        ec.write_ec_files(base, geo)
    for s in range(CLAY_GEO.data_shards):
        with open(str(tmp_path / "c2") + ec.to_ext(s), "rb") as f1, \
             open(str(tmp_path / "r2") + ec.to_ext(s), "rb") as f2:
            assert f1.read() == f2.read(), f"data shard {s} differs"


def test_clay_parity_matches_oracle(tmp_path):
    base, _ = make_ec_volume(tmp_path, CLAY_GEO, size=8 * 1024)
    shards = read_shards(base, CLAY_GEO)
    code = clay_matrix.code(10, 4)
    small, alpha = CLAY_GEO.small_block_size, code.alpha
    win_a = small // alpha
    n_win = len(shards[0]) // small
    data = np.stack([np.frombuffer(shards[i], np.uint8)
                     for i in range(10)])
    flat = np.ascontiguousarray(
        data.reshape(10, n_win, alpha, win_a).transpose(0, 2, 1, 3)
    ).reshape(10 * alpha, -1)
    want = gf256.matmul(clay_matrix.generator_flat(10, 4), flat)
    want = np.ascontiguousarray(
        want.reshape(4, alpha, n_win, win_a).transpose(0, 2, 1, 3)
    ).reshape(4, -1)
    for p in range(4):
        assert np.frombuffer(shards[10 + p], np.uint8).tobytes() \
            == want[p].tobytes(), f"parity {p}"


@pytest.mark.parametrize("geo", [CLAY_GEO, LRC_GEO],
                         ids=["clay", "lrc"])
def test_single_loss_rebuild_byte_identical(tmp_path, geo):
    base, _ = make_ec_volume(tmp_path, geo)
    golden = read_shards(base, geo)
    for lost in (0, 3, geo.total_shards - 1):
        os.remove(base + ec.to_ext(lost))
        stats: dict = {}
        rebuilt = ec.rebuild_ec_files(base, stats=stats)
        assert rebuilt == [lost]
        with open(base + ec.to_ext(lost), "rb") as f:
            assert f.read() == golden[lost], f"shard {lost} corrupt"
        assert stats["bytes_read"] > 0


def test_clay_repair_reads_fraction_of_helpers(tmp_path):
    """The MSR selling point, measured on real shard files: 1-loss clay
    repair reads beta/alpha = 1/q of every helper vs RS's k full shards
    — and the advantage must match the oracle's accounting (3.08x for
    (10,4))."""
    base, _ = make_ec_volume(tmp_path, CLAY_GEO)
    shard_size = os.path.getsize(base + ec.to_ext(0))
    os.remove(base + ec.to_ext(2))
    clay_stats: dict = {}
    ec.rebuild_ec_files(base, stats=clay_stats)
    code = clay_matrix.code(10, 4)
    n_helpers = CLAY_GEO.total_shards - 1
    assert clay_stats["plan_kind"] == "clay-plane"
    assert clay_stats["bytes_read"] == \
        n_helpers * shard_size * code.beta // code.alpha
    # RS reference on the same data shape
    base_rs, _ = make_ec_volume(tmp_path / "rs", RS_GEO)
    os.remove(base_rs + ec.to_ext(2))
    rs_stats: dict = {}
    ec.rebuild_ec_files(base_rs, stats=rs_stats)
    assert rs_stats["plan_kind"] == "rs-full"
    assert rs_stats["bytes_read"] == 10 * shard_size
    advantage = rs_stats["bytes_read"] / clay_stats["bytes_read"]
    want = code.rs_repair_read_symbols() / code.repair_read_symbols()
    assert abs(advantage - want) < 0.01, (advantage, want)
    assert advantage > 2.9


def test_lrc_single_loss_reads_local_group_only(tmp_path):
    base, _ = make_ec_volume(tmp_path, LRC_GEO)
    shard_size = os.path.getsize(base + ec.to_ext(0))
    os.remove(base + ec.to_ext(1))  # data shard in group 0
    stats: dict = {}
    ec.rebuild_ec_files(base, stats=stats)
    lgeo = ec.codes.lrc_geometry(LRC_GEO)
    assert stats["plan_kind"] == "local"
    assert len(stats["read_shards"]) == lgeo.group_size  # 5, not k=10
    assert stats["bytes_read"] == lgeo.group_size * shard_size
    # group members only: data 0..4 + local parity 10, minus the lost one
    assert set(stats["read_shards"]) <= {0, 2, 3, 4, 10}


@pytest.mark.parametrize("geo,lost", [
    (CLAY_GEO, [1, 5, 12]),
    (CLAY_GEO, [0, 3, 10, 13]),
    (LRC_GEO, [2, 7]),
], ids=["clay-3loss", "clay-4loss", "lrc-2loss"])
def test_multi_loss_rebuild(tmp_path, geo, lost):
    base, _ = make_ec_volume(tmp_path, geo)
    golden = read_shards(base, geo)
    for s in lost:
        os.remove(base + ec.to_ext(s))
    rebuilt = ec.rebuild_ec_files(base)
    assert sorted(rebuilt) == sorted(lost)
    for s in lost:
        with open(base + ec.to_ext(s), "rb") as f:
            assert f.read() == golden[s], f"shard {s} corrupt"


@pytest.mark.parametrize("geo", [CLAY_GEO, LRC_GEO], ids=["clay", "lrc"])
def test_degraded_needle_reads(tmp_path, geo):
    """EcVolume reads every needle back with shards missing — the
    kind-aware on-the-fly reconstruct (LRC local-group plan, clay
    window-aligned flat decode)."""
    import random

    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    r = random.Random(77)
    v = Volume(str(tmp_path), "", 7)
    needles = {}
    for i in range(1, 30):
        data = bytes(r.getrandbits(8) for _ in range(r.randint(1, 5000)))
        n = Needle(id=i, cookie=r.getrandbits(32), data=data)
        v.write_needle(n)
        needles[i] = (n.cookie, data)
    v.close()
    base = str(tmp_path / "7")
    ec.encode_volume_to_ec(base, version=3, geo=geo)
    for s in (1, 11):  # one data + one parity shard gone
        os.remove(base + ec.to_ext(s))
    ev = ec.EcVolume(str(tmp_path), "", 7, geo)
    try:
        for s in range(geo.total_shards):
            if s not in (1, 11):
                ev.add_shard(s)
        for nid, (cookie, data) in needles.items():
            assert ev.read_needle(nid, cookie).data == data, f"needle {nid}"
    finally:
        ev.close()


def test_shell_clay_roundtrip(tmp_path):
    """Operator flow at clay(10,4): upload -> `ec.encode -kind clay` ->
    lose shards -> `ec.rebuild` (reports the plane-read stats) -> every
    blob reads back.  The production RPC chain end to end."""
    import glob

    from seaweedfs_tpu import operation, shell
    from seaweedfs_tpu.testing import SimCluster

    with SimCluster(volume_servers=2, base_dir=str(tmp_path)) as c:
        blobs = {}
        for i in range(5):
            payload = os.urandom(1500 + 37 * i)
            fid = operation.assign_and_upload(c.master_grpc, payload)
            blobs[fid] = payload
        vid = int(next(iter(blobs)).split(",")[0])
        env = shell.CommandEnv(c.master_grpc)
        shell.run_command(env, "lock")
        out = json.loads(shell.run_command(
            env, f"ec.encode -volumeId {vid} -kind clay"))
        assert out["encoded"][0]["volume_id"] == vid
        c.sync_heartbeats()
        for fid, payload in blobs.items():
            assert c.read(fid) == payload, "read after clay encode"
        # delete one shard through the production RPCs, then rebuild
        lost = 3
        for vs in c.volume_servers:
            held = any(glob.glob(os.path.join(d.directory,
                                              f"{vid}.ec{lost:02d}"))
                       for d in vs.store.locations)
            if not held:
                continue
            client = env.volume_server(vs.grpc_address)
            client.call("VolumeEcShardsUnmount",
                        {"volume_id": vid, "shard_ids": [lost]})
            client.call("VolumeEcShardsDelete",
                        {"volume_id": vid, "collection": "",
                         "shard_ids": [lost]})
        c.sync_heartbeats()
        out = json.loads(shell.run_command(
            env, f"ec.rebuild -volumeId {vid}"))
        c.sync_heartbeats()
        # the verb output carries the repair-IO accounting (VERDICT r3
        # #9): a single clay loss must report the beta-plane plan, and
        # the rebuilder's /metrics counters must record the same bytes
        res = out["rebuilt"][0]
        st = res["rebuild_stats"]
        assert st["plan_kind"] == "clay-plane"
        assert 0 < st["bytes_read"]
        metrics_text = "".join(
            vs.metrics.render() for vs in c.volume_servers)
        want_line = ("seaweedfs_volume_ec_rebuild_read_bytes_total"
                     '{plan_kind="clay-plane"} '
                     f"{float(st['bytes_read'])}")
        assert want_line in metrics_text, metrics_text
        for fid, payload in blobs.items():
            assert c.read(fid) == payload, "read after clay rebuild"


def test_rebuild_batch_routes_clay_per_volume(tmp_path):
    """The fleet batch API handles clay groups by delegating to the
    kind-aware per-volume path (the [V, B] fold is RS-specific)."""
    bases = []
    golden = {}
    for vid in (7, 8):
        base, _ = make_ec_volume(tmp_path, CLAY_GEO, vid=vid,
                                 size=24 * 1024)
        golden[base] = read_shards(base, CLAY_GEO)
        os.remove(base + ec.to_ext(5))
        bases.append(base)
    out = ec.rebuild_ec_files_batch(bases)
    for base in bases:
        assert out[base] == [5]
        with open(base + ec.to_ext(5), "rb") as f:
            assert f.read() == golden[base][5]


def test_clay_decode_back_to_volume(tmp_path):
    """VolumeEcShardsToVolume works for clay volumes: shards -> .dat
    byte-identical (systematic data + kind-aware rebuild)."""
    base, payload = make_ec_volume(tmp_path, CLAY_GEO)
    for s in (0, 11):
        os.remove(base + ec.to_ext(s))
    from seaweedfs_tpu.storage.ec.decoder import write_dat_file
    ec.rebuild_ec_files(base)
    dat_size = ec.load_volume_info(base)["dat_size"]
    os.rename(base + ".dat", base + ".dat.orig")
    write_dat_file(base, dat_size, CLAY_GEO)
    with open(base + ".dat", "rb") as f:
        assert f.read() == payload.tobytes()


# -- Azure LRC(12,2,2) on the served path ------------------------------------

LRC12_GEO = EcGeometry(data_shards=12, parity_shards=4,
                       large_block_size=16 * 1024, small_block_size=1024,
                       code_kind="lrc", lrc_locals=2)


def _reference_lrc():
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.reference import lrc as ref
    return ref


def test_served_lrc12_encode_matches_oracle_and_reference(tmp_path):
    """write_ec_files' LRC(12,2,2) parity, issued on the RS executor,
    equals ops/lrc.py's oracle and the benchmark's independent
    reference, byte for byte, on seeded data."""
    base, _ = make_ec_volume(tmp_path, LRC12_GEO)
    shards = read_shards(base, LRC12_GEO)
    data = np.stack([np.frombuffer(shards[i], np.uint8) for i in range(12)])
    oracle = lrc.encode(ec.codes.lrc_geometry(LRC12_GEO), data)
    reference = _reference_lrc().encode(data, 2, 2)
    for p in range(4):
        got = np.frombuffer(shards[12 + p], np.uint8)
        assert np.array_equal(got, oracle[p]), f"parity {p} vs oracle"
        assert np.array_equal(got, reference[p]), f"parity {p} vs reference"


@pytest.mark.parametrize("lost,kind", [
    ([0], "local"), ([7], "local"), ([13], "local"), ([14], "global"),
    ([3, 9, 15], "global"), ([0, 1, 7, 10], "global"),
], ids=["data-g0", "data-g1", "local-parity", "global-parity", "3loss",
        "paper-4loss"])
def test_served_lrc12_rebuild_matches_references(tmp_path, lost, kind):
    """rebuild_ec_files regenerates LRC(12,2,2) shards in the RS loop:
    byte-identical to the sealed ones, a single data or local-parity
    loss read from its local group alone and equal to the reference's
    group XOR."""
    base, _ = make_ec_volume(tmp_path, LRC12_GEO)
    golden = read_shards(base, LRC12_GEO)
    for s in lost:
        os.remove(base + ec.to_ext(s))
    stats: dict = {}
    assert ec.rebuild_ec_files(base, stats=stats) == lost
    assert stats["plan_kind"] == kind
    assert stats["executor"] in ("native", "numpy", "jax")
    rebuilt = read_shards(base, LRC12_GEO)
    for s in lost:
        assert rebuilt[s] == golden[s], f"shard {s} corrupt"
    if kind == "local":
        ref = _reference_lrc()
        group = ref.local_group(lost[0], 12, 2)
        assert stats["read_shards"] == group
        shard_size = len(golden[0])
        assert stats["bytes_read"] == 6 * shard_size
        xor = ref.local_repair(
            {s: np.frombuffer(golden[s], np.uint8) for s in group},
            lost[0], 12, 2)
        assert xor.tobytes() == rebuilt[lost[0]]


def test_lrc_rebuild_regenerates_only_the_requested_shards(tmp_path):
    """shard_ids: the shards the rebuilder was asked for, although
    others are absent from its directory (alive on other servers)."""
    base, _ = make_ec_volume(tmp_path, LRC12_GEO)
    golden = read_shards(base, LRC12_GEO)
    keep = {1, 2, 3, 4, 5, 12}          # shard 0's local group
    for s in range(16):
        if s not in keep:
            os.remove(base + ec.to_ext(s))
    stats: dict = {}
    assert ec.rebuild_ec_files(base, stats=stats, shard_ids=[0]) == [0]
    assert stats["plan_kind"] == "local"
    with open(base + ec.to_ext(0), "rb") as f:
        assert f.read() == golden[0]
    present = {s for s in range(16) if os.path.exists(base + ec.to_ext(s))}
    assert present == keep | {0}


@pytest.mark.parametrize("geo", [LRC12_GEO, RS_GEO, CLAY_GEO],
                         ids=["lrc", "rs", "clay"])
def test_vif_without_lrc_construction(tmp_path, geo):
    """An LRC .vif that does not record the construction of its global
    rows was sealed under other coefficients: rebuild and degraded
    reconstruct refuse it, naming the volume.  RS and clay .vifs carry
    no such key and are unaffected."""
    base, _ = make_ec_volume(tmp_path, geo, vid=41)
    golden = read_shards(base, geo)
    info = ec.load_volume_info(base)
    info.pop("lrc_construction", None)
    with open(base + ".vif", "w") as f:
        json.dump(info, f)
    os.remove(base + ec.to_ext(0))
    if geo.code_kind != "lrc":
        assert ec.rebuild_ec_files(base) == [0]
        assert read_shards(base, geo)[0] == golden[0]
        return
    with pytest.raises(ValueError, match="LRC volume 41"):
        ec.rebuild_ec_files(base)
    import shutil
    shutil.copyfile(base + ".ec01", base + ".ecx")   # any index will do
    ev = ec.EcVolume(str(tmp_path), "", 41)
    try:
        with pytest.raises(ec.EcShardUnavailableError,
                           match="LRC volume 41"):
            ev._reconstruct_interval(0, 0, 64)
    finally:
        ev.close()
