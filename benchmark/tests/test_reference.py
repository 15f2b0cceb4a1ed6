"""The plain reference against the program, at tiny sizes on the CPU:
they must agree byte for byte, so either one can find the other's
fault."""

import numpy as np
import pytest

from benchmark import fixture
from benchmark.reference import clay, gf256, layout, needle, rs


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (16, 8)])
def test_rs_generator_matches_program(k, m):
    from seaweedfs_tpu.ops import rs_matrix
    want = rs_matrix.generator_matrix(k, m)
    assert np.array_equal(np.array(rs.generator(k, m), np.uint8), want)


@pytest.mark.parametrize("n", [1, 2, 4097, 1 << 16])
def test_rs_encode_matches_program(n):
    from seaweedfs_tpu.ops import gf256 as pgf, rs_matrix
    data = np.random.default_rng(n).integers(0, 256, (10, n), np.uint8)
    want = pgf.matmul(rs_matrix.generator_matrix(10, 4)[10:], data)
    assert np.array_equal(rs.encode(data, 4), want)


def test_gf256_tables():
    from seaweedfs_tpu.ops import gf256 as pgf
    table = np.array([[gf256.mul(a, b) for b in range(256)]
                      for a in range(256)], np.uint8)
    assert np.array_equal(table, pgf.MUL_TABLE)
    assert all(gf256.mul(a, gf256.inv(a)) == 1 for a in range(1, 256))


def test_clay_encode_and_repair_match_program():
    from seaweedfs_tpu.ops import clay as pclay
    ref, prog = clay.Clay(10, 4), pclay.ClayCode(10, 4)
    data = np.random.default_rng(7).integers(0, 256, (10, 256, 5), np.uint8)
    parity = ref.encode(data)
    assert np.array_equal(parity, prog.encode(data))
    full = np.concatenate([data, parity])
    for lost in range(14):
        plan = prog.repair_plan(lost)
        got = ref.repair(lost, {h: full[h][z] for h, z in plan.items()})
        assert np.array_equal(got, full[lost]), lost


def test_layout_and_volume_match_program_encoder(tmp_path):
    """The needle writer's volume opens in the program and reads back;
    the reference's shards equal write_ec_files' for RS and clay."""
    from seaweedfs_tpu.storage.ec import encoder
    from seaweedfs_tpu.storage.ec.layout import EcGeometry
    from seaweedfs_tpu.storage.volume import Volume
    cfg = {"blob_size_min": 4096, "blob_size_max": 1 << 20,
           "volume_size_mb": 3, "collection": "t"}
    vol = fixture.make_volume(cfg, 2**31 + 11)
    base = str(tmp_path / "t_1")
    vol.write(base)
    v = Volume(str(tmp_path), "t", 1)
    for i in range(len(vol.sizes)):
        n = v.read_needle(int(vol.keys[i]), int(vol.cookies[i]))
        assert bytes(n.data) == vol.blob(i)
    v.close()
    small, large = 1 << 16, 1 << 20      # large rows reached at 10 MiB
    data = layout.data_shards(vol.dat(), 10, large, small)
    for kind in ("rs", "clay"):
        geo = EcGeometry(large_block_size=large, small_block_size=small,
                         code_kind=kind)
        encoder.write_ec_files(base, geo)
        if kind == "rs":
            parity = rs.encode(data, 4)
        else:
            code = clay.Clay(10, 4)
            sym = np.stack([clay.to_layers(r, code.alpha, small)
                            for r in data])
            parity = [clay.from_layers(p, small) for p in code.encode(sym)]
        for s, row in enumerate(list(data) + list(parity)):
            got = np.fromfile(base + f".ec{s:02d}", np.uint8)
            assert np.array_equal(got, row), (kind, s)
    encoder.write_sorted_file_from_idx(base)
    with open(base + ".ecx", "rb") as f:
        assert f.read() == vol.ecx()
    assert needle.fid(1, int(vol.keys[0]), int(vol.cookies[0])) \
        == vol.fid(0)


def test_large_rows_in_reference_layout():
    k, large, small = 3, 8, 2
    dat = np.arange(3 * 8 + 7, dtype=np.uint8)
    out = layout.data_shards(dat, k, large, small)
    assert out.shape == (3, 8 + 2 * 2)
    assert np.array_equal(out[1, :8], dat[8:16])
    tail = np.zeros(12, np.uint8)
    tail[:7] = dat[24:]
    assert np.array_equal(out[:, 8:], tail.reshape(2, 3, 2)
                          .transpose(1, 0, 2).reshape(3, 4))
