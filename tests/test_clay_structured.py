"""Structured (layered) clay encode == flat generator == numpy oracle,
byte for byte — and the device (jit) executor == the host executor.

The structured path (ops/clay_structured.py) is the production encode
behind ClayWindowCodec; the flat generator (clay_matrix.generator_flat)
stays as the cross-check and the decode engine.  Any divergence between
the three is data corruption, so everything here is np.array_equal."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import clay_matrix, clay_structured, gf256


@pytest.mark.parametrize("k,m", [(10, 4), (4, 2), (6, 3)])
def test_structured_equals_flat_generator(k, m):
    c = clay_matrix.code(k, m)
    rng = np.random.default_rng(k * 100 + m)
    B = 24
    data = rng.integers(0, 256, (k, c.alpha, B), dtype=np.uint8)
    flat = gf256.matmul(clay_matrix.generator_flat(k, m),
                        data.reshape(k * c.alpha, B))
    st = clay_structured.encode_np(k, m, data)
    assert np.array_equal(st, flat.reshape(m, c.alpha, B))


@pytest.mark.parametrize("k,m", [(10, 4), (4, 2)])
def test_structured_equals_oracle(k, m):
    c = clay_matrix.code(k, m)
    rng = np.random.default_rng(7)
    B = 16
    data = rng.integers(0, 256, (k, c.alpha, B), dtype=np.uint8)
    assert np.array_equal(clay_structured.encode_np(k, m, data),
                          c.encode(data))


def test_device_executor_matches_host():
    """encode_device (the jitted TPU path, here on the CPU backend) must
    produce the same bytes as encode_np from the same raw window data."""
    import jax.numpy as jnp
    k, m = 10, 4
    c = clay_matrix.code(k, m)
    small = c.alpha * 16          # 16-byte symbols
    n_win = 3
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (k, n_win * small), dtype=np.uint8)
    dev = np.asarray(clay_structured.encode_device(
        k, m, jnp.asarray(data), small=small))
    win_a = small // c.alpha
    sym = np.ascontiguousarray(
        data.reshape(k, n_win, c.alpha, win_a).transpose(0, 2, 1, 3)
    ).reshape(k, c.alpha, -1)
    par = clay_structured.encode_np(k, m, sym)
    host = np.ascontiguousarray(
        par.reshape(m, c.alpha, n_win, win_a).transpose(0, 2, 1, 3)
    ).reshape(m, n_win * small)
    assert np.array_equal(dev, host)


def test_window_codec_uses_structured_path(tmp_path):
    """ClayWindowCodec.encode == flat-generator gf_apply on real window
    shapes (the old flat path, kept as cross-check)."""
    from seaweedfs_tpu.storage.ec.codes import ClayWindowCodec
    from seaweedfs_tpu.storage.ec.layout import EcGeometry
    geo = EcGeometry(10, 4, large_block_size=1 << 20,
                     small_block_size=64 << 10, code_kind="clay")
    codec = ClayWindowCodec(geo)
    rng = np.random.default_rng(3)
    W = 2 * geo.small_block_size
    data = rng.integers(0, 256, (10, W), dtype=np.uint8)
    got = codec.encode(data)
    c = codec.code
    win_a = geo.small_block_size // c.alpha
    flat_in = np.ascontiguousarray(
        data.reshape(10, W // geo.small_block_size, c.alpha, win_a)
        .transpose(0, 2, 1, 3)).reshape(10 * c.alpha, -1)
    want_flat = gf256.matmul(clay_matrix.generator_flat(10, 4), flat_in)
    want = np.ascontiguousarray(
        want_flat.reshape(4, c.alpha, W // geo.small_block_size, win_a)
        .transpose(0, 2, 1, 3)).reshape(4, W)
    assert np.array_equal(got, want)


def test_tiled_device_path_matches_oracle(monkeypatch):
    """encode_device's XLA path (the fused gate off: CPU meshes, a 'jax'
    pin) on windows wide enough for the 128-lane tile is byte-identical
    to the numpy oracle, and the window codec's device branch gives the
    same bytes on the same windows."""
    import jax.numpy as jnp

    import seaweedfs_tpu.ops.codec as codec_mod
    from seaweedfs_tpu.storage.ec.codes import ClayWindowCodec
    from seaweedfs_tpu.storage.ec.layout import EcGeometry
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: False)
    monkeypatch.setattr(codec_mod, "device_compute_ok", lambda: True)
    k, m = 10, 4
    c = clay_matrix.code(k, m)
    small = c.alpha * 128           # the narrowest fused-size window
    n_win = 3
    W = n_win * small
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (k, W), dtype=np.uint8)
    got = np.asarray(clay_structured.encode_device(
        k, m, jnp.asarray(data), small=small))
    # oracle construction shared with the real-chip gate
    from clay_oracle import natural_layout_parity
    np.testing.assert_array_equal(
        got, natural_layout_parity(k, m, data, small))
    geo = EcGeometry(k, m, large_block_size=1 << 20,
                     small_block_size=small, code_kind="clay")
    np.testing.assert_array_equal(ClayWindowCodec(geo).encode(data), got)


def test_window_codec_tiled_path_round_trips(tmp_path, monkeypatch):
    """The window codec's device branch with the fused gate off (the
    jitted XLA encode_device) on real-sized small blocks: its shard
    files must be byte-identical to the host path's and still rebuild."""
    import os

    import seaweedfs_tpu.ops.codec as codec_mod
    import seaweedfs_tpu.storage.ec as ec
    from seaweedfs_tpu.storage.ec.layout import EcGeometry
    geo = EcGeometry(10, 4, large_block_size=1 << 20,
                     small_block_size=c_small(), code_kind="clay")
    rng = np.random.default_rng(5)
    payload = rng.integers(0, 256, 3 * geo.small_row_size() + 999,
                           dtype=np.uint8).tobytes()
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: False)
    bases = {}
    for mode in ("host", "tiled"):
        d = tmp_path / mode
        d.mkdir()
        base = str(d / "7")
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        # 'tiled' forces the device branch (here: CPU jax executor) so
        # the codec's device wiring itself is what runs
        monkeypatch.setattr(codec_mod, "device_compute_ok",
                            lambda: mode == "tiled")
        ec.write_ec_files(base, geo)
        bases[mode] = base
    for i in range(geo.total_shards):
        a = open(bases["host"] + f".ec{i:02d}", "rb").read()
        b = open(bases["tiled"] + f".ec{i:02d}", "rb").read()
        assert a == b, f"shard {i}: device codec path diverges from host"
    os.remove(bases["tiled"] + ".ec03")
    ec.rebuild_ec_files(bases["tiled"], geo)
    assert open(bases["tiled"] + ".ec03", "rb").read() \
        == open(bases["host"] + ".ec03", "rb").read()


def c_small() -> int:
    from seaweedfs_tpu.ops.clay_matrix import code
    return code(10, 4).alpha * 128
