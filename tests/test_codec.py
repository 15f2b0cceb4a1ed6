import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_matrix
from seaweedfs_tpu.ops.codec import RSCodec

rng = np.random.default_rng(3)


@pytest.fixture(scope="module")
def oracle():
    return RSCodec(10, 4, backend="numpy")


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_encode_roundtrip(backend, oracle):
    codec = RSCodec(10, 4, backend=backend)
    data = rng.integers(0, 256, (10, 300), dtype=np.uint8)
    parity = codec.encode(data)
    assert parity.shape == (4, 300) and parity.dtype == np.uint8
    assert np.array_equal(parity, oracle.encode(data))
    shards = [data[i] for i in range(10)] + [parity[i] for i in range(4)]
    assert codec.verify(shards)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_reconstruct_fills_missing(backend):
    codec = RSCodec(10, 4, backend=backend)
    data = rng.integers(0, 256, (10, 200), dtype=np.uint8)
    parity = codec.encode(data)
    full = [data[i].copy() for i in range(10)] + [parity[i].copy() for i in range(4)]
    shards = list(full)
    for lost in (0, 5, 11, 13):
        shards[lost] = None
    got = codec.reconstruct(shards)
    for i in range(14):
        assert np.array_equal(got[i], full[i]), f"shard {i}"


def test_reconstruct_data_only():
    codec = RSCodec(10, 4, backend="jax")
    data = rng.integers(0, 256, (10, 64), dtype=np.uint8)
    parity = codec.encode(data)
    shards = [data[i].copy() for i in range(10)] + [parity[i].copy() for i in range(4)]
    shards[3] = None
    shards[12] = None
    got = codec.reconstruct(shards, data_only=True)
    assert np.array_equal(got[3], data[3])
    assert got[12] is None  # parity not rebuilt in data_only mode


def test_reconstruct_too_few_raises():
    codec = RSCodec(4, 2, backend="numpy")
    shards = [np.zeros(8, np.uint8)] * 3 + [None] * 3
    with pytest.raises(ValueError):
        codec.reconstruct(shards)


def test_batched_encode():
    codec = RSCodec(10, 4, backend="jax")
    oracle = RSCodec(10, 4, backend="numpy")
    data = rng.integers(0, 256, (5, 10, 128), dtype=np.uint8)
    assert np.array_equal(codec.encode(data), oracle.encode(data))


def test_pallas_interpret_matches_numpy():
    """Fused kernel correctness via the pallas interpreter (no TPU needed)."""
    codec = RSCodec(10, 4, backend="pallas", block_b=256, interpret=True)
    oracle = RSCodec(10, 4, backend="numpy")
    data = rng.integers(0, 256, (2, 10, 300), dtype=np.uint8)  # pads to 512
    assert np.array_equal(codec.encode(data), oracle.encode(data))


def test_pallas_interpret_reconstruct():
    codec = RSCodec(10, 4, backend="pallas", block_b=256, interpret=True)
    data = rng.integers(0, 256, (10, 256), dtype=np.uint8)
    parity = RSCodec(10, 4, backend="numpy").encode(data)
    full = [data[i] for i in range(10)] + [parity[i] for i in range(4)]
    shards = list(full)
    for lost in (1, 2, 3, 10):
        shards[lost] = None
    got = codec.reconstruct(shards)
    for i in range(14):
        assert np.array_equal(got[i], full[i]), f"shard {i}"


@pytest.mark.parametrize("backend", ["numpy", "native", "jax", "pallas"])
@pytest.mark.parametrize("mo,ki", [(1, 6), (4, 12), (2, 12)],
                         ids=["lrc-local", "lrc-encode", "lrc-2loss"])
def test_apply_begin_any_small_matrix(backend, mo, ki):
    """apply_begin runs an arbitrary GF(2^8) matrix up to the codec's
    own [m, k] (LRC's group XOR, its parity rows, a global decode) on
    every backend, the shard-major Pallas kernel included, metered under
    the label it is given."""
    from seaweedfs_tpu.ops.codec import codec_metrics
    codec = RSCodec(12, 4, backend=backend, block_b=256,
                    interpret=backend == "pallas")
    M = rng.integers(0, 256, (mo, ki), dtype=np.uint8)
    x = rng.integers(0, 256, (ki, 777), dtype=np.uint8)
    before = codec_metrics().bytes.value("lrc", "reconstruct")
    got = codec.apply_begin(M, x, "reconstruct", label="lrc")()
    assert np.array_equal(got, gf256.matmul(M, x))
    assert codec_metrics().bytes.value("lrc", "reconstruct") \
        == before + x.nbytes


def test_apply_begin_refuses_a_matrix_beyond_the_codec():
    codec = RSCodec(10, 4, backend="numpy")
    with pytest.raises(ValueError):
        codec.apply_begin(np.ones((5, 10), np.uint8),
                          np.zeros((10, 8), np.uint8), "encode")
    with pytest.raises(ValueError):
        codec.apply_begin(np.ones((1, 12), np.uint8),
                          np.zeros((12, 8), np.uint8), "encode")


def test_plane_major_permutation_roundtrip():
    from seaweedfs_tpu.ops.rs_pallas import to_plane_major
    k, m = 10, 4
    bm = rs_matrix.parity_bit_matrix(k, m)
    pm = to_plane_major(bm, m, k)
    # invertible permutation: applying the inverse index map recovers bm
    i = np.arange(8 * m) // m
    r = np.arange(8 * m) % m
    rows = r * 8 + i
    j = np.arange(8 * k) // k
    c = np.arange(8 * k) % k
    cols = c * 8 + j
    back = np.empty_like(pm)
    back[rows[:, None], cols[None, :]] = pm[np.arange(8 * m)[:, None], np.arange(8 * k)[None, :]]
    assert np.array_equal(back, bm)


def test_wide_and_cauchy_geometries():
    for k, m, kind in [(16, 8, "vandermonde"), (28, 4, "cauchy")]:
        codec = RSCodec(k, m, kind=kind, backend="jax")
        oracle = RSCodec(k, m, kind=kind, backend="numpy")
        data = rng.integers(0, 256, (k, 160), dtype=np.uint8)
        assert np.array_equal(codec.encode(data), oracle.encode(data))


def test_shard_major_kernel_interpret():
    """The shard-major [K, V, B] kernel (the RS production kernel) is bit-exact
    for both int8 and bf16 MXU dtypes (pallas interpreter, no TPU)."""
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import gf256, rs_pallas
    k, m = 10, 4
    rng = np.random.default_rng(3)
    d = rng.integers(0, 256, (k, 8, 256), dtype=np.uint8)
    gen = rs_matrix.generator_matrix(k, m)
    for dtype in (jnp.int8, jnp.bfloat16):
        pm = jnp.asarray(
            rs_pallas.to_plane_major(
                np.asarray(rs_matrix.parity_bit_matrix(k, m)), m, k),
            dtype=dtype)
        out = np.asarray(rs_pallas.gf_matmul_bits_pallas_sm(
            pm, jnp.asarray(d), block_b=256, interpret=True))
        for v in range(8):
            want = gf256.matmul(gen[k:], d[:, v, :])
            assert np.array_equal(out[:, v, :], want), (dtype, v)


def test_layer_mds_sm_pads_unaligned_width(monkeypatch):
    """_layer_mds_matmul's shard-major branch pads the width up to the
    kernel block (zero columns -> zero parity) instead of handing Mosaic
    a sub-tile BlockSpec; interpret mode stands in for the TPU."""
    import jax.numpy as jnp
    import seaweedfs_tpu.ops.clay_structured as cs
    from seaweedfs_tpu.ops import rs_pallas
    monkeypatch.setattr(cs, "_use_pallas_engine", lambda: True)
    real = rs_pallas.gf_matmul_bits_pallas_sm
    monkeypatch.setattr(
        rs_pallas, "gf_matmul_bits_pallas_sm",
        lambda pmat, u, block_b: real(pmat, u, block_b=block_b,
                                      interpret=True))
    k, m = 4, 2
    k0 = cs.code(k, m).k0
    n = 24 * 128 + 40      # not a multiple of the 8*block_b block
    lrng = np.random.default_rng(6)
    u = lrng.integers(0, 256, (k0, n), dtype=np.uint8)
    got = np.asarray(cs._layer_mds_matmul(k, m, jnp.asarray(u), k0))
    R = cs.code(k, m).gen[k0:]
    from seaweedfs_tpu.ops import gf256
    want = gf256.matmul(np.ascontiguousarray(R), u)
    assert got.shape == (m, n)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("backend,block_b,width,padded", [
    ("pallas", 256, 1, 2048),         # floor: one kernel block (8*block_b)
    ("pallas", 256, 5000, 6144),      # 3 blocks: below 16, exact
    ("pallas", 512, 17 * 4096 + 1, 18 * 4096),   # 4 significant bits
    ("pallas", 384, 40 * 3072, 40 * 3072),       # non-power-of-two block
    ("pallas", 512, 1 << 20, 1 << 20),
    ("jax", 512, 129, 256),
    ("jax", 512, 100, 128),
    ("jax", 512, 1000 * 128 + 5, 1024 * 128),
])
def test_device_widths_bucket(backend, block_b, width, padded):
    """Degraded reads come in every needle size; each padded width is a
    device program to compile, so widths fall in 8 buckets per octave
    of kernel blocks, never padding more than 12.5% past one block."""
    codec = RSCodec(10, 4, backend=backend, block_b=block_b)
    out, b = codec._pad(np.zeros((10, width), np.uint8))
    assert b == width and out.shape == (10, padded)
    mult = 8 * block_b if backend == "pallas" else 128
    assert padded % mult == 0
    assert padded - width < max(mult, width / 8)
