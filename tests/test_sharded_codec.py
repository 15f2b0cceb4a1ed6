"""Multi-device codec tests on the 8-device virtual CPU mesh (conftest.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from seaweedfs_tpu.ops import gf256, rs_matrix
from seaweedfs_tpu.parallel import mesh as meshlib
from seaweedfs_tpu.parallel import sharded_codec

rng = np.random.default_rng(4)


def test_eight_devices_present():
    assert len(jax.devices()) == 8


def test_xor_psum_ring():
    mesh = meshlib.make_mesh(8, 1)
    vals = rng.integers(0, 256, (8, 4, 128), dtype=np.uint8)

    def f(x):
        return sharded_codec.xor_psum(x, "v")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("v", None, None),
                            out_specs=P("v", None, None), check_vma=False))(
        jnp.asarray(vals))
    want = vals[0]
    for i in range(1, 8):
        want = want ^ vals[i]
    got = np.asarray(out)
    for d in range(8):
        assert np.array_equal(got[d], want), f"device {d}"


def test_encode_volumes_dp_and_byte_sharded():
    mesh = meshlib.make_mesh(4, 2)
    k, m, V, B = 10, 4, 8, 1024
    data = rng.integers(0, 256, (V, k, B), dtype=np.uint8)
    pbits = jnp.asarray(rs_matrix.parity_bit_matrix(k, m))

    f = jax.jit(lambda d: sharded_codec.encode_volumes(mesh, pbits, d))
    got = np.asarray(f(jnp.asarray(data)))
    gen = rs_matrix.generator_matrix(k, m)
    for v in range(V):
        assert np.array_equal(got[v], gf256.matmul(gen[k:], data[v]))


@pytest.mark.parametrize("n_dev,k,m", [(8, 10, 4), (4, 16, 8), (8, 28, 4)])
def test_shard_parallel_encode(n_dev, k, m):
    mesh = meshlib.make_mesh(n_dev, 8 // n_dev)
    enc, k_pad = sharded_codec.make_shard_parallel_encoder(mesh, "v", k, m)
    B = 512
    data = rng.integers(0, 256, (k, B), dtype=np.uint8)
    padded = np.zeros((k_pad, B), dtype=np.uint8)
    padded[:k] = data
    # sm layout: [k_pad, 8, B/8] (free host view, see rs_pallas.to_sm_layout)
    got = np.asarray(enc(jnp.asarray(padded.reshape(k_pad, 8, -1))))
    want = gf256.matmul(rs_matrix.generator_matrix(k, m)[k:], data)
    assert np.array_equal(got.reshape(m, B), want)


def test_shard_parallel_reconstruct():
    n_dev, k, m, B = 8, 10, 4, 256
    mesh = meshlib.make_mesh(n_dev, 1)
    gen = rs_matrix.generator_matrix(k, m)
    data = rng.integers(0, 256, (k, B), dtype=np.uint8)
    shards = gf256.matmul(gen, data)

    lost = [2, 5, 11, 13]
    present = [i for i in range(k + m) if i not in lost]
    D = rs_matrix.decode_matrix(gen, present, lost)

    rec_fn, k_pad = sharded_codec.make_shard_parallel_reconstructor(mesh, "v", k, m)
    dec_bits = jnp.asarray(sharded_codec.pad_decode_bits(D, m, k, k_pad))
    chosen = np.zeros((k_pad, B), dtype=np.uint8)
    chosen[:k] = shards[present[:k]]
    got = np.asarray(rec_fn(dec_bits, jnp.asarray(
        chosen.reshape(k_pad, 8, -1)))).reshape(m, B)
    assert np.array_equal(got[:len(lost)], shards[lost])

    # same executable, different loss mask — no retrace beyond first call
    lost2 = [0, 10]
    present2 = [i for i in range(k + m) if i not in lost2]
    D2 = rs_matrix.decode_matrix(gen, present2, lost2)
    dec_bits2 = jnp.asarray(sharded_codec.pad_decode_bits(D2, m, k, k_pad))
    chosen2 = np.zeros((k_pad, B), dtype=np.uint8)
    chosen2[:k] = shards[present2[:k]]
    got2 = np.asarray(rec_fn(dec_bits2, jnp.asarray(
        chosen2.reshape(k_pad, 8, -1)))).reshape(m, B)
    assert np.array_equal(got2[:len(lost2)], shards[lost2])
