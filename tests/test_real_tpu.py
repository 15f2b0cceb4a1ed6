"""Opt-in REAL-TPU regression gate (`pytest -m tpu`).

The regular suite pins jax to the 8-device virtual CPU mesh
(conftest.py), so the Pallas kernels run under pytest only in interpret
mode.  This file runs the production kernels on the actual device —
byte-identity against the numpy oracle, never timing — gated by
SEAWEED_TEST_TPU=1 so it skips cleanly under the suite's CPU pin and
runs where an operator opts in (through the chip tool, as its only
process):

    SEAWEED_TEST_TPU=1 python -m pytest tests/test_real_tpu.py -m tpu -p no:cacheprovider

Note: the conftest CPU pin applies process-wide; the env gate exists so
a DEDICATED process (no conftest platform override honored — jax reads
the platform at first backend init) can run these against the chip.
Shapes are kept small: correctness, not throughput (chip_smoke.py runs
the served path at deployment size)."""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _tpu_ready() -> bool:
    if os.environ.get("SEAWEED_TEST_TPU") != "1":
        return False
    import jax
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False


skip_unless_tpu = pytest.mark.skipif(
    not _tpu_ready(),
    reason="SEAWEED_TEST_TPU!=1 or no TPU visible (the regular suite "
           "pins the CPU platform)")


def _rng(seed: int):
    """Fresh generator per test: a data-dependent chip failure must
    reproduce when the failing test reruns ALONE."""
    return np.random.default_rng(seed)


@skip_unless_tpu
def test_sm_kernel_byte_identity_on_chip():
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256, rs_matrix, rs_pallas
    k, m = 10, 4
    gen = rs_matrix.generator_matrix(k, m)
    bits = rs_matrix.bit_matrix(gen[k:])
    pm = jnp.asarray(rs_pallas.to_plane_major(bits, m, k),
                     dtype=jnp.int8)
    d = _rng(1).integers(0, 256, (k, 8, 512), dtype=np.uint8)
    got = np.asarray(rs_pallas.gf_matmul_bits_pallas_sm(
        pm, jnp.asarray(d)))
    want = gf256.matmul(gen[k:], d.reshape(k, -1)).reshape(m, 8, 512)
    np.testing.assert_array_equal(got, want)


@skip_unless_tpu
def test_rscodec_encode_reconstruct_on_chip():
    from seaweedfs_tpu.ops.codec import RSCodec
    codec = RSCodec(10, 4, backend="pallas")
    oracle = RSCodec(10, 4, backend="numpy")
    data = _rng(3).integers(0, 256, (10, 4096), dtype=np.uint8)
    parity = codec.encode(data)
    np.testing.assert_array_equal(parity, oracle.encode(data))
    shards = [data[i] for i in range(10)] + [parity[i] for i in range(4)]
    lost = list(shards)
    for i in (0, 5, 11, 13):
        lost[i] = None
    got = codec.reconstruct(lost)
    for i in range(14):
        np.testing.assert_array_equal(got[i], shards[i])


@skip_unless_tpu
def test_clay_tiled_encode_on_chip():
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import clay_structured
    from seaweedfs_tpu.ops.clay_matrix import code
    k, m = 10, 4
    c = code(k, m)
    small = c.alpha * 128
    W = 2 * small
    data = _rng(4).integers(0, 256, (k, W), dtype=np.uint8)
    shape4 = clay_structured.fused_shape(k, m, W, small)
    got = np.asarray(clay_structured.encode_device_fused(
        k, m, jnp.asarray(data.reshape(shape4)),
        small=small)).reshape(m, W)
    from clay_oracle import natural_layout_parity
    np.testing.assert_array_equal(
        got, natural_layout_parity(k, m, data, small))
