"""Compile the main path's kernels for a described TPU v5e, without a chip.

Interpret mode (the rest of the suite) cannot see what the chip's
compiler refuses: a tile the layout does not allow, a kernel that asks
for more VMEM than its scoped limit (clay (16,8) did, before the fused
kernels raised theirs to CLAY_FUSED_VMEM_LIMIT), a program that does
not fit HBM.  Each case here lowers a kernel at its production shape for
one chip of a `v5e:2x2` topology (four chips for MeshCodec) and compiles
it with the TPU compiler that ships with jaxlib.  Nothing runs, so
nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load libtpu, and test collection must
not depend on it.  Keep every such compile in this one file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.ops import clay_structured, rs_pallas
from seaweedfs_tpu.ops.clay import GAMMA
from seaweedfs_tpu.ops.clay_matrix import code

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip lands in the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("k,m,v", [
    (10, 4, 64),    # the smoke's direct call: [10, 64, 8 MiB], 5.4 GB
    (16, 8, 40),    # wide stripes at a comparable byte volume
    (28, 4, 16),
    (6, 1, 8),      # LRC(12,2,2) local repair: 6 rows in, 1 out
    (12, 4, 8),     # LRC(12,2,2) encode on RS's executor
])
def test_sm_kernel_compiles(one_chip, k, m, v):
    block_b = rs_pallas.sm_block_b_for(k, m)
    pm = _spec((8 * m, 8 * k), jnp.int8, one_chip)
    data = _spec((k, v, 8 * MIB), jnp.uint8, one_chip)
    compiled = _compile(lambda p, x: rs_pallas.gf_matmul_bits_pallas_sm(
        p, x, block_b=block_b), pm, data)
    mem = compiled.memory_analysis()
    # the HBM-resident operand and parity are the whole footprint
    assert mem.argument_size_in_bytes >= k * v * 8 * MIB
    assert mem.output_size_in_bytes == m * v * 8 * MIB


@pytest.mark.parametrize("k,m", [(10, 4), (16, 8)])
def test_clay_fused_encode_compiles(one_chip, k, m):
    """alpha = 512 at (16,8): refused at Mosaic's default 16 MiB scoped
    VMEM (35 MiB needed), compiles with the working-set-sized limit."""
    c = code(k, m)
    small = MIB                               # production small block
    w_a = small // c.alpha
    rb = _spec((8 * c.q, 8 * c.k0), jnp.int8, one_chip)
    d4 = _spec((k, 8, c.alpha, w_a), jnp.uint8, one_chip)
    _compile(lambda r, d: rs_pallas.clay_fused_encode_pallas(
        r, d, q=c.q, t=c.t, gamma=GAMMA, det_inv=int(c._det_inv),
        cb=rs_pallas.clay_fused_cb_for(c.alpha, w_a)), rb, d4)


@pytest.mark.parametrize("k,m", [(10, 4), (16, 8)])
def test_clay_fused_repair_compiles(one_chip, k, m):
    c = code(k, m)
    w_a = MIB // c.alpha
    lost = 2
    _, _, _, inv_gamma = clay_structured.repair_parts(k, m, lost)
    rb = _spec((8 * c.q, 8 * c.k0), jnp.int8, one_chip)
    x4 = _spec((k + m - 1, 8, c.beta, w_a), jnp.uint8, one_chip)
    _compile(lambda r, x: rs_pallas.clay_fused_repair_pallas(
        r, x, k=k, q=c.q, t=c.t, lost=lost, gamma=GAMMA,
        inv_gamma=inv_gamma,
        cb=rs_pallas.clay_fused_cb_for(c.beta, w_a)), rb, x4)


def test_mesh_codec_encode_compiles_for_four_chips(topo):
    """The production multi-chip picker's encode (MeshCodec, s=2 b=2):
    the shard-major kernel inside shard_map, per-device blocks."""
    from seaweedfs_tpu.parallel import mesh_codec
    mesh = mesh_codec.default_ec_mesh(np.asarray(topo.devices))
    assert dict(mesh.shape) == {"s": 2, "b": 2}
    k, m = 10, 4
    bits = _spec((8 * m, 8 * k), jnp.int8,
                 NamedSharding(mesh, P(None, None)))
    b = 4 * 64 * MIB                     # 64 MiB per shard per device
    data = _spec((k, 8, b // 8), jnp.uint8,
                 NamedSharding(mesh, P(None, None, ("s", "b"))))
    compiled = _compile(mesh_codec._encode_fn(mesh), bits, data)
    mem = compiled.memory_analysis()
    # each device holds a quarter of the operand
    assert mem.argument_size_in_bytes < k * b // 2


def test_clay_mesh_encode_compiles_for_four_chips(topo, monkeypatch):
    """The multi-chip clay encode (mesh_codec._clay_mesh_fn): under
    shard_map, encode_device hands each device's windows to the fused
    kernel.  The gate and the interpreter switch follow the chip, so
    both are set here as a TPU host would have them."""
    from seaweedfs_tpu.parallel import mesh_codec
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: True)
    monkeypatch.setattr(clay_structured, "_interpret", lambda: False)
    mesh = mesh_codec.default_ec_mesh(np.asarray(topo.devices))
    k, m = 10, 4
    w = 4 * 8 * MIB                      # 8 windows of 1 MiB per device
    data = _spec((k, w), jnp.uint8,
                 NamedSharding(mesh, P(None, ("s", "b"))))
    fn = mesh_codec._clay_mesh_fn.__wrapped__(mesh, k, m, MIB)
    compiled = fn.lower(data).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # each device holds a quarter of the windows
    assert compiled.memory_analysis().argument_size_in_bytes < k * w // 2


def test_mesh_codec_reconstruct_compiles_for_four_chips(topo):
    """The shard-parallel degraded-read/rebuild program: survivors split
    over "s", bytes over "b", partial products XOR-combined by the ring
    xor_psum (collective-permutes between chips)."""
    from seaweedfs_tpu.parallel import mesh_codec
    mesh = mesh_codec.default_ec_mesh(np.asarray(topo.devices))
    k, m = 10, 4
    fn, k_pad = mesh_codec._recon_fn(mesh, k, m)
    bits = _spec((8 * m, 8 * k_pad), jnp.uint8,
                 NamedSharding(mesh, P(None, None)))
    b = 2 * 64 * MIB
    shards = _spec((k_pad, 8, b // 8), jnp.uint8,
                   NamedSharding(mesh, P("s", None, "b")))
    text = fn.lower(bits, shards).compile().as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
