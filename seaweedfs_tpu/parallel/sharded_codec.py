"""Multi-chip EC codec: shard_map + ICI collectives.

Three parallelism modes, mirroring the reference's distributed-concurrency
inventory (SURVEY.md §2.7) the TPU way:

1. Volume data-parallel ("v" axis) — many independent volumes, one per-chip
   batch each; zero collectives.  Replaces the reference's per-volume
   goroutine fan-out in shell ec.encode (command_ec_encode.go:95).
2. Byte-axis parallel ("b" axis) — one volume's stripe columns split across
   chips; encode is columnwise-independent so this also needs no collectives
   (the large-object striping analogue, ec_locate.go row arithmetic).
3. Shard-axis parallel — the k data shards themselves live on different chips
   (as they live on different volume servers in the reference,
   store_ec.go:338 scatter-gather).  Each chip computes its partial GF
   product and the partials are XOR-combined across the mesh with a
   bandwidth-optimal ring `xor_psum` built from `ppermute` on *packed bytes*
   — the TPU-native replacement for the reference's "ship shard bytes to the
   rebuilder over gRPC streams and SIMD-combine there" (ec_encoder.go:233).

On TPU meshes the per-device local compute is the fused Pallas kernel
(ops/rs_pallas.py) — pallas_call composes with shard_map, so each chip runs
the same VMEM-fused unpack->MXU->pack pipeline that produces the single-chip
headline number, and only the packed parity partials ride the ICI ring.  On
CPU meshes (the driver's virtual-device dryrun, tests) the local compute
falls back to the pure-XLA bit-plane matmul (ops/rs_jax.py) — same math,
byte-identical output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import rs_jax, rs_matrix, rs_pallas


def mesh_is_tpu(mesh: Mesh) -> bool:
    """True when the mesh's devices run the Pallas TPU path."""
    return next(iter(np.asarray(mesh.devices).flat)).platform == "tpu"


def local_block_multiple(mesh: Mesh, byte_axes) -> int:
    """Column-count multiple callers must pad B to so every device's local
    byte block is one whole number of kernel tiles.  TPU: the Pallas block;
    CPU fallback: the 128-lane width."""
    n = 1
    for ax in byte_axes:
        n *= mesh.shape[ax]
    # TPU local compute is the shard-major kernel fed via a free
    # [k, 8, B/8] reshape, so B_loc must cover 8 sublane rows per block
    per_dev = 8 * rs_pallas.SM_DEFAULT_BLOCK_B if mesh_is_tpu(mesh) else 128
    return n * per_dev


def xor_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce XOR over a mesh axis via a ring of ppermutes.

    XLA collectives have no XOR reduction; doing psum on unpacked int32 bit
    planes would move 32x the bytes.  XOR is associative+commutative, so a
    ring rotation with local XOR gives an exact all-reduce on *packed uint8*
    at (n-1)/n link efficiency — each hop rides one ICI neighbor link.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(_, val):
        acc, cur = val
        cur = jax.lax.ppermute(cur, axis_name, perm)
        return acc ^ cur, cur

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (x, x))
    return acc


def encode_volumes(mesh: Mesh, parity_bits: jax.Array, data: jax.Array) -> jax.Array:
    """Mode 1+2: data [V, k, B] sharded (v, -, b) -> parity [V, m, B] same
    sharding.  Pure local compute; XLA partitions the einsum automatically."""
    shard = NamedSharding(mesh, P("v", None, "b"))
    data = jax.lax.with_sharding_constraint(data, shard)
    out = rs_jax.gf_matmul_bits(parity_bits, data)
    return jax.lax.with_sharding_constraint(out, shard)


def make_shard_parallel_matmul(mesh: Mesh, axis: str, k: int, m: int,
                               byte_axis: str | None = None):
    """Mode 3 core: jitted fn(bits[8m, 8*k_pad], shards[k_pad, B]) -> [m, B]
    with the shard axis sharded over `axis` (k padded to a multiple of the
    axis size with zero shards — zeros contribute nothing to the XOR).  Each
    chip multiplies its bit-matrix column block against its local shards
    (via rs_jax.gf_matmul_bits, the single source of exactness), then the
    packed partials are XOR-all-reduced over the ring.  The bit-matrix is a
    runtime input, so one executable serves encode and every loss mask.

    Shards arrive in the dense shard-major device layout
    [k_pad, 8, B/8] (rs_pallas.to_sm_layout: TPU pads the sublane dim of a
    2D [k, B] u8 array 1.6x in HBM, so the byte axis is pre-split into 8
    sublane rows host-side where the reshape is a free view) and the result
    is [m, 8, B/8].  `byte_axis` shards the trailing B/8 axis — mode 2+3
    combined, the layout a wide-stripe degraded read uses: B must then be a
    multiple of local_block_multiple(mesh, (byte_axis,)).  The ring xor_psum
    runs per byte-column block; no cross-column communication is ever needed.

    On TPU the local product is the fused Pallas kernel: the device's
    shard-major bit-matrix column block is permuted plane-major in-jit (a
    static gather on a tiny [8m, 8k_loc] matrix) and fed to
    rs_pallas.gf_matmul_bits_pallas_sm, so no 8x bit-plane tensor ever
    touches HBM.  CPU meshes use rs_jax.gf_matmul_bits — identical bytes."""
    n_dev = mesh.shape[axis]
    k_pad = -(-k // n_dev) * n_dev
    k_loc = k_pad // n_dev
    b_spec = byte_axis  # None -> replicated columns
    use_pallas = mesh_is_tpu(mesh)
    pm_rows, pm_cols = rs_pallas.plane_major_perm(m, k_loc)

    def _local(bits_full, local_shards):
        idx = jax.lax.axis_index(axis)
        cols = jax.lax.dynamic_slice(
            bits_full, (0, idx * 8 * k_loc), (8 * m, 8 * k_loc))
        if use_pallas:
            pm = cols[pm_rows][:, pm_cols].astype(jnp.int8)
            packed = rs_pallas.gf_matmul_bits_pallas_sm(pm, local_shards)
        else:
            flat = local_shards.reshape(k_loc, -1)
            packed = rs_jax.gf_matmul_bits(cols, flat).reshape(m, 8, -1)
        return xor_psum(packed, axis)  # [m, 8, B_loc/8]

    mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, b_spec)),
        out_specs=P(None, None, b_spec),
        check_vma=False)

    return jax.jit(mapped), k_pad


def make_shard_parallel_encoder(mesh: Mesh, axis: str, k: int, m: int,
                                kind: str = "vandermonde"):
    """Mode 3 encode: jitted fn(data[k_pad, 8, B/8]) -> parity[m, 8, B/8]
    (sm layout, see make_shard_parallel_matmul)."""
    matmul, k_pad = make_shard_parallel_matmul(mesh, axis, k, m)
    gen = rs_matrix.generator_matrix(k, m, kind)
    full = np.zeros((m, k_pad), dtype=np.uint8)
    full[:, :k] = gen[k:]
    bits = jnp.asarray(rs_matrix.bit_matrix(full))  # [8m, 8*k_pad]
    return functools.partial(matmul, bits), k_pad


def make_shard_parallel_reconstructor(mesh: Mesh, axis: str, k: int, m: int,
                                      kind: str = "vandermonde"):
    """Mode 3 degraded read/rebuild: fn(dec_bits[8m, 8*k_pad], shards) with
    the decode bit-matrix built host-side per loss mask (pad_decode_bits)."""
    return make_shard_parallel_matmul(mesh, axis, k, m)


def pad_decode_bits(D: np.ndarray, m: int, k: int, k_pad: int) -> np.ndarray:
    """Host helper: decode matrix [t, k] -> padded bit matrix [8m, 8*k_pad]."""
    full = np.zeros((m, k_pad), dtype=np.uint8)
    full[:D.shape[0], :k] = D
    return rs_matrix.bit_matrix(full)
