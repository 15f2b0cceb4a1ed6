"""MeshCodec: the multi-chip EC codec behind the production serving paths.

`ops.codec.RSCodec` is the single-chip engine; this is its drop-in,
API-compatible mesh version, picked automatically by the EC encode/rebuild
entry points (storage/ec/encoder.py:_codec_for) whenever the process sees
more than one JAX device.  It is what the reference's operators reach through
`ec.encode` / `ec.rebuild` shell verbs and the VolumeEcShardsGenerate /
VolumeEcShardsRebuild RPCs (weed/shell/command_ec_encode.go:95-190,
weed/server/volume_grpc_erasure_coding.go:38-74) — except that where the
reference fans work out to one SIMD loop per volume server, here one host
drives an ICI-connected chip mesh:

- encode: stripe columns are independent under the GF(2) bit-plane matmul,
  so encode is pure byte-axis data parallelism over EVERY device — zero
  collectives, linear scaling (sharded_codec mode 1+2).
- reconstruct: the surviving shards are laid out along the mesh's "s" axis
  (as they live on distinct servers in the reference's scatter-gather,
  store_ec.go:338); each chip computes its partial GF product and the
  partials are XOR-combined with the bandwidth-optimal ring `xor_psum`,
  while the byte axis stays sharded over "b" (mode 2+3 combined).

The per-device compute is the fused Pallas kernel on TPU meshes and the
pure-XLA bit-plane matmul on CPU meshes (driver dryrun) — see
sharded_codec.make_shard_parallel_matmul.  Batched [V, B] shard stacks fold
onto the byte axis (stripe columns are independent), so a 1000-volume fleet
rebuild is one device round per window, not a host-side loop per volume.

All jitted executables are cached per (devices, k, m, kind) so server RPC
handlers can construct MeshCodec freely per request, and decode bit-matrices
are cached per loss mask (they repeat across windows and volumes).
"""

from __future__ import annotations

import functools
import time as _time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import rs_jax, rs_matrix, rs_pallas
from . import sharded_codec


def default_ec_mesh(devices=None) -> Mesh:
    """("s", "b") mesh over all local devices.

    Both axes are populated whenever the device count allows (b=2 from 4
    devices up): encode scales over s*b byte-DP either way, and reconstruct
    then exercises the combined shard-axis ring + byte-axis split layout —
    the one a wide-stripe degraded read uses.  For 8 devices this is
    s=4, b=2; for 16, s=8, b=2.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    b = 2 if n % 2 == 0 and n >= 4 else 1
    return Mesh(devices.reshape(n // b, b), axis_names=("s", "b"))


@functools.lru_cache(maxsize=32)
def _encode_fn(mesh: Mesh):
    """Jitted byte-DP encode: (bits, data[k, 8, B/8]) -> [m, 8, B/8] with
    the trailing byte axis sharded over every device (both mesh axes).

    Data rides the dense shard-major layout (rs_pallas.to_sm_layout — the
    host-side view that keeps TPU u8 tiling unpadded); shard_map (not
    auto-partitioned jit) so each device's local block runs the fused
    Pallas kernel on TPU.  `bits` is the plane-major int8 matrix there and
    the shard-major uint8 matrix on the CPU fallback."""
    use_pallas = sharded_codec.mesh_is_tpu(mesh)

    def _local(bits, data):
        if use_pallas:
            return rs_pallas.gf_matmul_bits_pallas_sm(bits, data)
        k = data.shape[0]
        out = rs_jax.gf_matmul_bits(bits, data.reshape(k, -1))
        return out.reshape(out.shape[0], 8, -1)

    mapped = shard_map(
        _local, mesh=mesh,
        in_specs=(P(None, None), P(None, None, ("s", "b"))),
        out_specs=P(None, None, ("s", "b")),
        check_vma=False)
    return jax.jit(mapped)


@functools.lru_cache(maxsize=32)
def _recon_fn(mesh: Mesh, k: int, m: int):
    """Jitted mode-2+3 reconstruct over ("s", "b"); returns (fn, k_pad)."""
    return sharded_codec.make_shard_parallel_matmul(
        mesh, "s", k, m, byte_axis="b")


@functools.lru_cache(maxsize=4096)
def _decode_bits_cached(k: int, m: int, kind: str, k_pad: int,
                        present: tuple, chunk: tuple) -> np.ndarray:
    """Padded decode bit-matrix per loss mask.  Masks repeat across rebuild
    windows and across volumes in a fleet rebuild; the GF mat_inv +
    bit-expansion is host-side work worth doing once per mask."""
    gen = rs_matrix.generator_matrix(k, m, kind)
    D = rs_matrix.decode_matrix(gen, list(present), list(chunk))
    return sharded_codec.pad_decode_bits(np.asarray(D), m, k, k_pad)


class MeshCodec:
    """RSCodec-compatible host API; mesh-parallel device math."""

    def __init__(self, data_shards: int = rs_matrix.DEFAULT_DATA_SHARDS,
                 parity_shards: int = rs_matrix.DEFAULT_PARITY_SHARDS,
                 *, kind: str = "vandermonde", mesh: Mesh | None = None):
        self.mesh = mesh if mesh is not None else default_ec_mesh()
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        self.kind = kind
        self.backend = "mesh"
        self.gen = rs_matrix.generator_matrix(self.k, self.m, kind)
        pbits = rs_matrix.parity_bit_matrix(self.k, self.m, kind)
        if sharded_codec.mesh_is_tpu(self.mesh):
            self._parity_bits = jnp.asarray(
                rs_pallas.to_plane_major(pbits, self.m, self.k),
                dtype=jnp.int8)
        else:
            self._parity_bits = jnp.asarray(pbits)
        self._rec_mult = sharded_codec.local_block_multiple(self.mesh, ("b",))

    # -- helpers ---------------------------------------------------------
    def _pad_cols(self, arr: np.ndarray, mult: int) -> tuple[np.ndarray, int]:
        b = arr.shape[-1]
        pad = (-b) % mult
        if pad:
            arr = np.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, pad)])
        return arr, b

    # -- RSCodec API -----------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, B] (or [.., k, B]) uint8 -> parity [.., m, B] uint8.

        Leading batch axes fold into the byte axis: stripe columns are
        independent, so a [V, k, B] batch is exactly a [k, V*B] encode.
        """
        return self.encode_begin(data)()

    def encode_begin(self, data: np.ndarray):
        """Issue the mesh encode asynchronously; returns fetch() -> parity.
        Same contract as RSCodec.encode_begin — the seam the pipelined disk
        paths use to overlap IO with device compute."""
        from ..ops.codec import device_stage, metered_fetch
        t0 = _time.perf_counter()
        stage = functools.partial(device_stage, backend="rs_mesh",
                                  op="encode")
        with stage("pack"):
            data = np.asarray(data, dtype=np.uint8)
            assert data.shape[-2] == self.k, \
                f"expected {self.k} data shards"
            lead = data.shape[:-2]
            volumes = int(np.prod(lead, dtype=np.int64)) if lead else 1
            if lead:
                # [.., k, B] -> [k, prod(lead)*B] keeping each stripe
                # contiguous
                flat = np.ascontiguousarray(
                    np.moveaxis(data, -2, 0)).reshape(self.k, -1)
            else:
                flat = data
            inner = _mesh_matmul_begin(self.mesh, self._parity_bits,
                                       self.m, flat, stage)
        if not lead:
            return metered_fetch(inner, "rs_mesh", "encode", data.nbytes,
                                 t0)

        def fetch():
            parity = inner()
            with stage("unpack"):
                return np.ascontiguousarray(np.moveaxis(
                    parity.reshape(self.m, *lead, -1), 0, -2))
        return metered_fetch(fetch, "rs_mesh", "encode", data.nbytes, t0,
                             volumes=volumes)

    def reconstruct(self, shards: list[np.ndarray | None], *,
                    data_only: bool = False) -> list[np.ndarray]:
        """Fill None slots (enc.Reconstruct / enc.ReconstructData) with the
        shard-axis-parallel ring-xor_psum kernel.

        Present shards may be [B] or batched [V, B] (one loss mask across
        the batch): volumes fold onto the byte axis exactly as encode's
        batch does, so a fleet rebuild is one device call per window."""
        return self.reconstruct_begin(shards, data_only=data_only)()

    def reconstruct_begin(self, shards: list[np.ndarray | None], *,
                          data_only: bool = False):
        """Async form of reconstruct: every per-chunk device call is issued
        before returning; fetch() drains them (RSCodec.encode_begin
        contract)."""
        from ..ops.codec import device_stage, metered_fetch
        t0 = _time.perf_counter()
        if len(shards) != self.n:
            raise ValueError(f"expected {self.n} shard slots, got {len(shards)}")
        present = [i for i, s in enumerate(shards) if s is not None]
        targets = [i for i, s in enumerate(shards) if s is None
                   and (not data_only or i < self.k)]
        if len(present) < self.k:
            raise ValueError(
                f"too few shards to reconstruct: {len(present)} < {self.k}")
        if not targets:
            res = list(shards)
            return lambda: res
        stage = functools.partial(device_stage, backend="rs_mesh",
                                  op="reconstruct")
        with stage("pack"):
            chosen = np.stack([np.asarray(shards[i], dtype=np.uint8)
                               for i in present[:self.k]], axis=0)
            if chosen.ndim not in (2, 3):
                raise ValueError(
                    "MeshCodec.reconstruct expects [B] or [V, B] shards")
            lead = chosen.shape[1:-1]  # () or (V,)
            # per-volume bytes stay contiguous
            flat = chosen.reshape(self.k, -1)
            fn, k_pad = _recon_fn(self.mesh, self.k, self.m)
            full = np.zeros((k_pad, flat.shape[-1]), dtype=np.uint8)
            full[:self.k] = flat
            padded, b = self._pad_cols(full, self._rec_mult)
            # free view
            dev_shards = jnp.asarray(padded.reshape(k_pad, 8, -1))
            present_key = tuple(present[:self.k])
            # the cached executable produces m rows per call; chunk wider
            # target lists (possible for data_only bulk decodes of wide
            # stripes)
            pending = []
            for i in range(0, len(targets), self.m):
                chunk = targets[i:i + self.m]
                dec_bits = jnp.asarray(_decode_bits_cached(
                    self.k, self.m, self.kind, k_pad, present_key,
                    tuple(chunk)))
                pending.append((chunk, fn(dec_bits, dev_shards)))

        def fetch():
            out = list(shards)
            for chunk, dev in pending:
                with stage("wait"):
                    rec = np.asarray(jax.device_get(dev))
                with stage("unpack"):
                    rec = rec.reshape(self.m, -1)[:, :b]
                    for row, t in enumerate(chunk):
                        out[t] = np.ascontiguousarray(
                            rec[row].reshape(*lead, -1))
            return out
        volumes = int(np.prod(lead, dtype=np.int64)) if lead else 1
        return metered_fetch(fetch, "rs_mesh", "reconstruct",
                             chosen.nbytes, t0, volumes=volumes)

    def verify(self, shards: list[np.ndarray]) -> bool:
        data = np.stack(shards[:self.k], axis=-2)
        parity = np.stack(shards[self.k:], axis=-2)
        return bool(np.array_equal(self.encode(data), parity))


@functools.lru_cache(maxsize=16)
def _clay_mesh_fn(mesh: Mesh, k: int, m: int, small: int):
    """Jitted byte-DP clay encode: the structured encode_device runs
    per device under shard_map with the window axis split over every
    mesh device — clay's whole transform (uncouple, layer-MDS matmul,
    couple) is window-local, so no collectives.

    Fused ride-along: encode_device routes wide windows through the
    fully-fused VMEM kernel whenever clay_structured.use_fused_engine()
    says so, so TPU meshes get the fused path per device with no
    mesh-specific wiring (the split lands on window boundaries, which
    is all the fused kernel's grid needs)."""
    from ..ops import clay_structured

    def local(data):
        return clay_structured.encode_device(k, m, data, small=small)

    mapped = shard_map(local, mesh=mesh,
                       in_specs=P(None, ("s", "b")),
                       out_specs=P(None, ("s", "b")), check_vma=False)
    return jax.jit(mapped)


def clay_mesh_encode_begin(k: int, m: int, data: np.ndarray, small: int,
                           mesh: Mesh | None = None):
    """Multi-chip clay window encode; returns fetch() -> parity [m, W].

    W pads up to whole windows per device (clay is linear, so zero
    windows encode to zero parity and the pad strips off)."""
    mesh = mesh if mesh is not None else default_ec_mesh()
    n_dev = mesh.devices.size
    w = data.shape[-1]
    pad = (-w) % (small * n_dev)
    if pad:
        data = np.pad(data, ((0, 0), (0, pad)))
    dev = _clay_mesh_fn(mesh, k, m, small)(jnp.asarray(data))

    def fetch():
        out = np.asarray(jax.device_get(dev))
        return np.ascontiguousarray(out[:, :w]) if pad else out
    return fetch


def _mesh_matmul_begin(mesh: Mesh, bits_dev, mo: int, flat: np.ndarray,
                       stage=None):
    """Shared core of every mesh byte-DP encode (MeshCodec RS parity and
    the generic/LRC matrix path): pad to the mesh's local block multiple,
    dense shard-major relayout, dispatch, deferred fetch+strip.
    `stage(name)`, when given, times fetch's wait and unpack (MeshCodec
    passes its ops.codec.device_stage)."""
    mult = sharded_codec.local_block_multiple(mesh, ("s", "b"))
    ki = flat.shape[0]
    b = flat.shape[-1]
    pad = (-b) % mult
    if pad:
        flat = np.pad(flat, ((0, 0), (0, pad)))
    sm = flat.reshape(ki, 8, -1)   # free host view -> dense tiling
    out = _encode_fn(mesh)(bits_dev, jnp.asarray(sm))
    stage = stage or (lambda name: nullcontext())

    def fetch():
        with stage("wait"):
            host = np.asarray(jax.device_get(out))
        with stage("unpack"):
            return np.ascontiguousarray(host.reshape(mo, -1)[:, :b])
    return fetch


def gf_mesh_encode_begin(M: np.ndarray, data: np.ndarray,
                         mesh: Mesh | None = None):
    """Generic parity = M ∘GF∘ data[ki, B] with the byte axis split over
    every mesh device — the LRC window codec's multi-chip path (LRC
    encode is scalar per byte column, exactly like RS, just a different
    matrix).  Returns fetch() -> [mo, B]."""
    mesh = mesh if mesh is not None else default_ec_mesh()
    mo, ki = M.shape
    bits = rs_matrix.bit_matrix(np.ascontiguousarray(M))
    if sharded_codec.mesh_is_tpu(mesh):
        bits_dev = jnp.asarray(rs_pallas.to_plane_major(bits, mo, ki),
                               dtype=jnp.int8)
    else:
        bits_dev = jnp.asarray(bits)
    return _mesh_matmul_begin(mesh, bits_dev, mo, data)


def multi_device_host() -> bool:
    """One definition of 'this process sees a device mesh' shared by the
    RS picker and the clay/LRC window codecs."""
    try:
        return len(jax.devices()) > 1
    except RuntimeError:
        return False


def mesh_picked() -> bool:
    """The production rule for RS, clay and LRC alike: ride the device
    mesh when this process sees more than one device (driver dryrun,
    multi-chip hosts).  A CPU-pinned WEED_EC_BACKEND keeps a TPU mesh
    host on the pinned CPU codec (ops.codec.mesh_compute_ok)."""
    from ..ops.codec import mesh_compute_ok
    return multi_device_host() and mesh_compute_ok()


def codec_for_devices(k: int, m: int, *, kind: str = "vandermonde"):
    """The production codec picker: MeshCodec under mesh_picked(),
    single-chip RSCodec otherwise."""
    from ..ops.codec import RSCodec
    if mesh_picked():
        return MeshCodec(k, m, kind=kind)
    return RSCodec(k, m, kind=kind)


def ec_backend_status() -> dict:
    """What EC work in this process runs on, for the servers' /status:
    the backend codec_for_devices picks plus the device JAX opened, so a
    CPU codec on a TPU host is visible instead of silent.  Until EC work
    has opened a device it says so, rather than open the chip itself: a
    health check must not be what first takes it."""
    from jax._src import xla_bridge

    from ..ops.codec import ec_backend_override, resolve_backend
    pin = ec_backend_override() or "auto"
    if not xla_bridge.backends_are_initialized():
        return {"opened": False, "pin": pin}
    devices = jax.devices()
    return {"opened": True,
            "backend": "mesh" if mesh_picked() else resolve_backend(),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "devices": len(devices), "pin": pin}
