"""High-level Reed-Solomon codec API — the TPU replacement for the reference's
`reedsolomon.Encoder` (created at weed/storage/erasure_coding/ec_encoder.go:198,
used via enc.Encode / enc.Reconstruct / enc.ReconstructData).

    codec = RSCodec(10, 4)                       # ec_encoder.go:17-19 geometry
    parity = codec.encode(data_blocks)           # enc.Encode
    codec.reconstruct(shards)                    # enc.Reconstruct (fills None)
    codec.reconstruct(shards, data_only=True)    # enc.ReconstructData

Accepts/returns numpy uint8; shapes are [k, B] or batched [V, k, B].  Three
backends:
  - "pallas": fused TPU kernel (ops/rs_pallas.py) — the fast path
  - "jax":    pure-XLA bit-plane matmul (ops/rs_jax.py) — runs anywhere
  - "native": C++ AVX2 split-nibble codec (native/rs_gf256.cpp) — the
              CPU fast path, klauspost-class single-core throughput
  - "numpy":  gf256 table matmul — tiny, the correctness oracle
"auto" picks pallas on TPU; on CPU it prefers the native codec and falls
back to jax when the .so cannot build.  B is padded to the lane/block multiple
internally (zero columns encode independently, so padding is exact) and
stripped on return.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from contextlib import contextmanager, nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from ..util import tracing
from . import gf256, rs_jax, rs_matrix, rs_pallas


def _tpu_available() -> bool:
    """True when JAX's default device is a TPU.  A JAX that cannot start
    at all raises.  A TPU that JAX tried and failed to open (held by
    another process, or absent from a host with libtpu installed) makes
    JAX run on the CPU; that is logged once, so EC on the CPU codec is
    never a silent outcome on a TPU host."""
    if jax.devices()[0].platform == "tpu":
        return True
    _log_tpu_open_failure()
    return False


@functools.lru_cache(maxsize=1)
def _log_tpu_open_failure() -> None:
    from jax._src import xla_bridge
    err = getattr(xla_bridge, "_backend_errors", {}).get("tpu")
    if err:
        from ..util.weedlog import logger
        logger(__name__).warning(
            "JAX could not open a TPU (%s); EC runs on the CPU codec",
            err)


# -- codec hot-path metrics -------------------------------------------------
#
# Process-global: the codec is shared by every server in the process, so
# one registry captures all EC compute.  Servers append this registry's
# text to their GET /metrics (volume_server/server.py), which turns the
# TPU-vs-CPU claim into a scrapeable per-backend latency/throughput
# number instead of a bench artifact.  Labels name the code family AND
# executor ('rs_pallas', 'rs_jax', 'rs_native', 'rs_numpy', 'clay',
# 'lrc'); ops are 'encode'/'reconstruct'.

_codec_metrics = None
_codec_metrics_lock = threading.Lock()

# buckets tuned for codec calls: an 80MB batch encodes in ~ms on the MXU
# and ~100ms on numpy tables — the default request buckets would dump
# everything in two buckets
_CODEC_BUCKETS = [0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0]

_STAGE_HELP = {
    "gather": "EC codec inputs copied into the host buffer (encode: "
              "the .dat batch; degraded read: the k survivor intervals)",
    "pack": "EC codec call from issue until the device dispatch returns "
            "(stack, pad, relayout, bit matrix, host-to-device copy)",
    "wait": "EC codec call blocked on the device result",
    "unpack": "EC codec result on the host to the returned arrays",
    "write": "EC shard-file writes behind codec calls",
    "cpu": "calling thread's CPU time in EC codec pack, wait and unpack",
}


class _CodecMetrics:
    def __init__(self):
        from ..stats import Registry
        self.registry = Registry()
        self.seconds = self.registry.histogram(
            "seaweedfs_codec_op_seconds",
            "EC codec call wall time, dispatch through fetch",
            ["backend", "op"], buckets=_CODEC_BUCKETS)
        self.bytes = self.registry.counter(
            "seaweedfs_codec_bytes_total",
            "payload bytes processed by the EC codec",
            ["backend", "op"])
        # each dispatch pays a fixed issue cost (h2d transfer setup,
        # kernel launch), so volumes_total / dispatch_total IS the
        # fleet-encode batch amortization factor, scrapeable at
        # /metrics.  Labels are the same bounded (backend, op) set as
        # the histograms (WL140).
        self.dispatch = self.registry.counter(
            "seaweedfs_codec_dispatch_total",
            "EC codec dispatches (one backend call each)",
            ["backend", "op"])
        self.dispatch_volumes = self.registry.counter(
            "seaweedfs_codec_dispatch_volumes_total",
            "volumes carried by EC codec dispatches",
            ["backend", "op"])
        # where a codec call's host time goes, one series per stage
        # (see codec_stage / device_stage); wall minus cpu over pack,
        # wait and unpack is time spent blocked on the device or the GIL
        self.stages = {
            stage: self.registry.counter(
                f"seaweedfs_codec_{stage}_seconds_total", help_text,
                ["backend", "op"])
            for stage, help_text in _STAGE_HELP.items()}

    def observe(self, backend: str, op: str, nbytes: int,
                seconds: float, volumes: int = 1) -> None:
        self.seconds.observe(backend, op, value=seconds)
        self.bytes.inc(backend, op, value=float(nbytes))
        self.dispatch.inc(backend, op)
        self.dispatch_volumes.inc(backend, op, value=float(volumes))


def codec_metrics() -> _CodecMetrics:
    global _codec_metrics
    if _codec_metrics is None:
        with _codec_metrics_lock:
            if _codec_metrics is None:
                _codec_metrics = _CodecMetrics()
    return _codec_metrics


def metered_fetch(fetch, backend: str, op: str, nbytes: int, t0: float,
                  volumes: int = 1):
    """Wrap an async-codec fetch() so the span from issue (t0) to fetch
    completion lands in the codec histograms — the window the pipelined
    encoder actually waits on, covering h2d transfer + kernel + d2h.
    `volumes` is how many volumes this single dispatch carried (the
    batched fleet-encode path passes >1; see _CodecMetrics.dispatch)."""
    def timed():
        out = fetch()
        codec_metrics().observe(backend, op, nbytes,
                                time.perf_counter() - t0, volumes=volumes)
        return out
    return timed


def metrics_backend(codec) -> str:
    """The `backend` label of a codec's calls in the registry:
    rs_<executor> for RSCodec/MeshCodec, the family for the clay/LRC
    window codecs."""
    backend = getattr(codec, "backend", "")
    return backend if backend in ("clay", "lrc") else f"rs_{backend}"


def codec_stage(stage: str, backend: str, op: str) -> tracing.stage:
    """tracing.stage("codec.<stage>") whose wall time also feeds
    seaweedfs_codec_<stage>_seconds_total{backend, op}."""
    counter = codec_metrics().stages[stage]
    return tracing.stage("codec." + stage, observe=lambda seconds:
                         counter.inc(backend, op, value=seconds))


@contextmanager
def device_stage(stage: str, backend: str, op: str):
    """One host stage of a device codec call (pack, wait, unpack): its
    wall time as codec_stage, and the calling thread's CPU time in it
    into seaweedfs_codec_cpu_seconds_total."""
    c0 = time.thread_time()
    try:
        with codec_stage(stage, backend, op):
            yield
    finally:
        codec_metrics().stages["cpu"].inc(
            backend, op, value=time.thread_time() - c0)


# -- backend selection ------------------------------------------------------
#
# The reference picks its SIMD encoder once per binary and is always right
# for its host (ec_encoder.go:198).  Same rule here, by platform: a TPU
# host runs EC on the device (pallas), a CPU host on the native AVX2
# codec (jax when the .so cannot build).  `WEED_EC_BACKEND` pins the
# exact backend either way.

_DEVICE_BACKENDS = ("pallas", "jax")
_CPU_BACKENDS = ("native", "numpy")


def ec_backend_override() -> "str | None":
    """The `WEED_EC_BACKEND` env knob (mirrored by the global -ec.backend
    flag): pin the exact backend — 'native'/'numpy' (CPU) or
    'pallas'/'jax' (device) — or 'auto'/unset for the platform rule.
    RSCodec/gf_apply 'auto' resolve to the pinned name verbatim; mesh
    selection follows its CPU/device class (codec_for_devices)."""
    v = os.environ.get("WEED_EC_BACKEND", "").strip().lower()
    if v in ("", "auto"):
        return None
    if v not in _DEVICE_BACKENDS + _CPU_BACKENDS:
        raise ValueError(
            f"WEED_EC_BACKEND={v!r}: expected one of "
            f"{', '.join(_DEVICE_BACKENDS + _CPU_BACKENDS)} or auto")
    return v


def _native_codec_built() -> bool:
    from .. import native
    return native.lib() is not None and hasattr(native.lib(), "gf256_matmul")


def device_compute_ok() -> bool:
    """May single-device EC work ride the accelerator?  The one gate for
    every 'TPU or CPU?' branch (RSCodec auto, clay window codec, pipeline
    depth): a TPU exists and no CPU backend is pinned."""
    return ec_backend_override() not in _CPU_BACKENDS and _tpu_available()


def mesh_compute_ok() -> bool:
    """May EC work ride a multi-device mesh?  CPU virtual meshes (driver
    dryruns) always — there the 'device' IS the host, even under a
    'native' pin; TPU meshes unless a CPU backend is pinned."""
    return ec_backend_override() not in _CPU_BACKENDS \
        or not _tpu_available()


def validate_ec_backend_pin() -> None:
    """Raise if WEED_EC_BACKEND pins a backend this host cannot run —
    called at CLI startup and at auto-resolution so a bad pin fails at
    construction with a clear message, not mid-serve in the first encode."""
    v = ec_backend_override()
    if v == "native" and not _native_codec_built():
        raise RuntimeError(
            "WEED_EC_BACKEND=native pinned but the native codec .so "
            "is unavailable on this host (no compiler?)")
    if v == "pallas" and not _tpu_available():
        raise RuntimeError(
            "WEED_EC_BACKEND=pallas pinned but this host has no TPU")


def resolve_backend() -> str:
    """The backend RSCodec(backend="auto") runs: the pin, else pallas on
    a TPU, else the native codec (jax when the .so cannot build)."""
    override = ec_backend_override()
    if override is not None:
        validate_ec_backend_pin()
        return override
    if _tpu_available():
        return "pallas"
    return "native" if _native_codec_built() else "jax"


def gf_apply_backend() -> str:
    """The backend gf_apply(backend="auto") runs: the jax bit-plane
    matmul on a device ('pallas' pins mean the device here), else the
    native codec, numpy tables when the .so cannot build."""
    override = ec_backend_override()
    if override is not None:
        validate_ec_backend_pin()
        backend = "jax" if override in _DEVICE_BACKENDS else override
    else:
        backend = "jax" if _tpu_available() else "native"
    if backend == "native" and not _native_codec_built():
        return "numpy"
    return backend


def gf_apply(M: np.ndarray, x: np.ndarray, *,
             backend: str = "auto") -> np.ndarray:
    """out[MO, B] = M ∘GF∘ x[KI, B] for an ARBITRARY GF(2^8) matrix —
    the executor behind clay's flat-matrix paths (storage/ec/codes.py).

    TPU: the bit-plane MXU matmul (ops/rs_jax) — unlike the Pallas
    kernel, the [8MO, 8KI] bit matrix streams from HBM, so clay's
    [m*alpha, k*alpha] (e.g. [1024, 2560]) sizes are fine.  CPU: the
    native AVX2 codec, numpy tables as last resort.  Bytes are identical
    on every path."""
    if backend == "auto":
        backend = gf_apply_backend()
    if backend == "native":
        if _native_codec_built():
            from .. import native
            return native.gf256_matmul(np.ascontiguousarray(M),
                                       np.ascontiguousarray(x))
        backend = "numpy"
    if backend == "numpy":
        return gf256.matmul(M, x)
    bits = rs_matrix.bit_matrix(np.ascontiguousarray(M))
    b = x.shape[-1]
    pad = (-b) % 128
    if pad:
        x = np.pad(x, [(0, 0), (0, pad)])
    out = rs_jax.encode(jnp.asarray(bits), jnp.asarray(x))
    return np.asarray(jax.device_get(out))[:, :b]


def _width_bucket(n: int) -> int:
    """n rounded up to at most 4 significant bits, (8..15) << e: every
    new width is a new device program and degraded reads come in every
    needle size, so widths fall in 8 buckets per octave (never more than
    12.5% of padding) instead of one program per interval size."""
    shift = max(0, n.bit_length() - 4)
    return -(-n >> shift) << shift


class RSCodec:
    def __init__(self, data_shards: int = rs_matrix.DEFAULT_DATA_SHARDS,
                 parity_shards: int = rs_matrix.DEFAULT_PARITY_SHARDS,
                 *, kind: str = "vandermonde", backend: str = "auto",
                 block_b: "int | None" = None,
                 interpret: bool = False):
        if backend == "auto":
            backend = resolve_backend()
        if backend not in ("pallas", "jax", "numpy", "native"):
            raise ValueError(f"unknown backend {backend!r}")
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        self.kind = kind
        self.backend = backend
        # default block is geometry-aware: wide stripes (k > 16) shrink
        # the batch tile so the kernel's VMEM working set stays at the
        # swept (16, 8)-geometry budget instead of spilling
        self.block_b = block_b if block_b is not None \
            else rs_pallas.sm_block_b_for(self.k, self.m)
        self.interpret = interpret
        self.gen = rs_matrix.generator_matrix(self.k, self.m, kind)
        self._parity_bits = rs_matrix.parity_bit_matrix(self.k, self.m, kind)
        self._parity_bits_dev = None  # lazy device constant

    # -- helpers ---------------------------------------------------------
    def _pad(self, arr: np.ndarray) -> tuple[np.ndarray, int]:
        b = arr.shape[-1]
        # pallas rides the shard-major kernel via the vm wrapper, which
        # splits each volume's byte axis into 8 sublane rows
        mult = 8 * self.block_b if self.backend == "pallas" else 128
        pad = mult * _width_bucket(-(-b // mult)) - b
        if pad:
            arr = np.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, pad)])
        return arr, b

    def _stage(self, stage: str, op: str, label: "str | None" = None):
        """device_stage of this codec's calls, under `label` (default
        rs_<backend>); the CPU backends compute inside the call and
        record no pack, wait or unpack."""
        if self.backend in _CPU_BACKENDS:
            return nullcontext()
        return device_stage(stage, label or f"rs_{self.backend}", op)

    def _matmul_begin(self, bits_shard_major: np.ndarray, mo: int,
                      inputs: np.ndarray, op: str,
                      label: "str | None" = None):
        """Dispatch out = M ∘GF∘ inputs[..., KI, B] to the chosen backend.

        Returns a zero-arg fetch() -> np.ndarray.  On device backends the
        transfer + kernel are ISSUED here (JAX dispatch is async) and only
        fetch() blocks on the result — the seam the pipelined disk paths in
        storage/ec/encoder.py use to overlap disk reads, device compute and
        shard-file writes.  fetch() times its `wait` and `unpack` stages
        under `op` and `label`."""
        squeeze = inputs.ndim == 2
        if squeeze:
            inputs = inputs[None]
        if self.backend in _CPU_BACKENDS:
            M = np.asarray(bits_shard_major)  # here: the GF matrix itself
            if self.backend == "native":
                from .. import native
                out = np.stack([native.gf256_matmul(M, x)
                                for x in inputs])
            else:
                out = np.stack([gf256.matmul(M, x) for x in inputs])
            res = out[0] if squeeze else out
            return lambda: res
        padded, b = self._pad(inputs)
        if self.backend == "pallas":
            ki = padded.shape[-2]
            if bits_shard_major is self._parity_bits:  # hot path: cached device constant
                pm = self._parity_bits_pm()
            else:
                pm = jnp.asarray(
                    rs_pallas.to_plane_major(bits_shard_major, mo, ki),
                    dtype=jnp.int8)
            # host-side relayout to the dense shard-major [KI, 8V, B/8]
            # (free view for one volume) — see rs_pallas.to_sm_layout
            lead = padded.shape[:-2]
            bp = padded.shape[-1]  # scalar only — don't pin padded in fetch
            sm = rs_pallas.to_sm_layout(padded)
            dev = rs_pallas.gf_matmul_bits_pallas_sm(
                pm, jnp.asarray(sm), block_b=self.block_b,
                interpret=self.interpret)

            def fetch():
                with self._stage("wait", op, label):
                    host = np.asarray(jax.device_get(dev))
                with self._stage("unpack", op, label):
                    out = rs_pallas.from_sm_layout(host, lead, bp)
                    out = out[..., :b]
                    return out[0] if squeeze else out
            return fetch
        dev = rs_jax.gf_matmul_bits(
            jnp.asarray(bits_shard_major), jnp.asarray(padded))

        def fetch():
            with self._stage("wait", op, label):
                host = np.asarray(jax.device_get(dev))
            with self._stage("unpack", op, label):
                out = host[..., :b]
                return out[0] if squeeze else out
        return fetch

    def _parity_bits_pm(self):
        """Cached device-resident plane-major parity bit-matrix (pallas only).
        int8: doubles MXU throughput vs bf16 and is exact (0/1 operands,
        partial sums <= 8K <= 2040 in the int32 accumulator)."""
        assert self.backend == "pallas"
        if self._parity_bits_dev is None:
            self._parity_bits_dev = jnp.asarray(
                rs_pallas.to_plane_major(self._parity_bits, self.m, self.k),
                dtype=jnp.int8)
        return self._parity_bits_dev

    # -- public API ------------------------------------------------------
    def apply_begin(self, M: np.ndarray, inputs: np.ndarray, op: str, *,
                    label: "str | None" = None,
                    volumes: "int | None" = None):
        """Issue out[..., MO, B] = M ∘GF∘ inputs[..., KI, B] for an
        arbitrary GF(2^8) matrix M no larger than this codec's own
        [m, k] parity block, on this codec's backend (pallas: the
        shard-major kernel, tiled for that block).  Returns fetch(),
        metered as `op` under `label` (default rs_<backend>), with the
        pack, wait and unpack stages as encode_begin's.  LRC encode and
        the RS and LRC rebuilds run their matrices through it.
        `volumes` counts the volumes the call carries where they share
        the byte axis (default: the leading batch axes)."""
        t0 = time.perf_counter()
        M = np.ascontiguousarray(M, dtype=np.uint8)
        mo, ki = M.shape
        if mo > self.m or ki > self.k:
            raise ValueError(f"matrix [{mo}, {ki}] exceeds the codec's "
                             f"[{self.m}, {self.k}]")
        label = label or f"rs_{self.backend}"
        with self._stage("pack", op, label):
            inputs = np.asarray(inputs, dtype=np.uint8)
            assert inputs.shape[-2] == ki, (inputs.shape, M.shape)
            bits = M if self.backend in _CPU_BACKENDS \
                else rs_matrix.bit_matrix(M)
            fetch = self._matmul_begin(bits, mo, inputs, op, label)
        if volumes is None:
            volumes = int(np.prod(inputs.shape[:-2], dtype=np.int64))
        return metered_fetch(fetch, label, op, inputs.nbytes, t0,
                             volumes=volumes)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [.., k, B] uint8 -> parity [.., m, B] uint8."""
        return self.encode_begin(data)()

    def encode_begin(self, data: np.ndarray):
        """Issue the encode asynchronously; returns fetch() -> parity.

        Device backends return immediately after dispatching the
        host->device copy + kernel; only fetch() blocks.  CPU backends
        compute eagerly and fetch() is a no-op — same contract either way,
        so pipeline code needs no backend branches."""
        t0 = time.perf_counter()
        with self._stage("pack", "encode"):
            data = np.asarray(data, dtype=np.uint8)
            assert data.shape[-2] == self.k, \
                f"expected {self.k} data shards"
            bits = self.gen[self.k:] if self.backend in _CPU_BACKENDS \
                else self._parity_bits
            fetch = self._matmul_begin(bits, self.m, data, "encode")
        volumes = int(np.prod(data.shape[:-2], dtype=np.int64)) \
            if data.ndim > 2 else 1
        return metered_fetch(fetch, f"rs_{self.backend}", "encode",
                             data.nbytes, t0, volumes=volumes)

    def encode_jax(self, data: jax.Array) -> jax.Array:
        """Device-resident encode for jit/shard_map composition (jax arrays
        in/out, no host copies).  Pallas expects the dense shard-major
        layout [K, 8V, B/8] (rs_pallas.to_sm_layout) and returns
        [M, 8V, B/8]; the jax backend takes [..., K, B]."""
        if self.backend == "pallas":
            return rs_pallas.gf_matmul_bits_pallas_sm(
                self._parity_bits_pm(), data, block_b=self.block_b,
                interpret=self.interpret)
        if self._parity_bits_dev is None:
            self._parity_bits_dev = jnp.asarray(self._parity_bits)
        return rs_jax.gf_matmul_bits(self._parity_bits_dev, data)

    def reconstruct(self, shards: list[np.ndarray | None], *,
                    data_only: bool = False) -> list[np.ndarray]:
        """Fill in missing (None) shards in place of the reference's
        enc.Reconstruct / enc.ReconstructData (ec_encoder.go:270,
        store_ec.go:360).  `shards` has length k+m; present entries must share
        one [B] or [V, B] shape."""
        return self.reconstruct_begin(shards, data_only=data_only)()

    def reconstruct_begin(self, shards: list[np.ndarray | None], *,
                          data_only: bool = False):
        """Async form of reconstruct: issues the decode matmul, returns
        fetch() -> filled shard list (see encode_begin for the contract)."""
        t0 = time.perf_counter()
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
        with self._stage("pack", "reconstruct"):
            D = _decode_matrix_cached(self.k, self.m, self.kind,
                                      tuple(present), tuple(targets))
            chosen = np.stack([np.asarray(shards[i], dtype=np.uint8)
                               for i in present[:self.k]], axis=-2)
            if self.backend not in _CPU_BACKENDS:
                D = rs_matrix.bit_matrix(D)
            raw = self._matmul_begin(D, len(targets), chosen,
                                     "reconstruct")

        def fetch():
            rec = raw()
            with self._stage("unpack", "reconstruct"):
                out = list(shards)
                for row, t in enumerate(targets):
                    out[t] = np.ascontiguousarray(rec[..., row, :])
                return out
        volumes = int(np.prod(chosen.shape[:-2], dtype=np.int64)) \
            if chosen.ndim > 2 else 1
        return metered_fetch(fetch, f"rs_{self.backend}", "reconstruct",
                             chosen.nbytes, t0, volumes=volumes)

    def verify(self, shards: list[np.ndarray]) -> bool:
        """Check parity consistency (reference enc.Verify)."""
        data = np.stack(shards[:self.k], axis=-2)
        parity = np.stack(shards[self.k:], axis=-2)
        return bool(np.array_equal(self.encode(data), parity))


@functools.lru_cache(maxsize=1024)
def _decode_matrix_cached(k: int, m: int, kind: str,
                          present: tuple, targets: tuple) -> np.ndarray:
    """Loss masks repeat across rebuild windows; the GF inversion is host
    work worth one pass per mask (keyed by geometry, not codec instance, so
    per-call RSCodecs share hits and are not pinned by the cache —
    MeshCodec._decode_bits_cached is the same pattern)."""
    gen = rs_matrix.generator_matrix(k, m, kind)
    return rs_matrix.decode_matrix(gen, list(present), list(targets))
