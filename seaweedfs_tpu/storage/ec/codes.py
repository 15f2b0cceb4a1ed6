"""Beyond-RS erasure-code families over the SAME shard-file layout:
Clay (MSR regenerating) and LRC (local reconstruction), production-wired.

The reference hard-codes RS(10,4) (erasure_coding/ec_encoder.go:17-19);
here `EcGeometry.code_kind` selects the family and everything else —
shard file names, .ecx, locate math, mounting, reads — is unchanged,
because all three codes are systematic: data shards are byte-identical
to RS's.  Only parity generation and rebuild differ.

Symbol layout (clay): every `small_block_size` window of a shard is
[alpha, win/alpha] layer-major — layer z of window w occupies bytes
[w*small + z*win_a, +win_a) of the shard file.  Single-node repair
therefore reads only the beta = alpha/q plane layers of each helper
window — real partial-range file reads, the whole point of MSR codes
(1/q the repair IO at identical storage overhead).

Execution: the numpy oracles (ops/clay.py, ops/lrc.py) are matrix
factories (ops/clay_matrix.py).  LRC's matrices are as small as RS's and
run on RS's own executor (RSCodec.apply_begin: the shard-major Pallas
kernel on TPU, the native codec on CPU); its rebuild is RS's pipelined
loop (encoder.rebuild_ec_files).  Clay's flat matrices are too large for
that kernel and go through ops.codec.gf_apply, or its fused kernels.
"""

from __future__ import annotations

import functools
import os
import time

import numpy as np

from ...ops import clay_matrix, lrc
from ...ops.codec import (RSCodec, codec_metrics, gf_apply, gf_apply_backend,
                          metered_fetch)
from .layout import EcGeometry, to_ext
from .plan import RepairPlan, lrc_geometry


def window_codec_for(geo: EcGeometry):
    """The encode codec write_ec_files uses for non-RS kinds."""
    if geo.code_kind == "clay":
        return ClayWindowCodec(geo)
    if geo.code_kind == "lrc":
        return LrcWindowCodec(geo)
    raise ValueError(f"unknown code_kind {geo.code_kind!r}")


def require_construction(volume: str, recorded: "str | None") -> None:
    """Refuse an LRC volume whose .vif does not record the construction
    of ops/lrc.py's global rows: its parities were computed with other
    coefficients, and decoding them with these would write wrong bytes."""
    if recorded != lrc.CONSTRUCTION:
        raise ValueError(
            f"LRC volume {volume}: .vif records lrc_construction "
            f"{recorded!r}, not {lrc.CONSTRUCTION!r}; its global parities "
            f"were sealed under other coefficients")


def _multi_device() -> bool:
    """Ride the device mesh?  The picker's own rule (mesh_picked)."""
    from ...parallel.mesh_codec import mesh_picked
    return mesh_picked()


class LrcWindowCodec:
    """LRC is scalar (per byte column) like RS — encode is one matmul,
    issued on RS's executor and fetched later, so write_ec_files
    pipelines it as it does RS; the local-repair advantage lives in the
    rebuild planner.  Multi-device hosts ride the mesh byte-DP path
    (all three code families scale over the chips, not just RS)."""

    def __init__(self, geo: EcGeometry):
        self.geo = geo
        self.lgeo = lrc_geometry(geo)
        self.k = geo.data_shards
        self.m = geo.parity_shards
        self.backend = "lrc"
        self.parity_rows = np.ascontiguousarray(
            lrc.generator_matrix(self.lgeo)[self.k:])
        # single chip: RS's executor, whose apply_begin runs any matrix
        # up to [m, k]
        self.executor = None if _multi_device() \
            else RSCodec(self.k, self.m)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.encode_begin(data)()

    def encode_begin(self, data: np.ndarray, *, volumes: int = 1):
        data = np.asarray(data, dtype=np.uint8)
        assert data.shape[0] == self.k
        if self.executor is not None:
            return self.executor.apply_begin(self.parity_rows, data,
                                             "encode", label="lrc",
                                             volumes=volumes)
        t0 = time.perf_counter()
        from ...parallel.mesh_codec import gf_mesh_encode_begin
        fetch = gf_mesh_encode_begin(self.parity_rows, data)
        return metered_fetch(fetch, "lrc", "encode", data.nbytes, t0,
                             volumes=volumes)


class ClayWindowCodec:
    """Clay encode: each small-block window's [k, small] bytes viewed as
    [k, alpha, small/alpha] layer-major symbols, encoded by the STRUCTURED
    path (ops/clay_structured.py: uncouple -> one [m, k0] layer-MDS matmul
    -> couple) — ~alpha x fewer GF multiplies than the flat [m*alpha,
    k*alpha] generator, bit-identical output.  On TPU the whole transform
    (transposes included) runs jitted on device; encode_begin defers only
    the parity fetch so write_ec_files pipelines it."""

    def __init__(self, geo: EcGeometry):
        self.geo = geo
        self.k = geo.data_shards
        self.m = geo.parity_shards
        self.code = clay_matrix.code(self.k, self.m)
        if geo.small_block_size % self.code.alpha:
            raise ValueError(
                f"small_block_size {geo.small_block_size} must be a "
                f"multiple of clay alpha {self.code.alpha}")
        self.backend = "clay"

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.encode_begin(data)()

    def encode_begin(self, data: np.ndarray, *, volumes: int = 1):
        """`volumes`: how many volumes this window's bytes span —
        encode_ec_files_batch folds a group of same-layout volumes onto
        the byte axis so one dispatch (and its fixed issue cost)
        covers them all; the count feeds the amortization counters."""
        t0 = time.perf_counter()
        data = np.asarray(data, dtype=np.uint8)
        return metered_fetch(self._encode_begin_raw(data), "clay",
                             "encode", data.nbytes, t0, volumes=volumes)

    def _encode_begin_raw(self, data: np.ndarray):
        k, W = data.shape
        small = self.geo.small_block_size
        assert k == self.k, f"expected {self.k} data shards"
        assert W % small == 0, \
            f"window {W} not a multiple of small block {small}"
        from ...ops import clay_structured
        from ...ops.codec import device_compute_ok
        if _multi_device():
            from ...parallel.mesh_codec import clay_mesh_encode_begin
            return clay_mesh_encode_begin(self.k, self.m, data, small)
        if device_compute_ok():
            import jax
            import jax.numpy as jnp
            shape4 = clay_structured.fused_shape(self.k, self.m, W,
                                                 small)
            if shape4 is not None and clay_structured.use_fused_engine():
                # uncouple + layer-MDS + couple in one VMEM-resident
                # pallas_call (rs_pallas); the 4D view is a FREE host
                # reshape both ways
                dev = _clay_device_fn_fused(self.k, self.m, small)(
                    jnp.asarray(np.ascontiguousarray(data).reshape(shape4)))
            else:
                dev = _clay_device_fn(self.k, self.m, small)(
                    jnp.asarray(data))

            def fetch():
                return np.asarray(jax.device_get(dev)).reshape(self.m, W)
            return fetch
        alpha = self.code.alpha
        win_a = small // alpha
        n_win = W // small
        sym = np.ascontiguousarray(
            data.reshape(k, n_win, alpha, win_a).transpose(0, 2, 1, 3)
        ).reshape(k, alpha, -1)
        par = clay_structured.encode_np(self.k, self.m, sym)
        parity = np.ascontiguousarray(
            par.reshape(self.m, alpha, n_win, win_a).transpose(0, 2, 1, 3)
        ).reshape(self.m, W)
        return lambda: parity


@functools.lru_cache(maxsize=8)
def _clay_device_fn(k: int, m: int, small: int):
    import jax

    from ...ops import clay_structured
    return jax.jit(functools.partial(
        clay_structured.encode_device, k, m, small=small))


@functools.lru_cache(maxsize=8)
def _clay_device_fn_fused(k: int, m: int, small: int):
    import jax

    from ...ops import clay_structured
    return jax.jit(functools.partial(
        clay_structured.encode_device_fused, k, m, small=small))


@functools.lru_cache(maxsize=32)
def _clay_repair_fn_fused(k: int, m: int, lost: int):
    import jax

    from ...ops import clay_structured
    return jax.jit(functools.partial(
        clay_structured.repair_device_fused, k, m, lost))


# -- rebuild ---------------------------------------------------------------

PLANE_MESSAGE_BYTES = 1 << 20


def plane_file(base_path: str, helper: int, lost: int) -> str:
    """Where a rebuilder keeps the repair planes of `helper` copied for
    the loss of `lost` (`<base>.ec03.planes07`): a name no shard scan
    (`.ecNN`) matches, so it is never mounted or counted as a shard."""
    return f"{base_path}{to_ext(helper)}.planes{lost:02d}"


def iter_repair_planes(path: str, geo: EcGeometry, lost: int):
    """The repair planes of one helper's shard file for the loss of
    `lost`: of every small-block window the beta layers repair_flat
    names, in its order, read as rebuild_clay reads a local helper (a
    memmap and a take, so only those pages are read).  Yields contiguous
    [wn, beta, win_a] uint8 arrays of whole windows, at most
    PLANE_MESSAGE_BYTES each but at least one window; together they are
    the shard size / q."""
    k, m = geo.data_shards, geo.parity_shards
    _, plane, _ = clay_matrix.repair_flat(k, m, lost)
    alpha = clay_matrix.code(k, m).alpha
    small = geo.small_block_size
    shard = np.memmap(path, dtype=np.uint8, mode="r")
    if len(shard) % small:
        raise ValueError(f"{path}: {len(shard)} B is not whole windows "
                         f"of {small} B")
    windows = shard.reshape(-1, alpha, small // alpha)
    plane_idx = np.asarray(plane)
    step = max(1, PLANE_MESSAGE_BYTES // (len(plane) * (small // alpha)))
    for w0 in range(0, len(windows), step):
        yield windows[w0:w0 + step][:, plane_idx]


def rebuild_clay(base_path: str, geo: EcGeometry, plan: RepairPlan,
                 batch_bytes: int, stats: "dict | None" = None,
                 planes: "dict[int, str] | None" = None) -> list[int]:
    """Clay rebuild of plan.missing.  One loss ("clay-plane"):
    bandwidth-optimal repair reading ONLY the beta plane layers of every
    helper window, beta/alpha = 1/q of each helper's bytes: a local
    helper's from its shard file, a remote one's from the plane file its
    copy left (`planes`, {helper: plane_file path}, holding those layers
    alone, [windows, beta, win_a]), which is removed once the lost shard
    is written.  Otherwise ("clay-decode"): flat decode from the plan's
    k full survivors, same engine."""
    t0 = time.perf_counter()
    code = clay_matrix.code(geo.data_shards, geo.parity_shards)
    small = geo.small_block_size
    alpha, win_a = code.alpha, small // code.alpha
    missing = list(plan.missing)
    planes = planes or {}
    bytes_read = 0

    if plan.kind == "clay-plane":
        lost = missing[0]
        from ...ops import clay_structured
        from ...ops.codec import device_compute_ok
        helpers, plane, R = clay_matrix.repair_flat(
            geo.data_shards, geo.parity_shards, lost)
        # fused path: same helper reads, but uncouple + [q, k0] row
        # solve + out-of-plane back-substitution run in one VMEM-resident
        # pallas_call (rs_pallas._clay_fused_repair_kernel) instead of
        # the [alpha, (n-1)*beta] flat matmul + host transposes
        use_fused = (clay_structured.use_fused_engine()
                     and device_compute_ok() and win_a % 128 == 0)
        # every helper as [windows, layers, win_a]: alpha layers from a
        # shard file, the beta plane layers alone from a plane file
        inputs = {h: np.memmap(planes.get(h, base_path + to_ext(h)),
                               dtype=np.uint8, mode="r").reshape(
                                   -1, len(plane) if h in planes else alpha,
                                   win_a) for h in helpers}
        n_win = len(inputs[helpers[0]])
        if any(len(v) != n_win for v in inputs.values()):
            counts = sorted((h, len(v)) for h, v in inputs.items())
            raise ValueError(f"clay repair of shard {lost}: the helpers' "
                             f"window counts differ: {counts}")
        wins_per_batch = max(1, batch_bytes // small)
        plane_idx = np.asarray(plane)

        def layers(h: int, w0: int, wn: int) -> np.ndarray:
            """Helper h's plane layers of windows [w0, w0+wn):
            [wn, beta, win_a], the partial-range read of a shard file."""
            span = inputs[h][w0:w0 + wn]
            return span if h in planes else span[:, plane_idx]

        with open(base_path + to_ext(lost), "wb") as out:
            for w0 in range(0, n_win, wins_per_batch):
                wn = min(wins_per_batch, n_win - w0)
                if use_fused:
                    # helper-major [H, wn, beta, win_a] — the gather is
                    # the partial-range plane read, no transposes; the
                    # kernel returns the natural [wn, alpha, win_a]
                    # layer-major layout, written verbatim
                    x4 = np.empty((len(helpers), wn, len(plane), win_a),
                                  dtype=np.uint8)
                    for hi, h in enumerate(helpers):
                        x4[hi] = layers(h, w0, wn)
                    bytes_read += x4.size
                    import jax
                    import jax.numpy as jnp
                    fn = _clay_repair_fn_fused(
                        geo.data_shards, geo.parity_shards, lost)
                    rec = np.asarray(jax.device_get(fn(jnp.asarray(x4))))
                    out.write(rec.tobytes())
                    continue
                # x rows: helper-major, plane-layer-minor (repair_flat's
                # input order); columns: window-major, win_a-minor
                x = np.empty((len(helpers) * len(plane), wn * win_a),
                             dtype=np.uint8)
                for hi, h in enumerate(helpers):
                    part = layers(h, w0, wn)
                    # [wn, beta, win_a] -> [beta, wn*win_a]
                    x[hi * len(plane):(hi + 1) * len(plane)] = \
                        np.ascontiguousarray(
                            part.transpose(1, 0, 2)).reshape(
                                len(plane), -1)
                    bytes_read += part.size
                rec = gf_apply(R, x)  # [alpha, wn*win_a]
                rec = np.ascontiguousarray(
                    rec.reshape(alpha, wn, win_a).transpose(1, 0, 2))
                out.write(rec.tobytes())
        for path in planes.values():
            os.remove(path)
        codec_metrics().observe("clay", "reconstruct", bytes_read,
                                time.perf_counter() - t0)
        if stats is not None:
            stats["bytes_read"] = bytes_read
            stats["plan_kind"] = "clay-plane-fused" if use_fused \
                else "clay-plane"
            stats["helpers"] = list(helpers)
            stats["read_shards"] = list(helpers)
            stats["layers_per_helper"] = len(plane)
            stats["copy"] = "planes" if planes else "whole"
            stats["helpers_from_planes"] = len(planes)
            stats["executor"] = "pallas" if use_fused \
                else gf_apply_backend()
        return missing

    # multi-loss: flat decode over the plan's k full survivors
    chosen = plan.read_shards
    D = clay_matrix.decode_flat(geo.data_shards, geo.parity_shards,
                                chosen, tuple(missing))
    inputs = {i: np.memmap(base_path + to_ext(i), dtype=np.uint8,
                           mode="r") for i in chosen}
    shard_size = len(next(iter(inputs.values())))
    wins_per_batch = max(1, batch_bytes // small)
    outputs = {i: open(base_path + to_ext(i), "wb") for i in missing}
    try:
        for w0 in range(0, shard_size // small, wins_per_batch):
            wn = min(wins_per_batch, shard_size // small - w0)
            x = np.empty((geo.data_shards * alpha, wn * win_a),
                         dtype=np.uint8)
            for ci, i in enumerate(chosen):
                span = np.asarray(inputs[i][w0 * small:(w0 + wn) * small])
                bytes_read += span.size
                x[ci * alpha:(ci + 1) * alpha] = np.ascontiguousarray(
                    span.reshape(wn, alpha, win_a).transpose(1, 0, 2)
                ).reshape(alpha, -1)
            rec = gf_apply(D, x)  # [len(missing)*alpha, wn*win_a]
            for row, t in enumerate(missing):
                part = rec[row * alpha:(row + 1) * alpha]
                part = np.ascontiguousarray(
                    part.reshape(alpha, wn, win_a).transpose(1, 0, 2))
                outputs[t].write(part.tobytes())
    finally:
        for f in outputs.values():
            f.close()
    codec_metrics().observe("clay", "reconstruct", bytes_read,
                            time.perf_counter() - t0)
    if stats is not None:
        stats["bytes_read"] = bytes_read
        stats["plan_kind"] = "clay-decode"
        stats["read_shards"] = list(chosen)
        stats["copy"] = "whole"
        stats["helpers_from_planes"] = 0
        stats["executor"] = gf_apply_backend()
    return missing
