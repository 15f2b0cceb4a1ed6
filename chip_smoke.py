#!/usr/bin/env python3
"""Run the EC data path once on the TPU, through the entry points a user
calls, at a size a SeaweedFS deployment has.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the MeshCodec path on a 2x2 host

One chip:
  1. An in-process SimCluster (master + 4 volume servers) loads one
     volume of >= 2 GiB through assign + HTTP POST: blobs of 4 KiB-1 MiB,
     log-uniform, bytes from --seed.  This is a cut: SeaweedFS encodes a
     volume when it is nearly full, and the master's default
     -volumeSizeLimitMB is 30000.
  2. `ec.encode` (shell verb -> VolumeEcShardsGenerate -> write_ec_files
     -> the production picker) must dispatch on the device codec only;
     every parity shard file is checked against the native AVX2 codec
     (gf256 tables where the .so cannot build).
  3. Four shards are deleted; every acknowledged blob reads back through
     the degraded EC read; `ec.rebuild` regenerates the four shard files
     byte for byte.
  4. Clay (10,4): `ec.encode -kind clay` on a >= 256 MiB volume and a
     single-shard rebuild must take the fused VMEM kernels; parity is
     checked against the host structured encode and, on sampled columns,
     the flat ClayCode generator.
  5. One direct call of the RS shard-major kernel at [10, 64, 8 MiB]
     resident in HBM, sampled columns against gf256.matmul.

Compile seconds, GB/s and peak HBM are printed for information only.
The last line of stdout is the contract line
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}};
any failed check raises, so the script exits non-zero without it.
"""

from __future__ import annotations

import argparse
import filecmp
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait

import numpy as np

from seaweedfs_tpu import operation, shell
from seaweedfs_tpu.ops import gf256, rs_matrix
from seaweedfs_tpu.ops.codec import codec_metrics, gf_apply
from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
from seaweedfs_tpu.testing import SimCluster

K, M = 10, 4
RS_BYTES = 2 << 30      # one RS(10,4) volume (cut from 30000 MB, see above)
CLAY_BYTES = 256 << 20
MESH_BYTES = 512 << 20  # four chips cost four times as much per second
CPU_BACKENDS = ("rs_native", "rs_numpy")
_BACKENDS = ("rs_pallas", "rs_jax", "rs_native", "rs_numpy", "rs_mesh",
             "clay", "lrc")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_seconds = [0.0]


def log(msg: str) -> None:
    print(msg, flush=True)


# -- measurement helpers (information only) ----------------------------------

def _on_compile(event: str, seconds: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_seconds[0] += seconds


def compile_seconds() -> float:
    return _compile_seconds[0]


def hbm_peaks() -> str:
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            out.append(f"{d.id}:{st['peak_bytes_in_use'] / 2**30:.3f}GiB")
    return " ".join(out) or "not reported by this backend"


def dispatches() -> dict:
    d = codec_metrics().dispatch
    return {(b, op): d.value(b, op) for b in _BACKENDS
            for op in ("encode", "reconstruct")}


def assert_dispatched(before: dict, op: str, backend: str) -> None:
    """The EC work since `before` ran on `backend`, and none of it on a
    CPU codec (unless the CPU codec is what this host resolves to)."""
    now = dispatches()
    delta = {key: now[key] - before[key] for key in now}
    ran = {key: v for key, v in delta.items() if v}
    assert delta[(backend, op)] > 0, \
        f"no {backend} {op} dispatch; codec dispatches: {ran}"
    for cpu in CPU_BACKENDS:
        if cpu != backend:
            for o in ("encode", "reconstruct"):
                assert delta[(cpu, o)] == 0, \
                    f"{cpu} {o} dispatched on the device path: {ran}"
    log(f"  codec dispatches: {ran}")


# -- data ---------------------------------------------------------------------

def blob_sizes(total: int, seed: int, lo: int = 4 << 10,
               hi: int = 1 << 20) -> list[int]:
    """Log-uniform blob sizes in [lo, hi] summing to >= total."""
    rng = np.random.default_rng([seed, 1])
    sizes: list[int] = []
    while sum(sizes) < total:
        draw = np.exp(rng.uniform(np.log(lo), np.log(hi), 4096))
        sizes.extend(int(x) for x in draw)
    acc = np.cumsum(sizes)
    return sizes[:int(np.searchsorted(acc, total)) + 1]


def blob_bytes(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, 2, i]).bytes(size)


def load_volume(cluster, total: int, seed: int, collection: str,
                threads: int = 8) -> tuple[int, list]:
    """Fill ONE volume with >= `total` bytes of blobs through Assign +
    HTTP POST; returns (vid, [(fid, blob index, size)]).

    The master spreads assignments over the volumes it grew for the
    collection; a client that wants one full volume keeps the
    assignments that land on the first one (count=16 fids each, under
    one write token) and lets the others go unused."""
    sizes = blob_sizes(total, seed)
    master = cluster.master_grpc
    target, blobs = None, []

    def upload(r, fids, first):
        for j, fid in enumerate(fids):
            i = first + j
            operation.upload_data(r.url, fid, blob_bytes(seed, i, sizes[i]),
                                  jwt=r.auth)
        return [(fid, first + j, sizes[first + j])
                for j, fid in enumerate(fids)]

    i, misses = 0, 0
    with ThreadPoolExecutor(threads) as pool:
        pending = set()
        while i < len(sizes):
            r = operation.assign(master, count=16, collection=collection)
            vid = int(r.fid.split(",", 1)[0])
            target = vid if target is None else target
            if vid != target:
                misses += 1
                if misses > 1000:   # the volume stopped taking writes
                    raise RuntimeError(f"volume {target} left the "
                                       "writable set mid-load")
                continue
            misses = 0
            fids = operation.derive_fids(r)[:len(sizes) - i]
            pending.add(pool.submit(upload, r, fids, i))
            i += len(fids)
            if len(pending) >= 2 * threads:
                done, pending = wait(pending, return_when=FIRST_COMPLETED)
                for f in done:
                    blobs.extend(f.result())
        for f in pending:
            blobs.extend(f.result())
    return target, blobs


def read_back(cluster, blobs: list, seed: int, threads: int = 8) -> None:
    """Every acknowledged blob reads back byte for byte."""
    def check(item):
        fid, i, size = item
        got = cluster.read(fid)
        assert got == blob_bytes(seed, i, size), f"blob {fid} corrupt"
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(check, blobs))


def shard_paths(cluster, collection: str, vid: int) -> dict[int, str]:
    stem = f"{collection}_{vid}" if collection else str(vid)
    out = {}
    for vs in cluster.volume_servers:
        for loc in vs.store.locations:
            for p in glob.glob(os.path.join(loc.directory,
                                            stem + ".ec[0-9][0-9]")):
                out[int(p[-2:])] = p
    return out


def drop_shards(cluster, env, collection: str, vid: int,
                shard_ids: list[int]) -> None:
    """Lose shards cluster-wide through the unmount + delete RPCs (as
    volume.server.evacuate would), then let the master see it."""
    held = shard_paths(cluster, collection, vid)
    for vs in cluster.volume_servers:
        mine = [s for s in shard_ids
                if os.path.dirname(held[s]) in
                {loc.directory for loc in vs.store.locations}]
        if not mine:
            continue
        client = env.volume_server(vs.grpc_address)
        client.call("VolumeEcShardsUnmount",
                    {"volume_id": vid, "shard_ids": mine})
        client.call("VolumeEcShardsDelete",
                    {"volume_id": vid, "collection": collection,
                     "shard_ids": mine})
    cluster.sync_heartbeats()
    time.sleep(0.5)


def check_rs_parity(paths: dict[int, str], k: int, m: int,
                    window: int = 32 << 20) -> int:
    """Every parity shard file equals the oracle's encode of the data
    shard files, window by window.  Returns the shard size."""
    files = [np.memmap(paths[s], dtype=np.uint8, mode="r")
             for s in range(k + m)]
    size = len(files[0])
    assert all(len(f) == size for f in files), "shard sizes differ"
    rows = rs_matrix.generator_matrix(k, m)[k:]
    for off in range(0, size, window):
        data = np.stack([np.asarray(f[off:off + window])
                         for f in files[:k]])
        want = gf_apply(rows, data, backend="native")   # numpy w/o .so
        for p in range(m):
            got = np.asarray(files[k + p][off:off + window])
            assert np.array_equal(got, want[p]), \
                f"parity shard {k + p} differs at window {off}"
    return size


def lose_shards(cluster, env, collection: str, vid: int,
                lost: list[int], scratch: str) -> dict:
    """Copy the shards about to be lost into `scratch`, then lose them;
    returns {shard id: copy} for compare_rebuilt."""
    paths = shard_paths(cluster, collection, vid)
    keep = {}
    for s in lost:
        keep[s] = os.path.join(scratch, f"golden.ec{s:02d}")
        shutil.copyfile(paths[s], keep[s])
    drop_shards(cluster, env, collection, vid, lost)
    assert not set(lost) & set(shard_paths(cluster, collection, vid))
    return keep


def compare_rebuilt(cluster, collection: str, vid: int,
                    keep: dict) -> None:
    paths = shard_paths(cluster, collection, vid)
    for s, golden in keep.items():
        assert filecmp.cmp(paths[s], golden, shallow=False), \
            f"rebuilt shard {s} differs from the original"
        os.remove(golden)


# -- phases -------------------------------------------------------------------

def phase_rs_volume(cluster, total: int, seed: int, backend: str,
                    collection: str = "rs",
                    read_backend: "str | None" = None) -> dict:
    """RS(10,4): load, ec.encode, parity check, lose 4 shards, degraded
    reads of every blob, ec.rebuild, byte-identical shard files.
    `backend` is the codec label the verbs must dispatch on (rs_pallas
    on one chip, rs_mesh on a mesh); degraded reads always run the
    single-chip codec (`read_backend`, default `backend`)."""
    env = shell.CommandEnv(cluster.master_grpc)
    t0 = time.perf_counter()
    vid, blobs = load_volume(cluster, total, seed, collection)
    dt = time.perf_counter() - t0
    nbytes = sum(b[2] for b in blobs)
    log(f"rs: loaded volume {vid}: {len(blobs)} blobs, {nbytes} bytes in "
        f"{dt:.1f}s ({nbytes / dt / 1e9:.3f} GB/s through HTTP)")
    assert nbytes >= total

    before, c0, t0 = dispatches(), compile_seconds(), time.perf_counter()
    do_ec_encode(env, vid, collection)
    dt = time.perf_counter() - t0
    log(f"rs: ec.encode {dt:.1f}s, {nbytes / dt / 1e9:.3f} GB/s of blobs, "
        f"compile {compile_seconds() - c0:.1f}s")
    assert_dispatched(before, "encode", backend)
    cluster.sync_heartbeats()
    paths = shard_paths(cluster, collection, vid)
    assert sorted(paths) == list(range(K + M)), sorted(paths)
    shard_size = check_rs_parity(paths, K, M)
    log(f"rs: {M} parity shard files of {shard_size} bytes match the "
        f"oracle")

    lost = [0, 7, 10, 13]
    scratch = tempfile.mkdtemp(prefix="smoke-rs-")
    try:
        keep = lose_shards(cluster, env, collection, vid, lost,
                                   scratch)
        before, t0 = dispatches(), time.perf_counter()
        read_back(cluster, blobs, seed)
        dt = time.perf_counter() - t0
        log(f"rs: {len(blobs)} blobs read back through the degraded EC "
            f"read with shards {lost} lost, {dt:.1f}s")
        assert_dispatched(before, "reconstruct", read_backend or backend)

        before, c0, t0 = dispatches(), compile_seconds(), time.perf_counter()
        out = do_ec_rebuild(env, vid, collection)
        dt = time.perf_counter() - t0
        assert sorted(out["rebuilt"]) == lost, out
        log(f"rs: ec.rebuild of {lost} {dt:.1f}s, "
            f"{K * shard_size / dt / 1e9:.3f} GB/s of survivors read, "
            f"compile {compile_seconds() - c0:.1f}s")
        assert_dispatched(before, "reconstruct", backend)
        cluster.sync_heartbeats()
        compare_rebuilt(cluster, collection, vid, keep)
        log(f"rs: rebuilt shard files {lost} are byte-identical")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return {"vid": vid, "blobs": blobs, "paths": shard_paths(
        cluster, collection, vid)}


def check_clay_parity(paths: dict[int, str], k: int, m: int, small: int,
                      seed: int, windows_per_call: int = 8) -> None:
    """Every clay parity window equals the host structured encode; a
    few sampled columns also equal the flat ClayCode generator (the
    independent oracle, ops/clay.py)."""
    from seaweedfs_tpu.ops import clay_matrix, clay_structured
    c = clay_matrix.code(k, m)
    alpha, win_a = c.alpha, small // c.alpha
    files = [np.memmap(paths[s], dtype=np.uint8, mode="r")
             for s in range(k + m)]
    n_win = len(files[0]) // small
    rng = np.random.default_rng([seed, 3])
    flat = clay_matrix.generator_flat(k, m)
    for w0 in range(0, n_win, windows_per_call):
        wn = min(windows_per_call, n_win - w0)
        span = slice(w0 * small, (w0 + wn) * small)
        data = np.stack([np.asarray(f[span]) for f in files[:k]])
        sym = np.ascontiguousarray(
            data.reshape(k, wn, alpha, win_a).transpose(0, 2, 1, 3)
        ).reshape(k, alpha, -1)
        par = clay_structured.encode_np(k, m, sym)
        want = np.ascontiguousarray(
            par.reshape(m, alpha, wn, win_a).transpose(0, 2, 1, 3)
        ).reshape(m, -1)
        got = np.stack([np.asarray(f[span]) for f in files[k:]])
        assert np.array_equal(got, want), \
            f"clay parity differs in windows {w0}..{w0 + wn}"
        cols = rng.choice(sym.shape[-1], 16, replace=False)
        oracle = gf256.matmul(flat, sym[:, :, cols].reshape(k * alpha, -1))
        assert np.array_equal(par[:, :, cols].reshape(m * alpha, -1),
                              oracle), "clay parity != ClayCode oracle"


def phase_clay(cluster, total: int, seed: int,
               collection: str = "clay") -> None:
    """Clay (10,4): ec.encode -kind clay and a single-shard ec.rebuild
    through the verbs, both on the fused VMEM kernels."""
    from seaweedfs_tpu.ops import clay_structured
    from seaweedfs_tpu.storage.ec import codes
    from seaweedfs_tpu.storage.ec.layout import DEFAULT_GEOMETRY
    assert clay_structured.use_fused_engine(), \
        "the fused clay engine is off on this host"
    env = shell.CommandEnv(cluster.master_grpc)
    vid, blobs = load_volume(cluster, total, seed + 1, collection)
    nbytes = sum(b[2] for b in blobs)
    log(f"clay: loaded volume {vid}: {len(blobs)} blobs, {nbytes} bytes")

    def fused_calls(fn):
        info = fn.cache_info()
        return info.hits + info.misses

    enc0 = fused_calls(codes._clay_device_fn_fused)
    c0, t0 = compile_seconds(), time.perf_counter()
    do_ec_encode(env, vid, collection, kind="clay")
    dt = time.perf_counter() - t0
    assert fused_calls(codes._clay_device_fn_fused) > enc0, \
        "clay encode did not run the fused kernel"
    log(f"clay: ec.encode -kind clay {dt:.1f}s, "
        f"{nbytes / dt / 1e9:.3f} GB/s of blobs, compile "
        f"{compile_seconds() - c0:.1f}s, fused kernel")
    cluster.sync_heartbeats()
    paths = shard_paths(cluster, collection, vid)
    assert sorted(paths) == list(range(K + M)), sorted(paths)
    check_clay_parity(paths, K, M, DEFAULT_GEOMETRY.small_block_size, seed)
    log("clay: parity shard files match the structured encode and the "
        "ClayCode oracle")

    scratch = tempfile.mkdtemp(prefix="smoke-clay-")
    try:
        keep = lose_shards(cluster, env, collection, vid, [2],
                                   scratch)
        rep0 = fused_calls(codes._clay_repair_fn_fused)
        c0, t0 = compile_seconds(), time.perf_counter()
        out = do_ec_rebuild(env, vid, collection)
        dt = time.perf_counter() - t0
        assert out["rebuilt"] == [2], out
        assert out["rebuild_stats"]["plan_kind"] == "clay-plane-fused", out
        assert fused_calls(codes._clay_repair_fn_fused) > rep0
        log(f"clay: single-shard ec.rebuild {dt:.1f}s on the fused repair "
            f"kernel, read {out['rebuild_stats']['bytes_read']} bytes, "
            f"compile {compile_seconds() - c0:.1f}s")
        cluster.sync_heartbeats()
        compare_rebuilt(cluster, collection, vid, keep)
        log("clay: rebuilt shard file is byte-identical")
        read_back(cluster, blobs, seed + 1)
        log(f"clay: {len(blobs)} blobs read back")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def phase_kernel(shape: tuple, seed: int, interpret: bool = False) -> None:
    """One direct call of the RS shard-major kernel on an HBM-resident
    [k, V, B] batch; sampled columns against gf256.matmul."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_pallas
    k, v, b = shape
    pm = jnp.asarray(rs_pallas.to_plane_major(
        rs_matrix.parity_bit_matrix(k, M), M, k), dtype=jnp.int8)
    data = jax.jit(lambda key: jax.random.randint(
        key, shape, 0, 256, dtype=jnp.uint8))(jax.random.PRNGKey(seed))
    data.block_until_ready()
    t0 = time.perf_counter()
    fn = jax.jit(lambda p, x: rs_pallas.gf_matmul_bits_pallas_sm(
        p, x, interpret=interpret)).lower(pm, data).compile()
    t_compile = time.perf_counter() - t0
    fn(pm, data).block_until_ready()      # warm
    t0 = time.perf_counter()
    out = fn(pm, data)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    assert out.shape == (M, v, b) and out.dtype == jnp.uint8
    rows = rs_matrix.generator_matrix(k, M)[k:]
    rng = np.random.default_rng([seed, 4])
    width = min(b, 4096)
    for _ in range(4):
        vi = int(rng.integers(v))
        c0 = int(rng.integers(b - width + 1))
        d = np.asarray(data[:, vi, c0:c0 + width])
        got = np.asarray(out[:, vi, c0:c0 + width])
        assert np.array_equal(got, gf256.matmul(rows, d)), \
            f"kernel output differs at volume {vi} column {c0}"
    log(f"kernel: gf_matmul_bits_pallas_sm {list(shape)} compile "
        f"{t_compile:.1f}s, one call {dt * 1e3:.1f} ms = "
        f"{data.size / dt / 1e9:.2f} GB/s of data, sampled columns match "
        f"gf256.matmul")


def phase_mesh(cluster, total: int, seed: int,
               interpret: bool = False) -> None:
    """Several chips: ec.encode + ec.rebuild through the verbs on the
    MeshCodec the production picker builds (s=2, b=2 on four chips),
    the shard-parallel ring xor_psum reconstruct, and the same volume
    encoded by RSCodec("pallas") on device 0."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seaweedfs_tpu.ops.codec import RSCodec, resolve_backend
    from seaweedfs_tpu.parallel import mesh_codec
    codec = mesh_codec.codec_for_devices(K, M)
    assert isinstance(codec, mesh_codec.MeshCodec), type(codec)
    mesh = codec.mesh
    n_dev = mesh.devices.size
    assert n_dev == len(jax.devices()) and mesh.shape["b"] == 2, \
        dict(mesh.shape)
    # degraded reads run the single-chip codec (storage/ec/ec_volume.py)
    st = phase_rs_volume(cluster, total, seed, "rs_mesh",
                         collection="mesh",
                         read_backend="rs_" + resolve_backend())
    paths = st["paths"]

    # the same volume through the single-chip kernel on device 0
    files = [np.memmap(paths[s], dtype=np.uint8, mode="r")
             for s in range(K + M)]
    single = RSCodec(K, M, backend="pallas", interpret=interpret)
    size, window = len(files[0]), 64 << 20
    for off in range(0, size, window):
        data = np.stack([np.asarray(f[off:off + window])
                         for f in files[:K]])
        got = single.encode(data)
        for p in range(M):
            assert np.array_equal(np.asarray(files[K + p][off:off + window]),
                                  got[p]), \
                f"MeshCodec parity {K + p} != RSCodec(pallas) at {off}"
    log(f"mesh: MeshCodec shard files == RSCodec('pallas') on device 0 "
        f"({size} bytes per shard)")

    # shard-parallel reconstruct: survivors sharded over "s", bytes
    # over "b", partial products combined by the ring xor_psum
    fn, k_pad = mesh_codec._recon_fn(mesh, K, M)
    width = min(size, 64 << 20)
    width -= width % codec._rec_mult
    present = [1, 2, 3, 4, 5, 6, 8, 9, 11, 12]
    lost = [0, 7, 10, 13]
    survivors = np.zeros((k_pad, width), np.uint8)
    for row, s in enumerate(present):
        survivors[row] = files[s][:width]
    x = jax.device_put(survivors.reshape(k_pad, 8, -1),
                       NamedSharding(mesh, P("s", None, "b")))
    assert len(x.sharding.device_set) == n_dev, x.sharding
    bits = jax.numpy.asarray(mesh_codec._decode_bits_cached(
        K, M, "vandermonde", k_pad, tuple(present), tuple(lost)))
    rec = fn(bits, x)
    assert len(rec.sharding.device_set) >= 2, rec.sharding
    rec = np.asarray(rec).reshape(M, -1)
    for row, s in enumerate(lost):
        assert np.array_equal(rec[row], np.asarray(files[s][:width])), \
            f"xor_psum reconstruct of shard {s} differs"
    log(f"mesh: ring xor_psum reconstruct of {lost} over "
        f"{dict(mesh.shape)} ({width} bytes per shard, input on "
        f"{len(x.sharding.device_set)} devices) matches")
    for d in jax.devices():
        log(f"mesh: device {d.id} memory_stats {d.memory_stats()}")


# -- entry --------------------------------------------------------------------

def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from seaweedfs_tpu.util.compile_cache import place_compile_cache
    cache = place_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {dev.platform} "
        f"({dev.device_kind}); compile cache {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    jax.monitoring.register_event_duration_secs_listener(_on_compile)

    t0 = time.perf_counter()
    scratch = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        with SimCluster(volume_servers=4,
                        base_dir=os.path.join(scratch, "cluster")) as c:
            if args.chips == 4:
                phase_mesh(c, MESH_BYTES, args.seed)
            else:
                phase_rs_volume(c, RS_BYTES, args.seed, "rs_pallas")
                log(f"hbm peak after rs: {hbm_peaks()}")
                phase_clay(c, CLAY_BYTES, args.seed)
                log(f"hbm peak after clay: {hbm_peaks()}")
        if args.chips == 1:
            phase_kernel((K, 64, 8 << 20), args.seed)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    log(f"total {time.perf_counter() - t0:.1f}s, compile "
        f"{compile_seconds():.1f}s, hbm peak {hbm_peaks()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
