"""Blob-read traffic: closed-loop clients GET blobs of one EC volume over
HTTP while shards are lost.

Set-up builds the volume from the seed in memory, encodes the shards it
keeps with the reference encoder and writes them, with .ecx and .vif,
straight into the servers' directories (shard s on server s mod
servers, the lost ones nowhere), then mounts them through the servers'
RPCs.  Warm-up reads, in parallel, every blob that has bytes on a lost
data shard: that is every reconstruct shape the traffic can ask for.

Each client has its own request stream drawn from the seed: Zipf(`zipf_s`)
over the blobs' popularity ranks, which a fixed permutation maps to
blobs (fixture.py), so the hot set does not follow shard position.  Every answer is checked once the window has
closed, by CRC-32C against the seeded content."""

from __future__ import annotations

import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import google_crc32c
import numpy as np

from seaweedfs_tpu import shell

from .. import cluster as cl
from .. import core, fixture
from ..reference import layout, needle, rs


def setup(run) -> None:
    cfg, mix = run.config, run.mix
    k, m = cfg["data_shards"], cfg["parity_shards"]
    small = cfg["small_block_size"]
    lost = set(mix["lost_shards"])
    vol = fixture.make_volume(cfg, run.seed)
    n_servers = cfg["volume_servers"]
    c = cl.make(run, n_servers)
    stem = cl.base_name(vol.collection, vol.vid)
    with core.span("fixture"):
        dat = vol.dat()
        data = layout.data_shards(dat, k, cfg["large_block_size"], small)
        want_parity = [s for s in range(k, k + m) if s not in lost]
        gen = rs.generator(k, m)
        parity = dict(zip(want_parity, rs.encode_rows(
            data, [list(gen[s]) for s in want_parity])))
        held: dict[int, list[int]] = {}
        for s in range(k + m):
            if s in lost:
                continue
            d = cl.server_dir(c, s % n_servers)
            (data[s] if s < k else parity[s]).tofile(
                os.path.join(d, stem + f".ec{s:02d}"))
            held.setdefault(s % n_servers, []).append(s)
        for i in held:
            base = os.path.join(cl.server_dir(c, i), stem)
            with open(base + ".ecx", "wb") as f:
                f.write(vol.ecx())
            with open(base + ".vif", "w") as f:
                json.dump({"version": 3}, f)
    c.start()
    env = shell.CommandEnv(c.master_grpc)
    for i, shards in held.items():
        env.volume_server(c.volume_servers[i].grpc_address).call(
            "VolumeEcShardsMount", {"volume_id": vol.vid,
                                    "collection": vol.collection,
                                    "shard_ids": shards})
    cl.settle(c, env, vol.vid, set(range(k + m)) - lost, False)
    run.state.update(vol=vol, env=env, lost=lost)
    degraded = _blobs_on_shards(vol, k, small,
                                {s for s in lost if s < k})
    core.log(f"{len(vol.sizes)} blobs, {len(degraded)} with bytes on a "
             f"lost data shard")
    with core.span("warmup"), ThreadPoolExecutor(mix["clients"]) as pool:
        run.state["warm"] = list(pool.map(lambda i: _get(run, i),
                                          degraded))


def _blobs_on_shards(vol, k: int, small: int, shards: set[int]
                     ) -> list[int]:
    """Blobs whose record covers a small block of one of `shards`
    (volumes here stay under one large row)."""
    vol.dat()
    out = []
    for i, (off, n) in enumerate(zip(vol.offsets, vol.sizes)):
        first = int(off) // small
        last = (int(off) + needle.record_length(int(n)) - 1) // small
        if any(b % k in shards for b in range(first, last + 1)):
            out.append(i)
    return out


def _get(run, i: int) -> dict:
    """One timed GET; the CRC is taken after the clock stops."""
    vol, c = run.state["vol"], run.state["cluster"]
    t0 = time.perf_counter()
    try:
        body = c.read(vol.fid(i))
    except Exception as e:
        return {"blob": i, "seconds": time.perf_counter() - t0,
                "ok": False, "error": f"{type(e).__name__}: {e}"}
    dt = time.perf_counter() - t0
    return {"blob": i, "seconds": dt, "ok": True,
            "crc": google_crc32c.value(bytes(body))}


def _streams(run, n_blobs: int, length: int) -> list[np.ndarray]:
    mix = run.mix
    ranks = np.arange(1, n_blobs + 1, dtype=np.float64)
    p = ranks ** -mix["zipf_s"]
    p /= p.sum()
    hot = run.state["vol"].hot
    return [hot[fixture.rng(run.seed, 6, j).choice(n_blobs, length, p=p)]
            for j in range(mix["clients"])]


def window(run) -> core.Window:
    vol = run.state["vol"]
    w = core.Window(codec_before=core.codec_counters())
    streams = _streams(run, len(vol.sizes), run.mix["stream_length"])
    results: list[list[dict]] = [[] for _ in streams]
    t0 = time.perf_counter()
    deadline = t0 + run.seconds

    def client(j: int) -> None:
        for i in streams[j]:
            if time.perf_counter() >= deadline:
                return
            results[j].append(_get(run, int(i)))
        raise RuntimeError(f"client {j} ran out of its request stream")

    with core.span("window"):
        threads = [threading.Thread(target=client, args=(j,))
                   for j in range(len(streams))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    w.seconds = time.perf_counter() - t0
    w.codec_after = core.codec_counters()
    w.reads = [r for rs_ in results for r in rs_]
    w.attempted = len(w.reads)
    w.failed = sum(not r["ok"] for r in w.reads)
    core.log(f"window {w.seconds:.3f}s: {w.attempted} reads, "
             f"{w.failed} failed")
    return w


def check(run, w: core.Window) -> list[core.Compared]:
    vol = run.state["vol"]
    reads = w.reads + run.state["warm"]
    want: dict[int, int] = {}
    for r in reads:
        if r["blob"] not in want:
            want[r["blob"]] = google_crc32c.value(vol.blob(r["blob"]))
    return [core.Compared("reads_wrong", sum(
                r["ok"] and r["crc"] != want[r["blob"]] for r in reads), 0),
            core.Compared("reads_failed", sum(not r["ok"] for r in reads),
                          0)] + core.dispatch_compared(
        w, "reconstruct", run.expect["reconstruct_backend"])


def p99_ms(w: core.Window) -> float:
    """Nearest-rank 99th percentile over every request; a failed one
    counts as the whole window, longer than any answer in it."""
    lat = sorted(r["seconds"] if r["ok"] else w.seconds for r in w.reads)
    if not lat:
        return w.seconds * 1e3
    return lat[max(0, -(-len(lat) * 99 // 100) - 1)] * 1e3


def end_to_end(w: core.Window) -> dict:
    done = sum(r["ok"] for r in w.reads)
    return {"read_p99_ms": p99_ms(w), "read_ops_s": done / w.seconds}
