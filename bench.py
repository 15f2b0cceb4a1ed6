"""Headline benchmark: sustained ec.encode throughput (GB/s of volume data
consumed) through the fused Pallas TPU kernel, batched volumes resident in
HBM in the shard-major [K, V, B] layout.

Reference baseline: the klauspost/reedsolomon AVX2 path the reference
drives from weed/storage/erasure_coding/ec_encoder.go:179 sustains
~2 GB/s/core-ish on a modern x86 (BASELINE.md pegs the north star at
>=20 GB/s, >=10x that single-node path).

Methodology (sustained throughput on the TPU; without one, bench.py fails —
it never times the CPU under a device metric's name):
- the kernel runs as a Pallas custom call, so its full parity output is
  always materialized (custom calls cannot be partially DCE'd);
- per measured call, completion is confirmed by fetching an on-device
  reduction of one parity tile (cheap: one VMEM tile, does not re-read
  the 2+ GB parity);
- `iters` calls are dispatched asynchronously and THEN drained, so the
  per-call dispatch latency pipelines away instead of being charged to
  every iteration;
- the dot runs on the MXU in int8 (exact for 0/1 bit-planes: partial sums
  <= 8K <= 2040 in the int32 accumulator), 2x bf16 throughput on v5e.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

AVX2_BASELINE_GBPS = 2.0  # klauspost single-node encode, BASELINE.md


def spread(values: list[float], digits: int = 3) -> tuple[float, dict]:
    """(median, {value, n, min, max}) for a volatile metric — host IO and
    memory rates swing run to run, so a bare best-of-N makes the next
    regression check guesswork.  The scalar stays the headline; the
    spread rides next to it in the extras."""
    med = float(np.median(values))
    return round(med, digits), {
        "value": round(med, digits), "n": len(values),
        "min": round(min(values), digits),
        "max": round(max(values), digits)}


def bench_disk_path(quick: bool) -> dict:
    """End-to-end FILE->codec->FILE EC numbers plus the measured roofline
    components that bound them on this box.

    Three timed paths, same production write_ec_files/rebuild_ec_files
    pipeline (read batch N+1 / encode N / write N-1 overlapped):
      - disk:   /tmp on the real block device, native CPU codec — the
                number a single spinning/virtual disk sustains;
      - disk_production: same medium, codec UNPINNED — the production
        picker chooses (the TPU's pallas kernel on a TPU host);
      - stream: tmpfs, native CPU codec — the medium-independent software
                ceiling of the pipeline + codec.
    Rebuild = 4 lost shards (2 data + 2 parity), the worst RS(10,4) case.
    """
    import shutil
    import tempfile

    from seaweedfs_tpu.ops.codec import RSCodec
    from seaweedfs_tpu.storage import ec as ec_pkg
    from seaweedfs_tpu.storage.ec.encoder import (rebuild_ec_files,
                                                  write_ec_files)
    from seaweedfs_tpu.storage.ec.layout import DEFAULT_GEOMETRY, to_ext

    out: dict = {}
    geo = DEFAULT_GEOMETRY
    blk = np.random.default_rng(5).integers(
        0, 256, 8 << 20, dtype=np.uint8).tobytes()

    def make_vol(path: str, size: int) -> None:
        with open(path, "wb") as f:
            left = size
            while left > 0:
                n = min(left, len(blk))
                f.write(blk[:n])
                left -= n

    def run_path(workdir: str, size: int, codec_factory, tag: str,
                 rebuild: bool = True, runs: int = 3) -> None:
        # median-of-N with min/max recorded (spread()): these media
        # swing +-30-50% run to run under ambient host contention
        base = os.path.join(workdir, "v")
        make_vol(base + ".dat", size)
        enc_rates = []
        for _ in range(runs):
            t0 = time.perf_counter()
            write_ec_files(base, geo, codec_factory())
            enc_rates.append(size / (time.perf_counter() - t0) / 1e9)
        out[f"ec_encode_{tag}_gbps"], \
            out[f"ec_encode_{tag}_gbps_spread"] = spread(enc_rates)
        if not rebuild:
            return
        ec_pkg.save_volume_info(base, 3, dat_size=size,
                                data_shards=geo.data_shards,
                                parity_shards=geo.parity_shards)
        rb_rates = []
        for _ in range(runs):
            for i in (0, 7, 10, 13):
                os.remove(base + to_ext(i))
            t0 = time.perf_counter()
            rebuilt = rebuild_ec_files(base, geo, codec=codec_factory())
            rb_rates.append(size / (time.perf_counter() - t0) / 1e9)
            assert rebuilt == [0, 7, 10, 13]
        # volume-equivalent rate, matching the resident rebuild metric:
        # one volume-size of survivor bytes streams through the decoder
        out[f"ec_rebuild_{tag}_gbps"], \
            out[f"ec_rebuild_{tag}_gbps_spread"] = spread(rb_rates)

    size = (64 if quick else 2048) << 20
    native = lambda: RSCodec(geo.data_shards, geo.parity_shards,
                             backend="native")
    # real block device
    tdir = tempfile.mkdtemp(prefix="ecdisk")
    try:
        run_path(tdir, size, native, "disk")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    # the PRODUCTION verb, codec unpinned: _codec_for picks by platform
    tdir = tempfile.mkdtemp(prefix="ecprod")
    try:
        run_path(tdir, size, lambda: None, "disk_production",
                 rebuild=False, runs=2)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    # tmpfs (medium-independent pipeline ceiling)
    shm = "/dev/shm"
    if os.path.isdir(shm) and shutil.disk_usage(shm).free > 4 * size:
        sdir = tempfile.mkdtemp(prefix="ecstream", dir=shm)
        try:
            run_path(sdir, size, native, "stream")
        finally:
            shutil.rmtree(sdir, ignore_errors=True)
    # context probes: what the box's disk and memory actually sustain
    try:
        probe = os.path.join(tempfile.gettempdir(), "ecdisk_probe")
        buf = blk * 16  # 128MB
        t0 = time.perf_counter()
        with open(probe, "wb") as f:
            for _ in range(2):
                f.write(buf)
            f.flush()
            os.fdatasync(f.fileno())
        out["disk_write_mbps"] = round(256 / (time.perf_counter() - t0), 1)
        os.remove(probe)
    except Exception as e:
        out["disk_probe_error"] = str(e)[:160]
    return out


def bench_hotset_reread(concurrency: int, quick: bool = False,
                        n_hot: int = 2000, passes: int = 3) -> dict:
    """Hot-set re-read throughput + needle-cache hit rate (ISSUE 4):
    a working set small enough to live entirely in the volume servers'
    hot-needle LRU is read repeatedly — pass 1 warms the cache, the
    timed passes measure cache-resident serving.  The hit rate is
    sampled per timed pass from the servers' own counters, so both
    extras carry {value, n, min, max} spreads like every other volatile
    metric here."""
    import threading

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster

    if quick:
        n_hot, passes = 400, 2
    payload = b"h" * 1024
    with SimCluster(volume_servers=2, max_volumes=60) as cluster:
        fids: list[str] = []
        for _ in range(0, n_hot, 100):
            r = operation.assign(cluster.master_grpc, count=100)
            for fid in operation.derive_fids(r):
                operation.upload_to(r, fid, payload)
                fids.append(fid)

        def read_slice(sub):
            for fid in sub:
                operation.read_file(cluster.master_grpc, fid)

        def one_pass() -> float:
            per = max(1, len(fids) // concurrency)
            slices = [fids[i * per:(i + 1) * per]
                      for i in range(concurrency)]
            slices = [s for s in slices if s]
            threads = [threading.Thread(target=read_slice, args=(s,))
                       for s in slices]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return len(fids) / (time.perf_counter() - t0)

        def cache_counts() -> tuple[int, int]:
            hits = misses = 0
            for vs in cluster.volume_servers:
                if vs is not None:
                    hits += vs.needle_cache.hits
                    misses += vs.needle_cache.misses
            return hits, misses

        one_pass()   # warm: populates the hot-needle LRU
        rates, hit_rates = [], []
        for _ in range(passes):
            h0, m0 = cache_counts()
            rates.append(one_pass())
            h1, m1 = cache_counts()
            looked = (h1 - h0) + (m1 - m0)
            hit_rates.append((h1 - h0) / looked if looked else 0.0)
        out: dict = {}
        out["smallfile_hotset_reread_rps"], \
            out["smallfile_hotset_reread_rps_spread"] = spread(rates,
                                                               digits=1)
        out["needle_cache_hit_rate"], \
            out["needle_cache_hit_rate_spread"] = spread(hit_rates,
                                                         digits=4)
        return out


def bench_degraded_read(concurrency: int, quick: bool = False,
                        n_files: int = 400, runs: int = 2) -> dict:
    """Degraded-mode extras (ISSUE 6): read latency with one replica
    hard-killed, and how long reads take to recover after the kill.

    Reads ride the production failover path — cached TCP routes to the
    dead server fail once, get negative-cached, and the walk lands on
    the survivor — so `degraded` p99 includes the real discovery cost,
    and `post_kill_recovery_ms` is the wall time from the kill to the
    first successful read of an affected blob."""
    import threading

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster

    if quick:
        n_files, runs = 100, 1
    payload = b"d" * 1024
    healthy_p99, degraded_p99, recovery = [], [], []
    degraded_rps = []

    def read_all(master_grpc, fids) -> list[float]:
        lat: list[float] = []
        lock = threading.Lock()
        work = list(fids)

        def reader():
            while True:
                with lock:
                    if not work:
                        return
                    fid = work.pop()
                t0 = time.perf_counter()
                operation.read_file(master_grpc, fid)
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)

        threads = [threading.Thread(target=reader)
                   for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lat

    for _ in range(runs):
        with SimCluster(volume_servers=3, racks=2,
                        max_volumes=60) as cluster:
            fids = []
            for _ in range(n_files):
                fids.append(operation.assign_and_upload(
                    cluster.master_grpc, payload, replication="010"))
            lat = read_all(cluster.master_grpc, fids)
            healthy_p99.append(
                float(np.percentile(lat, 99)) * 1000)
            # pick a blob held by server 0, then kill that server
            victim_url = cluster.volume_servers[0].url
            affected = [f for f in fids
                        if any(l["url"] == victim_url
                               for l in operation.lookup_volume(
                                   cluster.master_grpc,
                                   int(f.split(",")[0])))]
            t_kill = time.perf_counter()
            cluster.kill_volume_server(0)
            probe = affected[0] if affected else fids[0]
            probe_deadline = t_kill + 30.0
            while True:
                try:
                    operation.read_file(cluster.master_grpc, probe)
                    break
                except Exception:
                    if time.perf_counter() >= probe_deadline:
                        # surfaces as degraded_read_error in the extras
                        # instead of hanging the whole bench run
                        raise RuntimeError(
                            f"read of {probe} never recovered within "
                            f"30s of the replica kill")
                    time.sleep(0.01)
            recovery.append((time.perf_counter() - t_kill) * 1000)
            t0 = time.perf_counter()
            lat = read_all(cluster.master_grpc, fids)
            wall = time.perf_counter() - t0
            degraded_p99.append(
                float(np.percentile(lat, 99)) * 1000)
            degraded_rps.append(len(lat) / wall if wall else 0.0)

    h_med, h_spread = spread(healthy_p99)
    d_med, d_spread = spread(degraded_p99)
    r_med, r_spread = spread(recovery)
    rps_med, rps_spread = spread(degraded_rps, digits=1)
    return {
        "degraded_healthy_read_p99_ms": h_med,
        "degraded_healthy_read_p99_ms_spread": h_spread,
        "degraded_one_replica_down_read_p99_ms": d_med,
        "degraded_one_replica_down_read_p99_ms_spread": d_spread,
        "degraded_one_replica_down_read_rps": rps_med,
        "degraded_one_replica_down_read_rps_spread": rps_spread,
        "post_kill_recovery_ms": r_med,
        "post_kill_recovery_ms_spread": r_spread,
    }


def bench_self_healing(quick: bool = False, n_files: int = 80,
                       runs: int = 2) -> dict:
    """Self-healing extras (ISSUE 7): `repair_mttr_s` is the wall time
    from hard-killing one replica holder to the repair loop restoring
    full R=2 replication (loss observed -> VolumeCopy -> heartbeat
    registered), and `scrub_volumes_per_s` is the anti-entropy digest
    sweep rate over replicated volumes (shallow digests — the per-tick
    cost, not the deep CRC scan)."""
    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster

    if quick:
        n_files, runs = 30, 1
    payload = b"h" * 1024
    mttrs, scrub_rates = [], []
    for _ in range(runs):
        with SimCluster(volume_servers=3, racks=2, max_volumes=60,
                        pulse_seconds=0.3, repair_interval=0.25,
                        repair={"grace": 0.2, "scrub_interval": 0.0,
                                "liveness_staleness": 0.0,
                                "backoff_base": 0.3,
                                "scrub_quiet_seconds": 0.0,
                                "max_inflight": 4}) as cluster:
            fids = [operation.assign_and_upload(
                cluster.master_grpc, payload, replication="010")
                for _ in range(n_files)]
            vids = sorted({int(f.split(",")[0]) for f in fids})
            leader = cluster.masters[cluster.leader_index()]
            # scrub rate first, on the healthy cluster
            planner = leader.repair
            planner.cfg.scrub_batch = max(len(vids), 1)
            t0 = time.perf_counter()
            checked = planner.scrub_once(deep=False)
            dt = time.perf_counter() - t0
            if checked and dt > 0:
                scrub_rates.append(checked / dt)
            # kill-to-fully-replicated; the loss must first be
            # OBSERVED (stream break -> unregister) or the poll reads
            # the stale pre-kill topology and under-reports MTTR
            victim = cluster.volume_servers[0].url
            affected = [v for v in vids
                        if any(dn.url == victim
                               for dn in leader.topo.lookup("", v))]
            if not affected:
                continue  # victim held nothing: no MTTR to measure
            t_kill = time.perf_counter()
            cluster.kill_volume_server(0)
            obs_deadline = time.perf_counter() + 15
            while time.perf_counter() < obs_deadline and all(
                    len(leader.topo.lookup("", v)) >= 2
                    for v in affected):
                time.sleep(0.01)
            cluster.wait_for_replication(vids, copies=2, timeout=60.0)
            mttrs.append(time.perf_counter() - t_kill)
    out = {}
    if mttrs:  # empty when every victim held no affected volume
        out["repair_mttr_s"], out["repair_mttr_s_spread"] = \
            spread(mttrs, digits=3)
    if scrub_rates:
        out["scrub_volumes_per_s"], \
            out["scrub_volumes_per_s_spread"] = spread(scrub_rates,
                                                       digits=1)
    return out


def bench_s3_authz(quick: bool = False) -> dict:
    """ISSUE 8 extras: what the fused IAM+policy+ACL gate costs per
    request — S3 write/read rps with authz enforced vs short-circuited
    (same cluster, same identities, the `enforce_authz=False` knob).
    The common allowed path decides at step 1 (IAM) with the bucket
    meta cached, so the expected overhead is one dict lookup and a
    metrics bump — this records the evidence."""
    import concurrent.futures as cf

    from seaweedfs_tpu.s3 import IdentityAccessManagement, S3ApiServer
    from seaweedfs_tpu.s3.client import S3Client
    from seaweedfs_tpu.testing import SimCluster
    n = 150 if quick else 1200
    workers = 4
    payload = os.urandom(1024)
    out: dict = {}
    with SimCluster(volume_servers=1, filers=1) as c:
        iam = IdentityAccessManagement.from_config({"identities": [
            {"name": "bench",
             "credentials": [{"accessKey": "BENCHKEY",
                              "secretKey": "benchsecret"}],
             "actions": ["Admin"]}]})
        for label, enforce in (("authz", True), ("noauthz", False)):
            srv = S3ApiServer(c.filers[0].address,
                              c.filers[0].grpc_address, iam=iam,
                              enforce_authz=enforce)
            srv.start()
            try:
                cl = S3Client(srv.address, "BENCHKEY", "benchsecret")
                cl.create_bucket(f"bench-{label}")

                def wr(i, _label=label, _cl=cl):
                    _cl.put_object(f"bench-{_label}", f"o{i}.bin",
                                   payload)

                def rd(i, _label=label, _cl=cl):
                    _cl.get_object(f"bench-{_label}",
                                   f"o{i % n}.bin")

                with cf.ThreadPoolExecutor(workers) as ex:
                    t0 = time.perf_counter()
                    list(ex.map(wr, range(n)))
                    w_dt = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    list(ex.map(rd, range(n)))
                    r_dt = time.perf_counter() - t0
                out[f"s3_write_rps_{label}"] = round(n / w_dt, 1)
                out[f"s3_read_rps_{label}"] = round(n / r_dt, 1)
            finally:
                srv.stop()
    if out.get("s3_write_rps_noauthz") and out.get("s3_read_rps_noauthz"):
        out["s3_authz_write_overhead_pct"] = round(
            100.0 * (1 - out["s3_write_rps_authz"]
                     / out["s3_write_rps_noauthz"]), 1)
        out["s3_authz_read_overhead_pct"] = round(
            100.0 * (1 - out["s3_read_rps_authz"]
                     / out["s3_read_rps_noauthz"]), 1)
    return out


def bench_observability(quick: bool = False, n_files: int = 1500,
                        passes: int = 3) -> dict:
    """The observability tax (ISSUE 9): HTTP read rps with the span
    plane on vs WEED_TRACE=0, and with the sampling profiler on vs off,
    so the cost of always-on instrumentation is tracked next to the
    perf numbers instead of assumed.  The HTTP data path is the honest
    denominator — every request there mints/records a span when tracing
    is on; the TCP frame path only pays when a trace actually rides the
    frame."""
    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.util import profiling, tracing
    from seaweedfs_tpu.util.http import http_request

    if quick:
        n_files, passes = 300, 2
    payload = b"o" * 1024
    out: dict = {}
    with SimCluster(volume_servers=1) as cluster:
        r = operation.assign(cluster.master_grpc, count=n_files)
        fids = operation.derive_fids(r)
        for fid in fids:
            operation.upload_to(r, fid, payload)
        url = r.url

        def one_pass() -> float:
            t0 = time.perf_counter()
            for fid in fids:
                status, _, _ = http_request(f"http://{url}/{fid}")
                assert status == 200
            return len(fids) / (time.perf_counter() - t0)

        def set_config(traced: bool, profiled: bool) -> None:
            tracing.set_enabled(traced)
            s = profiling.sampler()     # (re)starts the parked thread
            if s is not None and not profiled:
                s.stop()

        was_traced = tracing.enabled()
        rates: dict[str, list] = {"base": [], "traced": [],
                                  "profiled": []}
        configs = [("base", False, False), ("traced", True, False),
                   ("profiled", False, True)]
        try:
            set_config(False, False)
            one_pass()   # warm connections / needle cache, untimed
            # interleave configs round-robin AND rotate the order each
            # round: box-level drift (thermal, neighbors, allocator
            # warm-up) ramps throughput over time, so both the round
            # position and the global trend must hit every config
            # equally
            # rounds rounded UP to a multiple of 3 so every config sees
            # every round position equally often (passes ~= samples per
            # config)
            for i in range((passes + 2) // 3 * 3):
                for key, traced, profiled in (configs[i % 3:]
                                              + configs[:i % 3]):
                    set_config(traced, profiled)
                    rates[key].append(one_pass())
        finally:
            tracing.set_enabled(was_traced)
            profiling.sampler()             # leave the sampler running
        for key, label in (("base", "obs_baseline_read_rps"),
                           ("traced", "obs_traced_read_rps"),
                           ("profiled", "obs_profiled_read_rps")):
            out[label], out[f"{label}_spread"] = spread(rates[key],
                                                        digits=1)
        # overhead ratios compare BEST passes: scheduler blips only
        # ever subtract throughput, so max-vs-max is the stable
        # estimator on a contended box
        base = max(rates["base"])
        out["tracing_overhead_pct"] = round(
            100.0 * (base - max(rates["traced"])) / base, 2)
        out["profiler_overhead_pct"] = round(
            100.0 * (base - max(rates["profiled"])) / base, 2)

        # v3 plane cost (ISSUE 14): a tick = one federated scrape +
        # history record + alert evaluation.  Overhead is reported the
        # way the PR 9 sampler budget is — deterministic per-tick cost
        # times the cadence — because a wall-clock A/B at any cadence
        # worth running gates on box weather (the true cost here is
        # single-digit ms per 10s tick; the A/B noise floor on this box
        # is +-5%).  min-over-ticks: noise only ever adds.
        plane = cluster.masters[0].plane
        tick_ms = []
        for _ in range(4):
            t0 = time.perf_counter()
            plane.tick()
            tick_ms.append((time.perf_counter() - t0) * 1000.0)
        out["history_tick_ms"] = round(min(tick_ms), 2)
        # alert evaluation alone, straight from the engine's self-gauge
        out["alert_eval_ms"] = round(
            plane.alerts.m_eval.value() * 1000.0, 3)
        interval_ms = plane.interval * 1000.0 if plane.interval > 0 \
            else 10_000.0                    # production default cadence
        out["history_scrape_overhead_pct"] = round(
            100.0 * min(tick_ms) / interval_ms, 3)
    return out


def bench_heat(quick: bool = False, ops: int = 1_000_000,
               n_keys: int = 100_000, n_files: int = 1200,
               passes: int = 3) -> dict:
    """Workload heat plane tax + fidelity (ISSUE 16).

    Two honest measurements:

    - an in-process zipfian million-op drive straight into
      HeatTracker.record — per-op cost, top-K recall against the TRUE
      top-10 of the drive, bounded sketch memory, and the
      merge_snapshots cost the master pays per federation tick;
    - the read-path A/B: HTTP read rps against a real volume server
      with the tracker constructed under WEED_HEAT=0 vs the default,
      interleaved round-robin like bench_observability so box drift
      hits both configs equally.  heat_track_overhead_pct compares
      BEST passes (noise only subtracts throughput)."""
    import random as _random

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.util.http import http_request
    from seaweedfs_tpu.util.sketch import HeatTracker, merge_snapshots

    if quick:
        ops, n_keys, n_files, passes = 100_000, 10_000, 300, 2
    out: dict = {}

    # -- zipfian drive into the sketches --------------------------------
    weights = [(i + 1) ** -1.2 for i in range(n_keys)]
    scale = ops / sum(weights)
    counts = [max(0, int(w * scale)) for w in weights]
    stream = [i for i, c in enumerate(counts) for _ in range(c)]
    _random.Random(1234).shuffle(stream)
    keys = [f"3,{i:08x}" for i in range(n_keys)]
    tracker = HeatTracker(enabled=True)
    t0 = time.perf_counter()
    for i in stream:
        tracker.record("read", volume=i & 7, key=keys[i], nbytes=1024)
    drive_s = time.perf_counter() - t0
    out["heat_record_ns_per_op"] = round(drive_s / len(stream) * 1e9)
    out["heat_drive_ops"] = len(stream)
    true_top = [keys[i] for i in range(10)]
    got_top = [k for k, *_ in tracker.objects.top(10)]
    out["heat_topk_recall"] = round(
        len(set(true_top) & set(got_top)) / 10.0, 2)
    out["heat_sketch_memory_bytes"] = tracker.memory_bytes()

    # master-side merge cost: one federation tick folds every
    # data-plane snapshot (8 stand-ins here, freq matrices included)
    snaps = [tracker.snapshot(include_freq=True) for _ in range(8)]
    merge_ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        merge_snapshots(snaps)
        merge_ms.append((time.perf_counter() - t0) * 1000.0)
    out["heat_merge_ms"] = round(min(merge_ms), 2)

    # -- read-path A/B: WEED_HEAT=0 vs on -------------------------------
    payload = b"h" * 1024
    with SimCluster(volume_servers=1) as cluster:
        vs = cluster.volume_servers[0]
        r = operation.assign(cluster.master_grpc, count=n_files)
        fids = operation.derive_fids(r)
        for fid in fids:
            operation.upload_to(r, fid, payload)
        url = r.url

        def one_pass() -> float:
            t0 = time.perf_counter()
            for fid in fids:
                status, _, _ = http_request(f"http://{url}/{fid}")
                assert status == 200
            return len(fids) / (time.perf_counter() - t0)

        def set_heat(on: bool) -> None:
            # the real knob: a tracker CONSTRUCTED under WEED_HEAT=0
            # is permanently disabled — record() returns at the top
            prev = os.environ.get("WEED_HEAT")
            os.environ["WEED_HEAT"] = "1" if on else "0"
            try:
                vs.heat = HeatTracker()
            finally:
                if prev is None:
                    os.environ.pop("WEED_HEAT", None)
                else:
                    os.environ["WEED_HEAT"] = prev

        rates: dict = {"off": [], "on": []}
        configs = [("off", False), ("on", True)]
        one_pass()      # warm connections / needle cache, untimed
        for i in range(passes * 2):
            for key, on in (configs[i % 2:] + configs[:i % 2]):
                set_heat(on)
                rates[key].append(one_pass())
        set_heat(True)
        out["heat_off_read_rps"], out["heat_off_read_rps_spread"] = \
            spread(rates["off"], digits=1)
        out["heat_on_read_rps"], out["heat_on_read_rps_spread"] = \
            spread(rates["on"], digits=1)
        base = max(rates["off"])
        out["heat_track_overhead_pct"] = round(
            100.0 * (base - max(rates["on"])) / base, 2)
    return out


def bench_replicated_write(concurrency: int, quick: bool = False,
                           n_files: int = 1000, runs: int = 3) -> dict:
    """Replicated small-write throughput (ISSUE 5): replication 001
    (same-rack copy) and 010 (cross-rack copy) through the leased-fid +
    frame-fan-out write path, with the fan-out latency breakdown and the
    assign-RPC-per-write ratio that the overhaul is supposed to move.

    Also asserts the no-socket-churn property in numbers: the pooled
    HTTP client's created-connection count and the per-replica fan-out
    transport counts ride along, so a regression to
    connection-per-request shows up as created ~ O(writes)."""
    import threading

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.util.http import connection_pool

    if quick:
        n_files, runs = 200, 1
    payload = b"r" * 1024
    out: dict = {}
    # 3 servers over 2 racks places BOTH policies: 001 needs two servers
    # in one rack, 010 needs two racks (test_cluster fixture geometry)
    with SimCluster(volume_servers=3, racks=2, max_volumes=60) as cluster:
        master = next(m for m in cluster.masters
                      if m is not None and m.is_leader)

        def one_run(replication: str) -> tuple[float, dict]:
            leaser = operation.FidLeaser(lease_size=50)
            remaining = [n_files]
            lock = threading.Lock()
            failed = [0]

            def writer():
                while True:
                    with lock:
                        if remaining[0] <= 0:
                            return
                        remaining[0] -= 1
                    try:
                        r = leaser.assign(cluster.master_grpc,
                                          replication=replication)
                        operation.upload_to(r, r.fid, payload)
                    except Exception:
                        with lock:
                            failed[0] += 1
            assigns0 = master.metrics.master_assign.value()
            threads = [threading.Thread(target=writer)
                       for _ in range(concurrency)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            assigns = master.metrics.master_assign.value() - assigns0
            ok = n_files - failed[0]
            return ok / wall if wall else 0.0, {
                "assign_rpcs": assigns,
                "assign_rpcs_per_write": round(assigns / max(1, ok), 4),
                "failed": failed[0]}

        pool0 = dict(connection_pool().stats)
        for replication, tag in (("001", "001"), ("010", "010")):
            rates, assigns, ok_writes, failures = [], 0.0, 0, 0
            for _ in range(runs):
                rps, extras = one_run(replication)
                rates.append(rps)
                # accumulate over ALL runs: a lease anomaly or failure
                # burst in run 1 must not be hidden by run N's numbers
                assigns += extras["assign_rpcs"]
                failures += extras["failed"]
                ok_writes += n_files - extras["failed"]
            out[f"replicated_write_{tag}_rps"], \
                out[f"replicated_write_{tag}_rps_spread"] = spread(
                    rates, digits=1)
            out[f"replicated_write_{tag}_assign_rpcs_per_write"] = \
                round(assigns / max(1, ok_writes), 4)
            if failures:
                out[f"replicated_write_{tag}_failed"] = failures
        # fan-out breakdown across all volume servers: per-transport
        # send counts and average per-replica latency
        for transport in ("tcp", "http"):
            n = sum(vs.metrics.replica_fanout_latency._totals.get(
                        (transport,), 0)
                    for vs in cluster.volume_servers if vs is not None)
            s = sum(vs.metrics.replica_fanout_latency._sums.get(
                        (transport,), 0.0)
                    for vs in cluster.volume_servers if vs is not None)
            ok_n = sum(vs.metrics.replica_fanout_ops.value(transport,
                                                           "ok")
                       for vs in cluster.volume_servers
                       if vs is not None)
            out[f"fanout_{transport}_sends"] = int(ok_n)
            if n:
                out[f"fanout_{transport}_avg_ms"] = round(s / n * 1e3, 3)
        pool1 = connection_pool().stats
        # O(pool size), not O(writes): the whole replicated bench must
        # not open more upstream HTTP connections than the pool cap
        out["http_pool_conns_created"] = \
            pool1["created"] - pool0["created"]
        out["http_pool_conns_reused"] = pool1["reused"] - pool0["reused"]
    return out


def bench_http_native_loop(quick: bool = False) -> dict:
    """Native HTTP serving loop extras (ISSUE 18): per-worker volume
    HTTP small-file read and write rps with the fastpath.c serving
    loop ON vs OFF — an interleaved, order-rotated A/B flipped by the
    WEED_FASTPATH_HTTP kill switch (read per connection, so the SAME
    server serves both arms) with {value, n, min, max} spreads — plus
    python_calls_per_http_op: Python-level call events inside the
    serving threads per HTTP GET, the interpreter overhead the C loop
    exists to delete."""
    import socket as _socket
    import threading as _threading

    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.util import http as uhttp
    from seaweedfs_tpu.util import tracing

    if uhttp._http_fastpath() is None:
        return {"http_native_error": "native http loop unavailable"}

    n_files = 40 if quick else 120
    reads_per_thread = 300 if quick else 1000
    writes_per_thread = 80 if quick else 250
    read_reps = 2 if quick else 4     # ~1s per arm: below that, the
    write_reps = 1 if quick else 2    # box's scheduling jitter wins
    conc = min(8, 2 * (os.cpu_count() or 1))
    rounds = 3 if quick else 5
    payload = b"n" * 1024
    # what real clients put on the wire — header parsing is a large
    # slice of the per-request loop cost on both arms
    req_hdrs = (b"Host: 127.0.0.1\r\nUser-Agent: weedbench/1.0\r\n"
                b"Accept: */*\r\nAccept-Encoding: identity\r\n")
    out: dict = {}
    was_tracing = tracing.enabled()
    prev_env = os.environ.get("WEED_FASTPATH_HTTP")
    prev_lockdep = os.environ.get("WEED_LOCKDEP")
    rates: dict = {"read": {"on": [], "off": []},
                   "write": {"on": [], "off": []}}
    ratios: dict = {"read": [], "write": []}

    def drive(port: int, blob: bytes, expect: int) -> None:
        # raw keep-alive client: one pipelined burst per thread keeps
        # the measurement on the SERVING loop, not a Python client
        s = _socket.create_connection(("127.0.0.1", port), timeout=30)
        try:
            s.sendall(blob)
            s.shutdown(_socket.SHUT_WR)
            got, tail = 0, b""
            while True:
                p = s.recv(1 << 16)
                if not p:
                    break
                # tail < marker length: a match is either inside p or
                # spans the chunk boundary — never counted twice
                buf = tail + p
                got += buf.count(b"HTTP/1.1 2")
                tail = buf[-9:]
            if got < expect:
                raise RuntimeError(f"pipelined burst: {got}/{expect} 2xx")
        finally:
            s.close()

    def measure(port: int, blobs: list, expect: int,
                reps: int) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            threads = [_threading.Thread(target=drive,
                                         args=(port, b, expect))
                       for b in blobs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return reps * len(blobs) * expect / (time.perf_counter() - t0)

    try:
        # the volume fast lane only arms with tracing off; the Python
        # arm runs the same way so both sides serve identical work
        tracing.set_enabled(False)
        # lockdep instrumentation is constant overhead on BOTH arms —
        # benching with it armed just dilutes the loop under test
        os.environ["WEED_LOCKDEP"] = "0"
        # jwt off: the write arm drives raw pipelined POSTs without
        # re-signing per-fid tokens inside the timed loop
        with SimCluster(volume_servers=1, max_volumes=60,
                        jwt_key="") as c:
            from seaweedfs_tpu import operation
            fids = [c.upload(payload) for _ in range(n_files)]
            vs = c.volume_servers[0]
            port = vs.http.port
            read_blobs = []
            for t in range(conc):
                reqs = [(f"GET /{fids[(t + i) % n_files]} "
                         f"HTTP/1.1\r\n").encode() + req_hdrs + b"\r\n"
                        for i in range(reads_per_thread)]
                read_blobs.append(b"".join(reqs))
            w = operation.assign(c.master_grpc,
                                 count=conc * writes_per_thread)
            wfids = operation.derive_fids(w)
            write_blobs = []
            for t in range(conc):
                chunk = wfids[t * writes_per_thread:
                              (t + 1) * writes_per_thread]
                reqs = [(f"POST /{f} HTTP/1.1\r\n").encode() + req_hdrs
                        + (f"Content-Length: {len(payload)}"
                           f"\r\n\r\n").encode() + payload
                        for f in chunk]
                write_blobs.append(b"".join(reqs))
            # warmup both arms (first-touch page cache, route setup)
            one = (f"GET /{fids[0]} HTTP/1.1\r\n".encode()
                   + req_hdrs + b"\r\n")
            for arm in ("1", "0"):
                os.environ["WEED_FASTPATH_HTTP"] = arm
                drive(port, one * 20, 20)
            for r in range(rounds):
                order = ("on", "off") if r % 2 == 0 else ("off", "on")
                got: dict = {"read": {}, "write": {}}
                for arm in order:
                    os.environ["WEED_FASTPATH_HTTP"] = \
                        "1" if arm == "on" else "0"
                    got["read"][arm] = measure(
                        port, read_blobs, reads_per_thread, read_reps)
                    got["write"][arm] = measure(
                        port, write_blobs, writes_per_thread,
                        write_reps)
                for kind in ("read", "write"):
                    for arm in ("on", "off"):
                        rates[kind][arm].append(got[kind][arm])
                    # paired within the round: immune to the slow
                    # drift that dominates this box's absolute rps
                    ratios[kind].append(
                        got[kind]["on"] / max(1e-9, got[kind]["off"]))
        for kind in ("read", "write"):
            for arm in ("on", "off"):
                key = f"http_native_{kind}_rps_{arm}"
                out[key], out[f"{key}_spread"] = \
                    spread(rates[kind][arm], digits=1)
            out[f"http_native_{kind}_speedup"], \
                out[f"http_native_{kind}_speedup_spread"] = \
                spread(ratios[kind], digits=3)
        # acceptance gate (ISSUE 18): >= +25% small-file read rps
        out["http_native_read_speedup_ok"] = \
            out["http_native_read_speedup"] >= 1.25

        # -- python_calls_per_http_op -----------------------------------
        # a fresh standalone server so threading.setprofile sees ONLY
        # its accept/conn threads (started after the hook is armed)
        calls = [0]

        def prof(frame, event, arg):  # noqa: ARG001
            if event == "call":
                calls[0] += 1

        for arm in ("on", "off"):
            _threading.setprofile(prof)
            try:
                srv = uhttp.HttpServer()
                srv.route("GET", "/hello",
                          lambda req: uhttp.Response(body=b"hi"))
                srv.start()
                try:
                    os.environ["WEED_FASTPATH_HTTP"] = \
                        "1" if arm == "on" else "0"
                    n = 50 if quick else 200
                    s = _socket.create_connection(
                        ("127.0.0.1", srv.port), timeout=10)
                    try:
                        one = b"GET /hello HTTP/1.1\r\n\r\n"
                        s.sendall(one)   # warm the conn thread
                        s.recv(1 << 16)
                        base = calls[0]
                        s.sendall(one * n)
                        got, tail = 0, b""
                        while got < n:
                            p = s.recv(1 << 16)
                            if not p:
                                break
                            buf = tail + p
                            got += buf.count(b"HTTP/1.1 2")
                            tail = buf[-9:]
                        out[f"python_calls_per_http_op_{arm}"] = \
                            round((calls[0] - base) / max(1, got), 1)
                    finally:
                        s.close()
                finally:
                    srv.stop()
            finally:
                _threading.setprofile(None)
    finally:
        tracing.set_enabled(was_tracing)
        for var, prev in (("WEED_FASTPATH_HTTP", prev_env),
                          ("WEED_LOCKDEP", prev_lockdep)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev
    return out


def bench_worker_scaling(quick: bool = False) -> dict:
    """Per-core scaling curve (ISSUE 12): the smallfile benchmark
    against ONE logical volume server running 1, 2 (and 4) worker
    processes.  smallfile_{read,write}_rps_workers_{w} land as
    first-class extras with {value, n, min, max} spreads (workers=1
    IS the unchanged in-process server).  On a 1-core box the curve
    documents the overhead of sharding without cores; a multi-core box
    should show >1.5x reads at 2 workers."""
    from seaweedfs_tpu.command.benchmark import run_benchmark
    from seaweedfs_tpu.testing import SimCluster

    counts = (1, 2) if quick else (1, 2, 4)
    n = 1200 if quick else 8000
    conc = min(16, 4 * (os.cpu_count() or 1))
    rounds = 1 if quick else 2
    out: dict = {}
    for w in counts:
        reads: list[float] = []
        writes: list[float] = []
        for _ in range(rounds):
            with SimCluster(volume_servers=1, max_volumes=60,
                            volume_workers=w) as cluster:
                r = run_benchmark(cluster.master_grpc, n_files=n,
                                  file_size=1024, concurrency=conc,
                                  quiet=True)
                writes.append(r["write"]["req_per_sec"])
                reads.append(r["read"]["req_per_sec"])
        out[f"smallfile_read_rps_workers_{w}"], \
            out[f"smallfile_read_rps_workers_{w}_spread"] = \
            spread(reads, digits=1)
        out[f"smallfile_write_rps_workers_{w}"], \
            out[f"smallfile_write_rps_workers_{w}_spread"] = \
            spread(writes, digits=1)
    if "smallfile_read_rps_workers_2" in out:
        out["worker_read_scaling_2w"] = round(
            out["smallfile_read_rps_workers_2"]
            / max(1e-9, out["smallfile_read_rps_workers_1"]), 3)
        out["worker_write_scaling_2w"] = round(
            out["smallfile_write_rps_workers_2"]
            / max(1e-9, out["smallfile_write_rps_workers_1"]), 3)
    return out


def bench_replication(quick: bool = False) -> dict:
    """Cross-cluster replication extras (ISSUE 11): steady-state
    replicated events/s through the journal-offset sync path, the
    replication lag p99 (source event ts -> applied on the target), and
    post-partition catch-up seconds — the backlog drain rate after a
    heal, which is the number an operator's staleness budget hangs on.
    Two complete SimClusters, sync running continuously, the partition
    injected through the seeded fault plane like test_georeplication."""
    import shutil
    import tempfile

    from seaweedfs_tpu.replication.filer_sync import SyncDirection
    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.util import faults
    from seaweedfs_tpu.util.http import http_request

    n_steady = 80 if quick else 400
    n_part = 40 if quick else 150
    payload = b"r" * 1024
    out: dict = {}
    base = tempfile.mkdtemp(prefix="georep-bench")
    try:
        a = SimCluster(volume_servers=1, filers=1, max_volumes=60,
                       base_dir=os.path.join(base, "A"), seed=71,
                       filer_store="sqlite").start()
        b = SimCluster(volume_servers=1, filers=1, max_volumes=60,
                       base_dir=os.path.join(base, "B"), seed=72,
                       filer_store="sqlite").start()
        d = SyncDirection(
            a.filers[0].grpc_address, a.master_grpc,
            b.filers[0].grpc_address, b.master_grpc,
            "benchA", "benchB", path_prefix="/bench",
            offset_path=os.path.join(base, "offset"))
        try:
            d.start()
            addr = a.filers[0].address

            def write(tag, i):
                status, body, _ = http_request(
                    f"http://{addr}/bench/{tag}/f{i:04d}",
                    method="POST", body=payload)
                assert status == 201, body

            def wait_applied(target, timeout=120.0) -> float:
                t0 = time.perf_counter()
                deadline = time.time() + timeout
                while time.time() < deadline:
                    if d.applied >= target:
                        return time.perf_counter() - t0
                    time.sleep(0.02)
                raise TimeoutError(
                    f"applied {d.applied} < {target}")

            # steady state: PACED writes while the sync tails live, so
            # the lag samples measure per-event replication latency
            # (write -> applied on the target), not backlog drain
            t0 = time.perf_counter()
            for i in range(n_steady):
                write("steady", i)
                time.sleep(0.02)
            wait_applied(n_steady)
            dt = time.perf_counter() - t0
            out["replication_steady_events_per_s"] = round(
                d.applied / dt, 1)
            if d.lag_samples:
                lags_ms = sorted(s * 1e3 for s in d.lag_samples)
                out["replication_lag_p99_ms"] = round(
                    lags_ms[min(len(lags_ms) - 1,
                                int(0.99 * len(lags_ms)))], 1)
            # post-partition catch-up: events accumulate behind a
            # seeded partition, then drain on heal
            rules = [
                faults.inject("rpc.call", mode="drop",
                              match=a.filers[0].grpc_address),
                faults.inject("rpc.call", mode="drop",
                              match=(a.master_grpc, "/LookupVolume")),
            ]
            for i in range(n_part):
                write("backlog", i)
            applied0 = d.applied
            for r in rules:
                faults.remove(r)
            catchup = wait_applied(applied0 + n_part)
            out["replication_catchup_s"] = round(catchup, 2)
            # backlog drain rate = the sustained apply throughput
            out["replication_drain_events_per_s"] = round(
                n_part / catchup, 1) if catchup > 0 else 0.0
            out["replication_chunks_deduped"] = \
                d.sink.stats["chunks_deduped"]
        finally:
            # the fault plane is process-global: a failure mid-partition
            # must not leave drop rules armed for the NEXT bench
            faults.clear()
            d.stop()
            a.stop()
            b.stop()
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


def bench_largefile(quick: bool = False) -> dict:
    """Large-object streaming extras (ISSUE 15): streamed PUT and GET
    MB/s + p99 on a multi-chunk object (64MB full / 16MB quick), a
    4-stream concurrent GET sweep, a readahead on/off A/B under
    injected chunk-fetch latency (the latency readahead exists to
    hide — an unloaded loopback fetch is too fast to show the
    pipelining), and the bytes a mid-object 1MB Range read moves off
    the volume servers (must be < 2 chunks: sub-chunk edges ride the
    ranged 'G'-frame path)."""
    import http.client
    import threading as _threading

    from seaweedfs_tpu.testing import PatternBody, SimCluster
    from seaweedfs_tpu.util import faults

    chunk = (2 if quick else 8) << 20
    size = (16 if quick else 64) << 20
    n_get = 3 if quick else 5
    out: dict = {"largefile_object_mb": size >> 20,
                 "largefile_chunk_mb": chunk >> 20}

    def stream_put(addr, path, total, seed):
        host, port = addr.rsplit(":", 1)
        conn = http.client.HTTPConnection(host, int(port), timeout=120)
        t0 = time.perf_counter()
        conn.request("POST", path, body=PatternBody(total, seed),
                     headers={"Content-Length": str(total)})
        r = conn.getresponse()
        r.read()
        conn.close()
        assert r.status == 201, r.status
        return time.perf_counter() - t0

    def stream_get(addr, path, headers=None):
        host, port = addr.rsplit(":", 1)
        conn = http.client.HTTPConnection(host, int(port), timeout=120)
        t0 = time.perf_counter()
        conn.request("GET", path, headers=headers or {})
        r = conn.getresponse()
        n = 0
        while True:
            piece = r.read(1 << 20)
            if not piece:
                break
            n += len(piece)
        conn.close()
        return time.perf_counter() - t0, n, r.status

    with SimCluster(volume_servers=2, filers=1, max_volumes=60,
                    filer_chunk_size=chunk, seed=81) as c:
        addr = c.filers[0].address
        # streamed PUT MB/s (each run writes a fresh object)
        put_s = [stream_put(addr, f"/bench/large{i}.bin", size, i)
                 for i in range(2 if quick else 3)]
        mbs, mbs_spread = spread(
            [size / 1e6 / s for s in put_s], digits=1)
        out["largefile_put_mb_s"] = mbs
        out["largefile_put_mb_s_spread"] = mbs_spread

        # single-stream GET MB/s + p99 across repeats
        gets = [stream_get(addr, "/bench/large0.bin")
                for _ in range(n_get)]
        assert all(n == size and st == 200 for _, n, st in gets)
        gmbs, gmbs_spread = spread(
            [size / 1e6 / t for t, _, _ in gets], digits=1)
        out["largefile_get_mb_s"] = gmbs
        out["largefile_get_mb_s_spread"] = gmbs_spread
        lats = sorted(t * 1e3 for t, _, _ in gets)
        out["largefile_get_p99_ms"] = round(
            lats[min(len(lats) - 1, int(0.99 * len(lats)))], 1)

        # 4 concurrent streams: aggregate MB/s + slowest-stream p99
        times = [0.0] * 4

        def worker(i):
            t, n, st = stream_get(addr, "/bench/large0.bin")
            assert n == size and st == 200
            times[i] = t

        t0 = time.perf_counter()
        threads = [_threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        out["largefile_get_4stream_mb_s"] = round(
            4 * size / 1e6 / wall, 1)
        out["largefile_get_4stream_p99_ms"] = round(
            max(times) * 1e3, 1)

        # readahead A/B under injected chunk-fetch latency: a FRESH
        # (cold-cache) object per read, same fault schedule, only
        # WEED_READAHEAD_CHUNKS differs — the pipelined reader must
        # hide the per-chunk stall the fault injects
        runs = 2 if quick else 3
        for i in range(2 * runs):
            stream_put(addr, f"/bench/ab{i}.bin", size, 100 + i)
        rules = [c.inject_disk_fault(i, op="pread", mode="latency",
                                     latency=0.03)
                 for i in range(2)]
        saved = os.environ.get("WEED_READAHEAD_CHUNKS")
        try:
            on_s, off_s = [], []
            for i in range(runs):
                os.environ["WEED_READAHEAD_CHUNKS"] = "0"
                off_s.append(
                    stream_get(addr, f"/bench/ab{2 * i}.bin")[0])
                os.environ["WEED_READAHEAD_CHUNKS"] = "3"
                on_s.append(
                    stream_get(addr, f"/bench/ab{2 * i + 1}.bin")[0])
        finally:
            if saved is None:
                os.environ.pop("WEED_READAHEAD_CHUNKS", None)
            else:
                os.environ["WEED_READAHEAD_CHUNKS"] = saved
            faults.clear()
            assert rules
        out["largefile_readahead_on_s"] = round(
            float(np.median(on_s)), 3)
        out["largefile_readahead_off_s"] = round(
            float(np.median(off_s)), 3)
        out["largefile_readahead_speedup"] = round(
            float(np.median(off_s)) / max(1e-9,
                                          float(np.median(on_s))), 2)

        # mid-object 1MB Range: bytes moved off the volume servers
        # (fresh object so the filer chunk cache is cold)
        stream_put(addr, "/bench/ranged.bin", size, 9)
        reader = c.filers[0]._chunk_reader
        before = (reader.stats["chunk_bytes"],
                  reader.stats["range_bytes"])
        lo = size // 2 + 12345
        t, n, st = stream_get(
            addr, "/bench/ranged.bin",
            headers={"Range": f"bytes={lo}-{lo + (1 << 20) - 1}"})
        assert st == 206 and n == 1 << 20, (st, n)
        moved = (reader.stats["chunk_bytes"] - before[0]) \
            + (reader.stats["range_bytes"] - before[1])
        out["largefile_range_1mb_bytes_moved"] = moved
        out["largefile_range_1mb_vs_2chunks"] = round(
            moved / (2 * chunk), 3)
    return out



def bench_weedlint(quick: bool = False) -> dict:
    """Static-analysis wall clock (ISSUE 17): a full cold weedlint run
    over the package (parallel parse, all checkers + the project-wide
    call-graph phase) and a warm re-run against the mtime cache.  The
    warm number is what `tools/check.sh` pays on an unchanged tree."""
    import shutil
    import subprocess
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    cache = tempfile.mkdtemp(prefix="weedlint-bench-")
    cmd = [sys.executable, "-m", "tools.weedlint", "seaweedfs_tpu",
           "--cache-dir", cache]
    try:
        t0 = time.perf_counter()
        r = subprocess.run(cmd, cwd=here, capture_output=True, timeout=600)
        cold = time.perf_counter() - t0
        if r.returncode not in (0, 1):
            return {"weedlint_error":
                    r.stderr.decode(errors="replace")[:200]}
        t0 = time.perf_counter()
        subprocess.run(cmd, cwd=here, capture_output=True, timeout=600)
        warm = time.perf_counter() - t0
        return {"weedlint_run_s": round(cold, 3),
                "weedlint_cached_run_s": round(warm, 3)}
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def bench_control_plane(quick: bool = False) -> dict:
    """Control-plane fast path (ISSUE 20): the three master hot paths
    measured the way the scale sim exercises them, with the paired
    delta-vs-full heartbeat A/B the acceptance bar asks for.

    - heartbeat_ingest_ms_per_node + bytes/pulse: N registered sim
      nodes pulse one real master through the production stream
      handler; rounds alternate delta-encoded vs full-snapshot wires
      (the WEED_HB_DELTA=0 shape) so the per-pair ratio cancels this
      box's run-to-run drift.  Payload build + encode happen OUTSIDE
      the timed region — the number is wire decode + master ingest,
      the master-side cost the delta path exists to cut.
    - assigns_per_s: sustained Assign RPCs over real gRPC against the
      incrementally maintained writable set.
    - lookup_p99_ms: resolving 8 vids per op — one batched
      LookupVolume RPC (master answers from the location cache) vs the
      per-vid RPC storm it replaced (8 round trips).
    """
    import random

    from seaweedfs_tpu.pb.rpc import POOL, _de, _ser
    from seaweedfs_tpu.testing import SimCluster
    from seaweedfs_tpu.testing.scale_sim import (RP_STR, SimNode,
                                                 volume_dict)
    from seaweedfs_tpu.wdclient import MasterClient

    n_nodes = 60 if quick else 1000
    vols_per_node = 8 if quick else 20
    hb_pairs = 2 if quick else 3           # (delta, full) round pairs
    assign_rounds, assigns_per_round = (2, 200) if quick else (3, 500)
    lookup_rounds, lookups_per_round = (2, 120) if quick else (3, 300)
    rng = random.Random(13)
    out: dict = {"cp_nodes": n_nodes,
                 "cp_volumes_per_node": vols_per_node}

    with SimCluster(masters=1, volume_servers=0, jwt_key="",
                    repair_interval=0.0,
                    history_interval=0.0) as cluster:
        master = cluster.masters[0]
        nodes, vids = [], []
        vid = 0
        for i in range(n_nodes):
            nodes.append(SimNode(i, 0, rack=f"rack-{i // 2 % 8}",
                                 max_file_key=0,
                                 max_volumes=4 * vols_per_node))
        # node pairs share rp-001 volumes so Assign has a writable set
        for i in range(0, n_nodes - 1, 2):
            a, b = nodes[i], nodes[i + 1]
            for _ in range(vols_per_node):
                vid += 1
                a.volumes[vid] = volume_dict(vid)
                b.volumes[vid] = volume_dict(vid)
                vids.append(vid)
        for n in nodes:
            n.pulse(master)             # register: full snapshot

        # paired heartbeat A/B.  Wires are pre-serialized so the timer
        # sees exactly what the master pays per pulse: _de + ingest.
        delta_ms, full_ms, ratios = [], [], []
        for _ in range(hb_pairs):
            for kind in ("delta", "full"):
                if kind == "delta":
                    wires = [_ser(n.enc.encode(n.full_payload()))
                             for n in nodes]
                else:
                    wires = [_ser(n.full_payload()) for n in nodes]
                t0 = time.perf_counter()
                for n, w in zip(nodes, wires):
                    n.stream.pulse(_de(w))
                per_node = (time.perf_counter() - t0) * 1000.0 / n_nodes
                (delta_ms if kind == "delta" else full_ms).append(
                    per_node)
                out[f"heartbeat_bytes_per_pulse_{kind}"] = round(
                    sum(len(w) for w in wires) / n_nodes, 1)
            ratios.append(full_ms[-1] / delta_ms[-1])
        out["heartbeat_ingest_ms_per_node"], \
            out["heartbeat_ingest_ms_per_node_spread"] = \
            spread(delta_ms, digits=4)
        out["heartbeat_ingest_ms_per_node_full"], \
            out["heartbeat_ingest_ms_per_node_full_spread"] = \
            spread(full_ms, digits=4)
        out["heartbeat_ingest_delta_speedup"], \
            out["heartbeat_ingest_delta_speedup_spread"] = \
            spread(ratios, digits=2)
        out["heartbeat_bytes_reduction"] = round(
            out["heartbeat_bytes_per_pulse_full"]
            / out["heartbeat_bytes_per_pulse_delta"], 1)

        # assigns/s over real gRPC against the cached writable set
        client = POOL.client(cluster.master_grpc, "Seaweed")
        client.call("Assign", {"replication": RP_STR})   # warm
        rates = []
        for _ in range(assign_rounds):
            t0 = time.perf_counter()
            for _ in range(assigns_per_round):
                assert client.call("Assign",
                                   {"replication": RP_STR}).get("fid")
            rates.append(assigns_per_round
                         / (time.perf_counter() - t0))
        out["assigns_per_s"], out["assigns_per_s_spread"] = \
            spread(rates, digits=1)

        # lookup p99: 8 vids per op, batched RPC vs per-vid storm.
        # _rpc_lookup (not lookup_batch) so the CLIENT cache cannot
        # answer — the wire + master location-cache path is the subject
        mc = MasterClient(cluster.master_grpc, client_name="cp-bench")
        mc._rpc_lookup(vids[:8])                         # warm
        b_p99s, n_p99s = [], []
        for _ in range(lookup_rounds):
            batched, naive = [], []
            for _ in range(lookups_per_round):
                batch = rng.sample(vids, k=min(8, len(vids)))
                t0 = time.perf_counter()
                got = mc._rpc_lookup(batch)
                batched.append((time.perf_counter() - t0) * 1000.0)
                assert all(got[v] for v in batch)
                t0 = time.perf_counter()
                for v in batch:
                    mc._rpc_lookup([v])
                naive.append((time.perf_counter() - t0) * 1000.0)
            b_p99s.append(float(np.percentile(batched, 99)))
            n_p99s.append(float(np.percentile(naive, 99)))
        out["lookup_p99_ms"], out["lookup_p99_ms_spread"] = \
            spread(b_p99s)
        out["lookup_naive_p99_ms"], out["lookup_naive_p99_ms_spread"] \
            = spread(n_p99s)
        out["lookup_batch_speedup"] = round(
            out["lookup_naive_p99_ms"] / out["lookup_p99_ms"], 2)
        lc = master.metrics.master_loc_cache
        hits, misses = lc.value("hit"), lc.value("miss")
        out["lookup_cache_hit_ratio"] = round(
            hits / max(1.0, hits + misses), 4)
        for n in nodes:
            n.kill()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for smoke")
    ap.add_argument("--volumes", type=int, default=64)
    ap.add_argument("--mib-per-shard", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--block-b", type=int, default=512)
    ap.add_argument("--rebuild", action="store_true",
                    help="measure ONLY ec.rebuild reconstruct throughput "
                         "(4 lost shards); default measures encode as the "
                         "headline and rebuild as an extra metric")
    ap.add_argument("--no-smallfile", action="store_true",
                    help="skip the small-file data-path benchmark")
    args = ap.parse_args()

    from seaweedfs_tpu.util.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_matrix, rs_pallas

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        # every headline number below is a device metric: a CPU run
        # would print XLA-CPU times under TPU names
        raise SystemExit(f"bench.py measures the TPU; JAX found "
                         f"{dev0.platform} ({dev0.device_kind})")

    # the shard-major kernel needs V % 8 == 0; round up (zero volumes
    # encode to zero parity, so padding is benign)
    V = 8 if args.quick else (args.volumes + 7) // 8 * 8
    B = (1 if args.quick else args.mib_per_shard) * (1 << 20)
    k, m = 10, 4
    iters = 3 if args.quick else args.iters

    data = jax.jit(
        lambda key: jax.random.randint(key, (k, V, B), 0, 256,
                                       dtype=jnp.uint8)
    )(jax.random.PRNGKey(0))

    def measure(bits_rows_cols: np.ndarray, d=None, kk: int = k,
                mm: int = m) -> float:
        """Sustained GB/s of shard-shaped input consumed by one bit-matrix
        pass — ONE timing harness for the headline, the rebuild matrix,
        and the wide-stripe geometries (same warmup/async-drain
        methodology for every number reported)."""
        if d is None:
            d = data
        pm = jnp.asarray(rs_pallas.to_plane_major(bits_rows_cols, mm, kk),
                         dtype=jnp.int8)

        @jax.jit
        def probe(x):
            # opaque custom call: the full parity is always
            # materialized, so a one-tile probe suffices for completion
            p = rs_pallas.gf_matmul_bits_pallas_sm(pm, x,
                                                   block_b=args.block_b)
            return p[0, :8, :128].astype(jnp.int32).sum()

        float(probe(d))  # compile + warmup
        t0 = time.perf_counter()
        futs = [probe(d) for _ in range(iters)]
        for f in futs:
            float(f)
        dt = (time.perf_counter() - t0) / iters
        vv, bb = d.shape[1], d.shape[2]
        return vv * kk * bb / 1e9 / dt

    # rebuild: reconstruct 4 lost shards from the 10 survivors — same
    # kernel, a decode matrix instead of the parity matrix (BASELINE's
    # ec.rebuild target).  Input = the 10 surviving shards.
    present = [0, 2, 3, 5, 6, 7, 9, 10, 11, 13]
    lost = [1, 4, 8, 12]
    gen = rs_matrix.generator_matrix(k, m)
    D = rs_matrix.decode_matrix(gen, present, lost)
    dbits = rs_matrix.bit_matrix(np.asarray(D))
    rebuild_bits = np.zeros((8 * m, 8 * k), dtype=dbits.dtype)
    rebuild_bits[:dbits.shape[0]] = dbits

    if args.rebuild:
        gbps = measure(rebuild_bits)
        print(json.dumps({
            "metric": "ec_rebuild_throughput_rs10_4_4lost",
            "value": round(gbps, 2),
            "unit": "GB/s",
            "vs_baseline": round(gbps / AVX2_BASELINE_GBPS, 2),
        }))
        return 0

    gbps = measure(np.asarray(rs_matrix.parity_bit_matrix(k, m)))
    rebuild_gbps = measure(rebuild_bits)

    def measure_geometry(kk: int, mm: int) -> float:
        """Encode throughput for another stripe geometry (the BASELINE
        wide-stripe targets) at a comparable total byte volume."""
        vv = max(8, (V * k // kk) // 8 * 8)
        d = jax.jit(
            lambda key: jax.random.randint(key, (kk, vv, B), 0, 256,
                                           dtype=jnp.uint8)
        )(jax.random.PRNGKey(1))
        bits = np.asarray(rs_matrix.parity_bit_matrix(kk, mm))
        return round(measure(bits, d, kk, mm), 2)

    wide = {}
    if not args.quick:
        wide = {
            "ec_encode_rs16_8_gbps": measure_geometry(16, 8),
            "ec_encode_rs28_4_gbps": measure_geometry(28, 4),
        }

    # MeshCodec through the Pallas kernel on a real-chip 1-device Mesh:
    # the production multi-device picker's path (shard_map + sm kernel +
    # ring xor_psum), which must stay within ~10% of the direct kernel.
    # Measured on fresh data after the headline arrays are dropped so the
    # 5GB batch and this 4GB batch never coexist in HBM.
    mesh_extra: dict = {}
    if not args.quick:
        del data  # free the 5GB headline batch before allocating 4GB
        from jax.sharding import Mesh
        from seaweedfs_tpu.parallel import mesh_codec
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("s", "b"))
        mcodec = mesh_codec.MeshCodec(k, m, mesh=mesh)
        enc = mesh_codec._encode_fn(mesh)
        pb = mcodec._parity_bits
        bt = 400 << 20  # bytes per shard
        md = jax.jit(lambda key: jax.random.randint(
            key, (k, 8, bt // 8), 0, 256, dtype=jnp.uint8))(
                jax.random.PRNGKey(7))

        @jax.jit
        def mprobe(x):
            return enc(pb, x)[0, 0, :128].astype(jnp.int32).sum()

        float(mprobe(md))
        t0 = time.perf_counter()
        futs = [mprobe(md) for _ in range(iters)]
        for f in futs:
            float(f)
        dt = (time.perf_counter() - t0) / iters
        mesh_extra["mesh_1dev_encode_gbps"] = round(md.size / 1e9 / dt, 2)
        del md

    # measured fleet rebuild: >=100 real small EC volumes
    # on disk, 3 shards lost each, rebuilt through the production
    # rebuild_ec_files_batch path ([V, B]-batched codec windows).
    rebuild_batch: dict = {}
    if not args.quick:
        import shutil
        import tempfile

        from seaweedfs_tpu.storage import ec as ec_pkg
        from seaweedfs_tpu.storage.ec.layout import EcGeometry
        geo = EcGeometry(10, 4, large_block_size=1 << 20,
                         small_block_size=64 << 10)
        nvol, vol_bytes = 120, 4 << 20
        tdir = tempfile.mkdtemp(prefix="ecfleet")
        try:
            base_buf = np.random.default_rng(11).integers(
                0, 256, vol_bytes, dtype=np.uint8)
            bases = []
            for vi in range(nvol):
                base = f"{tdir}/{vi}"
                base_buf[:8] = np.frombuffer(
                    vi.to_bytes(8, "little"), dtype=np.uint8)
                with open(base + ".dat", "wb") as fh:
                    fh.write(base_buf.tobytes())
                from seaweedfs_tpu.storage.ec.encoder import write_ec_files
                write_ec_files(base, geo)
                ec_pkg.save_volume_info(
                    base, 3, dat_size=vol_bytes,
                    data_shards=10, parity_shards=4,
                    large_block_size=geo.large_block_size,
                    small_block_size=geo.small_block_size)
                bases.append(base)
            import os as _os
            for base in bases:
                for s in (2, 5, 11):
                    _os.remove(base + ec_pkg.to_ext(s))
            # the production picker's codec (pallas on a one-chip host)
            from seaweedfs_tpu.parallel.mesh_codec import codec_for_devices
            codec = codec_for_devices(10, 4)
            t0 = time.perf_counter()
            out = ec_pkg.rebuild_ec_files_batch(bases, codec=codec)
            dt = time.perf_counter() - t0
            assert all(sorted(v) == [2, 5, 11] for v in out.values())
            rebuild_batch = {
                "ec_rebuild_batch_volumes": nvol,
                "ec_rebuild_batch_total_s": round(dt, 2),
                "ec_rebuild_batch_sec_per_volume": round(dt / nvol, 4),
                "ec_rebuild_batch_codec": codec.backend,
            }
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # clay(10,4) — the MSR regenerating code: encode
    # throughput through the flat-generator bit-plane matmul, and the
    # measured repair-IO advantage on real shard files vs RS(10,4).
    clay_extra: dict = {}
    if not args.quick:
        import shutil
        import tempfile

        from seaweedfs_tpu.storage import ec as ec_pkg
        from seaweedfs_tpu.storage.ec.layout import EcGeometry
        # the PRODUCTION clay encode: the structured layered path
        # (uncouple -> one [m, k0] layer-MDS matmul -> couple,
        # ops/clay_structured.py) jitted end-to-end on device,
        # transposes included — ~213x fewer GF multiplies than
        # round 3's flat [m*alpha, k*alpha] generator (2.54 GB/s)
        import functools as _ft

        from seaweedfs_tpu.ops import clay_structured
        small = 1 << 20          # production small block
        # >=2GB per call, so the fixed per-dispatch cost does not
        # stand in for the kernel
        wps = 205 << 20          # bytes per shard per call
        # the relayout-free tiled path: data generated directly
        # in the digit-tiled 5D layout (production builds it as
        # a free host view; ClayWindowCodec wiring)
        shape5 = clay_structured.tiled_shape(k, m, wps, small)
        cfn = jax.jit(_ft.partial(
            clay_structured.encode_device_tiled, k, m,
            small=small))
        cd = jax.jit(lambda key: jax.random.randint(
            key, shape5, 0, 256,
            dtype=jnp.uint8))(jax.random.PRNGKey(9))

        @jax.jit
        def cprobe(x):
            p = cfn(x)
            return jnp.sum(p[0, 0, :4].astype(jnp.int32))

        # the fused VMEM kernel (uncouple + layer-MDS + couple in
        # one pallas_call, virtual zero rows never streamed) on
        # the same bytes/call — measured back-to-back with the
        # tiled path inside each round so the ratio cancels this
        # box's run-to-run drift (PR 18 paired-median discipline)
        shape4 = clay_structured.fused_shape(k, m, wps, small)
        ffn = jax.jit(_ft.partial(
            clay_structured.encode_device_fused, k, m,
            small=small))
        cd4 = jax.jit(lambda key: jax.random.randint(
            key, shape4, 0, 256,
            dtype=jnp.uint8))(jax.random.PRNGKey(10))

        @jax.jit
        def fprobe(x):
            p = ffn(x)
            return jnp.sum(p[0, 0, :4].astype(jnp.int32))

        float(cprobe(cd))
        float(fprobe(cd4))
        rates, frates, ratios = [], [], []
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [cprobe(cd) for _ in range(5)]
            for f in futs:
                float(f)
            dt = (time.perf_counter() - t0) / 5
            rates.append(cd.size / 1e9 / dt)
            t0 = time.perf_counter()
            futs = [fprobe(cd4) for _ in range(5)]
            for f in futs:
                float(f)
            fdt = (time.perf_counter() - t0) / 5
            frates.append(cd4.size / 1e9 / fdt)
            ratios.append(dt / fdt)
        clay_extra["clay_encode_gbps"], \
            clay_extra["clay_encode_gbps_spread"] = \
            spread(rates, digits=2)
        clay_extra["clay_encode_fused_gbps"], \
            clay_extra["clay_encode_fused_gbps_spread"] = \
            spread(frates, digits=2)
        clay_extra["clay_encode_fused_vs_tiled"], \
            clay_extra["clay_encode_fused_vs_tiled_spread"] = \
            spread(ratios, digits=3)
        del cd, cd4

        # fused single-loss repair: helper planes in, lost node's
        # full grid row out, one VMEM pallas_call per tile.  The
        # rate is the OPERAND rate — bytes of helper planes
        # streamed per second (the repair-IO story measures the
        # same numerator)
        c_code = clay_structured.code(k, m)
        w_a = small // c_code.alpha
        n_win = max(1, (2 << 30) // ((k + m - 1) *
                                     c_code.beta * w_a))
        rfn = jax.jit(_ft.partial(
            clay_structured.repair_device_fused, k, m, 2))
        xd = jax.jit(lambda key: jax.random.randint(
            key, (k + m - 1, n_win, c_code.beta, w_a), 0, 256,
            dtype=jnp.uint8))(jax.random.PRNGKey(12))

        @jax.jit
        def rprobe(x):
            return jnp.sum(rfn(x)[0, 0, :4].astype(jnp.int32))

        float(rprobe(xd))
        rrates = []
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [rprobe(xd) for _ in range(5)]
            for f in futs:
                float(f)
            dt = (time.perf_counter() - t0) / 5
            rrates.append(xd.size / 1e9 / dt)
        clay_extra["clay_repair_fused_gbps"], \
            clay_extra["clay_repair_fused_gbps_spread"] = \
            spread(rrates, digits=2)
        del xd
        # measured repair IO on real shard files (disk path)
        tdir = tempfile.mkdtemp(prefix="claybench")
        try:
            geo = EcGeometry(10, 4, large_block_size=1 << 20,
                             small_block_size=64 << 10,
                             code_kind="clay")
            base = f"{tdir}/1"
            with open(base + ".dat", "wb") as fh:
                fh.write(np.random.default_rng(3).integers(
                    0, 256, 16 << 20, dtype=np.uint8).tobytes())
            from seaweedfs_tpu.storage.ec.encoder import write_ec_files
            write_ec_files(base, geo)
            ec_pkg.save_volume_info(
                base, 3, dat_size=16 << 20, data_shards=10,
                parity_shards=4,
                large_block_size=geo.large_block_size,
                small_block_size=geo.small_block_size,
                code_kind="clay")
            import os as _os
            _os.remove(base + ec_pkg.to_ext(2))
            st: dict = {}
            ec_pkg.rebuild_ec_files(base, stats=st)
            shard = _os.path.getsize(base + ec_pkg.to_ext(0))
            rs_read = 10 * shard
            clay_extra["clay_repair_bytes_read"] = st["bytes_read"]
            clay_extra["clay_repair_io_advantage_vs_rs"] = round(
                rs_read / st["bytes_read"], 2)
            # a 30GB volume's 1-loss repair: GB read clay vs RS
            clay_extra["clay_repair_read_gb_per_30gb_volume"] = round(
                30.0 * st["bytes_read"] / rs_read, 2)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # multi-volume batched encode (encode_ec_files_batch): a 100+-volume
    # clay fleet encoded through grouped [k, V*width] dispatches — the
    # number that shows the per-dispatch fixed cost amortizing across
    # volumes instead of being paid per volume.
    # CPU-safe: the grouping + dispatch plumbing is the same on every
    # executor; the dispatch/volume counter ratio rides along as the
    # amortization factor /metrics exposes.
    batch_encode: dict = {}
    if not args.quick:
        import shutil
        import tempfile

        from seaweedfs_tpu.ops.codec import codec_metrics
        from seaweedfs_tpu.storage import ec as ec_pkg
        from seaweedfs_tpu.storage.ec.layout import EcGeometry
        geo = EcGeometry(10, 4, large_block_size=1 << 20,
                         small_block_size=64 << 10, code_kind="clay")
        nvol, vol_bytes = 100, geo.small_row_size()
        tdir = tempfile.mkdtemp(prefix="ecbatchenc")
        try:
            buf = np.random.default_rng(17).integers(
                0, 256, vol_bytes, dtype=np.uint8)
            bases = []
            for vi in range(nvol):
                base = f"{tdir}/{vi}"
                buf[:8] = np.frombuffer(
                    vi.to_bytes(8, "little"), dtype=np.uint8)
                with open(base + ".dat", "wb") as fh:
                    fh.write(buf.tobytes())
                bases.append(base)
            mets = codec_metrics()
            d0 = mets.dispatch.value("clay", "encode")
            v0 = mets.dispatch_volumes.value("clay", "encode")
            t0 = time.perf_counter()
            ec_pkg.encode_ec_files_batch(bases, geo)
            dt = time.perf_counter() - t0
            disp = mets.dispatch.value("clay", "encode") - d0
            vols = mets.dispatch_volumes.value("clay", "encode") - v0
            batch_encode = {
                "clay_batch_encode_volumes": nvol,
                "clay_batch_encode_total_s": round(dt, 2),
                "clay_batch_encode_sec_per_volume": round(dt / nvol,
                                                          4),
                "clay_batch_encode_dispatches": int(disp),
                "clay_batch_encode_volumes_per_dispatch": round(
                    vols / disp, 1) if disp else 0.0,
            }
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # small-file data path (reference README.md:528-575 `weed benchmark`:
    # 15,708 writes/s / 47,019 reads/s, 1KB, c=16, on a 4-core i7 with a
    # separate client process).  Here EVERYTHING — client workers, master,
    # two volume servers — shares this host's cores; writes ride the
    # raw-TCP fast path with batched assigns, reads the pipelined frames.
    smallfile: dict = {}
    if not args.no_smallfile:
        try:
            from seaweedfs_tpu.command.benchmark import run_benchmark
            from seaweedfs_tpu.testing import SimCluster
            n = 2000 if args.quick else 30000
            # concurrency: 4 per core — the reference's own ratio (c=16
            # on a 4-core i7).  On this 1-core box 16 threads just thrash
            # the GIL (~40% off the c=4 number, measured in BENCH_NOTES).
            import os as _os
            conc = min(16, 4 * (_os.cpu_count() or 1))
            runs = []
            for _ in range(1 if args.quick else 3):
                # median-of-3 with spread recorded: the box's sustained
                # rates swing +-30% run to run
                with SimCluster(volume_servers=2,
                                max_volumes=60) as cluster:
                    runs.append(run_benchmark(
                        cluster.master_grpc, n_files=n, file_size=1024,
                        concurrency=conc, quiet=True))
            w_med, w_spread = spread(
                [r["write"]["req_per_sec"] for r in runs], digits=1)
            r_med, r_spread = spread(
                [r["read"]["req_per_sec"] for r in runs], digits=1)
            # p99 with spread across ALL runs (ISSUE 4: latency tails
            # are as volatile as throughput on this shared box)
            wp99_med, wp99_spread = spread(
                [r["write"].get("p99_ms") or 0.0 for r in runs])
            rp99_med, rp99_spread = spread(
                [r["read"].get("p99_ms") or 0.0 for r in runs])
            smallfile = {
                "smallfile_write_rps": w_med,
                "smallfile_write_rps_spread": w_spread,
                "smallfile_write_p99_ms": wp99_med,
                "smallfile_write_p99_ms_spread": wp99_spread,
                "smallfile_read_rps": r_med,
                "smallfile_read_rps_spread": r_spread,
                "smallfile_read_p99_ms": rp99_med,
                "smallfile_read_p99_ms_spread": rp99_spread,
                "smallfile_ref_write_rps": 15708,
                "smallfile_ref_read_rps": 47019,
            }
            try:
                # a flaked hotset extra must not discard the headline
                # smallfile numbers measured above
                smallfile.update(bench_hotset_reread(
                    conc, quick=args.quick))
            except Exception as e:
                smallfile["smallfile_hotset_error"] = str(e)[:200]
            try:
                smallfile.update(bench_replicated_write(
                    conc, quick=args.quick))
            except Exception as e:
                smallfile["replicated_write_error"] = str(e)[:200]
            try:
                smallfile.update(bench_degraded_read(
                    conc, quick=args.quick))
            except Exception as e:
                smallfile["degraded_read_error"] = str(e)[:200]
            try:
                smallfile.update(bench_self_healing(quick=args.quick))
            except Exception as e:
                smallfile["self_healing_error"] = str(e)[:200]
            try:
                smallfile.update(bench_s3_authz(quick=args.quick))
            except Exception as e:
                smallfile["s3_authz_error"] = str(e)[:200]
            try:
                smallfile.update(bench_observability(quick=args.quick))
            except Exception as e:
                smallfile["observability_error"] = str(e)[:200]
            try:
                smallfile.update(bench_heat(quick=args.quick))
            except Exception as e:
                smallfile["heat_error"] = str(e)[:200]
            try:
                smallfile.update(bench_replication(quick=args.quick))
            except Exception as e:
                smallfile["replication_error"] = str(e)[:200]
            try:
                smallfile.update(bench_worker_scaling(quick=args.quick))
            except Exception as e:
                smallfile["worker_scaling_error"] = str(e)[:200]
            try:
                smallfile.update(bench_http_native_loop(quick=args.quick))
            except Exception as e:
                smallfile["http_native_error"] = str(e)[:200]
            try:
                smallfile.update(bench_largefile(quick=args.quick))
            except Exception as e:
                smallfile["largefile_error"] = str(e)[:200]
            try:
                smallfile.update(bench_control_plane(quick=args.quick))
            except Exception as e:
                smallfile["control_plane_error"] = str(e)[:200]
            try:
                smallfile.update(bench_weedlint(quick=args.quick))
            except Exception as e:
                smallfile["weedlint_error"] = str(e)[:200]
        except Exception as e:   # never fail the headline metric
            smallfile = {"smallfile_error": str(e)[:200]}
    # end-to-end disk path
    disk_extra = bench_disk_path(args.quick)

    # rack-rebuild estimate (BASELINE's ec.rebuild scenario: 1000 x 30GB
    # volumes), derived from MEASURED end-to-end numbers, not the
    # device-resident rate: per-volume time = fixed cost (from the
    # 120-volume fleet run, minus its own streaming time) + 30GB through
    # the measured file->decode->file rate.  The device-resident rate is
    # reported separately as the compute bound it is.
    rack_extra: dict = {}
    stream_rate = disk_extra.get("ec_rebuild_stream_gbps") or \
        disk_extra.get("ec_rebuild_disk_gbps")
    per_vol = rebuild_batch.get("ec_rebuild_batch_sec_per_volume")
    if stream_rate and per_vol:
        fleet_vol_gb = (4 << 20) / 1e9
        fixed = max(0.0, per_vol - fleet_vol_gb / stream_rate)
        rack_extra = {
            "ec_rebuild_fixed_sec_per_volume": round(fixed, 4),
            "ec_rebuild_1000x30GB_disk_est_seconds":
                round(1000 * (fixed + 30.0 / stream_rate), 1),
        }
    rack_survivor_bytes = 1000 * 30e9
    print(json.dumps({
        "metric": "ec_encode_throughput_rs10_4",
        "value": round(gbps, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbps / AVX2_BASELINE_GBPS, 2),
        "extra": {
            "ec_rebuild_throughput_rs10_4_4lost_gbps": round(rebuild_gbps, 2),
            "ec_rebuild_1000x30GB_device_bound_seconds":
                round(rack_survivor_bytes / 1e9 / rebuild_gbps, 1),
            **wide,
            **mesh_extra,
            **rebuild_batch,
            **clay_extra,
            **batch_encode,
            **smallfile,
            **disk_extra,
            **rack_extra,
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
