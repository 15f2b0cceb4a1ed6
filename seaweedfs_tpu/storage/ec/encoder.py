"""EC encode/rebuild: volume files -> shard files, batched through the TPU.

Capability-equivalent to weed/storage/erasure_coding/ec_encoder.go
(WriteEcFiles:57, RebuildEcFiles:61, WriteSortedFileFromIdx:27) but
re-architected for the TPU:

- The reference streams 10x256KB buffers through a SIMD encoder one batch at
  a time (encodeDataOneBatch ec_encoder.go:162).  Here each read covers a
  whole *row batch*: one contiguous [k * block] slice of .dat reshapes —
  zero-copy — to the [k, block] stripe matrix, several stripes stack into a
  [k, B] batch, and ONE codec call (XLA/Pallas bit-plane matmul) produces all
  parity for the batch.  Data shards are pure memory views of the read
  buffer; only parity costs compute.
- Rebuild reads all surviving shards' aligned windows into a [n_have, B]
  batch and reconstructs every missing shard in one codec call per window.

One deliberate divergence: the reference encodes a .dat whose size is an
exact multiple of the large row as small blocks (`>` at ec_encoder.go:215)
but *decodes* it as large blocks (`>=` at ec_decoder.go:175) — an
inconsistent edge.  We use `>=` on both sides so every size round-trips.
"""

from __future__ import annotations

import os
import queue as _queue
import threading

import numpy as np

from ...ops import rs_matrix
from ...ops.codec import RSCodec, codec_stage, metrics_backend
from ...util import tracing
from ..idx import index_array_to_bytes, parse_index_bytes
from ..types import TOMBSTONE_FILE_SIZE
from .layout import DEFAULT_GEOMETRY, EcGeometry, to_ext

# Per-shard bytes fed to one codec call.  8 MB x 10 shards = 80 MB reads —
# large enough to saturate the MXU and amortize host->device transfer,
# small enough to double-buffer in HBM.
DEFAULT_BATCH_BYTES = 8 * 1024 * 1024

# Batches in flight between the reading/submitting producer and the
# shard-file writer thread.  2 = classic double buffering: while the device
# encodes batch N and the writer drains N-1, the producer reads N+1 from
# disk.  More depth buys nothing once the slowest stage is saturated and
# costs host RAM (depth * k * batch_bytes pinned).
PIPELINE_DEPTH = 2


def _begin_encode(codec, data: np.ndarray, volumes: int = 1):
    """codec.encode_begin when the codec has one (RSCodec/MeshCodec issue
    the device work and defer the blocking fetch); eager fallback keeps
    custom/window codecs on the same contract.

    `volumes` tells metrics how many volumes this one dispatch carries
    (encode_ec_files_batch's amortization).  It is forwarded only to
    codecs whose encode_begin declares it — the window codecs take it as
    a kwarg; RSCodec infers it from the leading batch axes; external
    custom codecs never see it."""
    begin = getattr(codec, "encode_begin", None)
    if begin is not None:
        if volumes != 1:
            import inspect
            try:
                params = inspect.signature(begin).parameters
            except (TypeError, ValueError):
                params = {}
            if "volumes" in params:
                return begin(data, volumes=volumes)
        return begin(data)
    parity = codec.encode(data)
    return lambda: parity


def _pipeline_depth(codec) -> int:
    """Read-ahead depth for the disk loops.

    Worth paying for when the codec dispatches to a device (the fetch wait
    and h2d/d2h transfers overlap disk IO) or the host has cores to spare.
    On a single-core host with a CPU codec every stage is the same core's
    CPU time, and the producer/writer GIL ping-pong measurably LOSES
    throughput (~2x on the 2GB stream bench) — run inline instead."""
    from ...ops.codec import device_compute_ok
    backend = getattr(codec, "backend", "")
    device_backed = backend in ("pallas", "jax", "mesh") or (
        backend in ("clay", "lrc") and device_compute_ok())
    if device_backed or (os.cpu_count() or 1) > 1:
        return PIPELINE_DEPTH
    return 0


def _begin_reconstruct(codec, shards):
    begin = getattr(codec, "reconstruct_begin", None)
    if begin is not None:
        return begin(shards)
    out = codec.reconstruct(shards)
    return lambda: out


def _pipelined(produce, consume, depth: int = PIPELINE_DEPTH) -> None:
    """Run `produce` (a generator issuing async device work per item) against
    `consume(item)` on a writer thread, `depth` items in flight.

    The producer runs on the calling thread: it reads the next window from
    disk and submits its codec call while the device chews the previous one
    and the writer blocks in fetch()/file-writes — the overlap the
    reference gets from its goroutine pipelines (ec_encoder.go's batch loop
    is synchronous; SURVEY §7(b) flags the overlap as the hard part).  A
    bounded queue keeps at most `depth` batches of host buffers alive, and
    writes happen in submission order (single consumer, FIFO queue), which
    append-only shard files require.

    depth <= 0 runs inline with no writer thread (see _pipeline_depth)."""
    if depth <= 0:
        for item in produce:
            consume(item)
        return
    q: _queue.Queue = _queue.Queue(maxsize=depth)
    errs: list[BaseException] = []

    def writer():
        while True:
            item = q.get()
            if item is None:
                return
            if not errs:
                try:
                    consume(item)
                except BaseException as e:  # surfaced to the caller below
                    errs.append(e)
            # after an error keep draining so the producer never deadlocks
            # on a full queue

    # the writer's fetch waits and file writes belong to the caller's
    # span (the VolumeEcShardsGenerate RPC's stage tags)
    t = threading.Thread(target=tracing.propagate(writer),
                         name="ec-writer")
    t.start()
    try:
        for item in produce:
            if errs:
                break
            q.put(item)
    finally:
        q.put(None)
        t.join()
    if errs:
        raise errs[0]


def _codec_for(geo: EcGeometry, codec: RSCodec | None):
    if codec is not None:
        if (codec.k, codec.m) != (geo.data_shards, geo.parity_shards):
            raise ValueError("codec geometry does not match EC geometry")
        return codec
    if geo.code_kind != "rs":
        # clay / lrc: the flat-matrix window codecs (codes.py) — same
        # shard files, different parity math
        from .codes import window_codec_for
        return window_codec_for(geo)
    # production picker: the multi-chip MeshCodec whenever this process has
    # a device mesh (so ec.encode/ec.rebuild verbs and the
    # VolumeEcShardsGenerate/Rebuild RPCs ride it), single-chip RSCodec
    # otherwise — same math, byte-identical shards either way.
    from ...parallel.mesh_codec import codec_for_devices
    return codec_for_devices(geo.data_shards, geo.parity_shards)


class _BufferPool:
    """Cycled preallocated [k, batch] gather buffers.

    Fresh 80MB numpy allocations per batch mean mmap + first-touch page
    faults + munmap every iteration — measurably dominant on this host
    class.  The pipeline holds at most PIPELINE_DEPTH queued batches plus
    one in the writer and one being produced, so `depth + 2` cycled
    buffers are never overwritten while still in flight."""

    def __init__(self, n: int, shape: tuple):
        self._bufs = [np.empty(shape, dtype=np.uint8) for _ in range(n)]
        self._i = 0

    def next(self) -> np.ndarray:
        buf = self._bufs[self._i]
        self._i = (self._i + 1) % len(self._bufs)
        return buf


def _iter_encode_batches(dat, dat_size: int, geo: EcGeometry,
                         batch_bytes: int):
    """Yield the [k, width] data matrices write_ec_files encodes, in shard
    append order: large rows first (column slices gathered across the k
    1GB blocks), then batched small rows, zero-padding the final partial
    row exactly like encodeDataOneBatch (ec_encoder.go:173).

    Yielded arrays are views into a cycled buffer pool: each is gathered
    from .dat in ONE copy pass and stays valid until PIPELINE_DEPTH + 1
    further batches have been yielded."""
    k = geo.data_shards
    pos = 0
    remaining = dat_size
    large_row = geo.large_row_size()
    # small-row batches are at least one whole block wide even when
    # batch_bytes is smaller (n_rows floors at 1)
    pool = _BufferPool(PIPELINE_DEPTH + 2,
                       (k, max(batch_bytes, geo.small_block_size)))
    while remaining >= large_row:
        # one large row = k x 1GB; stream it in batch_bytes column slices
        for col in range(0, geo.large_block_size, batch_bytes):
            width = min(batch_bytes, geo.large_block_size - col)
            # a column slice of a large row is NOT contiguous in .dat;
            # gather the k slices into a [k, width] matrix
            data = pool.next()[:, :width]
            for s in range(k):
                off = pos + s * geo.large_block_size + col
                data[s] = dat[off:off + width]
            yield data
        pos += large_row
        remaining -= large_row
    small_row = geo.small_row_size()
    rows_per_batch = max(1, batch_bytes // geo.small_block_size)
    block = geo.small_block_size
    while remaining > 0:
        n_rows = min(rows_per_batch,
                     (remaining + small_row - 1) // small_row)
        width = n_rows * block
        data = pool.next()[:, :width]
        # gather [k, n_rows*block] directly: shard s of row r sits at
        # .dat offset pos + r*small_row + s*block (one slice copy each,
        # no intermediate zeros + transpose materialization)
        for r in range(n_rows):
            row_off = pos + r * small_row
            for s in range(k):
                o = row_off + s * block
                dst = data[s, r * block:(r + 1) * block]
                n = min(block, max(0, dat_size - o))
                if n > 0:
                    dst[:n] = dat[o:o + n]
                if n < block:
                    dst[n:] = 0    # zero-pad the final partial row
        yield data
        pos += n_rows * small_row
        remaining -= min(remaining, n_rows * small_row)


def write_ec_files(base_path: str, geo: EcGeometry = DEFAULT_GEOMETRY,
                   codec: RSCodec | None = None,
                   batch_bytes: int = DEFAULT_BATCH_BYTES) -> None:
    """<base>.dat -> <base>.ec00 .. (WriteEcFiles ec_encoder.go:57).

    Pipelined: the calling thread reads batch N+1 from .dat and submits its
    encode while the device computes batch N and a writer thread appends
    batch N-1's shards — disk in, TPU, disk out all busy at once (the
    reference's encodeDatFile loop is strictly serial, ec_encoder.go:162).
    The codec registry times each stage under the codec's backend label:
    `gather` (the .dat copy into the batch buffer), the codec call's own
    pack/wait/unpack, and `write` (the shard files)."""
    codec = _codec_for(geo, codec)
    backend = metrics_backend(codec)
    dat_size = os.path.getsize(base_path + ".dat")
    dat = np.memmap(base_path + ".dat", dtype=np.uint8, mode="r") \
        if dat_size else np.zeros(0, dtype=np.uint8)
    outputs = [open(base_path + to_ext(i), "wb")
               for i in range(geo.total_shards)]
    k = geo.data_shards

    def produce():
        batches = _iter_encode_batches(dat, dat_size, geo, batch_bytes)
        while True:
            with codec_stage("gather", backend, "encode"):
                data = next(batches, None)
            if data is None:
                return
            yield data, _begin_encode(codec, data)

    def consume(item):
        data, fetch = item
        with codec_stage("write", backend, "encode"):
            for s in range(k):
                outputs[s].write(data[s])
        parity = fetch()
        with codec_stage("write", backend, "encode"):
            for p in range(geo.parity_shards):
                outputs[k + p].write(parity[p])

    try:
        _pipelined(produce(), consume, _pipeline_depth(codec))
    finally:
        for f in outputs:
            f.close()


def encode_ec_files_batch(base_paths: list[str],
                          geo: EcGeometry = DEFAULT_GEOMETRY,
                          codec: RSCodec | None = None,
                          batch_bytes: int = DEFAULT_BATCH_BYTES) -> None:
    """Fleet encode: <base>.dat -> shard files for MANY volumes with
    batched codec dispatches (the encode-side mirror of
    rebuild_ec_files_batch).

    A tier-seal or rack-migration encodes hundreds of volumes; looping
    write_ec_files pays the per-dispatch fixed cost (h2d setup + kernel
    launch) once per volume per batch.
    Stripe columns are independent, so volumes that share a shard-file
    size (ergo the same batch width sequence — the grouping key
    rebuild_ec_files_batch uses) fold into ONE codec call per window:
    RS stacks [V, k, width] onto the codec's leading batch axes, the
    clay/LRC window codecs fold onto the byte axis [k, V*width] (their
    transforms are window-local, so concatenated volumes encode
    independently and bit-identically).  Amortization is visible at
    /metrics as seaweedfs_codec_dispatch_volumes_total /
    seaweedfs_codec_dispatch_total.  Odd-sized volumes degrade to the
    per-volume path.  Shard bytes are identical to write_ec_files."""
    groups: dict[int, list[str]] = {}
    for base in base_paths:
        dat_size = os.path.getsize(base + ".dat")
        groups.setdefault(geo.shard_file_size(dat_size), []).append(base)
    for _, bases in sorted(groups.items()):
        if len(bases) == 1:
            write_ec_files(bases[0], geo, codec, batch_bytes)
            continue
        _encode_group(bases, geo, codec, batch_bytes)


def _encode_group(bases: list[str], geo: EcGeometry,
                  codec: RSCodec | None, batch_bytes: int) -> None:
    """One same-shard-size group of encode_ec_files_batch: V volumes'
    batch iterators advance in lockstep (equal shard size => provably
    equal width sequences) and every window is one grouped dispatch."""
    import itertools

    codec = _codec_for(geo, codec)
    k, m, v = geo.data_shards, geo.parity_shards, len(bases)
    small = geo.small_block_size
    # per-volume batch width shrinks with group size so the grouped
    # dispatch stays near batch_bytes of host copies total; floored to
    # one small block (width sequences must stay block-aligned)
    vol_batch = max(small, batch_bytes // v // small * small)
    rs = geo.code_kind == "rs"
    dats = []
    for b in bases:
        size = os.path.getsize(b + ".dat")
        dats.append((np.memmap(b + ".dat", dtype=np.uint8, mode="r")
                     if size else np.zeros(0, dtype=np.uint8), size))
    outputs = [[open(b + to_ext(i), "wb")
                for i in range(geo.total_shards)] for b in bases]
    sentinel = object()

    def produce():
        iters = [_iter_encode_batches(dat, size, geo, vol_batch)
                 for dat, size in dats]
        for parts in itertools.zip_longest(*iters, fillvalue=sentinel):
            # misalignment here would interleave volumes' bytes into the
            # wrong shards — corruption, not a perf bug — so assert, do
            # not truncate (a plain zip would silently drop the tail)
            assert not any(p is sentinel for p in parts), \
                "same-shard-size volumes must batch in lockstep"
            assert len({p.shape[1] for p in parts}) == 1, \
                [p.shape for p in parts]
            # stack/concatenate COPIES out of the per-volume cycled
            # pools, so the yielded batch stays valid in the pipeline
            data = np.stack(parts) if rs \
                else np.concatenate(parts, axis=1)
            yield data, _begin_encode(codec, data, volumes=v)

    def consume(item):
        data, fetch = item
        width = data.shape[-1] if rs else data.shape[-1] // v
        for vi in range(v):
            dpart = data[vi] if rs \
                else data[:, vi * width:(vi + 1) * width]
            for s in range(k):
                outputs[vi][s].write(dpart[s])
        parity = fetch()
        for vi in range(v):
            ppart = parity[vi] if rs \
                else parity[:, vi * width:(vi + 1) * width]
            for p in range(m):
                outputs[vi][k + p].write(ppart[p])

    try:
        _pipelined(produce(), consume, _pipeline_depth(codec))
    finally:
        for files in outputs:
            for f in files:
                f.close()


def rebuild_ec_files(base_path: str, geo: "EcGeometry | None" = None,
                     codec: RSCodec | None = None,
                     batch_bytes: int = DEFAULT_BATCH_BYTES,
                     stats: "dict | None" = None,
                     shard_ids: "list[int] | None" = None) -> list[int]:
    """Regenerate shards from the surviving local ones (RebuildEcFiles
    ec_encoder.go:61/233): `shard_ids`, or every missing .ecNN when not
    given.  Returns the rebuilt shard ids.

    The read set comes from the planner the shell's ec.rebuild copies by
    (plan.repair_plan).  RS and LRC share one pipelined loop: each batch
    of the read shards is gathered into a buffer, the plan's matrix is
    issued on the executor (RSCodec.apply_begin: the shard-major Pallas
    kernel on TPU, the native codec on CPU) and the writer thread appends
    the regenerated rows, with the gather, pack, wait, unpack and write
    stages timed under the code's backend label and op "reconstruct".

    A single clay loss also counts as present a helper of which only the
    repair planes for that loss were copied here (codes.plane_file, the
    shell's ec.rebuild copies remote helpers so); rebuild_clay reads and
    then removes those files.

    `stats`, when given, is filled with the rebuild's read accounting
    ({"bytes_read", "plan_kind", "read_shards", "executor"}; clay adds
    "copy", "planes" or "whole", and "helpers_from_planes") — how the
    clay/LRC repair-IO advantage is measured."""
    from . import geometry_from_vif, load_volume_info
    from .plan import repair_plan
    if geo is None:
        geo = geometry_from_vif(base_path)
    if geo.code_kind == "lrc":
        from .codes import require_construction
        require_construction(
            os.path.basename(base_path),
            load_volume_info(base_path).get("lrc_construction"))
    n = geo.total_shards
    have = [os.path.exists(base_path + to_ext(i)) for i in range(n)]
    if shard_ids is None:
        missing = [i for i in range(n) if not have[i]]
    else:
        missing = sorted({int(s) for s in shard_ids})
    if not missing:
        return []
    available = [i for i in range(n) if have[i]]
    if geo.code_kind == "clay" and codec is None:
        from .codes import plane_file, rebuild_clay
        # a helper whose repair planes for this very loss were copied
        # here is present for the single-loss plane repair, and only
        # for it: a decode reads whole shards
        planes = {}
        if len(missing) == 1:
            planes = {i: plane_file(base_path, i, missing[0])
                      for i in range(n) if not have[i] and i not in missing}
            planes = {i: p for i, p in planes.items() if os.path.exists(p)}
        plan = repair_plan(geo, missing, sorted(set(available) | set(planes)))
        if planes and plan.kind != "clay-plane":
            planes = {}
            plan = repair_plan(geo, missing, available)
        return rebuild_clay(base_path, geo, plan, batch_bytes, stats=stats,
                            planes=planes)
    plan = repair_plan(geo, missing, available)
    codec, backend, begin = _rebuild_executor(geo, codec, plan)
    read = plan.read_shards
    inputs = {i: np.memmap(base_path + to_ext(i), dtype=np.uint8, mode="r")
              for i in read}
    shard_size = len(inputs[read[0]])
    for i, arr in inputs.items():
        if len(arr) != shard_size:
            raise ValueError(f"shard {i} size {len(arr)} != {shard_size}")
    outputs = {i: open(base_path + to_ext(i), "wb") for i in missing}
    pool = _BufferPool(PIPELINE_DEPTH + 2,
                       (len(read), min(batch_bytes, shard_size)))

    def produce():
        for off in range(0, shard_size, batch_bytes):
            width = min(batch_bytes, shard_size - off)
            with codec_stage("gather", backend, "reconstruct"):
                x = pool.next()[:, :width]
                for row, i in enumerate(read):
                    x[row] = inputs[i][off:off + width]
            yield begin(x)

    def consume(fetch):
        rows = fetch()
        with codec_stage("write", backend, "reconstruct"):
            for row, i in enumerate(missing):
                outputs[i].write(rows[row])

    try:
        _pipelined(produce(), consume, _pipeline_depth(codec))
    finally:
        for f in outputs.values():
            f.close()
    if stats is not None:
        stats["bytes_read"] = len(read) * shard_size
        stats["plan_kind"] = plan.kind
        stats["read_shards"] = list(read)
        stats["executor"] = getattr(codec, "backend", "")
    return missing


def _rebuild_executor(geo: EcGeometry, codec, plan):
    """(codec, backend label, begin(x) -> fetch() -> the rows of
    plan.missing) for the rebuild loop, x the plan's read shards.  LRC
    applies the plan's matrix on its RSCodec executor; RS decodes with
    the production codec's own generator.  A codec without apply_begin
    (MeshCodec, or one a caller passes) reconstructs from the plan's
    shards in their slots."""
    read, missing = list(plan.read_shards), list(plan.missing)
    if geo.code_kind == "lrc" and codec is None:
        codec = RSCodec(geo.data_shards, geo.parity_shards)
        label = "lrc"
    else:
        codec = _codec_for(geo, codec)
        label = metrics_backend(codec)
    if hasattr(codec, "apply_begin"):
        M = plan.matrix if plan.matrix is not None \
            else rs_matrix.decode_matrix(codec.gen, read, missing)
        return codec, label, lambda x: codec.apply_begin(
            M, x, "reconstruct", label=label)

    def begin(x):
        shards: list[np.ndarray | None] = [None] * geo.total_shards
        for row, i in enumerate(read):
            shards[i] = x[row]
        fetch = _begin_reconstruct(codec, shards)

        def rows():
            out = fetch()
            return [out[i] for i in missing]
        return rows
    return codec, label, begin


def rebuild_ec_files_batch(base_paths: list[str],
                           batch_bytes: int = DEFAULT_BATCH_BYTES,
                           codec: RSCodec | None = None
                           ) -> dict[str, list[int]]:
    """Fleet rebuild: regenerate missing shards across MANY volumes with
    batched [V, B] codec calls.

    The reference's rack-rebuild loops RebuildEcFiles volume by volume
    (shell/command_ec_rebuild.go:103 per-volume fan-out); stripe columns are
    independent, so volumes sharing (geometry, loss mask, shard size) fold
    onto the codec's byte axis and every window is ONE device round for the
    whole group — the [V, B] path of MeshCodec.reconstruct / RSCodec's
    leading batch axes.  Odd-one-out volumes degrade to the single path.
    Returns {base_path: rebuilt shard ids}.
    """
    groups: dict[tuple, list[str]] = {}
    from . import geometry_from_vif
    for base in base_paths:
        geo = geometry_from_vif(base)
        n = geo.total_shards
        have = tuple(os.path.exists(base + to_ext(i)) for i in range(n))
        if all(have):
            continue
        if sum(have) < geo.data_shards:
            raise ValueError(f"{base}: need >= {geo.data_shards} shards, "
                             f"have {sum(have)}")
        size = os.path.getsize(base + to_ext(
            next(i for i in range(n) if have[i])))
        groups.setdefault((geo, have, size), []).append(base)

    out: dict[str, list[int]] = {b: [] for b in base_paths}
    for (geo, have, shard_size), bases in groups.items():
        if len(bases) == 1 or geo.code_kind != "rs":
            # clay/lrc volumes rebuild per-volume (their own reduced-IO
            # paths in codes.py; the RSCodec [V, B] batching below is
            # RS-specific)
            for b in bases:
                out[b] = rebuild_ec_files(
                    b, geo,
                    codec=codec if geo.code_kind == "rs" else None,
                    batch_bytes=batch_bytes)
            continue
        n = geo.total_shards
        missing = [i for i in range(n) if not have[i]]
        group_codec = _codec_for(geo, codec)
        inputs = {b: {i: np.memmap(b + to_ext(i), dtype=np.uint8, mode="r")
                      for i in range(n) if have[i]} for b in bases}
        for b in bases:
            for i, arr in inputs[b].items():
                if len(arr) != shard_size:
                    raise ValueError(
                        f"{b} shard {i}: size {len(arr)} != {shard_size}")
        outputs = {b: {i: open(b + to_ext(i), "wb") for i in missing}
                   for b in bases}
        # keep the stacked group near n_have * batch_bytes of host copies
        # regardless of group size (a 1000-volume group must not multiply
        # the window); the 4KB floor only bounds syscall count
        window = max(4096, batch_bytes // max(1, len(bases)))

        def produce():
            for off in range(0, shard_size, window):
                width = min(window, shard_size - off)
                shards: list[np.ndarray | None] = [
                    np.stack([np.asarray(inputs[b][i][off:off + width])
                              for b in bases]) if have[i] else None
                    for i in range(n)]
                yield _begin_reconstruct(group_codec, shards)

        def consume(fetch):
            rebuilt = fetch()  # missing -> [V, width]
            for i in missing:
                for vi, b in enumerate(bases):
                    outputs[b][i].write(rebuilt[i][vi])

        try:
            _pipelined(produce(), consume, _pipeline_depth(group_codec))
        finally:
            for b in bases:
                for f in outputs[b].values():
                    f.close()
        for b in bases:
            out[b] = list(missing)
    return out


def write_sorted_file_from_idx(base_path: str, ext: str = ".ecx") -> None:
    """<base>.idx -> <base>.ecx: live entries, ascending key order
    (WriteSortedFileFromIdx ec_encoder.go:27-54).

    The reference replays the idx into a tree then walks it; one vectorized
    pass does the same: last write per key wins, drop tombstoned/zero-offset
    keys, sort by key."""
    with open(base_path + ".idx", "rb") as f:
        arr = parse_index_bytes(f.read())
    if len(arr):
        # keep only the LAST entry per key (np.unique keeps the first ->
        # reverse first), then drop deletions
        rev = arr[::-1]
        _, first_idx = np.unique(rev["key"], return_index=True)
        latest = rev[first_idx]  # unique returns sorted keys
        live = latest[(latest["size"] != TOMBSTONE_FILE_SIZE)
                      & (latest["offset"] != 0)]
    else:
        live = arr
    with open(base_path + ext, "wb") as out:
        out.write(index_array_to_bytes(live))
