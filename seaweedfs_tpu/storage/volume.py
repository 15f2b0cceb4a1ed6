"""Volume: one append-only .dat blob file + .idx needle index.

Capability-equivalent to the reference's Volume (weed/storage/volume.go:21-51,
volume_write.go, volume_read.go, volume_checking.go):

- superblock at offset 0 (super_block.py)
- writes append a full needle record; the in-memory map tracks (offset, size)
- deletes append a zero-size needle tombstone to .dat and log TombstoneFileSize
  to .idx (volume_write.go doDeleteRequest)
- duplicate write of identical (id, checksum, size) is skipped
- load verifies idx↔dat consistency and truncates a torn .dat tail
  (volume_checking.go)
- vacuum() = Compact2 + commit: copy live needles to .cpd/.cpx then rename
  (volume_vacuum.go:67-91)

Read-path concurrency: readers take NO lock.  The needle map's get is a
plain dict read (atomic under the GIL; the sqlite kind has its own
internal lock), data reads are positioned `os.pread`-style IO
(storage/backend.py) so concurrent readers never contend on a shared
seek offset, and the (needle map, data backend) pair rides one
`_read_ref` tuple swapped atomically by vacuum — a reader either sees
the old pair or the new pair, never a torn mix.  If vacuum closes the
old backend under a reader mid-pread, the reader retries once under the
volume lock against the fresh pair.

File layout: <dir>/<collection>_<vid>.dat / .idx (or <vid>.dat when the
collection is empty), matching the reference's FileName convention.
"""

from __future__ import annotations

import os
import threading
from ..util import locks
import time
from dataclasses import dataclass

from . import types as t
from ..util.weedlog import logger
from .backend import BackendStorageFile, MemoryMappedFile, open_backend
from .idx import idx_entry_bytes, parse_index_bytes
from .needle import Needle, read_needle_header
from .needle_map import KIND_MEMORY, NeedleMapper, new_needle_map
from .super_block import ReplicaPlacement, SuperBlock
from .ttl import TTL, EMPTY_TTL

LOG = logger(__name__)


class VolumeError(Exception):
    pass


class NotFoundError(VolumeError):
    pass


class CookieMismatchError(VolumeError):
    pass


def volume_file_name(directory: str, collection: str, vid: int) -> str:
    if collection:
        return os.path.join(directory, f"{collection}_{vid}")
    return os.path.join(directory, str(vid))


def parse_volume_base_name(base: str) -> tuple[str, int]:
    """'c_12' -> ('c', 12); '12' -> ('', 12)."""
    if "_" in base:
        collection, vid_s = base.rsplit("_", 1)
    else:
        collection, vid_s = "", base
    return collection, int(vid_s)


@dataclass
class VolumeInfo:
    """Summary reported in heartbeats (pb VolumeInformationMessage)."""
    id: int
    size: int
    collection: str
    file_count: int
    delete_count: int
    deleted_byte_count: int
    read_only: bool
    replica_placement: int
    version: int
    ttl: int
    compact_revision: int
    modified_at_second: int = 0
    degraded_reason: str = ""  # why read_only flipped (IO fault), if so


class Volume:
    def __init__(self, directory: str, collection: str, vid: int,
                 needle_map_kind: str = KIND_MEMORY,
                 replica_placement: ReplicaPlacement | None = None,
                 ttl: TTL = EMPTY_TTL,
                 version: int = t.CURRENT_VERSION,
                 backend_kind: str = "disk",
                 read_only: bool = False):
        self.directory = directory
        self.collection = collection
        self.id = vid
        self.needle_map_kind = needle_map_kind
        self.read_only = read_only
        self.backend_kind = backend_kind
        self._lock = locks.RLock("Volume._lock")
        self.last_modified = 0
        # ns-resolution activity clock: the scrub's authority signal.
        # Seconds (last_modified) tie too easily — a write and the
        # delete that follows it often share a second, and a tie there
        # picks authority by needle count, which resurrects the delete.
        self.last_modified_ns = 0
        # set when a write-path IO error degraded this volume to
        # read-only (ENOSPC, a dying disk); reported via /status and the
        # heartbeat path so the master stops assigning here
        self.degraded_reason = ""
        # notified (vid) after a degrade flip — the volume server hooks
        # this to push an immediate heartbeat (store.set_on_degrade)
        self.on_degrade = None

        base = volume_file_name(directory, collection, vid)
        self.base_path = base
        # a .tier descriptor means the sealed .dat lives on remote storage
        # (storage/tier.py; the reference's s3_backend VolumeInfo files)
        from .tier import open_tiered_backend
        tiered = open_tiered_backend(base)
        if tiered is not None:
            self.data_backend: BackendStorageFile = tiered
            self.read_only = True
            dat_exists = True
        else:
            dat_exists = os.path.exists(base + ".dat")
            self.data_backend = open_backend(backend_kind, base + ".dat")
        if dat_exists and self.data_backend.get_stat()[0] >= 8:
            header = self.data_backend.read_at(512, 0)
            self.super_block = SuperBlock.from_bytes(header)
        else:
            self.super_block = SuperBlock(
                version=version,
                replica_placement=replica_placement or ReplicaPlacement(),
                ttl=ttl)
            self.data_backend.write_at(self.super_block.to_bytes(), 0)
        self.version = self.super_block.version
        if dat_exists and tiered is None:
            # restore the activity clocks across restarts from the
            # .dat mtime (every append — writes AND tombstones —
            # touches it).  A zero clock after restart would hand
            # scrub authority to any replica that stayed up, even one
            # that missed this replica's deletes (resurrection), and
            # would misreport the volume as infinitely quiet.
            try:
                st = os.stat(base + ".dat")
                self.last_modified_ns = st.st_mtime_ns
                self.last_modified = int(st.st_mtime)
            except OSError:
                pass
        self._check_and_fix(base)
        self.nm: NeedleMapper = new_needle_map(needle_map_kind, base)
        # the read snapshot: (needle map, data backend) swapped as ONE
        # tuple so lock-free readers never pair an old map with a new
        # backend (or vice versa) across a vacuum swap
        self._read_ref = (self.nm, self.data_backend)

    # -- consistency (volume_checking.go) ---------------------------------
    def _check_and_fix(self, base: str) -> None:
        """Verify the idx's last entry points inside .dat; truncate torn
        .dat tail / torn idx tail (CheckVolumeDataIntegrity)."""
        idx_path = base + ".idx"
        if not os.path.exists(idx_path):
            return
        idx_size = os.path.getsize(idx_path)
        torn = idx_size % t.NEEDLE_MAP_ENTRY_SIZE
        if torn:
            with open(idx_path, "r+b") as f:
                f.truncate(idx_size - torn)
            idx_size -= torn
        if idx_size == 0:
            return
        with open(idx_path, "rb") as f:
            f.seek(idx_size - t.NEEDLE_MAP_ENTRY_SIZE)
            arr = parse_index_bytes(f.read(t.NEEDLE_MAP_ENTRY_SIZE))
        key, offset, size = (int(arr[0]["key"]), int(arr[0]["offset"]),
                             int(arr[0]["size"]))
        if offset == 0 or t.size_is_deleted(size):
            return
        dat_size = self.data_backend.get_stat()[0]
        end = offset + t.get_actual_size(size, self.version)
        if end > dat_size:
            # torn last write: drop the idx entry; a stricter repair would
            # re-scan .dat, kept simple as the reference truncates too
            with open(idx_path, "r+b") as f:
                f.truncate(idx_size - t.NEEDLE_MAP_ENTRY_SIZE)
        elif end < dat_size:
            self.data_backend.truncate(end)

    # -- write path (volume_write.go:109-230) -----------------------------
    def write_needle(self, n: Needle, fsync: bool = False) -> int:
        """Append; returns stored data size."""
        if self.read_only:
            raise VolumeError(f"volume {self.id} is read-only")
        with self._lock:
            if self.read_only:
                # re-check under the lock: a freeze (ec.encode's
                # mark-readonly, a disk-fault degrade) that takes the
                # lock as a barrier afterwards is then guaranteed no
                # straggler write can land post-barrier
                raise VolumeError(f"volume {self.id} is read-only")
            # dedup identical re-write (volume_write.go:35-63 hasSameLastEntry
            # spirit: equal id+cookie+data -> skip)
            if n.id != 0:
                existing = self.nm.get(n.id)
                if existing is not None and t.size_is_valid(existing.size):
                    try:
                        old = Needle.read_from(self.data_backend,
                                               existing.offset,
                                               existing.size, self.version)
                        if old.cookie == n.cookie and old.data == n.data:
                            n.size = existing.size
                            return len(n.data)
                    except Exception as e:
                        # unreadable prior record: fall through and
                        # append the new copy, but leave a trace — this
                        # is the first sign of a corrupt tail
                        LOG.debug("dedup read of needle %s failed: %s",
                                  n.id, e)
            try:
                offset, size, _ = n.append_to(self.data_backend,
                                              self.version)
            except OSError as e:
                # disk gone bad / ENOSPC: degrade to read-only instead
                # of failing every future write the same way.  append_to
                # already truncated the torn tail, so the volume keeps
                # SERVING; the heartbeat reports read_only and the
                # master routes new writes elsewhere (f4's "never lose
                # acked data" posture: fail THIS write loudly, protect
                # the rest).
                self._degrade(f"write: {e}")
                raise VolumeError(
                    f"volume {self.id} degraded to read-only: {e}"
                ) from e
            # the map records the *body* size written in the header (n.size),
            # which is what ReadBytes validates against (volume_write.go nm.Put)
            prev = self.nm.get(n.id) if fsync else None
            self.nm.put(n.id, offset, n.size)
            if fsync:
                try:
                    self.data_backend.sync()
                except OSError as e:
                    # an unsyncable record is NOT durable: roll the map
                    # entry back before failing, or a later reader gets
                    # bytes the caller was told did not commit.  A
                    # same-id overwrite rolls back to the PRIOR record
                    # (still acked, still on disk), not to a tombstone.
                    if prev is not None and t.size_is_valid(prev.size) \
                            and prev.offset:
                        self.nm.put(n.id, prev.offset, prev.size)
                    else:
                        self.nm.delete(n.id, offset)
                    self._degrade(f"fsync: {e}")
                    raise VolumeError(
                        f"volume {self.id} degraded to read-only: {e}"
                    ) from e
            self.last_modified = int(time.time())
            self.last_modified_ns = time.time_ns()
            return size

    # -- group-commit write path (volume_write.go:233-306) ----------------
    def _ensure_write_worker(self) -> None:
        with self._lock:
            if getattr(self, "_gc_queue", None) is not None:
                return
            import queue as _queue
            from concurrent.futures import Future
            q = self._gc_queue = _queue.Queue()
            self._gc_future_cls = Future

            def worker():
                while True:
                    item = q.get()
                    if item is None:
                        return
                    batch = [item]
                    # coalesce everything already queued (asyncWrite batching)
                    while True:
                        try:
                            nxt = q.get_nowait()
                        except _queue.Empty:
                            break
                        if nxt is None:
                            q.put(None)
                            break
                        batch.append(nxt)
                    sizes: dict[int, int] = {}
                    prevs: dict[int, "object | None"] = {}
                    for n, fut in batch:
                        try:
                            # snapshot the prior entry right before the
                            # write: a failed batch fsync must roll a
                            # same-id overwrite back to its acked prior
                            # version, not to a tombstone
                            prevs[id(fut)] = self.nm.get(n.id)
                            sizes[id(fut)] = self.write_needle(
                                n, fsync=False)
                        except Exception as e:
                            fut.set_exception(e)
                            batch = [b for b in batch if b[1] is not fut]
                    # ONE fsync covers the whole batch
                    try:
                        self.data_backend.sync()
                        self._gc_sync_count = getattr(
                            self, "_gc_sync_count", 0) + 1
                    except Exception as e:
                        # none of the batch is durable: roll the map
                        # entries back before failing the futures, and
                        # degrade — an unsyncable disk must stop taking
                        # writes (see write_needle's fsync path).  The
                        # rollback itself appends to .idx on the same
                        # failing disk, so it must never be allowed to
                        # kill this worker: queued futures would then
                        # hang instead of failing fast.
                        try:
                            with self._lock:
                                for n, fut in batch:
                                    prev = prevs.get(id(fut))
                                    if prev is not None \
                                            and t.size_is_valid(
                                                prev.size) \
                                            and prev.offset:
                                        self.nm.put(n.id, prev.offset,
                                                    prev.size)
                                    else:
                                        self.nm.delete(n.id, 0)
                        except Exception as e2:
                            LOG.warning(
                                "group-commit rollback on volume %d "
                                "failed (degrading anyway): %s",
                                self.id, e2)
                        if isinstance(e, OSError):
                            self._degrade(f"group-commit fsync: {e}")
                        for _, fut in batch:
                            if not fut.done():
                                fut.set_exception(e)
                        continue
                    for (n, fut) in batch:
                        if not fut.done():
                            # report the same stored size write_needle
                            # returns on the non-fsync path
                            fut.set_result(sizes[id(fut)])

            # NB: not named `t` — the worker closure must keep seeing
            # the module-level `types as t` alias
            worker_thread = threading.Thread(target=worker, daemon=True)
            worker_thread.start()
            self._gc_thread = worker_thread

    def write_needle_durable(self, n: Needle):
        """Queue a durable (fsynced) write; returns a Future.  Concurrent
        callers share one fsync per drained batch — the reference's
        volume_write.go:233 asyncWrite worker.  Enqueue happens under
        _lock so a concurrent _stop_write_worker (vacuum/close) can never
        strand the item behind the stop sentinel."""
        while True:
            self._ensure_write_worker()
            with self._lock:
                q = getattr(self, "_gc_queue", None)
                if q is not None:
                    fut = self._gc_future_cls()
                    q.put((n, fut))
                    return fut
            # worker was stopped between ensure and put; recreate + retry

    # -- read path (volume_read.go:16-80) ---------------------------------
    # Lock-free: `_read_ref` gives a coherent (map, backend) pair, the
    # dict read is GIL-atomic, and the pread-style backend read needs no
    # shared seek offset.  A vacuum swapping the pair mid-read surfaces
    # as a read error (closed fd / stale offsets -> size or CRC
    # mismatch); `_locked_retry` re-runs the read under the volume lock,
    # where the pair cannot change, and re-raises the real error if the
    # failure wasn't the swap race.
    def _locked_retry(self, fn):
        with self._lock:
            return fn(self.nm, self.data_backend)

    def read_needle(self, n_id: int, cookie: int | None = None,
                    zero_copy: bool = False) -> Needle:
        def attempt(nm: NeedleMapper, backend: BackendStorageFile) -> Needle:
            nv = nm.get(n_id)
            if nv is None or nv.offset == 0 or t.size_is_deleted(nv.size):
                raise NotFoundError(
                    f"needle {n_id:x} not found in volume {self.id}")
            n = Needle.read_from(backend, nv.offset, nv.size, self.version,
                                 zero_copy=zero_copy)
            if n.id != n_id:
                # lock-free reads can race a vacuum's backend close with
                # the OS reusing the fd: the pread then lands in a
                # different file, and a same-size record there must not
                # be served as this needle (the locked retry re-reads
                # coherently)
                raise VolumeError(
                    f"needle id mismatch at offset {nv.offset}: "
                    f"read {n.id:x}, wanted {n_id:x}")
            n.volume_offset = nv.offset
            return n
        try:
            n = attempt(*self._read_ref)
        except NotFoundError:
            raise
        except Exception:
            n = self._locked_retry(attempt)
        self._check_read_needle(n, n_id, cookie)
        return n

    def needle_offset(self, n_id: int) -> "int | None":
        """Current .dat offset of a live needle (None when absent or
        deleted) — the volume server's cache-population guard: an entry
        is only admitted while the offset it was read at is still the
        live one."""
        nm, _ = self._read_ref
        nv = nm.get(n_id)
        if nv is None or nv.offset == 0 or t.size_is_deleted(nv.size):
            return None
        return nv.offset

    def _check_read_needle(self, n: Needle, n_id: int,
                           cookie: "int | None") -> None:
        """Post-parse read checks, shared by the full and fast paths."""
        if cookie is not None and n.cookie != cookie:
            raise CookieMismatchError(
                f"cookie mismatch for needle {n_id:x}")
        if n.has_ttl() and n.ttl is not None and n.last_modified:
            expire = n.last_modified + n.ttl.minutes() * 60
            if n.ttl.minutes() and time.time() > expire:
                raise NotFoundError(f"needle {n_id:x} expired")

    def read_needle_data(self, n_id: int, cookie: "int | None" = None,
                         meta: "dict | None" = None) -> bytes:
        """Fast-path blob read: just the data bytes.

        The plain-blob common case (no name/mime/ttl/pairs flags) parses
        + CRC-checks + cookie-checks in ONE native call
        (native/fastpath.c needle_data); rich needles, v1 volumes and
        every error path fall back to read_needle, which re-raises the
        precise error types.  The TCP data server's read handler rides
        this — the frame protocol can only return bytes anyway.

        `meta`, when given, receives {"ttl": bool} so the caller's cache
        can refuse TTL'd needles (expiry is enforced on the disk path,
        so a cache must never serve them)."""
        from .. import native
        fp = native.fastpath()
        if fp is None:
            n = self.read_needle(n_id, cookie)
            if meta is not None:
                meta["ttl"] = n.has_ttl()
            return bytes(n.data)

        def attempt(nm: NeedleMapper,
                    backend: BackendStorageFile) -> bytes:
            nv = nm.get(n_id)
            if nv is None or nv.offset == 0 or t.size_is_deleted(nv.size):
                raise NotFoundError(
                    f"needle {n_id:x} not found in volume {self.id}")
            raw = backend.read_at(
                t.get_actual_size(nv.size, self.version), nv.offset)
            try:
                data = fp.needle_data(raw, nv.size, self.version,
                                      -1 if cookie is None else cookie)
                if meta is not None:
                    meta["ttl"] = False  # fast parse == flags are 0
                return data
            except ValueError:
                # rich needle (flags set) or a mismatch: hydrate from
                # the buffer ALREADY read — no second disk read — and
                # let the Python parser/checks raise the precise error
                # types
                n = Needle()
                n.read_bytes(raw, nv.offset, nv.size, self.version)
                if n.id != n_id:
                    # fd-reuse race (see read_needle): locked retry
                    raise VolumeError(
                        f"needle id mismatch: read {n.id:x}, "
                        f"wanted {n_id:x}")
                self._check_read_needle(n, n_id, cookie)
                if meta is not None:
                    meta["ttl"] = n.has_ttl()
                return bytes(n.data)

        try:
            return attempt(*self._read_ref)
        except (NotFoundError, CookieMismatchError):
            raise
        except Exception:
            # closed/swapped backend mid-read (vacuum): one coherent
            # locked retry; real corruption re-raises the same error
            return self._locked_retry(attempt)

    def read_needle_range(self, n_id: int, cookie: "int | None",
                          offset: int, length: int) -> bytes:
        """Sub-range of a needle's DATA bytes with exactly the preads
        the range needs: one 21-byte header probe (cookie/id/size/
        dataSize + a flags peek) and one ranged pread — never the whole
        record.  This is the large-object fast path: a 1MB Range read
        out of an 8MB chunk moves 1MB off this disk, not 8.

        Restricted to plain blobs (flags==0 on v2+; any v1 record):
        compressed/TTL'd/named needles raise VolumeError so the caller
        falls back to the full read where the complete parse runs.
        Sub-range reads skip the data CRC — verifying it would require
        reading the whole record, defeating the point; whole-chunk
        reads on every path still verify, and the anti-entropy scrub
        owns at-rest rot detection."""
        if length <= 0:
            return b""

        def attempt(nm: NeedleMapper,
                    backend: BackendStorageFile) -> bytes:
            nv = nm.get(n_id)
            if nv is None or nv.offset == 0 or t.size_is_deleted(nv.size):
                raise NotFoundError(
                    f"needle {n_id:x} not found in volume {self.id}")
            head = backend.read_at(t.NEEDLE_HEADER_SIZE + 4, nv.offset)
            if len(head) < t.NEEDLE_HEADER_SIZE:
                raise VolumeError(
                    f"short header read at offset {nv.offset}")
            rec = Needle()
            rec.parse_header(head)
            if rec.id != n_id:
                # fd-reuse race with a vacuum swap (see read_needle):
                # the locked retry re-reads coherently
                raise VolumeError(
                    f"needle id mismatch at offset {nv.offset}: "
                    f"read {rec.id:x}, wanted {n_id:x}")
            if rec.size != nv.size:
                raise VolumeError(
                    f"needle {n_id:x} size mismatch: header "
                    f"{rec.size}, map {nv.size}")
            if cookie is not None and rec.cookie != cookie:
                raise CookieMismatchError(
                    f"cookie mismatch for needle {n_id:x}")
            if self.version == t.VERSION1:
                data_off, data_len = t.NEEDLE_HEADER_SIZE, rec.size
            else:
                import struct as _struct
                data_len = _struct.unpack_from(">I", head,
                                               t.NEEDLE_HEADER_SIZE)[0]
                data_off = t.NEEDLE_HEADER_SIZE + 4
                if rec.size != data_len + 5:
                    # flags/name/mime/ttl present: not a plain blob
                    raise VolumeError(
                        f"needle {n_id:x} is not a plain blob")
                flags_b = backend.read_at(
                    1, nv.offset + data_off + data_len)
                if not flags_b or flags_b[0] != 0:
                    raise VolumeError(
                        f"needle {n_id:x} has flags "
                        f"{flags_b[0] if flags_b else '??'}; ranged "
                        "reads serve plain blobs only")
            if offset >= data_len:
                raise VolumeError(
                    f"range start {offset} beyond needle data "
                    f"{data_len}")
            want = min(length, data_len - offset)
            piece = backend.read_at(want, nv.offset + data_off + offset)
            if len(piece) < want:
                raise VolumeError(
                    f"short ranged read: {len(piece)} of {want}")
            return piece

        try:
            return attempt(*self._read_ref)
        except (NotFoundError, CookieMismatchError):
            raise
        except Exception:
            return self._locked_retry(attempt)

    def data_fd_for_sendfile(self, n_id: int,
                             volume_offset: int) -> "int | None":
        """A dup'ed fd of the live .dat, taken under the volume lock and
        only while needle `n_id` still lives at `volume_offset` — the
        zero-copy serving guard.  The dup stays valid for the whole
        sendfile even if a vacuum swaps the backend mid-send (the old
        inode survives while the dup holds it); a swap BEFORE the dup is
        caught by the offset re-check, because the fresh map's offsets
        describe the fresh file.  None = serve from memory instead."""
        with self._lock:
            nv = self.nm.get(n_id)
            if nv is None or nv.offset != volume_offset \
                    or t.size_is_deleted(nv.size):
                return None
            b = self.data_backend
            if isinstance(b, MemoryMappedFile):
                b = b.disk
            fd = getattr(b, "fd", None)
            if fd is None or getattr(b, "_closed", False):
                return None   # tiered/in-memory backends: no real fd
            try:
                return os.dup(fd)
            except OSError:
                return None

    def needle_data_offset(self, volume_offset: int) -> int:
        """Absolute .dat offset of a needle's data bytes, given its
        record offset (header + the v2+ dataSize field) — where a
        zero-copy sendfile starts."""
        return volume_offset + t.NEEDLE_HEADER_SIZE \
            + (0 if self.version == t.VERSION1 else 4)

    def has_needle(self, n_id: int) -> bool:
        nm, _ = self._read_ref
        nv = nm.get(n_id)
        return nv is not None and not t.size_is_deleted(nv.size)

    # -- delete path (volume_write.go doDeleteRequest) --------------------
    def delete_needle(self, n_id: int, cookie: int | None = None) -> int:
        """Returns bytes freed (0 if absent)."""
        if self.read_only:
            raise VolumeError(f"volume {self.id} is read-only")
        with self._lock:
            if self.read_only:   # see write_needle: freeze barrier
                raise VolumeError(f"volume {self.id} is read-only")
            nv = self.nm.get(n_id)
            if nv is None or t.size_is_deleted(nv.size):
                return 0
            if cookie is not None:
                existing = Needle.read_from(self.data_backend, nv.offset,
                                            nv.size, self.version)
                if existing.cookie != cookie:
                    raise CookieMismatchError(
                        f"cookie mismatch deleting needle {n_id:x}")
            tomb = Needle(id=n_id, cookie=cookie or 0)
            try:
                tomb.append_to(self.data_backend, self.version)
            except OSError as e:
                self._degrade(f"delete: {e}")
                raise VolumeError(
                    f"volume {self.id} degraded to read-only: {e}"
                ) from e
            self.nm.delete(n_id, nv.offset)
            self.last_modified = int(time.time())
            self.last_modified_ns = time.time_ns()
            return nv.size

    # -- stats ------------------------------------------------------------
    def content_size(self) -> int:
        return self.data_backend.get_stat()[0]

    def garbage_level(self) -> float:
        """Deleted bytes / total (volume_vacuum checks this ratio)."""
        total = self.content_size()
        if total <= self.super_block.block_size():
            return 0.0
        return self.nm.deleted_size() / total

    def info(self) -> VolumeInfo:
        return VolumeInfo(
            id=self.id,
            size=self.content_size(),
            collection=self.collection,
            file_count=self.nm.file_count(),
            delete_count=self.nm.deleted_count(),
            deleted_byte_count=self.nm.deleted_size(),
            read_only=self.read_only,
            replica_placement=self.super_block.replica_placement.to_byte(),
            version=self.version,
            ttl=self.super_block.ttl.to_uint32(),
            compact_revision=self.super_block.compaction_revision,
            modified_at_second=self.last_modified,
            degraded_reason=self.degraded_reason,
        )

    def max_file_key(self) -> int:
        return self.nm.max_file_key()

    # -- vacuum (volume_vacuum.go Compact2/CommitCompact) ------------------
    def vacuum(self, preallocate: int = 0) -> int:
        """Compact + commit in one step (no concurrent-write diff tracking —
        callers freeze writes first, like the master's vacuum orchestration).
        Returns bytes reclaimed."""
        # the group-commit worker fsyncs the backend we are about to swap
        self._stop_write_worker()
        with self._lock:
            before = self.content_size()
            # swap-point forensics (ROADMAP soak SizeMismatchError): the
            # (map size, dat size) pair BEFORE and AFTER the swap, tagged
            # with the orchestrator's trace id, is what lets a torn
            # map/backend state be attributed to a specific vacuum pass
            from ..util import tracing as _tracing
            tid = _tracing.current_trace_id() or "-"
            LOG.info("vacuum volume %d trace=%s swap-in: map=%d needles "
                     "dat=%d bytes", self.id, tid, self.nm.file_count(),
                     before)
            base = self.base_path
            cpd, cpx = base + ".cpd", base + ".cpx"
            new_sb = SuperBlock(
                version=self.super_block.version,
                replica_placement=self.super_block.replica_placement,
                ttl=self.super_block.ttl,
                compaction_revision=self.super_block.compaction_revision,
            ).inc_compaction_revision()
            # vacuum swaps the live .dat/.idx under every reader; holding
            # the volume lock for the whole compact IS the design — this
            # is the per-volume serialization point, not a container lock
            with open(cpd, "wb") as dat, open(cpx, "wb") as idxf:  # weedlint: disable=WL001
                dat.write(new_sb.to_bytes())
                offset = len(new_sb.to_bytes())
                for nv in sorted(self.nm.items(), key=lambda v: v.offset):
                    if t.size_is_deleted(nv.size) or nv.offset == 0:
                        continue
                    raw = self.data_backend.read_at(
                        t.get_actual_size(nv.size, self.version), nv.offset)
                    dat.write(raw)
                    idxf.write(idx_entry_bytes(nv.key, offset, nv.size))
                    offset += len(raw)
            self.nm.close()
            self.data_backend.close()
            os.replace(cpd, base + ".dat")
            os.replace(cpx, base + ".idx")
            # drop any leveldb sidecar so it rebuilds from the fresh idx
            if os.path.exists(base + ".ldb"):
                os.remove(base + ".ldb")
            self.data_backend = open_backend(self.backend_kind, base + ".dat")
            self.super_block = new_sb
            self.nm = new_needle_map(self.needle_map_kind, base)
            # ONE atomic swap: lock-free readers pick up the fresh pair
            # together (never old map + new backend)
            self._read_ref = (self.nm, self.data_backend)
            LOG.info("vacuum volume %d trace=%s swap-out: map=%d "
                     "needles dat=%d bytes", self.id, tid,
                     self.nm.file_count(), self.content_size())
            return before - self.content_size()

    # -- degradation (write-path IO faults) --------------------------------
    def _degrade(self, reason: str) -> None:
        """Flip to read-only after a write-path IO error.  Reads keep
        being served (locally and from replicas); the master learns via
        the next heartbeat (nudged immediately through on_degrade) and
        stops assigning new writes here."""
        if self.read_only:
            return
        self.read_only = True
        self.degraded_reason = reason
        LOG.warning("volume %d degraded to read-only: %s", self.id,
                    reason)
        cb = self.on_degrade
        if cb is not None:
            try:
                cb(self.id)
            except Exception as e:
                LOG.debug("degrade callback for volume %d failed: %s",
                          self.id, e)

    # -- lifecycle ---------------------------------------------------------
    def freeze_writes(self) -> None:
        """Mark read-only AND drain: once this returns, no in-flight
        write/delete can still append — a straggler that passed the
        fast read_only check before the flag flipped is either already
        done (it held the lock we now barrier on) or will fail the
        under-lock re-check.  Snapshot flows (ec encode) need this
        guarantee: their .idx/.dat reads run by path, outside the
        volume lock."""
        self.read_only = True
        with self._lock:
            pass

    def sync(self) -> None:
        self.data_backend.sync()
        self.nm.sync()

    def _stop_write_worker(self) -> None:
        """Drain + stop the group-commit worker.  The refs swap out under
        _lock (so enqueuers race-free retry against a fresh worker), then
        the join runs OUTSIDE _lock (the worker's write_needle takes
        _lock) and UNBOUNDED: proceeding to swap/close the backend under
        a live worker corrupts acknowledged durable writes."""
        with self._lock:
            q = getattr(self, "_gc_queue", None)
            t = getattr(self, "_gc_thread", None)
            self._gc_queue = None
            self._gc_thread = None
        if q is None:
            return
        q.put(None)
        if t is not None:
            t.join()

    def close(self) -> None:
        self._stop_write_worker()
        with self._lock:
            self.nm.close()
            self.data_backend.close()

    def destroy(self) -> None:
        """Remove the volume's files.  The .vif stays where EC shards were
        sealed from this volume (an .ecx beside it): it is then their
        geometry record, which ec.encode's VolumeDelete must not take
        from the shards it leaves on this server."""
        self.close()
        sealed = os.path.exists(self.base_path + ".ecx")
        for ext in (".dat", ".idx", ".ldb", ".cpd", ".cpx", ".vif", ".note"):
            if ext == ".vif" and sealed:
                continue
            p = self.base_path + ext
            if os.path.exists(p):
                os.remove(p)

    # -- scan (used by vacuum-test, backup, ec encode prep) ----------------
    def scan_needles(self):
        """Yield (offset, needle, body_len) for every record in .dat order
        (the reference's ScanVolumeFile pattern)."""
        offset = self.super_block.block_size()
        size = self.content_size()
        while offset < size:
            n, body_len = read_needle_header(self.data_backend, self.version,
                                             offset)
            if n is None:
                break
            yield offset, n, body_len
            offset += t.NEEDLE_HEADER_SIZE + body_len
