"""Raft consensus for the master control plane.

Replaces the round-1 lease election (which had an admitted split-brain
window) with a real replicated log, capability-matching the reference's
raft layer (weed/server/raft_server.go:64-150; its state machine is the
max-volume-id counter, topology/cluster_commands.go, plus the sequencer
persisted in raft snapshots, raft_server.go:45-62).

Standard raft (Ongaro & Ousterhout) with the safety-relevant details:
- randomized election timeouts; term checks on every RPC;
- log consistency check + truncate-on-conflict in AppendEntries;
- commit index advances only over majority matches *in the current term*
  (§5.4.2), with a no-op entry appended at leadership start so prior-term
  entries commit promptly;
- leader lease step-down: a leader that cannot reach a quorum for two
  election timeouts stops serving (2x tolerates scheduler jitter on loaded
  hosts without flapping; safety never depends on the lease — see
  _check_lease).  Combined with block-reserved sequence
  allocation (ha.py) a partitioned minority can never acknowledge an
  assign — the round-1 duplicate-fid window is closed by construction;
- snapshot/compaction: the applied prefix folds into snapshot_fn()'s state
  dict once the log exceeds max_log_entries; lagging followers catch up
  via InstallSnapshot;
- optional state_dir persists term/vote/log/snapshot (JSON files) so a
  restarted master rejoins with vote and log intact.  Term/vote and log
  writes are appends: replacing a file over an existing one makes ext4
  flush it (auto_da_alloc), tens of ms under the node lock on every
  vote, which outlasts an election timeout and livelocks elections.

Transport is the repo's JSON-over-gRPC mesh (pb/rpc.py): the three RPCs
are unary methods on the "Raft" service of the master's RpcServer.
`set_partitioned(True)` simulates a full network partition of this node
(incoming raft RPCs rejected, outgoing dropped) for SimCluster fault
injection.
"""

from __future__ import annotations

import json
import os
import random
import threading
from ..util import locks
import time
from typing import Callable

from ..pb.rpc import POOL, RpcError
from ..util.weedlog import logger

LOG = logger(__name__)

FOLLOWER, CANDIDATE, LEADER = "follower", "candidate", "leader"
# term/vote records kept in meta.jsonl before it is folded to one line
_META_MAX_LINES = 1024


class NotLeaderError(RpcError):
    def __init__(self, leader: str):
        super().__init__(f"not the raft leader (leader={leader or '?'})")
        self.leader = leader


class _Future:
    def __init__(self):
        self._ev = threading.Event()
        self.result = None
        self.error: Exception | None = None

    def set(self, result, error=None):
        self.result, self.error = result, error
        self._ev.set()

    def wait(self, timeout: float) -> bool:
        return self._ev.wait(timeout)


class RaftNode:
    def __init__(self, self_addr: str, peers: list[str],
                 apply_fn: Callable[[dict], object],
                 snapshot_fn: Callable[[], dict],
                 restore_fn: Callable[[dict], None],
                 on_role_change: Callable[[bool], None] | None = None,
                 heartbeat_interval: float = 0.1,
                 election_timeout: float = 0.4,
                 state_dir: str | None = None,
                 max_log_entries: int = 1024,
                 max_log_bytes: "int | None" = None,
                 on_log_stats: "Callable[[int, int, int], None] | None"
                 = None,
                 seed: int | None = None):
        self.self_addr = self_addr
        self.peers = sorted(set(peers) | {self_addr})
        self.quorum = len(self.peers) // 2 + 1
        self.apply_fn = apply_fn
        self.snapshot_fn = snapshot_fn
        self.restore_fn = restore_fn
        self.on_role_change = on_role_change
        self.hb_interval = heartbeat_interval
        self.election_timeout = election_timeout
        self.state_dir = state_dir
        self.max_log_entries = max_log_entries
        # churn bound: compaction also triggers on SERIALIZED log size —
        # entry counts alone let a burst of fat commands (mass
        # re-registration under churn) balloon the log and every
        # follower catch-up that replays it
        if max_log_bytes is None:
            try:
                max_log_bytes = int(os.environ.get(
                    "WEED_RAFT_MAX_LOG_BYTES", str(1 << 20)))
            except ValueError:
                max_log_bytes = 1 << 20
        self.max_log_bytes = max_log_bytes
        # (entries, bytes, snap_index) observer — ha.py feeds the
        # seaweedfs_master_raft_log_* gauges from it
        self.on_log_stats = on_log_stats
        self._log_bytes = 0
        self._rng = random.Random(seed)

        self._lock = locks.RLock("RaftNode._lock")
        self._apply_mutex = locks.Lock("RaftNode._apply_mutex")
        self.term = 0
        self.voted_for: str | None = None
        # log entries: {"i": absolute index, "t": term, "c": command}
        self.log: list[dict] = []
        self.snap_index = 0
        self.snap_term = 0
        # state dict frozen AT compaction time — InstallSnapshot must ship
        # this, not a live snapshot_fn() read, or the receiver re-applies
        # entries (snap_index, last_applied] on top of state that already
        # includes them
        self._snap_state: dict = {}
        self.commit_index = 0
        self.last_applied = 0
        self.role = FOLLOWER
        self.leader_id = ""
        self._votes: set[str] = set()
        self._next_index: dict[str, int] = {}
        self._match_index: dict[str, int] = {}
        self._last_ack: dict[str, float] = {}
        self._futures: dict[int, _Future] = {}
        self._partitioned = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # wakes the long-lived per-peer replicator loops (no per-heartbeat
        # thread spawning)
        self._cond = locks.Condition(name="RaftNode._cond")
        self._election_deadline = 0.0
        self._meta_lines = 0
        if state_dir:
            os.makedirs(state_dir, exist_ok=True)
            self._load_state()

    # -- log helpers (hold _lock) ------------------------------------------
    @property
    def last_index(self) -> int:
        return self.log[-1]["i"] if self.log else self.snap_index

    def _term_at(self, i: int) -> int:
        if i == self.snap_index:
            return self.snap_term
        return self._entry(i)["t"]

    def _entry(self, i: int) -> dict:
        return self.log[i - self.snap_index - 1]

    @staticmethod
    def _entry_bytes(e: dict) -> int:
        # the persisted JSONL footprint: serialized entry + newline
        return len(json.dumps(e, separators=(",", ":"))) + 1

    def _recount_log_bytes(self) -> None:
        """O(n) — only after truncation/compaction/restore; appends
        track incrementally."""
        self._log_bytes = sum(self._entry_bytes(e) for e in self.log)

    def _rand_deadline(self) -> float:
        return time.monotonic() + self.election_timeout * (
            1.0 + self._rng.random())

    # -- persistence --------------------------------------------------------
    def _persist_meta(self) -> None:
        """Append term/vote to meta.jsonl; the last whole line wins on
        load, so a torn append falls back to the previous record (the
        torn vote was never answered: replies follow the write).  A rare
        rewrite folds the file to one line at _META_MAX_LINES."""
        if not self.state_dir:
            return
        line = json.dumps({"term": self.term, "voted_for": self.voted_for},
                          separators=(",", ":")) + "\n"
        path = os.path.join(self.state_dir, "meta.jsonl")
        if self._meta_lines >= _META_MAX_LINES:
            tmp = os.path.join(self.state_dir, ".meta.tmp")
            with open(tmp, "w") as f:
                f.write(line)
            os.replace(tmp, path)
            self._meta_lines = 1
            return
        with open(path, "a") as f:
            f.write(line)
        self._meta_lines += 1

    def _persist_log(self) -> None:
        """Full rewrite — only for truncation/compaction; plain appends go
        through _persist_append (O(1) per entry, not O(n))."""
        if not self.state_dir:
            return
        tmp = os.path.join(self.state_dir, ".log.tmp")
        with open(tmp, "w") as f:
            for e in self.log:
                f.write(json.dumps(e, separators=(",", ":")) + "\n")
        os.replace(tmp, os.path.join(self.state_dir, "log.jsonl"))

    def _persist_append(self, entry: dict) -> None:
        if not self.state_dir:
            return
        with open(os.path.join(self.state_dir, "log.jsonl"), "a") as f:
            f.write(json.dumps(entry, separators=(",", ":")) + "\n")

    def _persist_snapshot(self, state: dict) -> None:
        if not self.state_dir:
            return
        tmp = os.path.join(self.state_dir, ".snap.tmp")
        with open(tmp, "w") as f:
            json.dump({"snap_index": self.snap_index,
                       "snap_term": self.snap_term, "state": state}, f)
        os.replace(tmp, os.path.join(self.state_dir, "snap.json"))

    def _load_state(self) -> None:
        meta = None
        meta_p = os.path.join(self.state_dir, "meta.jsonl")
        legacy_p = os.path.join(self.state_dir, "meta.json")
        if os.path.exists(meta_p):
            with open(meta_p) as f:
                for line in f:
                    self._meta_lines += 1
                    try:
                        meta = json.loads(line)
                    except ValueError:
                        # torn tail: the previous record holds, and the
                        # next write rewrites the file so no append
                        # lands on the torn line
                        self._meta_lines = _META_MAX_LINES
        elif os.path.exists(legacy_p):     # single-record older layout
            with open(legacy_p) as f:
                meta = json.load(f)
        if meta is not None:
            self.term = meta.get("term", 0)
            self.voted_for = meta.get("voted_for")
        snap_p = os.path.join(self.state_dir, "snap.json")
        if os.path.exists(snap_p):
            with open(snap_p) as f:
                snap = json.load(f)
            self.snap_index = snap["snap_index"]
            self.snap_term = snap["snap_term"]
            self._snap_state = snap["state"]
            self.restore_fn(snap["state"])
            self.commit_index = self.last_applied = self.snap_index
        log_p = os.path.join(self.state_dir, "log.jsonl")
        if os.path.exists(log_p):
            with open(log_p) as f:
                self.log = [json.loads(line) for line in f if line.strip()]
            # drop entries the snapshot already covers
            self.log = [e for e in self.log if e["i"] > self.snap_index]
        self._recount_log_bytes()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            self._election_deadline = self._rand_deadline()
        # replay persisted-but-unapplied committed entries happens as the
        # cluster re-commits them; a single-node cluster self-commits below
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"raft-{self.self_addr}")
        self._thread.start()
        for p in self.peers:
            if p != self.self_addr:
                threading.Thread(target=self._peer_loop, args=(p,),
                                 daemon=True,
                                 name=f"raft-repl-{p}").start()

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._fail_pending(RpcError("raft node stopped"))

    def set_partitioned(self, flag: bool) -> None:
        with self._lock:
            self._partitioned = flag
            if flag and self.role == LEADER:
                # the lease would expire anyway; step down immediately so
                # the minority side stops serving without waiting a timeout
                self._become_follower(self.term, keep_vote=True)

    # -- main loop ----------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.wait(0.02):
            now = time.monotonic()
            with self._lock:
                if self._partitioned:
                    if self.role == LEADER:   # lost set_partitioned race
                        self._become_follower(self.term, keep_vote=True)
                    self._election_deadline = self._rand_deadline()
                    continue
                if self.role == LEADER:
                    self._check_lease(now)
                    behind = self.last_applied < self.commit_index
                elif now >= self._election_deadline:
                    self._start_election()
                    behind = False
                else:
                    behind = self.last_applied < self.commit_index
            if behind:
                self._apply_committed()

    def _check_lease(self, now: float) -> None:
        """Step down if no quorum of followers acked within 2x the election
        timeout — a partitioned leader must stop serving.  The 2x factor is
        deliberate: 1x flaps under scheduler jitter (4 heartbeat rounds),
        and the lease is an availability optimization only — correctness
        against duplicate fids is carried by block-reserved sequences
        (ha.py), not by the serving window's length."""
        if self.quorum == 1:
            return
        acks = sorted((self._last_ack.get(p, 0.0) for p in self.peers
                       if p != self.self_addr), reverse=True)
        # self counts toward the quorum; need quorum-1 follower acks
        lease_base = acks[self.quorum - 2]
        if now - lease_base > self.election_timeout * 2.0:
            LOG.info("raft %s: quorum lost, stepping down (term %d)",
                     self.self_addr, self.term)
            self._become_follower(self.term, keep_vote=True)

    def _become_follower(self, term: int, keep_vote: bool = False) -> None:
        was_leader = self.role == LEADER
        if term > self.term:
            self.term = term
            self.voted_for = None if not keep_vote else self.voted_for
            self._persist_meta()
        self.role = FOLLOWER
        self._election_deadline = self._rand_deadline()
        if was_leader:
            self._fail_pending(NotLeaderError(self.leader_id))
            if self.on_role_change:
                self.on_role_change(False)

    def _fail_pending(self, err: Exception) -> None:
        futures, self._futures = self._futures, {}
        for fut in futures.values():
            fut.set(None, err)

    # -- election -----------------------------------------------------------
    def _start_election(self) -> None:
        self.term += 1
        self.role = CANDIDATE
        self.voted_for = self.self_addr
        self._persist_meta()
        self._votes = {self.self_addr}
        self._election_deadline = self._rand_deadline()
        term = self.term
        req = {"term": term, "candidate": self.self_addr,
               "last_log_index": self.last_index,
               "last_log_term": self._term_at(self.last_index)}
        LOG.debug("raft %s: election for term %d", self.self_addr, term)
        if len(self._votes) >= self.quorum:
            self._become_leader()
            return
        for p in self.peers:
            if p != self.self_addr:
                threading.Thread(target=self._request_vote, daemon=True,
                                 args=(p, term, req)).start()

    def _request_vote(self, peer: str, term: int, req: dict) -> None:
        try:
            out = self._call(peer, "RequestVote", req,
                             timeout=self.election_timeout)
        except RpcError:
            return
        with self._lock:
            if out.get("term", 0) > self.term:
                self._become_follower(out["term"])
                return
            if (self.role == CANDIDATE and self.term == term
                    and out.get("granted")):
                self._votes.add(peer)
                if len(self._votes) >= self.quorum:
                    self._become_leader()

    def _become_leader(self) -> None:
        if self._partitioned:
            # a vote response may race set_partitioned — never claim
            # leadership while cut off
            self.role = FOLLOWER
            return
        LOG.info("raft %s: leader for term %d", self.self_addr, self.term)
        self.role = LEADER
        self.leader_id = self.self_addr
        last = self.last_index
        self._next_index = {p: last + 1 for p in self.peers}
        self._match_index = {p: 0 for p in self.peers}
        now = time.monotonic()
        self._last_ack = {p: now for p in self.peers}
        # no-op commits prior-term entries promptly (§5.4.2 / §8)
        self._append_local({"t": "noop"})
        self._broadcast()
        if self.on_role_change:
            self.on_role_change(True)

    # -- replication --------------------------------------------------------
    def _append_local(self, cmd: dict) -> int:
        index = self.last_index + 1
        entry = {"i": index, "t": self.term, "c": cmd}
        self.log.append(entry)
        self._log_bytes += self._entry_bytes(entry)
        self._persist_append(entry)
        self._match_index[self.self_addr] = index
        if self.quorum == 1:
            self._advance_commit()
        return index

    def _broadcast(self) -> None:
        """Wake every replicator loop for an immediate AppendEntries."""
        with self._cond:
            self._cond.notify_all()

    def _peer_loop(self, peer: str) -> None:
        """One long-lived replication loop per peer: heartbeat every
        hb_interval, sooner when _broadcast signals new entries."""
        while not self._stop.is_set():
            with self._cond:
                self._cond.wait(self.hb_interval)
            if self._stop.is_set():
                return
            with self._lock:
                if self.role != LEADER or self._partitioned:
                    continue
                term = self.term
            try:
                self._replicate_to(peer, term)
            except Exception as e:  # never kill the loop
                LOG.debug("replicate to %s failed: %s", peer, e)

    def _replicate_to(self, peer: str, term: int) -> None:
        with self._lock:
            if self.role != LEADER or self.term != term:
                return
            ni = self._next_index.get(peer, self.last_index + 1)
            snap_req = None
            if ni <= self.snap_index:
                # build under the lock, send outside it — a 2s RPC
                # holding _lock would stall heartbeats to healthy
                # followers and flap leadership
                snap_req = {"term": term, "leader": self.self_addr,
                            "snap_index": self.snap_index,
                            "snap_term": self.snap_term,
                            "state": self._snap_state}
        if snap_req is not None:
            self._send_snapshot(peer, term, snap_req)
            return
        with self._lock:
            if self.role != LEADER or self.term != term:
                return
            ni = self._next_index.get(peer, self.last_index + 1)
            if ni <= self.snap_index:
                return      # compacted again meanwhile; next round
            prev = ni - 1
            entries = [self._entry(i)
                       for i in range(ni, self.last_index + 1)]
            req = {"term": term, "leader": self.self_addr,
                   "prev_index": prev, "prev_term": self._term_at(prev),
                   "entries": entries, "commit": self.commit_index}
        try:
            out = self._call(peer, "AppendEntries", req,
                             timeout=self.election_timeout)
        except RpcError:
            return
        apply_now = False
        with self._lock:
            if out.get("term", 0) > self.term:
                self._become_follower(out["term"])
                return
            if self.role != LEADER or self.term != term:
                return
            self._last_ack[peer] = time.monotonic()
            if out.get("ok"):
                match = prev + len(entries)
                if match > self._match_index.get(peer, 0):
                    self._match_index[peer] = match
                self._next_index[peer] = match + 1
                apply_now = self._advance_commit()
            else:
                # follower hints its last index to jump back quickly
                self._next_index[peer] = max(
                    1, min(ni - 1, out.get("last", ni - 1) + 1))
        if apply_now:
            self._apply_committed()

    def _send_snapshot(self, peer: str, term: int, req: dict) -> None:
        """Called with _lock NOT held (req was built under it)."""
        try:
            out = self._call(peer, "InstallSnapshot", req, timeout=2.0)
        except RpcError:
            return
        with self._lock:
            if out.get("term", 0) > self.term:
                self._become_follower(out["term"])
            elif self.role == LEADER and self.term == term:
                self._last_ack[peer] = time.monotonic()
                self._next_index[peer] = req["snap_index"] + 1
                self._match_index[peer] = max(
                    self._match_index.get(peer, 0), req["snap_index"])

    def _advance_commit(self) -> bool:
        """Advance commit_index over majority matches in the current term.
        Returns True if it moved (caller applies outside handler locks)."""
        matches = sorted(self._match_index.get(p, 0) for p in self.peers)
        n = matches[len(self.peers) - self.quorum]
        if n > self.commit_index and n > self.snap_index \
                and self._term_at(n) == self.term:
            self.commit_index = n
            return True
        return False

    def _apply_committed(self) -> None:
        with self._apply_mutex:
            while True:
                with self._lock:
                    if self.last_applied >= self.commit_index:
                        break
                    self.last_applied += 1
                    e = self._entry(self.last_applied)
                    fut = self._futures.pop(self.last_applied, None)
                res, err = None, None
                if e["c"].get("t") != "noop":
                    try:
                        res = self.apply_fn(e["c"])
                    except Exception as ex:  # state machine bug — surface
                        err = ex
                if fut:
                    fut.set(res, err)
            self._maybe_compact()

    def _maybe_compact(self) -> None:
        with self._lock:
            over = (len(self.log) > self.max_log_entries
                    or self._log_bytes > self.max_log_bytes)
            if over and self.last_applied > self.snap_index:
                state = self.snapshot_fn()
                new_snap = self.last_applied
                self.snap_term = self._term_at(new_snap)
                self.log = [e for e in self.log if e["i"] > new_snap]
                self._recount_log_bytes()
                self.snap_index = new_snap
                self._snap_state = state
                # snapshot BEFORE log: a crash between the writes must
                # leave a snap covering everything the truncated log no
                # longer holds (_load_state drops log entries <=
                # snap_index, so the reverse order would corrupt the
                # index mapping on restart)
                self._persist_snapshot(state)
                self._persist_log()
            stats = (len(self.log), self._log_bytes, self.snap_index)
        if self.on_log_stats is not None:
            # outside _lock: the observer touches metrics, and metrics
            # must never nest under the raft lock
            self.on_log_stats(*stats)

    # -- client API ---------------------------------------------------------
    def propose(self, cmd: dict, timeout: float = 3.0):
        """Append cmd to the replicated log; block until it is committed and
        applied; return apply_fn's result.  Raises NotLeaderError on a
        non-leader, RpcError on commit timeout or lost leadership."""
        with self._lock:
            if self.role != LEADER or self._partitioned:
                raise NotLeaderError(self.leader_id
                                     if self.leader_id != self.self_addr
                                     else "")
            fut = _Future()
            index = self.last_index + 1
            self._futures[index] = fut
            self._append_local(cmd)
        self._broadcast()
        if self.quorum == 1:
            self._apply_committed()
        if not fut.wait(timeout):
            with self._lock:
                self._futures.pop(index, None)
            raise RpcError("raft commit timeout (no quorum?)")
        if fut.error:
            raise fut.error
        return fut.result

    # -- RPC handlers (registered on the master's RpcServer) ----------------
    def handle_request_vote(self, req: dict) -> dict:
        with self._lock:
            if self._partitioned:
                raise RpcError("partitioned")
            if req["term"] > self.term:
                self._become_follower(req["term"])
            granted = False
            if req["term"] == self.term \
                    and self.voted_for in (None, req["candidate"]):
                # §5.4.1 up-to-date check
                my_last_t = self._term_at(self.last_index)
                ok = (req["last_log_term"] > my_last_t
                      or (req["last_log_term"] == my_last_t
                          and req["last_log_index"] >= self.last_index))
                if ok:
                    granted = True
                    self.voted_for = req["candidate"]
                    self._persist_meta()
                    self._election_deadline = self._rand_deadline()
            return {"term": self.term, "granted": granted}

    def handle_append_entries(self, req: dict) -> dict:
        with self._lock:
            if self._partitioned:
                raise RpcError("partitioned")
            if req["term"] < self.term:
                return {"term": self.term, "ok": False,
                        "last": self.last_index}
            if req["term"] > self.term or self.role != FOLLOWER:
                self._become_follower(req["term"])
            self.leader_id = req["leader"]
            self._election_deadline = self._rand_deadline()
            prev = req["prev_index"]
            if prev > self.last_index:
                return {"term": self.term, "ok": False,
                        "last": self.last_index}
            if prev > self.snap_index \
                    and self._term_at(prev) != req["prev_term"]:
                # conflicting suffix: drop it and ask for earlier entries
                self.log = [e for e in self.log if e["i"] < prev]
                self._recount_log_bytes()
                self._persist_log()
                return {"term": self.term, "ok": False,
                        "last": self.last_index}
            truncated = False
            appended: list[dict] = []
            for e in req["entries"]:
                if e["i"] <= self.snap_index:
                    continue     # snapshot already covers it
                if e["i"] <= self.last_index:
                    if self._term_at(e["i"]) != e["t"]:
                        self.log = [x for x in self.log if x["i"] < e["i"]]
                        self.log.append(e)
                        truncated = True
                else:
                    self.log.append(e)
                    self._log_bytes += self._entry_bytes(e)
                    appended.append(e)
            if truncated:
                self._recount_log_bytes()
                self._persist_log()
            elif appended:
                for e in appended:
                    self._persist_append(e)
            if req["commit"] > self.commit_index:
                # bound by the last index THIS rpc covers — a stale
                # uncommitted suffix past it must not be committed
                covered = req["entries"][-1]["i"] if req["entries"] \
                    else req["prev_index"]
                self.commit_index = max(
                    self.commit_index,
                    min(req["commit"], max(covered, self.snap_index)))
            resp = {"term": self.term, "ok": True, "last": self.last_index}
        self._apply_committed()
        return resp

    def handle_install_snapshot(self, req: dict) -> dict:
        with self._lock:
            if self._partitioned:
                raise RpcError("partitioned")
            if req["term"] < self.term:
                return {"term": self.term}
            if req["term"] > self.term or self.role != FOLLOWER:
                self._become_follower(req["term"])
            self.leader_id = req["leader"]
            self._election_deadline = self._rand_deadline()
            if req["snap_index"] > self.snap_index:
                self.restore_fn(req["state"])
                self.snap_index = req["snap_index"]
                self.snap_term = req["snap_term"]
                self._snap_state = req["state"]
                self.log = [e for e in self.log
                            if e["i"] > self.snap_index]
                self._recount_log_bytes()
                self.commit_index = max(self.commit_index, self.snap_index)
                self.last_applied = max(self.last_applied, self.snap_index)
                # snapshot before log — same crash-safety order as
                # _maybe_compact
                self._persist_snapshot(req["state"])
                self._persist_log()
            return {"term": self.term}

    # -- transport ----------------------------------------------------------
    def _call(self, peer: str, method: str, req: dict,
              timeout: float) -> dict:
        if self._partitioned:
            raise RpcError("partitioned")
        return POOL.client(peer, "Raft").call(method, req, timeout=timeout)
