"""Cross-plane request tracing — trace ids, span trees, per-server ring
buffers.

The reference has no distributed tracing; its operational story is
per-store request stats (Haystack) and per-layer latency accounting
(Tectonic).  This module gives the three planes (filer -> volume ->
master) one correlating primitive:

- A request entering any HTTP surface adopts the `X-Trace-Id` header or
  mints a fresh id; the id rides a thread-local so every downstream hop
  made while serving that request — chunk uploads, master Assigns,
  replica fan-outs — carries it automatically (util/http.py injects the
  header on outgoing requests, pb/rpc.py attaches `x-trace-id` gRPC
  metadata).
- Every recorded span carries a `span_id` and the `parent_id` of the hop
  that caused it: servers mint a span id per request, install it as the
  thread's ambient span, and clients forward it as the parent
  (`X-Span-Id` header / `x-span-id` metadata / the extended TCP frame's
  trace slot).  `assemble_tree` turns any collection of spans for one
  trace back into the cross-server call tree.
- Each server owns a `Tracer`: a bounded in-memory span ring buffer
  (newest wins, O(1) memory) served as JSON at `GET /debug/traces`
  (filters: `?id=` / `?trace_id=`, `?min_ms=`, `?limit=`), plus a
  slow-request log through util/weedlog.py for spans over a configurable
  threshold (`WEED_TRACE_SLOW_MS`, default 1000).
- Work handed to a persistent executor loses the thread-local context;
  wrap the task with `propagate()` so replica fan-out and repair workers
  keep the submitting request's trace.

The raw-TCP fast path carries the trace in the extended 'X' frame's
optional trace slot (volume_server/tcp.py) — the former "deliberate gap"
is closed: frame hops appear as real child spans.

Inside a span, `stage(name)` times one step of the work (a disk read,
the base64/JSON framing of a message, a codec call's host-to-device
pack) and sums its seconds into the span's `<name>_s` tag; `add(key,
n)` sums a count (`bytes`).  The tags ride the open span's thread-local
context, so work handed on through `propagate()` adds to the span that
handed it on, and they are recorded with the span: /debug/traces shows
where a slow `VolumeEcShardsCopy` spent its time.  When JAX is loaded in
the process each stage is also a `jax.profiler.TraceAnnotation` named
`weed.<name>`, so a profile taken with `/debug/profile` shows the
stages on the device trace's own clock.

`WEED_TRACE=0` (or `set_enabled(False)`) turns span recording, stage
tags and propagation off process-wide.  Running the same load with
`WEED_TRACE=1` and `WEED_TRACE=0` prices what tracing costs.
"""

from __future__ import annotations

import os
import sys
import threading
from seaweedfs_tpu.util import locks
import time
from collections import deque
from contextlib import contextmanager

from .weedlog import logger

LOG = logger(__name__)

TRACE_HEADER = "X-Trace-Id"
SPAN_HEADER = "X-Span-Id"
TRACE_METADATA_KEY = "x-trace-id"  # grpc metadata keys must be lowercase
SPAN_METADATA_KEY = "x-span-id"
DEFAULT_CAPACITY = 1024

_ENABLED = os.environ.get("WEED_TRACE", "1") != "0"


def enabled() -> bool:
    """Process-wide tracing switch (WEED_TRACE env, or set_enabled to
    flip it in a running process)."""
    return _ENABLED


def set_enabled(flag: bool) -> None:
    global _ENABLED
    _ENABLED = bool(flag)


def slow_threshold_seconds() -> float:
    """The slow-request log knob: spans at least this long are logged
    (WEED_TRACE_SLOW_MS env; 0 disables the log entirely)."""
    try:
        return float(os.environ.get("WEED_TRACE_SLOW_MS", "1000")) / 1000.0
    except ValueError:
        return 1.0


# id minting is on the per-request hot path (two ids per served
# request); a urandom-seeded PRNG is ~16x cheaper per id than
# os.urandom and ids only need uniqueness, not unpredictability.
# getrandbits on a Random instance is one GIL-atomic C call, so no lock.
import random as _random

_ID_RNG = _random.Random(int.from_bytes(os.urandom(8), "little"))

# ids we mint are 16 hex chars; adopted ids are CLIENT-CONTROLLED
# (X-Trace-Id header / x-trace-id metadata) and must be bounded before
# they ride internal protocols — the TCP frame's trace slot is a u8
# length, and an unbounded id would bloat every span dict
MAX_ID_LEN = 128


def clamp_id(value: str) -> str:
    """Bound an externally-supplied trace/span id."""
    return value[:MAX_ID_LEN] if len(value) > MAX_ID_LEN else value


def new_trace_id() -> str:
    return f"{_ID_RNG.getrandbits(64):016x}"


def new_span_id() -> str:
    return f"{_ID_RNG.getrandbits(64):016x}"


_ctx = threading.local()


def current_trace_id() -> str:
    """The ambient trace id for this thread ('' outside any request)."""
    return getattr(_ctx, "trace_id", "")


def current_span_id() -> str:
    """The ambient span id — the span a downstream hop should name as
    its parent ('' outside any request)."""
    return getattr(_ctx, "span_id", "")


@contextmanager
def trace_scope(trace_id: str, span_id: str = "",
                tags: "dict | None" = None):
    """Install `trace_id` (and optionally `span_id`) as the thread's
    ambient trace for the block — outgoing HTTP/gRPC/frame calls inside
    it propagate both.  `tags` is the open span's tag dict, which
    `stage()` and `add()` sum into (None: no span collects them).
    Nests: the previous context is restored on exit, so a handler
    serving request B on a thread that still owns request A's suspended
    stream is labeled B only for its own duration."""
    prev_t = getattr(_ctx, "trace_id", "")
    prev_s = getattr(_ctx, "span_id", "")
    prev_tags = getattr(_ctx, "tags", None)
    _ctx.trace_id = trace_id
    _ctx.span_id = span_id
    _ctx.tags = tags
    try:
        yield trace_id
    finally:
        _ctx.trace_id = prev_t
        _ctx.span_id = prev_s
        _ctx.tags = prev_tags


# stage() and add() may sum into one span's tags from several threads
# (the encoder's writer thread, replica fan-out workers); a read-modify-
# write of a dict entry is not atomic under the GIL
_TAGS_LOCK = threading.Lock()


def add(key: str, value: float) -> None:
    """Sum `value` into tag `key` of the thread's open span (no-op
    outside a span, or with tracing off)."""
    if not _ENABLED:
        return
    tags = getattr(_ctx, "tags", None)
    if tags is not None:
        with _TAGS_LOCK:
            tags[key] = tags.get(key, 0) + value


def tag(key: str, value) -> None:
    """Set tag `key` of the thread's open span to `value` (no-op outside
    a span, or with tracing off)."""
    if not _ENABLED:
        return
    tags = getattr(_ctx, "tags", None)
    if tags is not None:
        with _TAGS_LOCK:
            tags[key] = value


def copy_tags(tags: "dict | None") -> dict:
    """A closing span's tags, copied under the lock stage()/add() sum
    under (a propagated task may still be adding)."""
    if not tags:
        return {}
    with _TAGS_LOCK:
        return dict(tags)


_annotation_cls = None


def _annotation():
    """jax.profiler.TraceAnnotation once JAX is loaded in the process;
    None before (this module never imports JAX: the master, filer and
    gateways load it too)."""
    global _annotation_cls
    if _annotation_cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation_cls = prof.TraceAnnotation
    return _annotation_cls


class stage:
    """`with stage("read"):` — one step of the work inside a span.

    On exit its wall time is summed into the open span's `read_s` tag
    and, when given, passed to `observe(seconds)` (a metrics counter
    that counts whether or not tracing is on).  While JAX is loaded the
    block is also a profiler annotation named `weed.read`.  With tracing
    off and no `observe`, it costs a flag check and the annotation."""

    __slots__ = ("name", "observe", "_ann", "_t0")

    def __init__(self, name: str, observe=None):
        self.name = name
        self.observe = observe
        self._ann = None
        self._t0 = 0.0

    def __enter__(self) -> "stage":
        cls = _annotation()
        if cls is not None:
            self._ann = cls("weed." + self.name)
            self._ann.__enter__()
        if _ENABLED or self.observe is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if not self._t0:
            return
        dt = time.perf_counter() - self._t0
        if self.observe is not None:
            self.observe(dt)
        add(self.name + "_s", dt)


def propagate(fn):
    """Wrap `fn` so it runs under the SUBMITTING thread's ambient trace.

    Thread-locals do not cross executor boundaries: a replica fan-out
    submitted to the persistent pool (volume_server) or a repair job on
    the planner pool would otherwise run traceless and its downstream
    hops would mint unrelated ids.  Capture happens at wrap time (the
    submit), installation at call time (the worker)."""
    tid = current_trace_id()
    sid = current_span_id()
    tags = getattr(_ctx, "tags", None)
    if not tid:
        return fn

    def wrapped(*args, **kwargs):
        with trace_scope(tid, sid, tags):
            return fn(*args, **kwargs)
    return wrapped


class Tracer:
    """Per-server span sink: bounded ring buffer + slow log.

    A span is a plain dict (JSON-ready for /debug/traces):
      {trace_id, span_id, parent_id, name, service, start, duration_ms,
       status, ...tags}.
    Recording is lock-cheap (deque append is atomic; the lock only
    guards snapshot iteration vs rotation)."""

    def __init__(self, service: str, capacity: int = DEFAULT_CAPACITY,
                 slow_seconds: "float | None" = None):
        self.service = service
        self.capacity = capacity
        self.slow_seconds = (slow_threshold_seconds()
                             if slow_seconds is None else slow_seconds)
        self.slow_count = 0
        self._spans: deque = deque(maxlen=capacity)
        self._lock = locks.Lock("Tracer._lock")

    def record(self, name: str, trace_id: str, start: float,
               duration: float, status: str = "ok",
               slow_log: bool = True, span_id: str = "",
               parent_id: str = "", **tags) -> None:
        """`slow_log=False` keeps the span out of the slow-request log —
        for long-lived streams (heartbeats, metadata subscriptions) whose
        duration is connection lifetime, not request latency."""
        span = {"trace_id": trace_id, "span_id": span_id,
                "parent_id": parent_id, "name": name,
                "service": self.service, "start": start,
                "duration_ms": round(duration * 1000.0, 3),
                "status": status}
        if tags:
            span.update(tags)
        with self._lock:
            self._spans.append(span)
        if slow_log and self.slow_seconds > 0 \
                and duration >= self.slow_seconds:
            self.slow_count += 1
            LOG.warning("slow request trace=%s %s %s took %.1fms "
                        "(threshold %.0fms)", trace_id or "-",
                        self.service, name, duration * 1000.0,
                        self.slow_seconds * 1000.0)

    @contextmanager
    def span(self, name: str, trace_id: str = ""):
        """Record one span around the block; adopts the ambient trace id
        when none is given and parents under the ambient span.
        Exceptions mark the span `error` and propagate."""
        tid = trace_id or current_trace_id() or new_trace_id()
        parent = current_span_id()
        sid = new_span_id()
        # span START stays wall-clock (cross-server waterfalls align on
        # it); the DURATION is monotonic — NTP must not bend a span
        t0 = time.time()
        p0 = time.perf_counter()
        tags: dict = {}
        with trace_scope(tid, sid, tags):
            try:
                yield tid
            except BaseException:
                self.record(name, tid, t0, time.perf_counter() - p0,
                            status="error", span_id=sid,
                            parent_id=parent, **copy_tags(tags))
                raise
        self.record(name, tid, t0, time.perf_counter() - p0,
                    span_id=sid, parent_id=parent, **copy_tags(tags))

    def snapshot(self, trace_id: str = "", limit: int = 0,
                 min_ms: float = 0.0) -> list[dict]:
        """Newest-last span dicts, optionally filtered to one trace,
        to spans at least `min_ms` long, and trimmed to the most recent
        `limit`."""
        with self._lock:
            spans = list(self._spans)
        if trace_id:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        if min_ms > 0:
            spans = [s for s in spans if s["duration_ms"] >= min_ms]
        if limit > 0:
            spans = spans[-limit:]
        return spans

    def to_dict(self, trace_id: str = "", limit: int = 0,
                min_ms: float = 0.0) -> dict:
        """The GET /debug/traces reply body."""
        spans = self.snapshot(trace_id=trace_id, limit=limit,
                              min_ms=min_ms)
        return {"service": self.service, "capacity": self.capacity,
                "slow_threshold_ms": round(self.slow_seconds * 1000.0),
                "span_count": len(spans), "spans": spans}


def traces_http_handler(tracer: Tracer):
    """The GET /debug/traces handler, shared by all three planes.
    `?id=` is the short alias of `?trace_id=`; `?min_ms=` keeps only
    spans at least that long."""
    from .http import Response  # local import: http.py imports tracing

    def handler(req):
        try:
            min_ms = float(req.qs("min_ms", "0") or 0)
        except ValueError:
            min_ms = 0.0
        return Response.json(tracer.to_dict(
            trace_id=req.qs("trace_id") or req.qs("id"),
            limit=int(req.qs("limit", "0") or 0),
            min_ms=min_ms))
    return handler


def traces_rpc_handler(tracer: Tracer):
    """The DebugTraces unary RPC handler (shell cluster.trace reaches
    filers/masters through their gRPC address)."""
    def handler(req: dict) -> dict:
        return tracer.to_dict(trace_id=req.get("trace_id", ""),
                              limit=int(req.get("limit", 0) or 0),
                              min_ms=float(req.get("min_ms", 0) or 0))
    return handler


# -- cross-server span-tree assembly ----------------------------------------

def assemble_tree(spans: list[dict]) -> list[dict]:
    """Link spans (one trace, any servers) into their call tree.

    Returns the root spans (parent absent from the set), each with a
    `children` list sorted by start time and a `self_ms` field (own
    duration minus the directly-nested child time) — the per-hop
    attribution Tectonic's per-layer accounting answers.  Orphans whose
    parent span fell out of a ring buffer surface as extra roots, so a
    partially-rotated trace still renders instead of vanishing."""
    by_id: dict[str, dict] = {}
    for s in spans:
        node = dict(s)
        node["children"] = []
        sid = node.get("span_id") or ""
        if sid:
            by_id[sid] = node
        else:
            # legacy/anonymous span: still shows up as a root
            by_id[f"anon-{id(node)}"] = node
    roots: list[dict] = []
    for node in by_id.values():
        parent = by_id.get(node.get("parent_id") or "")
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=lambda n: n.get("start", 0.0))
        child_ms = sum(c.get("duration_ms", 0.0)
                       for c in node["children"])
        node["self_ms"] = round(
            max(0.0, node.get("duration_ms", 0.0) - child_ms), 3)
    roots.sort(key=lambda n: n.get("start", 0.0))
    return roots


def render_tree(roots: list[dict]) -> str:
    """Indented waterfall of an assembled span tree: one line per hop
    with service, name, total and self time."""
    lines: list[str] = []

    def walk(node: dict, depth: int) -> None:
        lines.append(
            "%s%-8s %-40s %8.2fms (self %6.2fms) %s" % (
                "  " * depth, node.get("service", "?"),
                node.get("name", "?")[:40],
                node.get("duration_ms", 0.0),
                node.get("self_ms", 0.0),
                node.get("status", "")))
        for child in node["children"]:
            walk(child, depth + 1)

    for root in roots:
        walk(root, 0)
    return "\n".join(lines)
