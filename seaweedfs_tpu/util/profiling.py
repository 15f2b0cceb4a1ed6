"""Profiling: one-shot cpuprofile/memprofile hooks AND the always-on
sampling profiler behind `GET /debug/profile`.

One-shot (capability-equivalent to the reference's pprof setup,
weed/util/grace/pprof.go:11-55): `-cpuprofile FILE` records cProfile
data and dumps pstats on exit (read with `python -m pstats FILE` or
snakeviz); `-memprofile FILE` starts tracemalloc and writes the top
allocation sites.  Both dump on normal exit AND on SIGTERM/SIGINT.

Thread coverage: on CPython >= 3.12 cProfile rides sys.monitoring,
which is PROCESS-GLOBAL — one enable() in the main thread captures
every thread, including the HTTP/TCP handler threads where server work
actually happens (verified by test_profiling_captures_handler_threads).
That also means only one profiler can exist per process: -cpuprofile
cannot be combined with an outer profiler.

Continuous (`SamplingProfiler`): a daemon thread walks
`sys._current_frames()` at ~WEED_PROFILE_HZ (default 100) into bounded
collapsed-stack counters — always on, a few percent of one core at
worst, so "where is the GIL wall" is answerable from a live
cluster.  `GET /debug/profile?seconds=N` diffs
the counters over an N-second window and serves flamegraph-ready
collapsed lines (`a;b;c 12` — pipe straight into flamegraph.pl).  The
sampler also estimates GIL/scheduler contention from sample-interval
overruns: when the sampling thread itself cannot run on schedule, the
interpreter is saturated — the overrun fraction rides the
`X-Profile-Overrun-Pct` response header.  `WEED_PROFILE=0` disables."""

from __future__ import annotations

import atexit
import cProfile
import os
import signal
import sys
import threading
from seaweedfs_tpu.util import locks
import time
import tracemalloc

_ACTIVE: dict = {}


def setup_profiling(cpuprofile: str = "", memprofile: str = "") -> None:
    if not (cpuprofile or memprofile) or _ACTIVE:
        return
    if cpuprofile:
        prof = cProfile.Profile()
        prof.enable()
        _ACTIVE["cpu"] = (prof, cpuprofile)
    if memprofile:
        tracemalloc.start(25)
        _ACTIVE["mem"] = memprofile
    atexit.register(dump_profiles)
    for sig in (signal.SIGTERM, signal.SIGINT):
        old = signal.getsignal(sig)

        def handler(signum, frame, _old=old):
            dump_profiles()
            if _old is signal.SIG_IGN:
                return           # was a no-op before; stay a no-op
            if callable(_old):
                _old(signum, frame)
            else:                # SIG_DFL: default disposition is exit
                raise SystemExit(128 + signum)
        try:
            signal.signal(sig, handler)
        except ValueError:
            pass  # non-main thread: atexit still covers normal exit


def dump_profiles() -> None:
    cpu = _ACTIVE.pop("cpu", None)
    if cpu:
        prof, path = cpu
        prof.disable()
        prof.dump_stats(path)
    mem = _ACTIVE.pop("mem", None)
    if mem:
        snap = tracemalloc.take_snapshot()
        with open(mem, "w") as f:
            for stat in snap.statistics("lineno")[:100]:
                f.write(f"{stat}\n")
        tracemalloc.stop()


# -- continuous sampling profiler -------------------------------------------

def _default_hz() -> float:
    try:
        return max(1.0, float(os.environ.get("WEED_PROFILE_HZ", "100")))
    except ValueError:
        return 100.0


class SamplingProfiler:
    """Wall-clock stack sampler over every thread in the process.

    Each tick grabs `sys._current_frames()` and folds each thread's
    stack into a collapsed-format counter keyed
    `thread-name;mod.func;mod.func;...` (root first, leaf last — the
    orientation flamegraph.pl expects).  Memory is bounded: at most
    `max_stacks` distinct stacks (overflow folds into `(overflow)`),
    frame labels memoized per code object, depth capped.

    Overrun accounting: the loop records how late each sample fires.
    With a GIL, a sampler that cannot hold its cadence means runnable
    Python threads outnumber the interpreter — the overrun fraction is
    a cheap contention estimator that needs no interpreter hooks."""

    def __init__(self, hz: "float | None" = None, max_stacks: int = 512,
                 max_depth: int = 48, max_threads_per_tick: int = 32):
        self.hz = hz if hz is not None else _default_hz()
        self.interval = 1.0 / self.hz
        self.max_stacks = max_stacks
        self.max_depth = max_depth
        # per-tick work must stay bounded no matter how many threads the
        # process accumulates (a long-lived test process reaches
        # hundreds): above this count each tick walks a rotating slice,
        # trading per-thread sampling rate for a flat overhead ceiling
        self.max_threads_per_tick = max_threads_per_tick
        self._rotate_cursor = 0
        self._counts: dict[str, int] = {}
        # (id(code), co_name) -> "mod.func": co_name in the key keeps a
        # recycled code-object ADDRESS from resurrecting another
        # function's label; bounded below like _thread_names
        self._labels: dict[tuple, str] = {}
        self._thread_names: dict[int, str] = {}
        self._lock = locks.Lock("SamplingProfiler._lock")
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        self.samples = 0
        self.overruns = 0
        self.overrun_seconds = 0.0
        self.started_at = 0.0

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "SamplingProfiler":
        t = self._thread
        if t is not None and t.is_alive():
            if not self._stop.is_set():
                return self          # already running
            # stop() was called but the old thread is still draining its
            # in-flight tick: join it (bounded by one interval), then
            # restart — returning here would leave _stop set and the
            # sampler dead the moment the drain finishes
            t.join()
        self._stop.clear()
        self.started_at = time.time()
        self._thread = threading.Thread(target=self._loop,
                                        daemon=True,
                                        name="weed-profiler")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive() \
            and not self._stop.is_set()

    # -- sampling -----------------------------------------------------------
    def _loop(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.interval):
            now = time.monotonic()
            elapsed = now - last
            last = now
            if elapsed > 1.5 * self.interval:
                # the sampler itself got descheduled: the interpreter is
                # saturated (GIL) or the box is — either way, a signal
                self.overruns += 1
                self.overrun_seconds += elapsed - self.interval
            self._sample()

    def _sample(self) -> None:
        me = threading.get_ident()
        try:
            frames = sys._current_frames()
        except Exception:
            return
        items = [(tid, f) for tid, f in frames.items() if tid != me]
        # break the self-referential cycle NOW: the dict contains THIS
        # thread's frame, and that frame's `frames` local holds the
        # dict — left alone, every tick leaks one cycle pinning a
        # full-process frame snapshot (and every multi-MB local caught
        # in it, e.g. in-flight 8MB chunk bodies) until a gen-2 GC.
        # Found via the large-object RSS drill: the "always-on <5%"
        # sampler was retaining hundreds of MB between collections.
        frames.clear()
        cap = self.max_threads_per_tick
        if len(items) > cap:
            # rotating slice: uniform coverage across ticks, bounded
            # cost per tick
            items.sort(key=lambda tf: tf[0])
            at = self._rotate_cursor % len(items)
            self._rotate_cursor = at + cap
            items = (items + items)[at:at + cap]
        with self._lock:
            self.samples += 1
            for tid, frame in items:
                key = self._collapse(tid, frame)
                if key in self._counts:
                    self._counts[key] += 1
                elif len(self._counts) < self.max_stacks:
                    self._counts[key] = 1
                else:
                    self._counts["(overflow)"] = \
                        self._counts.get("(overflow)", 0) + 1

    def _collapse(self, tid: int, frame) -> str:
        parts: list[str] = []
        depth = 0
        while frame is not None and depth < self.max_depth:
            code = frame.f_code
            key = (id(code), code.co_name)
            label = self._labels.get(key)
            if label is None:
                mod = os.path.basename(code.co_filename)
                if mod.endswith(".py"):
                    mod = mod[:-3]
                if len(self._labels) > 8192:
                    # ephemeral code objects (per-request closures)
                    # would otherwise grow this for the process lifetime
                    self._labels.clear()
                label = self._labels[key] = f"{mod}.{code.co_name}"
            parts.append(label)
            frame = frame.f_back
            depth += 1
        parts.append(self._thread_name(tid))
        parts.reverse()           # root (thread) first, leaf last
        return ";".join(parts)

    def _thread_name(self, tid: int) -> str:
        name = self._thread_names.get(tid)
        if name is None:
            t = getattr(threading, "_active", {}).get(tid)
            name = t.name if t is not None else f"thread-{tid}"
            # unnamed worker threads get generic "Thread-N" names that
            # explode stack cardinality; collapse them into one root
            if name.startswith("Thread-"):
                name = "Thread"
            self._thread_names[tid] = name
            if len(self._thread_names) > 4096:
                self._thread_names.clear()
        return name

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {"counts": dict(self._counts),
                    "samples": self.samples,
                    "overruns": self.overruns,
                    "overrun_seconds": self.overrun_seconds,
                    "at": time.monotonic()}

    def window(self, seconds: float) -> dict:
        """Sample for `seconds`, then report only that window's stacks:
        {counts, samples, seconds, overrun_pct}."""
        before = self.snapshot()
        self._stop.wait(max(0.05, min(seconds, 30.0)))
        after = self.snapshot()
        counts = {}
        for key, n in after["counts"].items():
            delta = n - before["counts"].get(key, 0)
            if delta > 0:
                counts[key] = delta
        wall = max(1e-9, after["at"] - before["at"])
        return {"counts": counts,
                "samples": after["samples"] - before["samples"],
                "seconds": round(wall, 3),
                "overrun_pct": round(
                    100.0 * (after["overrun_seconds"]
                             - before["overrun_seconds"]) / wall, 2)}

    def collapsed(self, counts: "dict[str, int] | None" = None) -> str:
        """Flamegraph-ready collapsed text, hottest stacks first."""
        if counts is None:
            counts = self.snapshot()["counts"]
        lines = [f"{stack} {n}" for stack, n in
                 sorted(counts.items(), key=lambda kv: -kv[1])]
        return "\n".join(lines) + ("\n" if lines else "")


_SAMPLER: "SamplingProfiler | None" = None
_SAMPLER_LOCK = locks.Lock("profiling._SAMPLER_LOCK")


def sampler() -> "SamplingProfiler | None":
    """The process-wide always-on sampler; started on first server
    construction, shared by every co-located server (they live in one
    interpreter — per-server samplers would multiply the overhead for
    identical data).  None when WEED_PROFILE=0."""
    global _SAMPLER
    if os.environ.get("WEED_PROFILE", "1") == "0":
        return None
    with _SAMPLER_LOCK:
        if _SAMPLER is None:
            _SAMPLER = SamplingProfiler()
        if not _SAMPLER.running:
            _SAMPLER.start()
        return _SAMPLER


def profile_http_handler():
    """GET /debug/profile?seconds=N — collapsed stacks for an N-second
    window (default 2s, capped at 30), flamegraph.pl-ready.  Sampling
    stats ride response headers so the body stays pure collapsed
    format."""
    from .http import Response  # local import mirrors tracing's

    def handler(req):
        s = sampler()
        if s is None:
            return Response.error(
                "sampling profiler disabled (WEED_PROFILE=0)", 503)
        try:
            seconds = float(req.qs("seconds", "2") or 2)
        except ValueError:
            return Response.error("seconds must be a number", 400)
        win = s.window(seconds)
        return Response(
            200, s.collapsed(win["counts"]).encode(),
            content_type="text/plain; charset=utf-8",
            headers={"X-Profile-Samples": str(win["samples"]),
                     "X-Profile-Seconds": str(win["seconds"]),
                     "X-Profile-Hz": str(s.hz),
                     "X-Profile-Overrun-Pct": str(win["overrun_pct"])})
    return handler
