"""The verb window shared by seal and repair traffic: verbs run back to
back until --seconds have passed (one in flight then completes and
counts), each under a trace id of its own; between verbs the driver
re-arms the volume off the clock."""

from __future__ import annotations

import time

import google_crc32c

from seaweedfs_tpu.util import tracing

from .. import cluster as cl
from .. import core


def crc_file(path: str, block: int = 64 << 20) -> int:
    crc = 0
    with open(path, "rb") as f:
        while chunk := f.read(block):
            crc = google_crc32c.extend(crc, chunk)
    return crc


def loop(run, op, after, rearm) -> core.Window:
    """op(run) -> verb output; after(run, record) inspects the result off
    the clock; rearm(run) restores the volume for the next verb."""
    w = core.Window(codec_before=core.codec_counters())
    c = run.state["cluster"]
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    with core.span("window"):
        while True:
            tid = tracing.new_trace_id()
            rec = {"tid": tid, "wall_start": time.time()}
            w.attempted += 1
            try:
                with tracing.trace_scope(tid), core.span("verb"):
                    s = time.perf_counter()
                    rec["out"] = op(run)
                    rec["seconds"] = time.perf_counter() - s
            except Exception as e:     # the verb failed: the run is over
                core.log(f"verb failed: {type(e).__name__}: {e}")
                w.failed += 1
                break
            w.verbs.append(rec)
            with core.span("record"):
                after(run, rec)
                w.spans += cl.spans(c, tid)
            if time.perf_counter() >= deadline:
                break
            with core.span("rearm"):
                s = time.perf_counter()
                try:
                    rearm(run)
                except Exception as e:  # the volume is in no known state
                    core.log(f"re-arm failed: {type(e).__name__}: {e}")
                    w.failed += 1
                    break
                rec["rearm_seconds"] = time.perf_counter() - s
    w.seconds = time.perf_counter() - t0
    w.codec_after = core.codec_counters()
    rearms = [v["rearm_seconds"] for v in w.verbs if "rearm_seconds" in v]
    core.log(f"window {w.seconds:.3f}s: {len(w.verbs)} verbs "
             f"{[round(v['seconds'], 3) for v in w.verbs]} s, re-arm "
             f"{[round(r, 3) for r in rearms]} s")
    return w


def rate_gbps(w: core.Window) -> float:
    """GB of .dat behind the completed verbs over their summed time."""
    done = [v for v in w.verbs if v.get("complete")]
    t = sum(v["seconds"] for v in done)
    return sum(v["bytes"] for v in done) / t / 1e9 if t else 0.0
