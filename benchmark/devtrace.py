"""The device trace of a run: taken with jax.profiler around the window,
reduced here to device busy time, per-kernel time and the breakdown.

Device operations are the events of the "XLA Ops" line of each
"/device:TPU:N" plane.  Their names are the HLO instruction text, which
starts with "%<op>.<n> = "; the op name (a Pallas kernel's name for a
custom call) is what the readers and the breakdown key on.  Host and
device events share one clock in the profile, so a device gap can be set
against the host spans open at that moment."""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)")


def op_name(hlo_text: str) -> str:
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


@dataclass
class Trace:
    """Device ops per device and host spans, as (name, start_s, dur_s)
    on the profile's own clock."""
    devices: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # a Python call tracer would flood it
    opts.host_tracer_level = 2        # keeps TraceAnnotation spans
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str, host_prefix: str = "bench.") -> Trace:
    """Read the newest .xplane.pb under log_dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profile written under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_name(ev.name), ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9) for ev in line.events]
            out.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host += [(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                             for ev in line.events
                             if ev.name.startswith(host_prefix)]
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, sorted."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(ops: list[tuple[str, float, float]]) -> float:
    return sum(e - s for s, e in union([(s, s + d) for _, s, d in ops]))


def mean_busy_seconds(trace: Trace) -> float:
    if not trace.devices:
        return 0.0
    return sum(busy_seconds(ops) for ops in trace.devices.values()) \
        / len(trace.devices)


def kernel_seconds(trace: Trace, kernel: str) -> float:
    """Summed device time of one op name's events, over every device."""
    return sum(d for ops in trace.devices.values()
               for name, _, d in ops if name == kernel)


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[op, seconds]] by summed device time over all devices."""
    total: dict[str, float] = {}
    for ops in trace.devices.values():
        for name, _, d in ops:
            total[name] = total.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, window: tuple[float, float],
              labels: "list[tuple[str, float, float]] | None" = None,
              n: int = 10) -> list[list]:
    """The n longest gaps in the first device's busy time inside
    `window`, each named by the innermost labelled span covering the
    gap's midpoint ("idle" when none does): [[label, seconds]]."""
    if not trace.devices:
        return []
    first = sorted(trace.devices)[0]
    busy = union([(s, s + d) for _, s, d in trace.devices[first]])
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy:
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = sorted(labels if labels is not None else trace.host,
                   key=lambda x: x[2])
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        label = next((name for name, ls, ld in spans
                      if ls <= mid <= ls + ld), "idle")
        out.append([label, e - s])
    return out
