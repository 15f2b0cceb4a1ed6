"""The harness: one process runs one cell once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in BENCHMARK.json:

  configs/<config>.json   the deployment (sizes, guarantees, source)
  traffic/<mix>.json      the mix; its "driver" names drivers/<driver>.py,
                          which sets up, drives the window, re-arms and
                          checks the answers against reference/
  layers/<metric>.json    a per-layer metric; its "reader" names
                          readers/<reader>.py, given the rest as params

A run: set-up (JAX, cluster, fixture, warm-up: setup_s), the measured
window (--seconds; profiler on with --trace 1), the program's state
freed, then the check against the reference.  The last stdout line is
the result; the numbers compared, each beside its limit, are the last
lines of stderr and the last key of the result."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """JAX found no accelerator of the kind, or too few of them."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


@dataclass
class Compared:
    """One number the check compares, and its limit (value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Window:
    """What the measured window produced, for the metrics and the check."""
    seconds: float = 0.0
    verbs: list[dict] = field(default_factory=list)
    reads: list[dict] = field(default_factory=list)
    spans: list[dict] = field(default_factory=list)
    codec_before: dict = field(default_factory=dict)
    codec_after: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0


@dataclass
class Run:
    """One run as the drivers see it; `state` is theirs."""
    config: dict
    mix: dict
    seed: int
    seconds: float
    scratch: str
    expect: dict = field(default_factory=dict)
    state: dict = field(default_factory=dict)


# -- compile counting (copied from chip_smoke.py's listener) ----------------

class CompileCounter:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += seconds


_COUNTER: "CompileCounter | None" = None


def compile_counter() -> CompileCounter:
    """One listener per process (JAX keeps listeners for its lifetime)."""
    global _COUNTER
    if _COUNTER is None:
        import jax
        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
    return _COUNTER


@contextmanager
def span(name: str):
    """A host span in the profiler's trace, named bench.<name>."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


# -- devices ---------------------------------------------------------------

def open_devices(platform: str, chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(f"JAX found no {platform} (platform "
                       f"{devices[0].platform})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices


def memory_peak_bytes(devices: list) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks or [0]))


def peak(device_kind: str, key: str) -> float:
    """One peak of the chip, from peaks.json; an unknown kind is an
    error, never a default."""
    table = load_json(HERE, "peaks.json")["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return float(table[device_kind][key])


# -- codec counters (the program's own registry) ----------------------------

def codec_counters() -> dict:
    """{(series, backend, op): value} from the codec registry."""
    from seaweedfs_tpu.ops.codec import codec_metrics
    from seaweedfs_tpu.stats import parse_exposition
    out = {}
    for name, labels, value in parse_exposition(
            codec_metrics().registry.render()):
        if "backend" in labels and "le" not in labels:
            out[(name, labels["backend"], labels.get("op", ""))] = value
    return out


def counter_delta(w: Window, series: str, op: str = "",
                  backend: str = "") -> float:
    total = 0.0
    for key, v in w.codec_after.items():
        name, b, o = key
        if name == series and (not op or o == op) \
                and (not backend or b == backend):
            total += v - w.codec_before.get(key, 0.0)
    return total


def dispatches(w: Window) -> dict:
    """{(backend, op): dispatches in the window} where non-zero."""
    out = {}
    for (name, b, o), v in w.codec_after.items():
        if name == "seaweedfs_codec_dispatch_total":
            d = v - w.codec_before.get((name, b, o), 0.0)
            if d:
                out[(b, o)] = d
    return out


def dispatch_compared(w: Window, op: str, backend: str) -> list[Compared]:
    """The window's `op` work ran on `backend` and on nothing else."""
    ran = dispatches(w)
    log(f"codec dispatches in the window: "
        f"{ {f'{b}/{o}': v for (b, o), v in ran.items()} }")
    other = sum(v for (b, o), v in ran.items()
                if o == op and b != backend)
    return [Compared(f"{op}_dispatch_not_{backend}", other, 0),
            Compared(f"{op}_dispatch_missing",
                     float(ran.get((backend, op), 0) == 0), 0)]


# -- the run ---------------------------------------------------------------

def cell_entry(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, group: str, cell: str) -> list[dict]:
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def place_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program compiled, however short its compile.  No size limit:
    with one, JAX keeps an access-time file beside each entry, and on
    the chip hosts' filesystem an entry without one made every later
    write fail (PR 22's first chip runs recompiled everything)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             platform: str = "tpu", t_start: "float | None" = None,
             expect: "dict | None" = None, sizes: "dict | None" = None
             ) -> dict:
    """One run of one cell; returns the result object.  `platform` is
    "tpu" for every measured run; benchmark/tests/ pass "cpu", with
    `expect` naming the CPU's codec paths in place of the mix's and
    `sizes` shrinking the configuration to what a test can hold."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = benchmark_spec()
    cell = cell_entry(spec, name)
    config = {**load_json(HERE, "configs", cell["config"] + ".json"),
              **(sizes or {})}
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    os.environ.setdefault("WEED_LOCKDEP", "0")   # a test instrument only
    place_compile_cache()
    devices = open_devices(platform, cell["chips"])
    counter = compile_counter()
    scratch = tempfile.mkdtemp(prefix="bench-")
    run = Run(config, mix, seed, seconds, scratch,
              {**mix.get("expect", {}), **(expect or {})})
    try:
        with span("setup"):
            driver.setup(run)
        setup_s = time.perf_counter() - t_start
        log(f"setup {setup_s:.3f}s, {counter.count} compiles "
            f"({counter.seconds:.1f}s)")
        before = counter.count
        trace_dir = os.path.join(scratch, "trace")
        if trace:
            from . import devtrace as tr
            tr.start(trace_dir)
        try:
            w = driver.window(run)
        finally:
            if trace:
                tr.stop()
        log(f"compiles inside the window: {counter.count - before}")
        mem = memory_peak_bytes(devices)
        release(run)
        compared = driver.check(run, w)
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind,
               "count": len(devices), "memory_peak_bytes": mem}
        result: dict = {"correct": all(c.ok for c in compared),
                        "attempted": w.attempted, "failed": w.failed}
        if trace:
            from . import devtrace as tr
            t = tr.load(trace_dir)
            dev["busy_s"] = tr.mean_busy_seconds(t)
            dev["window_s"] = w.seconds
            result["metrics"] = layer_metrics(spec, name, w, t, devices)
            window = next(((s, s + d) for n, s, d in t.host
                           if n == "bench.window"), (0.0, 0.0))
            result["breakdown"] = {
                "device_ops": tr.top_ops(t),
                "idle_gaps": tr.idle_gaps(t, window,
                                          labels=gap_labels(w, t))}
        else:
            result["metrics"] = end_to_end(spec, name, driver, w, setup_s)
        result["device"] = dev
        result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                              for c in compared}
        for c in compared:
            log(f"compared {c.name} = {c.value} (limit {c.limit})"
                f"{'' if c.ok else '  FAILED'}")
        return result
    finally:
        release(run)
        shutil.rmtree(scratch, ignore_errors=True)


def release(run: Run) -> None:
    """Stop the system under test (every server and its threads)."""
    cluster = run.state.pop("cluster", None)
    if cluster is not None:
        cluster.stop()


def end_to_end(spec: dict, cell: str, driver, w: Window,
               setup_s: float) -> dict:
    values = driver.end_to_end(w)
    values["setup_s"] = setup_s
    out = {}
    for m in metrics_for(spec, "end_to_end", cell):
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def layer_metrics(spec: dict, cell: str, w: Window, t, devices) -> dict:
    out = {}
    for m in metrics_for(spec, "per_layer", cell):
        params = load_json(HERE, "layers", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{params.pop('reader')}")
        value = reader.read(w, t, devices, **params)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def gap_labels(w: Window, t) -> list[tuple[str, float, float]]:
    """Host spans on the profile's clock: the benchmark's own, and the
    program's RPC spans moved onto it through the verb spans, whose wall
    start the driver recorded."""
    labels = [(n[len("bench."):], s, d) for n, s, d in t.host]
    verbs = [x for x in t.host if x[0] == "bench.verb"]
    walls = [v.get("wall_start") for v in w.verbs]
    if verbs and len(verbs) == len(walls) and walls[0] is not None:
        offset = verbs[0][1] - walls[0]
        for sp in w.spans:
            labels.append((sp["name"].rsplit("/", 1)[-1],
                           sp["start"] + offset, sp["duration_ms"] / 1e3))
    return labels


def main(argv: "list[str] | None" = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoDevice as e:
        log(f"benchmark: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
