"""Faults planted under the timed path, and the control: each must turn
`correct` false.  Used by test_faults.py on the CPU and by control.py on
the chip.  They patch the program in this process only."""

from __future__ import annotations

import importlib
from contextlib import ExitStack, contextmanager

import numpy as np

from benchmark import core


def mix(cell: str) -> dict:
    """The traffic mix of a cell, as BENCHMARK.json names it.  Besides
    what the driver reads it holds `cpu`, the codec paths the CPU takes
    in place of the mix's `expect`, and `faults`, each fault the cell
    can have and how it is planted: {"noop": <attribute>} replaces that
    name in the driver's module by a call that does nothing;
    {"alter": <codec op, or clay_repair>, "how": <how>} alters every
    answer of that op where the codec produces it."""
    spec = core.benchmark_spec()
    return core.load_json(core.HERE, "traffic",
                          core.cell_entry(spec, cell)["traffic"] + ".json")


def cpu_expect(cell: str) -> dict:
    return mix(cell)["cpu"]


def faults(cell: str) -> list[str]:
    return list(mix(cell)["faults"])


def _alter(out, how: str):
    """A codec's answer, changed as the fault says."""
    if isinstance(out, np.ndarray):
        out = np.array(out, copy=True)
        flat = out.reshape(-1, out.shape[-1]) if out.ndim else out
        width = flat.shape[-1]
        if how == "flip":
            flat[0, 0] ^= 1
        elif how == "zero":
            flat[:] = 0
        elif how == "half":
            flat[:, width // 2:] = 0
        elif how.startswith("first_of_"):
            flat[:, width // int(how.split("_")[-1]):] = 0
        return out
    if isinstance(out, dict):
        return {k: _alter(v, how) for k, v in out.items()}
    if isinstance(out, (list, tuple)):
        return type(out)(_alter(v, how) if v is not None else None
                         for v in out)
    return out


def _patch(stack: ExitStack, obj, name: str, value) -> None:
    old = getattr(obj, name)
    setattr(obj, name, value)
    stack.callback(setattr, obj, name, old)


def _wrap_fetches(stack: ExitStack, how: str, ops: tuple) -> None:
    """Every codec call of `ops` whose fetch is metered returns an
    altered answer (RS, mesh and clay encode; RS reconstruct)."""
    from seaweedfs_tpu.ops import codec
    from seaweedfs_tpu.storage.ec import codes
    orig = codec.metered_fetch

    def metered_fetch(fetch, backend, op, *a, **kw):
        if op in ops:
            inner = fetch
            fetch = lambda: _alter(inner(), how)  # noqa: E731
        return orig(fetch, backend, op, *a, **kw)
    _patch(stack, codec, "metered_fetch", metered_fetch)
    _patch(stack, codes, "metered_fetch", metered_fetch)


def _wrap_clay_repair(stack: ExitStack, how: str) -> None:
    from seaweedfs_tpu.storage.ec import codes
    orig_apply = codes.gf_apply
    _patch(stack, codes, "gf_apply",
           lambda *a, **kw: _alter(orig_apply(*a, **kw), how))
    orig_fused = codes._clay_repair_fn_fused

    def fused(*a, **kw):
        fn = orig_fused(*a, **kw)
        return lambda x: _alter(np.asarray(fn(x)), how)
    _patch(stack, codes, "_clay_repair_fn_fused", fused)


def _plant(stack: ExitStack, driver, how: dict, devices: int) -> None:
    if "noop" in how:
        _patch(stack, driver, how["noop"], lambda *a, **kw: {})
        return
    alter = how["how"].replace("chips", str(devices))
    if how["alter"] == "clay_repair":
        _wrap_clay_repair(stack, alter)
    else:
        _wrap_fetches(stack, alter, (how["alter"],))


@contextmanager
def fault(cell: str, name: str, devices: int = 1):
    """Plant one fault of the cell's mix under the timed path, active
    from the window's start (set-up and warm-up run sound).  unchanged:
    the step returns leaving its state as it was (the verb does nothing;
    a reconstruct hands back zeros); half: half of the batch left out;
    exchange: only the first chip's share of the output comes back;
    flip: one byte of an answer altered where produced."""
    m = mix(cell)
    driver = importlib.import_module(f"benchmark.drivers.{m['driver']}")
    how = m["faults"][name]
    with ExitStack() as stack:
        sound_window = driver.window

        def window(run):
            with ExitStack() as inner:
                _plant(inner, driver, how, devices)
                return sound_window(run)
        _patch(stack, driver, "window", window)
        yield


@contextmanager
def control():
    """The control breaks the format guarantee the configurations state:
    every RS matrix (RS itself, and Clay's layer code) built by the
    Cauchy construction (klauspost's WithCauchyMatrix: also MDS, every
    submatrix invertible) in place of the Vandermonde one SeaweedFS
    writes.  Apply it before the program builds any codec."""
    from seaweedfs_tpu.ops import rs_matrix
    with ExitStack() as stack:
        orig = rs_matrix.generator_matrix

        def cauchy(k=10, m=4, kind="vandermonde"):
            return orig(k, m, "cauchy")
        _patch(stack, rs_matrix, "generator_matrix", cauchy)
        yield
