"""Native (C++) runtime components, loaded via ctypes.

The reference ships as a single Go binary whose only "native" hot path is the
vendored SIMD Reed-Solomon codec; here the TPU owns the codec and this package
owns the host-side hot loops: CRC32C needle checksums, the CPU GF(2^8) codec,
and the TCP/HTTP frame loop. Everything has a pure-Python fallback so the
framework runs unbuilt; the .so files are compiled on demand with g++/gcc on
the host that runs them (no pip deps — plain ctypes ABI), keyed by source
content and host CPU.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["crc32c.cpp", "rs_gf256.cpp"]
_lock = threading.Lock()
_lib = None
_tried = False


def _host_cpu() -> str:
    """What -march=native compiles for: the machine and its CPU flags."""
    import platform
    ident = platform.machine()
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith(("model name", "flags", "Features")):
                    ident += "|" + line.strip()
                if line.strip() == "":
                    break      # first processor block only
    except OSError:
        pass
    return ident


def _compile(stem: str, srcs: list[str], cmd: list[str]) -> "str | None":
    """Build `srcs` into <stem>.<key>.so, where the key hashes the source
    bytes, the command and the host CPU; reuse it when it exists.  A .so
    built on another machine (or from other sources) carries another key,
    so it is never run here.  Returns the path, or None when the build
    fails (callers then take their pure-Python fallbacks)."""
    import glob
    import hashlib
    h = hashlib.sha256("\0".join(cmd + [_host_cpu()]).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    so = os.path.join(_DIR, f"{stem}.{h.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"   # parallel test workers build too
    try:
        subprocess.run(cmd + srcs + ["-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, so)
    except Exception as e:
        try:
            os.remove(tmp)
        except OSError:
            pass
        import warnings
        detail = getattr(e, "stderr", b"")
        detail = detail.decode(errors="replace")[-400:] \
            if isinstance(detail, bytes) else str(e)
        warnings.warn(f"native build of {stem} failed, using the Python "
                      f"fallbacks: {detail}", RuntimeWarning)
        return None
    for old in glob.glob(os.path.join(_DIR, f"{stem}.*.so")):
        if old != so:
            try:
                os.remove(old)
            except OSError:
                pass
    return so


def build() -> str | None:
    """Compile the native library for this host (or reuse this host's
    build). Returns its path or None."""
    srcs = [os.path.join(_DIR, s) for s in _SOURCES]
    return _compile("libseaweed_native", srcs,
                    ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                     "-std=c++17"])


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # the lock EXISTS to serialize the one-time cc build; nothing
        # on a hot path can contend (both loaders are once-guarded)
        so = build()  # weedlint: disable=WL150
        if so is None:
            return None
        try:
            _lib = ctypes.CDLL(so)
        except OSError:
            return None
        _lib.sw_crc32c.restype = ctypes.c_uint32
        _lib.sw_crc32c.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
        try:
            _lib.gf256_matmul.restype = None
            _lib.gf256_matmul.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
            _lib.gf256_has_avx2.restype = ctypes.c_int
        except AttributeError:
            pass   # stale .so without the codec: crc still works
        return _lib


def gf256_matmul(M, inputs, out=None):
    """Native GF(2^8) matmul: out[mo, n] = M[mo, ki] * inputs[ki, n].
    numpy uint8 arrays; returns out (allocated if not given), or raises
    RuntimeError when the native library is unavailable."""
    import numpy as np
    lib_ = _load()
    if lib_ is None or not hasattr(lib_, "gf256_matmul"):
        raise RuntimeError("native gf256 codec unavailable")
    M = np.ascontiguousarray(M, dtype=np.uint8)
    inputs = np.ascontiguousarray(inputs, dtype=np.uint8)
    mo, ki = M.shape
    if inputs.ndim != 2:          # a batched [V, ki, B] with V == ki
        raise ValueError(          # would silently read garbage
            f"inputs must be 2-D [ki, n], got shape {inputs.shape}")
    if inputs.shape[0] != ki:     # real check — asserts vanish under -O
        raise ValueError(f"inputs rows {inputs.shape[0]} != ki {ki}")
    n = inputs.shape[1]
    if out is None:
        out = np.empty((mo, n), dtype=np.uint8)
    elif (out.dtype != np.uint8 or out.shape != (mo, n)
          or not out.flags.c_contiguous):
        # the C side writes mo*n raw bytes at the base pointer — a view
        # or wrong dtype would corrupt unrelated memory
        raise ValueError("out must be a C-contiguous uint8 [mo, n] array")
    lib_.gf256_matmul(M.tobytes(), mo, ki,
                      inputs.ctypes.data_as(ctypes.c_void_p),
                      out.ctypes.data_as(ctypes.c_void_p), n)
    return out


def _crc32c(data: bytes, crc: int = 0) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return lib.sw_crc32c(crc, data, len(data))


def _crc32c_region(buf: bytes, offset: int, length: int,
                   crc: int = 0) -> int:
    """CRC of buf[offset:offset+length] WITHOUT materializing the slice —
    the zero-copy needle read path checksums its data region in place
    (c_char_p accepts a raw address; the caller keeps `buf` alive)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if offset < 0 or length < 0 or offset + length > len(buf):
        raise ValueError("crc region out of bounds")
    base = ctypes.cast(ctypes.c_char_p(buf), ctypes.c_void_p).value
    return lib.sw_crc32c(crc, ctypes.c_char_p(base + offset), length)


def _crc_available() -> bool:
    return _load() is not None


# public handles (None when unavailable -> callers fall back to Python)
crc32c = _crc32c if _crc_available() else None
crc32c_region = _crc32c_region if _crc_available() else None


def lib():
    """The raw ctypes CDLL, or None."""
    return _load()


# -- CPython extension for the TCP frame hot loop --------------------------
# (separate .so: it links against Python.h, unlike the plain-ABI library)

_fp = None
_fp_tried = False


def _build_fastpath() -> "str | None":
    import sysconfig
    inc = sysconfig.get_paths()["include"]
    return _compile("_seaweed_fastpath", [os.path.join(_DIR, "fastpath.c")],
                    ["gcc", "-O2", "-march=native", "-shared", "-fPIC",
                     f"-I{inc}"])


def fastpath():
    """The _seaweed_fastpath extension module (C frame loop), or None —
    callers (volume_server/tcp.py, operation, storage/needle.py) fall
    back to the Python codecs when the build is unavailable.  Lock-free
    after first resolution: this sits on per-frame hot paths."""
    global _fp, _fp_tried
    if _fp_tried:
        return _fp
    with _lock:
        if _fp_tried:
            return _fp
        if os.environ.get("WEED_FASTPATH", "1") == "0":
            # global kill switch: every native caller sees None and runs
            # its pure-Python fallback (tools/check.sh uses this to keep
            # the fallbacks from rotting)
            _fp_tried = True
            return None
        # one-time cc build serialized on purpose (see _load above)
        so = _build_fastpath()  # weedlint: disable=WL150
        if so is not None:
            try:
                from importlib.machinery import ExtensionFileLoader
                from importlib.util import (module_from_spec,
                                            spec_from_loader)
                loader = ExtensionFileLoader("_seaweed_fastpath", so)
                spec = spec_from_loader("_seaweed_fastpath", loader)
                mod = module_from_spec(spec)
                loader.exec_module(mod)
                _fp = mod
            except Exception:
                _fp = None
        # publish _fp BEFORE the tried flag: the lock-free fast path
        # must never observe tried=True with _fp still unset
        _fp_tried = True
        return _fp
