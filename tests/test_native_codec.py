"""Native C++ GF(2^8) codec (native/rs_gf256.cpp) — the CPU fast path
mirroring the reference's one native component (its vendored SIMD RS
codec).  Byte-identity against the numpy oracle is the contract."""

import numpy as np
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.ops import gf256, rs_matrix
from seaweedfs_tpu.ops.codec import RSCodec


def _have_native() -> bool:
    lib = native.lib()
    return lib is not None and hasattr(lib, "gf256_matmul")


pytestmark = pytest.mark.skipif(not _have_native(),
                                reason="native codec did not build")


def test_native_matmul_matches_oracle():
    rng = np.random.default_rng(3)
    for k, m in ((10, 4), (16, 8), (28, 4), (3, 2)):
        gen = rs_matrix.generator_matrix(k, m)
        P = np.asarray(gen[k:])
        X = rng.integers(0, 256, size=(k, 1000), dtype=np.uint8)
        assert np.array_equal(native.gf256_matmul(P, X),
                              gf256.matmul(P, X)), (k, m)


def test_native_codec_backend_end_to_end():
    """RSCodec(backend='native'): encode + every-position reconstruct
    byte-identical to the numpy backend."""
    rng = np.random.default_rng(5)
    nat = RSCodec(10, 4, backend="native")
    ora = RSCodec(10, 4, backend="numpy")
    data = rng.integers(0, 256, size=(10, 4096), dtype=np.uint8)
    p_nat = nat.encode(data)
    p_ora = ora.encode(data)
    assert np.array_equal(p_nat, p_ora)
    shards = [data[i] for i in range(10)] + [p_nat[j] for j in range(4)]
    for lost in ((0,), (3, 11), (0, 1, 12, 13)):
        holed = [None if i in lost else s
                 for i, s in enumerate(shards)]
        rec = nat.reconstruct(holed)
        for i in lost:
            assert np.array_equal(rec[i], shards[i]), lost


def test_native_is_the_cpu_auto_choice(monkeypatch):
    """With no TPU visible, auto picks the native backend."""
    import seaweedfs_tpu.ops.codec as codec_mod
    monkeypatch.setattr(codec_mod, "_tpu_available", lambda: False)
    c = RSCodec(10, 4, backend="auto")
    assert c.backend == "native"


def test_native_throughput_sanity():
    """The native path must beat the numpy oracle (it exists to be the
    CPU fast path).  AVX2-only and a loose 2x bar: wall-clock ratios on
    loaded shared runners are noisy, and the scalar build's margin is
    smaller."""
    import time
    if not native.lib().gf256_has_avx2():
        pytest.skip("scalar build: timing margin too small to assert")
    rng = np.random.default_rng(7)
    P = np.asarray(rs_matrix.generator_matrix(10, 4)[10:])
    X = rng.integers(0, 256, size=(10, 1 << 20), dtype=np.uint8)
    native.gf256_matmul(P, X)
    t_native = min(
        _timed(lambda: native.gf256_matmul(P, X)) for _ in range(3))
    t_numpy = min(
        _timed(lambda: gf256.matmul(P, X[:, :1 << 18])) * 4
        for _ in range(3))
    assert t_native < t_numpy / 2, (t_native, t_numpy)


def _timed(fn) -> float:
    import time
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_build_is_keyed_by_source_and_host_cpu(tmp_path, monkeypatch):
    """A .so built elsewhere (other CPU, other sources) is never run
    here: the file name carries a hash of both, so only this host's
    own build is reused."""
    import glob
    import os
    import shutil
    src = tmp_path / "crc32c.cpp"
    shutil.copy(os.path.join(native._DIR, "crc32c.cpp"), src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    cmd = ["g++", "-O1", "-shared", "-fPIC", "-std=c++17"]
    so = native._compile("libtest", [str(src)], cmd)
    assert so and os.path.basename(so).startswith("libtest.")
    assert native._compile("libtest", [str(src)], cmd) == so   # reused
    # the same sources on another CPU name another file ...
    monkeypatch.setattr(native, "_host_cpu", lambda: "other-cpu")
    other = native._compile("libtest", [str(src)], cmd)
    assert other and other != so
    # ... and a rebuild drops the stale one
    assert glob.glob(str(tmp_path / "libtest.*.so")) == [other]
    src.write_text(src.read_text() + "\n// edited\n")
    assert native._compile("libtest", [str(src)], cmd) != other
