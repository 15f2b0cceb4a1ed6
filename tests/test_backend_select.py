"""The production codec picker's platform rule (ops.codec.resolve_backend).

The reference picks its SIMD encoder once per binary and is always right
for its host (weed/storage/erasure_coding/ec_encoder.go:198).  Here the
rule is by platform: a TPU host runs EC on the device, a CPU host on the
native AVX2 codec (jax when the .so cannot build), and WEED_EC_BACKEND
pins the exact backend.  These tests pin the rule with a mocked
platform — no real device needed.
"""

import jax
import numpy as np
import pytest

import seaweedfs_tpu.ops.codec as codec_mod
from seaweedfs_tpu.ops.codec import RSCodec, gf_apply


@pytest.fixture(autouse=True)
def _no_pin(monkeypatch):
    monkeypatch.delenv("WEED_EC_BACKEND", raising=False)


def _mock_platform(monkeypatch, *, tpu: bool, native: bool = True):
    monkeypatch.setattr(codec_mod, "_tpu_available", lambda: tpu)
    _mock_native_lib(monkeypatch, native)


def _mock_native_lib(monkeypatch, built: bool = True):
    """Stub the native .so so the rule is tested on compiler-less hosts
    too (the product code itself supports them)."""
    import seaweedfs_tpu.native as native_mod

    class FakeLib:
        gf256_matmul = staticmethod(lambda M, x: None)
    monkeypatch.setattr(native_mod, "lib",
                        (lambda: FakeLib) if built else (lambda: None))


@pytest.mark.parametrize("tpu,native,want", [
    (True, True, "pallas"),     # TPU present -> the device, always
    (True, False, "pallas"),
    (False, True, "native"),    # CPU host with the .so -> AVX2 codec
    (False, False, "jax"),      # CPU host without it -> XLA bit-planes
])
def test_auto_rule(monkeypatch, tpu, native, want):
    _mock_platform(monkeypatch, tpu=tpu, native=native)
    assert codec_mod.resolve_backend() == want
    assert RSCodec(10, 4).backend == want


@pytest.mark.parametrize("tpu,device_path", [(True, True), (False, False)])
def test_gf_apply_auto_follows_the_platform(monkeypatch, tpu, device_path):
    _mock_platform(monkeypatch, tpu=tpu, native=False)
    seen = []
    real = codec_mod.rs_jax.encode

    def spy(bits, x):
        seen.append(1)
        return real(bits, x)
    monkeypatch.setattr(codec_mod.rs_jax, "encode", spy)
    M = np.array([[1, 2], [3, 4]], dtype=np.uint8)
    x = np.arange(2 * 64, dtype=np.uint8).reshape(2, 64)
    out = gf_apply(M, x, backend="auto")
    assert bool(seen) == device_path
    np.testing.assert_array_equal(out, gf_apply(M, x, backend="numpy"))


@pytest.mark.parametrize("tpu,pin,want", [
    (True, None, True),
    (True, "jax", True),
    (True, "pallas", True),
    (True, "native", False),
    (True, "numpy", False),
    (False, None, False),
])
def test_device_compute_gate(monkeypatch, tpu, pin, want):
    _mock_platform(monkeypatch, tpu=tpu)
    if pin:
        monkeypatch.setenv("WEED_EC_BACKEND", pin)
    assert codec_mod.device_compute_ok() is want


@pytest.mark.parametrize("tpu,pin,want_mesh", [
    (True, None, True),          # multi-chip TPU host -> MeshCodec
    (True, "native", False),     # ...unless a CPU codec is pinned
    (False, None, True),         # CPU virtual mesh (driver dryrun)
    (False, "native", True),     # ...where the 'device' IS the host
])
def test_multi_device_picker(monkeypatch, tpu, pin, want_mesh):
    import seaweedfs_tpu.storage.ec.codes as codes_mod
    from seaweedfs_tpu.parallel import mesh_codec
    _mock_platform(monkeypatch, tpu=tpu)
    if pin:
        monkeypatch.setenv("WEED_EC_BACKEND", pin)
    monkeypatch.setattr(mesh_codec, "multi_device_host", lambda: True)
    c = mesh_codec.codec_for_devices(10, 4)
    assert isinstance(c, mesh_codec.MeshCodec) is want_mesh
    if not want_mesh:
        assert c.backend == pin
    # clay/LRC window codecs ride the same gate
    assert codes_mod._multi_device() is want_mesh


def test_single_chip_tpu_picks_rscodec_pallas(monkeypatch):
    from seaweedfs_tpu.parallel import mesh_codec
    _mock_platform(monkeypatch, tpu=True)
    monkeypatch.setattr(mesh_codec, "multi_device_host", lambda: False)
    c = mesh_codec.codec_for_devices(10, 4)
    assert isinstance(c, RSCodec) and c.backend == "pallas"


@pytest.mark.parametrize("backend,tpu,cores,depth", [
    ("pallas", True, 1, 2),      # device codecs always pipeline
    ("jax", True, 1, 2),
    ("mesh", False, 1, 2),
    ("clay", True, 1, 2),        # window codecs on a TPU ride the device
    ("clay", False, 1, 0),       # ...on a 1-core CPU host they run inline
    ("native", False, 1, 0),
    ("native", False, 8, 2),
])
def test_pipeline_depth(monkeypatch, backend, tpu, cores, depth):
    from seaweedfs_tpu.storage.ec import encoder
    _mock_platform(monkeypatch, tpu=tpu)
    monkeypatch.setattr(encoder.os, "cpu_count", lambda: cores)

    class Fake:
        pass
    codec = Fake()
    codec.backend = backend
    assert encoder._pipeline_depth(codec) == depth


def test_clay_window_codec_takes_the_device_path_on_tpu(monkeypatch):
    import seaweedfs_tpu.storage.ec.codes as codes_mod
    from seaweedfs_tpu.storage.ec.layout import EcGeometry
    _mock_platform(monkeypatch, tpu=True)
    monkeypatch.delenv("WEED_EC_BACKEND", raising=False)
    monkeypatch.setattr(codes_mod, "_multi_device", lambda: False)
    calls = []

    def fake_fn(k, m, small):
        def run(x):
            calls.append(x.shape)
            return np.zeros((m,) + tuple(x.shape[1:]), np.uint8)
        return run
    monkeypatch.setattr(codes_mod, "_clay_device_fn_fused", fake_fn)
    geo = EcGeometry(10, 4, small_block_size=256 * 128, code_kind="clay")
    out = codes_mod.ClayWindowCodec(geo).encode(
        np.zeros((10, 256 * 128), np.uint8))
    assert calls and out.shape == (4, 256 * 128)


def test_env_override_rejects_garbage(monkeypatch):
    monkeypatch.setenv("WEED_EC_BACKEND", "cuda")
    with pytest.raises(ValueError, match="WEED_EC_BACKEND"):
        codec_mod.ec_backend_override()
    # 'mesh' is a picker outcome, not a backend — typos must fail loudly
    monkeypatch.setenv("WEED_EC_BACKEND", "mesh")
    with pytest.raises(ValueError, match="WEED_EC_BACKEND"):
        codec_mod.ec_backend_override()


def test_pin_validated_against_host_capability(monkeypatch):
    # pinning pallas on a TPU-less host must fail at construction with a
    # clear message, not mid-serve inside the first pallas_call
    monkeypatch.setattr(codec_mod, "_tpu_available", lambda: False)
    monkeypatch.setenv("WEED_EC_BACKEND", "pallas")
    with pytest.raises(RuntimeError, match="no TPU"):
        RSCodec(10, 4)
    # pinning native without the .so likewise
    monkeypatch.setenv("WEED_EC_BACKEND", "native")
    _mock_native_lib(monkeypatch, built=False)
    with pytest.raises(RuntimeError, match="native"):
        RSCodec(10, 4)
    # ...and gf_apply fails the same way instead of silently degrading
    M = np.eye(2, dtype=np.uint8)
    with pytest.raises(RuntimeError, match="native"):
        gf_apply(M, np.zeros((2, 8), dtype=np.uint8), backend="auto")


@pytest.mark.parametrize("pin", ["jax", "numpy", "native", "pallas"])
def test_env_override_pins_the_exact_backend(monkeypatch, pin):
    # '-ec.backend jax' must NOT silently upgrade to pallas (debugging a
    # suspected pallas kernel needs the XLA path specifically), and
    # 'numpy' must not upgrade to native
    _mock_platform(monkeypatch, tpu=True)
    monkeypatch.setenv("WEED_EC_BACKEND", pin)
    assert RSCodec(10, 4).backend == pin


def test_clay_layer_mds_honors_a_jax_pin(monkeypatch):
    # the clay window path must reach the XLA engine under '-ec.backend
    # jax' too — on this CPU host the pallas branch would crash, so
    # merely running proves the pin routed away from it
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import clay_structured
    monkeypatch.setattr(codec_mod, "_tpu_available", lambda: True)
    monkeypatch.setenv("WEED_EC_BACKEND", "jax")
    k0 = clay_structured.code(4, 2).k0
    u = jnp.zeros((k0, 128), dtype=jnp.uint8)
    out = clay_structured._layer_mds_matmul(4, 2, u, k0)
    assert out.shape == (2, 128)


def test_cli_ec_backend_flag_sets_env_and_validates(monkeypatch):
    import os
    from seaweedfs_tpu.command import main
    # registering the vars with monkeypatch first makes teardown restore
    # the pre-test state even though main() rewrites them directly
    monkeypatch.setenv("WEED_EC_BACKEND", "auto")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    _mock_native_lib(monkeypatch)
    assert main(["-ec.backend", "native", "version"]) == 0
    assert os.environ.get("WEED_EC_BACKEND") == "native"
    assert not codec_mod.device_compute_ok()
    with pytest.raises(ValueError, match="WEED_EC_BACKEND"):
        main(["-ec.backend", "cuda", "version"])
    # a rejected pin must not leak into the process environment
    assert os.environ.get("WEED_EC_BACKEND") == "native"


def test_status_reports_the_codec_backend():
    from seaweedfs_tpu.parallel.mesh_codec import ec_backend_status
    jax.devices()
    # the test host: the 8-device virtual CPU mesh (conftest.py)
    st = ec_backend_status()
    assert st["opened"] and st["platform"] == "cpu" and st["devices"] == 8
    assert st["backend"] == "mesh" and st["pin"] == "auto"


def test_status_never_opens_the_device(monkeypatch):
    from jax._src import xla_bridge

    from seaweedfs_tpu.parallel.mesh_codec import ec_backend_status
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)

    def no_open():
        raise AssertionError("/status opened the device")
    monkeypatch.setattr(jax, "devices", no_open)
    monkeypatch.setenv("WEED_EC_BACKEND", "native")
    assert ec_backend_status() == {"opened": False, "pin": "native"}


def test_failed_tpu_open_is_logged_not_silent(monkeypatch):
    from jax._src import xla_bridge

    from seaweedfs_tpu.util import weedlog
    said = []

    class Rec:
        def warning(self, fmt, *args):
            said.append(fmt % args)
    monkeypatch.setattr(weedlog, "logger", lambda name: Rec())
    monkeypatch.setattr(xla_bridge, "_backend_errors",
                        {"tpu": "TPU is already in use by process 1"},
                        raising=False)
    codec_mod._log_tpu_open_failure.cache_clear()
    try:
        assert not codec_mod._tpu_available()
        assert not codec_mod._tpu_available()
        assert len(said) == 1     # once per process, not per call
        assert "already in use" in said[0] and "CPU codec" in said[0]
    finally:
        codec_mod._log_tpu_open_failure.cache_clear()
