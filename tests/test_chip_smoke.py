"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses to run without a TPU (main() checks before
any phase); here its phase functions run against an in-process
SimCluster with the CPU codec the platform rule picks, the fused clay
kernels under the Pallas interpreter, and the mesh phase on the
conftest's 8 virtual devices — so the checks the chip run relies on
(dispatch accounting, parity oracles, byte-identical rebuilds) are
themselves exercised by tier-1.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs
import seaweedfs_tpu.ops.codec as codec_mod
from seaweedfs_tpu.ops import clay_structured
from seaweedfs_tpu.parallel import mesh_codec
from seaweedfs_tpu.testing import SimCluster


@pytest.fixture
def cluster():
    with SimCluster(volume_servers=4) as c:
        yield c


def test_rs_and_clay_phases(cluster, monkeypatch):
    # one-device host: the single-chip codec, as on the chip
    monkeypatch.setattr(mesh_codec, "multi_device_host", lambda: False)
    # fused clay kernels (through the interpreter on this CPU host); the
    # window codec's device gate must let them run here
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: True)
    monkeypatch.delenv("WEED_EC_BACKEND", raising=False)
    monkeypatch.setattr(codec_mod, "device_compute_ok", lambda: True)
    backend = "rs_" + codec_mod.resolve_backend()
    assert backend in ("rs_native", "rs_jax")
    st = cs.phase_rs_volume(cluster, 6 << 20, 0, backend)
    assert sorted(st["paths"]) == list(range(14))
    assert sum(b[2] for b in st["blobs"]) >= 6 << 20
    cs.phase_clay(cluster, 2 << 20, 0)


def test_rs_phase_refuses_a_cpu_codec_on_the_device_path(cluster,
                                                          monkeypatch):
    monkeypatch.setattr(mesh_codec, "multi_device_host", lambda: False)
    monkeypatch.delenv("WEED_EC_BACKEND", raising=False)
    with pytest.raises(AssertionError, match="no rs_pallas encode"):
        cs.phase_rs_volume(cluster, 1 << 20, 1, "rs_pallas")


def test_mesh_phase(cluster):
    assert len(jax.devices()) == 8          # conftest's virtual mesh
    cs.phase_mesh(cluster, 4 << 20, 0, interpret=True)


def test_kernel_phase():
    cs.phase_kernel((10, 8, 4096), 0, interpret=True)


def test_blob_sizes_are_seeded_and_bounded():
    a = cs.blob_sizes(8 << 20, 5)
    assert a == cs.blob_sizes(8 << 20, 5)
    assert sum(a) >= 8 << 20 and sum(a[:-1]) < 8 << 20
    assert min(a) >= 4 << 10 and max(a) <= 1 << 20
    assert cs.blob_bytes(5, 3, 100) == cs.blob_bytes(5, 3, 100)
    assert cs.blob_bytes(5, 3, 100) != cs.blob_bytes(5, 4, 100)


def test_main_refuses_without_a_tpu():
    """No accelerator: exit non-zero, print no result line."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
