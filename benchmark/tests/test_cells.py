"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU:
set-up, window, check, metrics; a cell of several chips on as many
virtual devices in a process of its own.  And the command refuses to
run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import core
from benchmark.tests import plant

ROOT = core.ROOT
TINY = {"volume_size_mb": 24}
CELLS = core.benchmark_spec()["workloads"]
ONE_CHIP = [c["name"] for c in CELLS if c["chips"] == 1]
SEVERAL = [(c["name"], c["chips"]) for c in CELLS if c["chips"] > 1]


@pytest.mark.parametrize("cell,chips",
                         [(c["name"], c["chips"]) for c in CELLS])
def test_mix_lists_the_faults_of_its_cell(cell, chips):
    """The faults the contract asks a cell to catch are in its mix."""
    want = {"unchanged", "half", "flip"} | ({"exchange"} if chips > 1
                                            else set())
    assert want <= set(plant.faults(cell))
    assert plant.cpu_expect(cell)


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(cell, trace):
    r = core.run_cell(cell, 2**31 + 3, 1.0, trace, "cpu",
                      expect=plant.cpu_expect(cell), sizes=TINY)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = core.benchmark_spec()
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in core.metrics_for(spec, group, cell)}
    if trace:
        # the CPU has no device plane: only host-side readers report
        assert set(r["metrics"]) <= want
        assert r["device"]["busy_s"] == 0.0
    else:
        assert set(r["metrics"]) == want
        assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "compared"


def _control(cell, plant_name, devices=1, seeds="2147483659"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "control.py"),
         "--cell", cell, "--seeds", seeds, "--plant", plant_name,
         "--seconds", "1", "--cpu", "--size-mb", "24"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("cell,chips", SEVERAL)
def test_cell_on_virtual_devices(cell, chips):
    (r,) = _control(cell, "none", devices=chips)
    assert r["correct"], r["compared"]
    assert r["device"]["count"] == chips
    expect = plant.cpu_expect(cell)
    for backend in expect.values():
        assert any(k.endswith(f"_not_{backend}") for k in r["compared"])


def test_command_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rs10_4.seal",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no tpu" in out.stderr.lower()


def test_command_refuses_in_a_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and benchmark/: no program."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rs10_4.seal",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
