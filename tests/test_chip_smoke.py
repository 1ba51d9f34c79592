"""chip_smoke.py's phases at SMOKE size on the CPU, its refusal to run
without a TPU, and the mesh and compile-cache helpers it runs on."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core.plan import ParallelPlan, StagePlacement
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh, make_train_mesh
from repro.models import registry

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

ARCH = chip_smoke.ARCH


def test_meshes_are_auto():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_train_mesh_without_enough_devices_runs_pod_less():
    plan = ParallelPlan(stages=(StagePlacement(0, 1, 1, 1),
                                StagePlacement(1, 1, 1, 1, True)),
                        micro_bs=1, global_batch=4, seq_len=32)
    mesh = make_train_mesh(plan, jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert make_train_mesh(None, jax.devices()[:1]).axis_names == (
        "data", "model")


def test_compile_cache_placement(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_one_chip_phase_at_smoke_size():
    r = chip_smoke.run_one_chip(registry.get_bundle(ARCH, smoke=True),
                                global_batch=4, seq_len=64, steps=3)
    assert len(r["losses"]) == 3 and np.all(np.isfinite(r["losses"]))
    # fp32 smoke: the jitted step-0 loss equals the unjitted one closely
    assert r["step0_abs_err"] <= 1e-5 * abs(r["step0_ref"])


def test_pipeline_phase_on_four_virtual_devices():
    code = f"""
import json, sys
sys.path.insert(0, {str(ROOT)!r})
import jax
import chip_smoke
from repro.models import registry
r = chip_smoke.run_pipeline(
    registry.get_bundle({ARCH!r}, smoke=True, num_layers=4),
    global_batch=8, seq_len=32, steps=3, devices=jax.devices()[:4])
print(json.dumps(r))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=str(ROOT), env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["plan"].startswith("pp=2 tp=1 dp=2")
    assert r["mesh"] == {"pod": 2, "data": 2, "model": 1}
    assert r["placement"] == {"0": [0, 1], "1": [2, 3]}
    # fp32 smoke: the pipeline tracks the plain step to the repo's fp32
    # pipeline contract, far inside the bf16 bound the chip run uses
    np.testing.assert_allclose(r["losses"], r["ref_losses"], rtol=0,
                               atol=1e-4)


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_fails_alone_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
