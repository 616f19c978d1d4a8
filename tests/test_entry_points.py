"""Entry points: the chip smoke script's device gate, the persistent
compile cache helper, the device mesh of the training launcher, and the
cost-model profile chosen from ``device_kind``."""

import os
import subprocess
import sys
import types

import jax
import pytest

from repro.collectives.cost_model import (HW_BY_DEVICE_KIND, TPU_V5E,
                                          hw_for_device)
from repro.launch import cache
from repro.launch.mesh import make_device_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stdout and "not a TPU" in r.stdout
    assert '"ok"' not in r.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, str(lone)], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def _capture_config(monkeypatch):
    calls = []
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    calls = _capture_config(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert calls == [("jax_compilation_cache_dir", str(tmp_path))]


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch):
    calls = _capture_config(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_device_mesh_spans_present_devices():
    n = len(jax.devices())
    mesh = make_device_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (n, 1)
    with pytest.raises(ValueError, match="does not divide"):
        make_device_mesh(tp=n + 1)


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_hw_profile_from_device_kind():
    assert hw_for_device(_dev("tpu", "TPU v5 lite")) is TPU_V5E
    assert HW_BY_DEVICE_KIND["TPU v5 lite"] is TPU_V5E


def test_cpu_keeps_the_named_rehearsal_profile():
    assert hw_for_device(_dev("cpu", "cpu")) is TPU_V5E
    assert hw_for_device() is TPU_V5E      # the tests run on the CPU


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 imaginary"),
                                           ("gpu", "NVIDIA B300")])
def test_unknown_accelerator_kind_is_an_error(platform, kind):
    with pytest.raises(ValueError, match="no cost-model profile"):
        hw_for_device(_dev(platform, kind))
