"""The examples/ scripts must stay runnable (reference demo parity —
every flow a switching user copy-pastes first)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, extra=(), cwd=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
                "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=8"
                              ).strip()})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *extra],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=cwd or ROOT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    return r.stdout


def test_train_lenet(tmp_path):
    # cwd=tmp_path: the script saves lenet.pdparams into its cwd
    out = _run("train_lenet.py", ["--limit-batches", "3"], cwd=tmp_path)
    assert "loss" in out and "saved" in out
    assert (tmp_path / "lenet.pdparams").exists()


def test_train_fleet_dp_tp():
    out = _run("train_fleet_dp_tp.py")
    assert out.count("loss") >= 5


def test_generate_llama():
    out = _run("generate_llama.py")
    assert "greedy:" in out and "streaming:" in out


def test_deploy_predictor():
    out = _run("deploy_predictor.py")
    assert "parity" in out and "from_layer passes" in out
