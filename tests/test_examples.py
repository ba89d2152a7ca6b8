"""examples/ scripts run end-to-end (subprocess, CPU backend, tiny
args) — the switching-user surface must not rot.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
EX = os.path.join(ROOT, "examples")


def _run(script, *args, env_extra=None, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(EX, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_train_mnist_example(tmp_path):
    out = _run("train_mnist.py", "--steps", "12",
               "--outdir", str(tmp_path / "m"))
    assert "inference model saved" in out


def test_train_gpt_tpu_example(tmp_path):
    out = _run("train_gpt_tpu.py", "--windows", "2", "--k", "2",
               "--seq", "64", "--d-model", "64", "--batch", "2",
               "--ckpt", str(tmp_path / "ck"))
    assert "done:" in out and "window 2" in out


def test_train_multichip_example():
    out = _run("train_multichip.py", "--steps", "6",
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "final loss" in out and "'data': 4" in out
