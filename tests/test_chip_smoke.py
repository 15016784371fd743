"""chip_smoke.py off the chip: it refuses to run, and its phases rehearse.

The script's ``main`` checks for a TPU before any phase and never falls
back to the CPU, so these tests call its phases directly at the tiny
rehearsal sizes, with the Pallas kernel interpreted.
"""

import argparse
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, SRC

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run_script(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args],
                          cwd=os.path.dirname(script), env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("args", [(), ("--chips", "4"), ("--tiny",)])
def test_refuses_without_tpu(args):
    proc = _run_script(SCRIPT, *args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    proc = _run_script(str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_tiny_rehearsal_one_device(tmp_path, monkeypatch, capsys):
    # set, so the training entry point leaves this process's cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    args = argparse.Namespace(chips=1, seed=0, tiny=True,
                              out=str(tmp_path / "out"))
    chip_smoke.run(args, 1, interpret=True)
    out = capsys.readouterr().out
    assert out.count("identical") == 8  # table + evictions, sum and mean
    assert "[sim] delivered table" in out
    assert "all phases passed" in out
    assert not os.listdir(tmp_path / "out")  # checkpoints removed


def test_tiny_rehearsal_four_devices(tmp_path):
    code = ("import sys, argparse; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; chip_smoke.run(argparse.Namespace(chips=4, "
            "seed=0, tiny=True, out=sys.argv[2]), 4, interpret=True)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code, REPO,
                           str(tmp_path / "out")], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "split over 4 distinct devices" in proc.stdout
    assert "tree_compress vs flat" in proc.stdout
    assert "all phases passed" in proc.stdout


def test_group_by_reference():
    import numpy as np

    keys = np.array([3, 1, 3, 3, 0], np.int32)
    np.testing.assert_array_equal(chip_smoke.group_by(keys, 5),
                                  [1, 1, 0, 3, 0])
    with pytest.raises(chip_smoke.SmokeFailure, match="differs"):
        chip_smoke.compare_totals("t", "x", np.array([3, 1, -1]),
                                  np.array([3.0, 2.0, 0.0]),
                                  chip_smoke.group_by(keys, 5))
