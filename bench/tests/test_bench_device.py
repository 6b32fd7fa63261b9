"""The benchmark refuses to measure anything but a TPU it knows, and
loads no accelerator library while it is imported."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(["bench/run.py", "--workload", "sift1m.batch", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_on_an_unknown_workload():
    p = _run(["bench/run.py", "--workload", "nope.batch", "--seed", "1",
              "--seconds", "1"], ROOT)
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["bench/run.py", "--workload", "sift1m.batch", "--seed", "1",
              "--seconds", "1"], tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.HarnessError, match="peaks.json"):
        harness.load_peaks("TPU v99 imaginary")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_require_chip_refuses_the_cpu():
    with pytest.raises(harness.HarnessError, match="needs a TPU"):
        harness.require_chip(1)


def test_import_loads_no_accelerator_backend():
    code = ("import sys; sys.path[:0] = ['.', 'src']\n"
            "import bench.harness, bench.system, bench.reference, "
            "bench.trace, bench.roofline, bench.traffic, bench.corpus\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "assert not any('libtpu' in m for m in sys.modules), 'libtpu'\n"
            "print('ok')")
    p = _run(["-c", code], ROOT)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"
