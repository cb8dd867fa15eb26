"""`bench/run.py` refuses to run without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from bench import cells

ARGS = ["--workload", "s9-strong-closed32", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(cells.ROOT)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "platform=cpu" in p.stderr and "device_kind=" in p.stderr
    assert "count=" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
