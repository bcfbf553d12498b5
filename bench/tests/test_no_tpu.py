"""The measurement path fails, printing no result, without a TPU, and
without the program beside the benchmark."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_on_cpu_before_any_work():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr
    assert "set-up" not in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ prints nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
