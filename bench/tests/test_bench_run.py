"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: without a TPU, and in a directory that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "zamba2-marina-step", "--seed", "3000000000", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_exits_non_zero_without_a_tpu():
    res = _run(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
    assert "no TPU" in res.stderr


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path, {"PYTHONPATH": ""})
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
