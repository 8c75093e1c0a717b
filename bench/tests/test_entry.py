"""The command refuses to run where it cannot measure the program."""
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2.5-3b.decode_heavy", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_no_tpu_prints_no_result():
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
