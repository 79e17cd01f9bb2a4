"""The command fails, printing no result, where it cannot measure: with no
TPU, and in a tree that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2-1.5b.chat", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def run_in(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py")]
                          + ARGS, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
