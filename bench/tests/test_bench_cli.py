"""``bench/run.py`` without a chip: it exits non-zero and prints no
result, both in a full checkout and in one that holds only
``BENCHMARK.json`` and the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "cifar.poisson80", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py")]
                          + ARGS, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache",
                                                  ".out"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
