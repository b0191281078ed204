"""No chip, no numbers: the command exits non-zero and prints no result
line without an accelerator, and without the program beside it."""
import json
import os
import shutil
import subprocess
import sys

from .conftest import ROOT

ARGS = ["--workload", "qwen3b.rag_hot", "--seed", "3", "--seconds", "1", "--trace", "0"]


def run_in(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_cpu_only_exits_nonzero_without_result():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
