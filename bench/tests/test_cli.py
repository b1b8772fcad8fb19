"""bench/run.py refuses to run without a TPU, and in a directory that
holds only BENCHMARK.json and bench/: a non-zero exit and no result."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.spec import ROOT


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2_pd.docqa",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_no_result_without_a_chip_or_the_program(tmp_path, where):
    cwd = ROOT
    if where == "bench_only":
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    p = _run(cwd)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_unknown_workload_is_a_usage_error():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "x.y",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
