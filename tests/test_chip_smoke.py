"""chip_smoke.py on the CPU: it must refuse to run without a TPU, its
prefill->decode phase must hold at smoke width, and the compile-cache
helper must pick the right directory."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.base import get_config

ROOT = Path(__file__).resolve().parents[1]


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """No TPU (or no repo beside the script): non-zero exit, no result."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        cwd = tmp_path
    env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    if where == "alone":
        env.pop("PYTHONPATH")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_pd_phase_matches_unified_at_smoke_width():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = get_config("internlm2_1_8b", smoke=True)
    tokens, unified = smoke.pd_vs_unified(cfg)    # raises on any mismatch
    assert len(tokens) == len(smoke.PD_PROMPT_LENS)
    assert all(len(t) == smoke.PD_MAX_NEW for t in tokens)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    """The env directory when set (JAX reads it; the helper sets nothing),
    else the fixed <checkout>/.jax_cache — read back from jax.config in a
    fresh interpreter that compiles nothing."""
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    code = ("import jax; from repro.launch.cache import enable_compile_cache;"
            "d = enable_compile_cache();"
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=_env(**extra),
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert out == [want, want]
