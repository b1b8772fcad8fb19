"""The benchmark's own tests; run them explicitly (``pytest bench/tests``):
the repository's ``pytest.ini`` collects only ``tests/``.  Everything here
runs on the CPU at smoke widths or on recorded data."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import time  # noqa: E402

import pytest  # noqa: E402


def checked_cell(cell_name):
    """A smoke cell whose run checks several hundred served tokens, as the
    chip runs do: answers of 64-128 tokens and 12 sampled requests."""
    from bench.smoke import smoke_cell
    from bench.spec import resolve
    cell = smoke_cell(resolve(cell_name), output={
        "median": 96, "sigma": 0.3, "min": 64, "max": 128})
    s = cell.config["serving"]
    s["max_seq"] = 512
    (s["decode"] if "decode" in s else s)["pages"] = 4 * 32
    cell.config["check"]["requests"] = 12
    return cell


def smoke_run(cell_name, seed=20260001, seconds=4.0, trace=False, cell=None,
              **traffic):
    """One run of a cell at smoke widths on the CPU: the harness's whole
    path except the look for a chip."""
    from bench.cell import run_cell
    from bench.smoke import CPU_PEAKS, cpu_device, smoke_cell
    from bench.spec import resolve
    cell = cell or smoke_cell(resolve(cell_name), **traffic)
    return run_cell(cell, seed, seconds, trace, time.perf_counter(),
                    CPU_PEAKS, cpu_device())


@pytest.fixture
def run_smoke():
    return smoke_run
