"""Cells are resolved by name: a new traffic mix is one data file and one
BENCHMARK.json entry, with no other edit, and the harness serves it."""
import json
import shutil

import pytest

from bench.smoke import smoke_cell
from bench.spec import ROOT, SpecError, resolve
from conftest import smoke_run


def test_an_added_traffic_file_and_entry_are_picked_up(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/docqa.json").read_text())
    mix.update(about="fewer, more often re-asked documents", rate_per_s=2.0)
    mix["shared"]["new_share"] = 0.125
    (tmp_path / "bench/traffic/docqa_hot.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": "internlm2_pd.docqa_hot",
                               "config": "internlm2_1_8b_pd",
                               "traffic": "docqa_hot", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = resolve("internlm2_pd.docqa_hot", root=tmp_path)
    assert cell.traffic["shared"]["new_share"] == 0.125
    assert cell.bench_dir == tmp_path / "bench"
    res = smoke_run(None, cell=smoke_cell(cell))
    assert res["correct"] is True and res["attempted"] > 0


def test_unknown_cell_and_missing_file():
    with pytest.raises(SpecError):
        resolve("no.such_cell")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "absent"
    with pytest.raises(SpecError):
        resolve(bench["workloads"][0]["name"], bench=bench)
