"""Each cell end to end at smoke widths on the CPU, untraced (with the
Pallas kernels in interpret mode) and traced: the result object has the
contract's keys, every due request is served and checked against the
reference, and the counter-based per-layer metrics are read (device
metrics are not: there is no device trace)."""
import json

import pytest

from repro.kernels import ops

from bench.spec import load_benchmark

CELLS = [w["name"] for w in load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_end_to_end_metrics(run_smoke, cell):
    ops.set_backend("pallas")               # interpret mode off the chip
    try:
        res = run_smoke(cell)
    finally:
        ops.set_backend("auto")
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, json.dumps(res["checks"])
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in load_benchmark()["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["max_logit_gap"]["value"] <= \
        res["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_counter_metrics(run_smoke, cell):
    res = run_smoke(cell, trace=True)
    assert res["correct"] is True
    layer = {m["name"] for m in load_benchmark()["per_layer"]
             if cell in m["workloads"] and m["source"] != "device_trace"}
    assert layer <= set(res["metrics"])
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" in res["device"] and "window_s" in res["device"]
