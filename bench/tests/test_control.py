"""The control at a size a test run can hold: the reference computed with
float8 e4m3 matmul inputs (one step below the configurations' bfloat16), put in
the program's place, must come out not correct against the cell's limit,
while the program over the same sampled requests comes out correct.
The chip readings at the cells' own sizes are bench/control.py's, in
PERF.md."""
import gc
import time

import pytest

from bench import correct
from bench.cell import Bench
from conftest import checked_cell


@pytest.mark.parametrize("cell_name", ["internlm2_pd.docqa",
                                       "qwen1_5_4b.longgen"])
@pytest.mark.parametrize("seed", [101, 202, 303])
def test_control_fails_the_limit(cell_name, seed):
    cell = checked_cell(cell_name)
    b = Bench(cell, seed, False, time.perf_counter(), 8.0)
    try:
        run = b.serve(cell.traffic)
    finally:
        b.close()
    b.system.free()
    gc.collect()
    v = correct.check(cell, b.params, run, seed, control=True)
    limit = v["checks"]["max_logit_gap"]["limit"]
    program = v["checks"]["max_logit_gap"]["value"]
    print(cell_name, seed, "program", program, "control", v["control_gap"])
    assert program <= limit < v["control_gap"]
