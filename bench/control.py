"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, the cell at its own size and load (weights and
traffic from that seed, a short window), then the widest logit gap of
the program's served tokens and of the control's first-ranked tokens
(the reference with float8 e4m3 matmul inputs), both read against the float32
reference over the same sampled requests.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

The limit goes above the largest program reading and below the smallest
control reading (PERF.md gives both).  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

T_START = time.perf_counter()

from run import ROOT  # noqa: E402,F401  (puts the checkout on sys.path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    from bench import correct
    from bench.cell import Bench
    from bench.spec import resolve
    cell = resolve(a.workload)
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        b = Bench(cell, seed, False, time.perf_counter(), a.seconds)
        try:
            run = b.serve(cell.traffic)
        finally:
            b.close()
        b.system.free()
        b.orch = None
        gc.collect()
        v = correct.check(cell, b.params, run, seed, control=True)
        print(json.dumps({"seed": seed, "program_gap":
                          v["checks"]["max_logit_gap"]["value"],
                          "control_gap": v["control_gap"],
                          "requests": v["requests"], "tokens": v["tokens"],
                          "due": len(run.records),
                          "done": sum(r.done for r in run.records),
                          "seconds": time.perf_counter() - t}), flush=True)
        del b, run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
