"""Find an open-loop cell's knee once, on the chip: serve its traffic at
several fixed rates in one process (one set-up) and print, per rate, the
tails, the throughput and whether the backlog kept up.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.3,0.5,0.8

The knee is the highest rate at which every request due in the window
finished within its drain cap and the generator never fell behind; the
cell's traffic file then states about four fifths of it as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

T_START = time.perf_counter()

from run import ROOT  # noqa: E402,F401  (puts the checkout on sys.path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    from bench.cell import Bench, end_to_end
    from bench.spec import resolve
    cell = resolve(a.workload)
    b = Bench(cell, a.seed, False, T_START, a.seconds)
    print(f"setup {b.split}", flush=True)
    try:
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            spec = dict(cell.traffic, rate_per_s=rate)
            if b.system.warm_fn is not None:    # this rate's block lengths
                b.system.warm_fn(b.system, b.traffic(spec).prompt_lengths())
            run = b.serve(spec, seed=a.seed + 1 + i)
            tl = run.timeline
            e2e = end_to_end(run, 0.0)
            done = sum(r.done and not r.req.failed for r in run.records)
            late = max(tl.lateness or [0.0])
            print(json.dumps({
                "rate_per_s": rate, "due": len(run.records), "done": done,
                "drain_s": tl.end - tl.close, "late_max_s": late,
                "compiles": b.clock.between(tl.open, tl.close)[0],
                **{k: v for k, v in e2e.items() if k != "setup_s"}}),
                flush=True)
            if done < len(run.records):
                break                           # past the knee: stop here
            b.orch.drain(timeout=120)           # next rate starts empty
    finally:
        b.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
