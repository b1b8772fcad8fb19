"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json at the checkout root.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The last line of stdout is the result
object; the numbers compared for ``correct`` are the last lines of
stderr and the ``checks`` entry of the result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

EXIT_USAGE, EXIT_NO_CHIP = 2, 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import SpecError, resolve
    try:
        cell = resolve(args.workload)
    except (SpecError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_USAGE

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        import repro.core.orchestrator  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return EXIT_USAGE
    import jax
    # the compile cache lives at a fixed path inside the checkout; JAX
    # persists every program, the sub-second eager ones included
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench.peaks import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    peaks = peaks_for(dev.device_kind)
    print(f"bench: {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} on {device}", file=sys.stderr, flush=True)

    from bench.cell import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, peaks, device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
