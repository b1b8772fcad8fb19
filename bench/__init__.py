"""On-chip serving benchmark: cells are named in BENCHMARK.json and resolved
to the files under this directory (configs/, models/, traffic/, graphs/,
references/, layer_metrics/) by name.  ``python3 bench/run.py --help``."""
