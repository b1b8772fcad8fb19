"""Resolve a cell named in ``BENCHMARK.json`` to its files, by name only.

- configuration ``<c>``: the ``file`` its entry names (``bench/configs/``);
- model kind: ``bench/models/<model>.py``, ``model`` read from the
  configuration file (``dense`` where it names none): the program's
  ``ModelConfig``, the weights, the FLOP counts and the smoke widths;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- serving graph: ``bench/graphs/<graph>.py``, ``graph`` read from the
  configuration file;
- plain reference: ``bench/references/<reference>.py``, likewise;
- per-layer metric ``<m>``: ``bench/layer_metrics/<m>.py``, whose
  ``read(ctx)`` returns the number or ``None`` when it finds nothing.

A later change adds a configuration, a model kind, a mix, a graph or a
metric by adding its file and its entry; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: the kind of a configuration file that names none
DEFAULT_MODEL = "dense"


class SpecError(ValueError):
    """The cell or one of its files cannot be resolved."""


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold dots, e.g. ``a.b.py``)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    name = "bench_file:" + str(path.resolve())
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict              # the configuration file's contents
    traffic_name: str
    traffic: Dict             # the traffic file's contents
    end_to_end: List[Dict]    # metric entries this cell reports, trace 0
    per_layer: List[Dict]     # metric entries this cell reports, trace 1
    bench_dir: Path = BENCH_DIR

    def model_module(self) -> ModuleType:
        return model_module(self.config, self.bench_dir)

    def graph_module(self) -> ModuleType:
        return load_module(self.bench_dir / "graphs"
                           / f"{self.config['graph']}.py")

    def reference_module(self) -> ModuleType:
        return load_module(self.bench_dir / "references"
                           / f"{self.config['reference']}.py")

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "layer_metrics" / f"{name}.py")


def model_module(config: Dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The configuration's model kind, ``bench/models/<model>.py``, which
    exports ``model_config(cfg)``, ``param_shapes(cfg)``,
    ``kv_pool_shape(cfg, pages)``, ``init_params(cfg, seed)``,
    ``dims(cfg)``, ``prefill_chunk_flops(dims, start, valid)``,
    ``decode_step_flops(dims, seq_lens)`` and ``smoke_widths(cfg)``."""
    kind = config.get("model", DEFAULT_MODEL)
    return load_module(bench_dir / "models" / f"{kind}.py")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> Dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def resolve(workload: str, bench: Dict = None, root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r} (have "
                        f"{sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    cfile = root / centry["file"]
    tfile = root / "bench" / "traffic" / f"{w['traffic']}.json"
    for f in (cfile, tfile):
        if not f.is_file():
            raise SpecError(f"missing file {f}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), w["config"],
                json.loads(cfile.read_text()), w["traffic"],
                json.loads(tfile.read_text()), e2e, per_layer,
                root / "bench")
