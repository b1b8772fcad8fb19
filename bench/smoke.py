"""Smoke-width copies of a cell, for the CPU: the same graph, traffic
shape and code path at a size the CPU can serve in seconds.  The model's
widths are its kind's ``smoke_widths``; serving and traffic shrink here
alike for every kind.  Used by ``bench/tests``; never by a measured run."""
from __future__ import annotations

import copy
from typing import Dict

from bench.spec import Cell


def _shrink_len(d: Dict, lo: int, hi: int) -> Dict:
    return {"median": (lo + hi) // 3, "sigma": 0.5, "min": lo, "max": hi}


def smoke_cell(cell: Cell, **traffic_overrides) -> Cell:
    c = copy.deepcopy(cell)
    cfg = c.config
    cfg.update(c.model_module().smoke_widths(cfg))
    s = cfg["serving"]
    s.update(max_seq=256, chunk=32)
    t = c.traffic
    t.update(warmup_s=1.0, drain_s=60.0)
    if t.get("shared"):
        t["shared"]["tokens"] = _shrink_len(t["shared"]["tokens"], 48, 160)
        t["prompt"] = _shrink_len(t["prompt"], 4, 24)
        t["output"] = _shrink_len(t["output"], 4, 12)
        t["rate_per_s"] = 4.0
    else:
        t["prompt"] = _shrink_len(t["prompt"], 8, 64)
        t["output"] = _shrink_len(t["output"], 8, 48)
        t["clients"] = 4
    if "prefill" in s:
        s["prefill"].update(pages=96)
        s["decode"].update(max_batch=4, pages=4 * 16)
    else:
        s.update(max_batch=4, pages=48)
    t.update(traffic_overrides)
    return c


def cpu_device() -> Dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


#: stand-in peaks for CPU runs: the readers need some table, and no
#: number from a CPU run is ever reported under a device metric's name
CPU_PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
