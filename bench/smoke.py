"""Smoke-width copies of a cell, for the CPU: the same graph, traffic
shape and code path at a size the CPU can serve in seconds.  Used by
``bench/tests`` and ``bench/rehearse.py``; never by a measured run."""
from __future__ import annotations

import copy
from typing import Dict

from bench.spec import Cell

SMOKE_WIDTHS = {"hidden_size": 256, "intermediate_size": 512,
                "num_hidden_layers": 2, "num_attention_heads": 8,
                "head_dim": 32, "vocab_size": 1024}


def _shrink_len(d: Dict, lo: int, hi: int) -> Dict:
    return {"median": (lo + hi) // 3, "sigma": 0.5, "min": lo, "max": hi}


def smoke_cell(cell: Cell, **traffic_overrides) -> Cell:
    c = copy.deepcopy(cell)
    cfg = c.config
    kv_ratio = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    cfg.update(SMOKE_WIDTHS)
    cfg["num_key_value_heads"] = SMOKE_WIDTHS["num_attention_heads"] // kv_ratio
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
