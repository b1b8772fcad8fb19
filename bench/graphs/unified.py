"""One unified AR stage: the program's ``AREngine`` runs chunked prefill
and paged decode for every request, streaming each token
(``stream_chunk=1``).  Sizes come from the configuration file's
``serving`` block."""
from __future__ import annotations

from typing import Dict

from bench.serving import System


def build(cfg: Dict, model_cfg, params, seed: int) -> System:
    from repro.core.graph import StageGraph
    from repro.core.stage import StageSpec
    from repro.engine.ar_engine import AREngine
    from repro.engine.kv_cache import PagedKVConfig

    s = cfg["serving"]
    page = s["page_size"]
    eng = AREngine(
        "unified", model_cfg, params,
        kv=PagedKVConfig(num_pages=s["pages"], page_size=page,
                         max_pages_per_seq=s["max_seq"] // page),
        max_batch=s["max_batch"], chunk_size=s["chunk"],
        token_budget=s["token_budget"], stream_chunk=1,
        enable_prefix_cache=s["prefix_cache"], default_sampling=None,
        seed=seed)
    graph = StageGraph()
    graph.add_stage(StageSpec("unified", "ar", is_output=True))
    return System(graph, {"unified": eng}, output="unified", entry="unified")
