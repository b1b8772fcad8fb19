"""Prefill -> decode disaggregation over the shm connector: two of the
program's ``AREngine``s sharing one parameter tree.  The prefill engine
caches prefixes and ships each prompt's KV (``emit_kv``); the decode
engine injects it and streams every token (``stream_chunk=1``).  Sizes
come from the configuration file's ``serving`` block.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from bench.serving import System


def _prefill_to_decode(data, payload):
    return {"kv_seed": (payload["kv_k"], payload["kv_v"]),
            "prompt_len": payload["prompt_len"],
            "first_token": int(payload["tokens"][0])}


def build(cfg: Dict, model_cfg, params, seed: int) -> System:
    from repro.core.graph import StageGraph
    from repro.core.stage import StageSpec
    from repro.engine.ar_engine import AREngine
    from repro.engine.kv_cache import PagedKVConfig
    from repro.engine.sampling import SamplingParams

    s = cfg["serving"]
    page = s["page_size"]
    pps = s["max_seq"] // page

    def kv(pages):
        return PagedKVConfig(num_pages=pages, page_size=page,
                             max_pages_per_seq=pps)

    pre, dec = s["prefill"], s["decode"]
    prefill = AREngine(
        "prefill", model_cfg, params, kv=kv(pre["pages"]),
        max_batch=pre["max_batch"], chunk_size=s["chunk"],
        token_budget=s["token_budget"], emit_kv=True,
        enable_prefix_cache=pre["prefix_cache"],
        default_sampling=SamplingParams(max_new_tokens=1, temperature=0.0),
        seed=seed)
    decode = AREngine(
        "decode", model_cfg, params, kv=kv(dec["pages"]),
        max_batch=dec["max_batch"], chunk_size=s["chunk"],
        token_budget=s["token_budget"], stream_chunk=1,
        default_sampling=None, seed=seed)
    graph = StageGraph()
    graph.add_stage(StageSpec("prefill", "ar"))
    graph.add_stage(StageSpec("decode", "ar", is_output=True))
    graph.add_edge("prefill", "decode", _prefill_to_decode,
                   connector=s["connector"])
    return System(graph, {"prefill": prefill, "decode": decode},
                  output="decode", entry="prefill",
                  connector=s["connector"], warm_fn=_warm_kv_hop)


def _warm_kv_hop(system: System, prompt_lens) -> None:
    """Compile the KV hop's eager programs for every page count the
    traffic's prompts span, by running the program's own
    ``PagedRunner.extract_kv`` on the prefill pool and ``inject_kv`` on
    the decode pool.  The pages written are free ones that a request
    overwrites before it reads them.  One page count at a time: each
    injection makes a transient copy of a decode pool half."""
    pre = system.engines["prefill"].runner
    dec = system.engines["decode"].runner
    page = pre.kv.page_size
    for n in sorted({-(-n // page) for n in prompt_lens}):
        bt = np.arange(n, dtype=np.int32)
        k, v = pre.extract_kv(bt, n * page)
        dec.inject_kv(k, v, bt, n * page)
        jax.block_until_ready(dec.k_pages)
