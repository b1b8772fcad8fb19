"""The PD prefill engine with prefix caching and ``emit_kv`` together
(no test in tests/ covers the pair): at smoke widths, docqa's greedy
outputs with the cache on equal those with it off, including re-asked
documents (partial-page hits, copied on write) and a page-aligned prompt
sent twice (a whole-prompt hit, whose last page is copied on write)."""
import copy
import time

import numpy as np

from bench.cell import Bench
from bench.smoke import smoke_cell
from bench.spec import resolve
from bench.traffic import Traffic


def serve(cell, prompts, max_new):
    from repro.core.request import Request
    b = Bench(cell, 7, False, time.perf_counter(), 4.0)
    try:
        reqs = [Request(inputs={"tokens": p},
                        sampling={"max_new_tokens": n, "temperature": 0.0})
                for p, n in zip(prompts, max_new)]
        for r in reqs:                        # one at a time: hits are sure
            b.orch.submit(r)
            got = b.orch.completions.get(timeout=300)
            assert got is r and not r.failed, r.failed
        stats = b.system.engines["prefill"].prefix_stats
        return [np.concatenate([c["tokens"] for c in r.outputs["decode"]])
                for r in reqs], stats
    finally:
        b.close()


def test_prefix_cache_keeps_pd_outputs():
    on = smoke_cell(resolve("internlm2_pd.docqa"))
    off = copy.deepcopy(on)
    off.config["serving"]["prefill"]["prefix_cache"] = False
    items = Traffic(on.traffic, 11, on.config["vocab_size"], 4.0).items()
    batch = [next(items) for _ in range(10)]
    prompts = [it.tokens for it in batch]
    aligned = prompts[0][:len(prompts[0]) // 16 * 16]
    prompts += [aligned, aligned]
    max_new = [it.max_new for it in batch] + [6, 6]
    assert len({it.doc for it in batch}) < len(batch)   # documents re-asked
    got_on, stats = serve(on, prompts, max_new)
    got_off, _ = serve(off, prompts, max_new)
    for a, b in zip(got_on, got_off):
        np.testing.assert_array_equal(a, b)
    assert stats["hits"] >= 3 and stats["partial_hits"] >= 1
    assert stats["cached_tokens"] > 0
