"""A run with the timed path broken underneath must come out not correct,
once for each fault the cell can have.  The harness's look for a chip is
skipped (smoke widths on the CPU); everything else runs as in a measured
run, and the fault is planted in the program for the length of the test.

- ``token``: the sampled token is altered where it is produced;
- ``state``: the decode step returns the KV pool unchanged (the new
  token's keys and values are never written);
- ``half_batch``: half of the decode batch is left out of the step;
- ``hop`` (PD only): the prompt KV never reaches the decode engine's pool
  (the exchange between the engines is left out).
"""
import numpy as np
import pytest

from conftest import checked_cell, smoke_run


def plant(monkeypatch, fault):
    from repro.engine import ar_engine, runner
    if fault == "token":
        real = ar_engine.sample_tokens

        def altered(logits, temperature, top_k, key):
            return (real(logits, temperature, top_k, key) + 1) \
                % logits.shape[-1]
        monkeypatch.setattr(ar_engine, "sample_tokens", altered)
    elif fault == "state":
        real = runner.PagedRunner._decode_impl

        def unchanged(self, params, kp, vp, ks, vs, *rest):
            out = real(self, params, kp, vp, ks, vs, *rest)
            return (*out[:2], kp, vp, ks, vs)
        monkeypatch.setattr(runner.PagedRunner, "_decode_impl", unchanged)
    elif fault == "half_batch":
        real = runner.PagedRunner.decode

        def half(self, embeds, tables, positions, active):
            active = np.asarray(active).copy()
            active[1::2] = False
            return real(self, embeds, tables, positions, active)
        monkeypatch.setattr(runner.PagedRunner, "decode", half)
    elif fault == "hop":
        monkeypatch.setattr(runner.PagedRunner, "inject_kv",
                            lambda self, *a, **k: None)


@pytest.mark.parametrize("cell,fault", [
    ("internlm2_pd.docqa", "token"), ("internlm2_pd.docqa", "state"),
    ("internlm2_pd.docqa", "half_batch"), ("internlm2_pd.docqa", "hop"),
    ("qwen1_5_4b.longgen", "token"), ("qwen1_5_4b.longgen", "state"),
    ("qwen1_5_4b.longgen", "half_batch"),
])
def test_a_broken_path_is_not_correct(monkeypatch, cell, fault):
    plant(monkeypatch, fault)
    res = smoke_run(None, cell=checked_cell(cell), seconds=6.0)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False, gap
    assert gap["value"] > gap["limit"] or res["failed"] > 0
