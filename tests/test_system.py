"""End-to-end behaviour tests for the full disaggregated serving system."""
import sys

import jax
import numpy as np
import pytest

from repro.baselines.monolithic import MonolithicQwenOmni
from repro.configs.pipelines import build_qwen_omni
from repro.core.orchestrator import Orchestrator
from repro.core.request import Request
from repro.launch import serve
from repro.launch.serve import build_single_arch
from repro.models.dit import DiTConfig, init_dit


def _run(graph, engines, reqs):
    orch = Orchestrator(graph, engines)
    for r in reqs:
        orch.submit(r)
    return orch, orch.run()


def test_single_arch_serving_all_families():
    """The serve launcher must serve dense, MoE and SSM archs alike."""
    rng = np.random.default_rng(0)
    for arch in ("internlm2_1_8b", "mixtral_8x7b", "falcon_mamba_7b"):
        graph, engines, _ = build_single_arch(arch, max_batch=2, max_new=4)
        reqs = [Request(inputs={"tokens": rng.integers(
            0, 500, size=6).astype(np.int32)}) for _ in range(3)]
        _, done = _run(graph, engines, reqs)
        assert len(done) == 3, arch
        for r in done:
            toks = r.outputs[arch][0]["tokens"]
            assert len(toks) == 4, arch


def test_qwen3_style_cnn_vocoder_pipeline():
    graph, engines, _ = build_qwen_omni(
        max_batch=2, thinker_tokens=4, talker_tokens=12, stream_chunk=4,
        vocoder_kind="cnn")
    reqs = [Request(inputs={"tokens": np.arange(8, dtype=np.int32)})]
    _, done = _run(graph, engines, reqs)
    assert len(done) == 1
    chunks = done[0].outputs["vocoder"]
    total = sum(c["latent"].shape[0] for c in chunks)
    assert total == 12 * 2          # CNN vocoder upsamples 2x


def test_request_data_dict_flows_through_stages():
    """The per-request data dict (paper §3.3) must accumulate intermediate
    tensors visible to downstream transfer/preprocess functions."""
    graph, engines, _ = build_qwen_omni(max_batch=2, thinker_tokens=4,
                                        talker_tokens=8, dit_steps=2)
    req = Request(inputs={"tokens": np.arange(6, dtype=np.int32)})
    _, done = _run(graph, engines, [req])
    assert "thinker_hidden" in req.data
    assert "thinker_tokens" in req.data
    assert req.data["thinker_hidden"].shape[0] == 4


def test_monolithic_baseline_runs():
    graph, engines, bundle = build_qwen_omni(max_batch=2, thinker_tokens=4,
                                             talker_tokens=8, dit_steps=2)
    vcfg = DiTConfig(name="v", num_layers=2, d_model=128, num_heads=4,
                     d_ff=256, in_dim=32, cond_dim=128, num_steps=2)
    mono = MonolithicQwenOmni(bundle, (vcfg, init_dit(vcfg,
                                                      jax.random.PRNGKey(0))),
                              dit_steps=2)
    res = mono.run([np.arange(6, dtype=np.int32)])
    assert len(res) == 1
    assert res[0]["text"].shape == (4,)
    assert res[0]["codec"].shape == (8,)
    assert res[0]["wave"].shape[1] == 16   # 8 codec tokens * 2 frames
    assert np.isfinite(res[0]["wave"]).all()


def test_jct_monotone_with_queueing():
    """Later-submitted identical requests cannot finish before earlier ones
    under FIFO admission with a saturated single-slot engine."""
    graph, engines, _ = build_qwen_omni(max_batch=1, thinker_tokens=3,
                                        talker_tokens=4, dit_steps=2)
    reqs = [Request(inputs={"tokens": np.arange(6, dtype=np.int32)})
            for _ in range(3)]
    _, done = _run(graph, engines, reqs)
    assert len(done) == 3
    finish = {r.req_id: r.completion_time for r in done}
    ids = [r.req_id for r in reqs]
    assert finish[ids[0]] <= finish[ids[1]] <= finish[ids[2]]


def test_int8_kv_cache_end_to_end():
    """Quantized-KV decode must stay close to full-precision decode."""
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models import transformer as T
    cfg = get_config("internlm2_1_8b", smoke=True).replace(dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0,
                              cfg.vocab_size)
    full, _ = T.forward_full(cfg, params, toks, remat=False)
    cfgq = cfg.replace(kv_cache_dtype="int8")
    lo, cache = T.forward_prefill(cfgq, params, toks[:, :8], max_seq=16,
                                  remat=False)
    assert cache["k"].dtype == jnp.int8
    lo, cache = T.forward_decode(cfgq, params, cache, toks[:, 8:9],
                                 jnp.array([8]))
    rel = float(jnp.max(jnp.abs(lo[:, 0] - full[:, 8]))
                / jnp.max(jnp.abs(full[:, 8])))
    assert rel < 0.05, rel


@pytest.mark.parametrize("online", [False, True])
@pytest.mark.parametrize("fail", [False, True])
def test_serve_exit_status(monkeypatch, online, fail):
    """launch/serve.py exits 1 when a request fails (here: inputs the AR
    stage rejects at admission), 0 when every request is served."""
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    if fail:
        monkeypatch.setattr(serve, "_make_inputs",
                            lambda pipeline, rng: {"bogus": np.zeros(3)})
    argv = ["serve", "--arch", "internlm2_1_8b", "--requests", "2",
            "--max-batch", "2", "--max-new", "2"]
    if online:
        argv += ["--online", "--rate", "100"]
    monkeypatch.setattr(sys, "argv", argv)
    assert serve.main() == int(fail)
