"""End-to-end stage-graph serving tests (tiny Qwen-Omni pipeline)."""
import numpy as np
import pytest

from repro.configs.pipelines import (build_ar_dit, build_mimo_audio,
                                     build_qwen_omni)
from repro.core.orchestrator import Orchestrator
from repro.core.request import Request


def _prompts(n, lo=6, hi=20, vocab=500, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture(scope="module")
def omni():
    return build_qwen_omni(max_batch=4, thinker_tokens=6, talker_tokens=18,
                           stream_chunk=6, dit_steps=2)


def test_omni_pipeline_completes(omni):
    graph, engines, bundle = omni
    orch = Orchestrator(graph, engines)
    for p in _prompts(3):
        orch.submit(Request(inputs={"tokens": p}))
    done = orch.run()
    assert len(done) == 3
    for r in done:
        assert r.jct is not None and r.jct > 0
        assert "thinker_hidden" in r.data
        assert r.data["thinker_hidden"].shape == (6, 128)
        chunks = r.outputs["vocoder"]
        assert len(chunks) == 3            # 18 talker tokens / 6 per chunk
        total = sum(c["latent"].shape[0] for c in chunks)
        assert total == 18 * 2             # out_len_per_cond = 2


def test_streaming_overlaps_stages(omni):
    """First vocoder chunk must be produced before the talker finishes."""
    graph, engines, bundle = build_qwen_omni(
        max_batch=2, thinker_tokens=4, talker_tokens=24, stream_chunk=6,
        dit_steps=2)
    orch = Orchestrator(graph, engines)
    orch.submit(Request(inputs={"tokens": np.arange(8, dtype=np.int32)}))
    first_voc_chunk_tick = None
    talker_done_tick = None
    for tick in range(2000):
        busy = any(engines[n].has_work for n in graph.stages)
        for name in graph.topo_order():
            for ev in engines[name].step():
                ev.stage = ev.stage or name
                if name == "vocoder" and first_voc_chunk_tick is None:
                    first_voc_chunk_tick = tick
                if name == "talker" and ev.kind == "finished":
                    talker_done_tick = tick
                orch._route(ev)
        if not busy:
            break
    assert first_voc_chunk_tick is not None and talker_done_tick is not None
    assert first_voc_chunk_tick < talker_done_tick, \
        "streaming must overlap vocoder with talker decoding"


def test_multimodal_inputs_via_mm_encode(omni):
    """Audio/image frontend embeddings (stubbed) flow through the Thinker's
    mm_encode preprocess and extend its prompt (paper Fig 4)."""
    graph, engines, _ = build_qwen_omni(max_batch=2, thinker_tokens=4,
                                        talker_tokens=8, dit_steps=2)
    rng = np.random.default_rng(3)
    req = Request(inputs={"tokens": np.arange(6, dtype=np.int32)},
                  data={"mm_embeds": rng.standard_normal(
                      (10, 32)).astype(np.float32)})
    orch = Orchestrator(graph, engines)
    orch.submit(req)
    done = orch.run()
    assert len(done) == 1
    assert req.data["mm_frames_used"] == 10
    assert req.outputs["vocoder"]


def test_connector_stats_populated(omni):
    graph, engines, bundle = omni
    orch = Orchestrator(graph, engines)
    orch.submit(Request(inputs={"tokens": np.arange(10, dtype=np.int32)}))
    orch.run()
    stats = orch.connector_stats()
    assert stats["shm"].calls >= 1          # thinker->talker hidden states
    assert stats["inline"].calls >= 1       # talker->vocoder chunks
    assert stats["shm"].bytes > 0


def test_ar_dit_pipeline():
    graph, engines, _ = build_ar_dit("glm", max_batch=2, ar_tokens=5,
                                     image_latents=16, dit_steps=2)
    orch = Orchestrator(graph, engines)
    for p in _prompts(2, seed=1):
        orch.submit(Request(inputs={"tokens": p}))
    done = orch.run()
    assert len(done) == 2
    for r in done:
        img = r.outputs["glm_dit"][0]["latent"]
        assert img.shape == (16, 32)
        assert np.isfinite(img).all()


def test_mimo_pipeline():
    graph, engines, _ = build_mimo_audio(max_batch=2, ar_tokens=6, patch=4)
    orch = Orchestrator(graph, engines)
    rng = np.random.default_rng(0)
    for _ in range(2):
        orch.submit(Request(
            inputs={"audio": rng.standard_normal((32, 16)).astype(np.float32)}))
    done = orch.run()
    assert len(done) == 2
    for r in done:
        audio = r.outputs["patch_dec"][0]["audio"]
        assert audio.shape == (6, 64)       # 6 tokens * patch(4)*16


def test_disaggregated_beats_nothing_lost():
    """All requests complete even when arrival exceeds batch capacity."""
    graph, engines, _ = build_qwen_omni(max_batch=2, thinker_tokens=3,
                                        talker_tokens=6, stream_chunk=0,
                                        dit_steps=2)
    orch = Orchestrator(graph, engines)
    for p in _prompts(7, seed=2):
        orch.submit(Request(inputs={"tokens": p}))
    done = orch.run()
    assert len(done) == 7
