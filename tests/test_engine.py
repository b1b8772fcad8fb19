"""AR engine integration tests.

The crucial one: the paged-KV engine with greedy sampling must generate
EXACTLY the tokens a naive dense-cache decode loop produces with the same
weights — validating chunked prefill + paged attention end-to-end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.pipelines import tiny_lm
from repro.engine.ar_engine import AREngine
from repro.engine.kv_cache import PagedKVConfig
from repro.engine.sampling import SamplingParams
from repro.kernels.paged_attention import pages_per_block
from repro.models import transformer as T


def _greedy_reference(cfg, params, prompt, n_new, max_seq=256):
    toks = jnp.asarray(prompt)[None]
    logits, cache = T.forward_prefill(cfg, params, toks, max_seq,
                                      remat=False)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        t = jnp.array([[out[-1]]], jnp.int32)
        logits, cache = T.forward_decode(cfg, params, cache, t,
                                         jnp.array([pos]))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def _engine(cfg, params, **kw):
    kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=16)
    defaults = dict(kv=kv, max_batch=4, token_budget=64, chunk_size=16)
    defaults.update(kw)
    return AREngine("eng", cfg, params, **defaults)


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_lm("t", vocab=256)
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def test_paged_engine_matches_dense_greedy(lm):
    cfg, params = lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in (5, 23, 17, 40)]   # exercise multi-chunk prefill
    n_new = 8
    eng = _engine(cfg, params,
                  default_sampling=SamplingParams(max_new_tokens=n_new,
                                                  temperature=0.0))
    for i, p in enumerate(prompts):
        eng.enqueue(i, {"tokens": p}, SamplingParams(), {})
    results = {}
    for _ in range(500):
        for ev in eng.step():
            if ev.kind == "finished":
                results[ev.req_id] = list(ev.payload["tokens"])
        if not eng.has_work:
            break
    assert len(results) == len(prompts)
    for i, p in enumerate(prompts):
        want = _greedy_reference(cfg, params, p, n_new)
        assert results[i] == want, f"req {i}: {results[i]} != {want}"


def test_engine_streams_chunks(lm):
    cfg, params = lm
    eng = _engine(cfg, params, stream_chunk=4,
                  default_sampling=SamplingParams(max_new_tokens=10,
                                                  temperature=0.0))
    eng.enqueue(0, {"tokens": np.arange(6, dtype=np.int32)},
                SamplingParams(), {})
    chunks, fin = [], []
    for _ in range(200):
        for ev in eng.step():
            (chunks if ev.kind == "chunk" else fin).append(ev)
        if not eng.has_work:
            break
    assert len(fin) == 1
    total = np.concatenate([c.payload["tokens"] for c in chunks])
    np.testing.assert_array_equal(total, fin[0].payload["tokens"])
    assert chunks[-1].is_last
    assert [c.chunk_index for c in chunks] == list(range(len(chunks)))


def test_engine_hidden_collection(lm):
    cfg, params = lm
    eng = _engine(cfg, params, collect_hidden=True,
                  default_sampling=SamplingParams(max_new_tokens=5,
                                                  temperature=0.0))
    eng.enqueue(0, {"tokens": np.arange(4, dtype=np.int32)},
                SamplingParams(), {})
    fin = None
    for _ in range(100):
        for ev in eng.step():
            if ev.kind == "finished":
                fin = ev
        if not eng.has_work:
            break
    assert fin is not None
    assert fin.payload["hidden"].shape == (5, cfg.d_model)
    assert np.isfinite(fin.payload["hidden"]).all()


def test_engine_prompt_embeds_and_preprocess(lm):
    cfg, params = lm
    extra = np.zeros((cfg.d_model,), np.float32)
    calls = []

    def prep(data, state):
        calls.append(state["phase"])
        return {"extra_embed": extra}

    eng = _engine(cfg, params, preprocess=prep,
                  default_sampling=SamplingParams(max_new_tokens=4,
                                                  temperature=0.0))
    pe = np.asarray(params["embed"][jnp.arange(5)])
    eng.enqueue(0, {"prompt_embeds": pe}, SamplingParams(), {})
    for _ in range(100):
        eng.step()
        if not eng.has_work:
            break
    assert "prefill" in calls and "decode" in calls


def test_ssm_engine_generates():
    cfg = get_config("falcon_mamba_7b", smoke=True).replace(dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(4))
    eng = _engine(cfg, params,
                  default_sampling=SamplingParams(max_new_tokens=6,
                                                  temperature=0.0))
    eng.enqueue(0, {"tokens": np.arange(8, dtype=np.int32)},
                SamplingParams(), {})
    fin = None
    for _ in range(100):
        for ev in eng.step():
            if ev.kind == "finished":
                fin = ev
        if not eng.has_work:
            break
    want = _greedy_reference(cfg, params, np.arange(8, dtype=np.int32), 6)
    assert list(fin.payload["tokens"]) == want


def test_int8_paged_engine_matches_transformer_int8(lm):
    """The int8 paged serving engine must produce exactly the tokens of an
    int8 dense-cache greedy loop (same per-(token,head) quantization)."""
    cfg, params = lm
    cfgq = cfg.replace(kv_cache_dtype="int8")
    prompt = np.arange(11, dtype=np.int32)
    n_new = 6
    # reference: transformer-path int8 dense cache greedy
    toks = jnp.asarray(prompt)[None]
    logits, cache = T.forward_prefill(cfgq, params, toks, 64, remat=False)
    want = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        t = jnp.array([[want[-1]]], jnp.int32)
        logits, cache = T.forward_decode(cfgq, params, cache, t,
                                         jnp.array([pos]))
        want.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    # engine: int8 paged pool
    eng = _engine(cfgq, params,
                  default_sampling=SamplingParams(max_new_tokens=n_new,
                                                  temperature=0.0))
    assert eng.runner.k_pages.dtype == jnp.int8
    eng.enqueue(0, {"tokens": prompt}, SamplingParams(), {})
    got = None
    for _ in range(200):
        for ev in eng.step():
            if ev.kind == "finished":
                got = list(ev.payload["tokens"])
        if not eng.has_work:
            break
    assert got == want, (got, want)


def test_eos_stops_generation(lm):
    cfg, params = lm
    # find the greedy first token, then use it as EOS
    first = _greedy_reference(cfg, params, np.arange(5, dtype=np.int32), 1)[0]
    eng = _engine(cfg, params,
                  default_sampling=SamplingParams(max_new_tokens=50,
                                                  temperature=0.0,
                                                  eos_token=first))
    eng.enqueue(0, {"tokens": np.arange(5, dtype=np.int32)},
                SamplingParams(), {})
    fin = None
    for _ in range(200):
        for ev in eng.step():
            if ev.kind == "finished":
                fin = ev
        if not eng.has_work:
            break
    assert len(fin.payload["tokens"]) == 1


@pytest.mark.parametrize("window,positions,live", [
    # seq_lens 256 | 257 | 601 | inactive: 1 + 2 + 3 blocks of 256 tokens
    (0, (255, 256, 600, 7), 1 + 2 + 3),
    # a 100-token window: 500-599 -> blocks 1, 2; 0-40 -> 0; 200-299 -> 0, 1
    (100, (599, 40, 299, 7), 2 + 1 + 2),
])
def test_kernel_stats_count_live_blocks(lm, window, positions, live):
    """``kernel_stats`` counts the paged kernel's grid, B x ceil(pp / ppb)
    steps a call, and the steps whose block holds context, on a
    hand-built decode batch with an inactive slot."""
    cfg, params = lm
    if window:
        cfg = cfg.replace(attn_variant="swa", sliding_window=window)
    kv = PagedKVConfig(num_pages=8, page_size=16, max_pages_per_seq=40)
    assert pages_per_block(16, 40, cfg.num_kv_heads, cfg.head_dim, 4) == 16
    eng = _engine(cfg, params, kv=kv)
    assert eng.kernel_stats == {"calls": 0, "blocks_in_grid": 0,
                                "blocks_live": 0}
    b = eng.max_batch
    embeds = jnp.zeros((b, 1, cfg.d_model), jnp.float32)
    tables = np.zeros((b, kv.max_pages_per_seq), np.int32)
    active = np.array([True, True, True, False])
    for n in (1, 2):
        eng.runner.decode(embeds, tables, np.asarray(positions, np.int32),
                          active)
        assert eng.kernel_stats == {"calls": n, "blocks_in_grid": n * b * 3,
                                    "blocks_live": n * live}
