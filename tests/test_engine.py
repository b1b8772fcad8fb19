"""AR engine integration tests.

The crucial one: the paged-KV engine with greedy sampling must generate
EXACTLY the tokens a naive dense-cache decode loop produces with the same
weights — validating chunked prefill + paged attention end-to-end.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.pipelines import tiny_lm
from repro.engine.ar_engine import AREngine
from repro.engine.kv_cache import PagedKVConfig
from repro.engine.runner import PagedRunner, _mlp_or_moe
from repro.engine.sampling import SamplingParams
from repro.kernels import ops, ref
from repro.kernels.paged_attention import pages_per_block
from repro.models import layers as Lyr
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


# ---- the layer scan's former form, kept as the oracle of the in-place one:
# each layer's pool sliced out as the scan's ``xs`` and restacked as its
# ``ys`` (the runner's prefill and decode programs before the pools rode in
# the carry)

def _xs_ys_write(r, pools, pid, slot, k, v):
    kp, vp, ksp, vsp = pools
    if r.quant:
        kq, ks = Lyr.quantize_kv(k)
        vq, vs = Lyr.quantize_kv(v)
        return (kp.at[pid, :, slot].set(kq, mode="drop"),
                vp.at[pid, :, slot].set(vq, mode="drop"),
                ksp.at[pid, :, slot].set(ks, mode="drop"),
                vsp.at[pid, :, slot].set(vs, mode="drop"))
    return (kp.at[pid, :, slot].set(k.astype(kp.dtype), mode="drop"),
            vp.at[pid, :, slot].set(v.astype(vp.dtype), mode="drop"),
            ksp, vsp)


def _xs_ys_prefill(r, params, k_pages, v_pages, k_scales, v_scales, embeds,
                   block_table, start, valid_len):
    cfg, page = r.cfg, r.kv.page_size
    c = embeds.shape[1]
    positions = start + jnp.arange(c)[None, :]
    window = cfg.sliding_window if cfg.attn_variant == "swa" else 0
    pos_flat = start + jnp.arange(c)
    pid = jnp.where(pos_flat < start + valid_len,
                    block_table[pos_flat // page], r.kv.num_pages)
    slot = pos_flat % page

    def body(h, xs):
        lp, *pools = xs
        hn = Lyr.rmsnorm(lp["ln1"], h, cfg.rmsnorm_eps)
        q, k, v = Lyr._qkv(cfg, lp["attn"], hn)
        q = Lyr.rope(q, positions, cfg.rope_theta)
        k = Lyr.rope(k, positions, cfg.rope_theta)
        kp, vp, ksp, vsp = _xs_ys_write(r, pools, pid, slot, k[0], v[0])
        k_all = ref.gather_pages(kp, block_table, ksp).astype(h.dtype)
        v_all = ref.gather_pages(vp, block_table, vsp).astype(h.dtype)
        o = ref.chunk_attention(q, k_all[None], v_all[None], start,
                                window=window)
        h = h + jnp.einsum("bsqh,qhd->bsd", o, lp["attn"]["wo"])
        hn = Lyr.rmsnorm(lp["ln2"], h, cfg.rmsnorm_eps)
        return h + _mlp_or_moe(cfg, lp, hn), (kp, vp, ksp, vsp)

    h, pools = jax.lax.scan(body, embeds, (params["blocks"], k_pages,
                                           v_pages, k_scales, v_scales))
    return (T._unembed(cfg, params, h)[0], h[0], *pools)


def _xs_ys_decode(r, params, k_pages, v_pages, k_scales, v_scales, embeds,
                  block_tables, positions, active):
    cfg, page = r.cfg, r.kv.page_size
    window = cfg.sliding_window if cfg.attn_variant == "swa" else 0
    bidx = jnp.arange(embeds.shape[0])
    pid = jnp.where(active, block_tables[bidx, positions // page],
                    r.kv.num_pages)
    slot = positions % page
    seq_lens = jnp.where(active, positions + 1, 0)

    def body(h, xs):
        lp, *pools = xs
        hn = Lyr.rmsnorm(lp["ln1"], h, cfg.rmsnorm_eps)
        q, k, v = Lyr._qkv(cfg, lp["attn"], hn)
        q = Lyr.rope(q, positions[:, None], cfg.rope_theta)
        k = Lyr.rope(k, positions[:, None], cfg.rope_theta)
        kp, vp, ksp, vsp = _xs_ys_write(r, pools, pid, slot, k[:, 0],
                                        v[:, 0])
        o = ops.paged_attention(q[:, 0], kp, vp, block_tables, seq_lens,
                                window=window, k_scale_pages=ksp,
                                v_scale_pages=vsp)
        h = h + jnp.einsum("bqh,qhd->bd", o.astype(h.dtype),
                           lp["attn"]["wo"])[:, None]
        hn = Lyr.rmsnorm(lp["ln2"], h, cfg.rmsnorm_eps)
        return h + _mlp_or_moe(cfg, lp, hn), (kp, vp, ksp, vsp)

    h, pools = jax.lax.scan(body, embeds, (params["blocks"], k_pages,
                                           v_pages, k_scales, v_scales))
    return (T._unembed(cfg, params, h)[:, 0], h[:, 0], *pools)


@pytest.mark.parametrize("program,variant", [
    ("prefill", "plain"), ("prefill", "int8"), ("prefill", "swa"),
    ("decode", "plain"), ("decode", "int8"), ("decode", "swa"),
    ("decode", "pallas_int8"),     # the kernel, interpreted, by layer index
])
def test_in_place_layer_scan_matches_xs_ys_form(monkeypatch, program,
                                                variant):
    """One prefill chunk (the second of a request, cut short by
    ``valid_len``) or one decode step (with an inactive slot) of the
    runner, whose layer scan carries the whole pools and writes them in
    place, gives logits, hidden states and pools bit for bit those of the
    former form.  The pools start random: every (page, slot) the step does
    not write comes back as it went in, in every layer and pool."""
    cfg = tiny_lm("t", vocab=256, layers=3)
    params = T.init_params(cfg, jax.random.PRNGKey(5))
    if "int8" in variant:
        cfg = cfg.replace(kv_cache_dtype="int8")
    if variant == "swa":
        cfg = cfg.replace(attn_variant="swa", sliding_window=6)
    if variant.startswith("pallas"):
        monkeypatch.setattr(ops, "_BACKEND", "pallas")
    kv = PagedKVConfig(num_pages=12, page_size=8, max_pages_per_seq=6)
    r = PagedRunner(cfg, params, kv)
    rng = np.random.default_rng(11)
    shape = r.k_pages.shape
    if r.quant:
        pools = [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                 for _ in range(2)]
        pools += [jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]),
                              jnp.float32) for _ in range(2)]
    else:
        pools = [jnp.asarray(rng.standard_normal(shape), r.k_pages.dtype)
                 for _ in range(2)] + [None, None]
    page = kv.page_size
    written = np.zeros((kv.num_pages, page), bool)     # (page id, slot)
    if program == "prefill":
        table = rng.permutation(kv.num_pages)[:6].astype(np.int32)
        start, valid = 8, 5                  # tokens 8..12; 13..15 dropped
        for pos in range(start, start + valid):
            written[table[pos // page], pos % page] = True
        args = (jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)),
                            jnp.float32), jnp.asarray(table),
                jnp.int32(start), jnp.int32(valid))
    else:
        tables = np.stack([rng.permutation(kv.num_pages)[:6]
                           for _ in range(4)]).astype(np.int32)
        positions = np.array([3, 17, 40, 9], np.int32)
        active = np.array([True, True, True, False])
        tables[np.arange(4), positions // page] = [0, 1, 2, 3]
        for b in np.flatnonzero(active):
            written[tables[b, positions[b] // page], positions[b] % page] \
                = True
        args = (jnp.asarray(rng.standard_normal((4, 1, cfg.d_model)),
                            jnp.float32), jnp.asarray(tables),
                jnp.asarray(positions), jnp.asarray(active))
    new, old = ((r._prefill_impl, _xs_ys_prefill) if program == "prefill"
                else (r._decode_impl, _xs_ys_decode))
    got = jax.jit(new)(params, *pools, *args)
    want = jax.jit(functools.partial(old, r))(params, *pools, *args)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for before, after in zip(pools, got[2:]):
        if before is None:
            continue
        # (L, P, nkv, page[, hd]) -> (L, P, page, ...): index (page, slot)
        before, after = (np.moveaxis(np.asarray(x), 3, 2)
                         for x in (before, after))
        np.testing.assert_array_equal(after[:, ~written],
                                      before[:, ~written])
        assert not np.array_equal(after[:, written], before[:, written])
