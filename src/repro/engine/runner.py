"""Model runners: the jitted step functions one AR engine executes.

PagedRunner (dense / moe / vlm stages):
  - ``prefill_chunk``: process C prompt tokens of ONE request, writing their
    K/V into the request's pages and attending over all its history pages
    (chunked prefill, Sarathi-style).
  - ``decode``: batched one-token step for ALL active slots against the
    shared page pool (vLLM-style paged attention).
  Both scan the layers with the whole pools, K and V (L, P, nkv, page, hd)
  and the int8 scales (L, P, nkv, page), in the scan's carry: a layer
  scatters its new tokens into them at its index, and attention reads
  them by that index, so no layer's pool is sliced out or restacked and
  the donated pools are updated in place.

StateRunner (ssm / hybrid stages): constant-size recurrent state per slot
(+ dense KV for the hybrid's shared-attention sites), reusing the
transformer's prefill/decode paths.

Both runners return final-layer hidden states so stage-transfer functions
can forward them downstream (e.g. Thinker hidden states → Talker).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.engine.kv_cache import PagedKVConfig, init_kv_pages
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import transformer as T


def _mlp_or_moe(cfg, lp, h):
    if cfg.is_moe:
        y, _ = moe_mod.moe_forward(cfg, lp["moe"], h)
        return y
    return L.mlp(lp["mlp"], h)


def _write_kv(pools, layer, pid, slot, k, v, quant: bool):
    """Scatter new K/V (T, nkv, hd) into the whole pools at ``layer``,
    token t at page ``pid[t]``, slot ``slot[t]``; an out-of-range page id
    drops its token.  pools: (k, v, k_scales, v_scales), scales None
    unless ``quant``."""
    heads = jnp.arange(k.shape[1])[None, :]

    def put(pool, x):
        return pool.at[layer, pid[:, None], heads, slot[:, None]].set(
            x.astype(pool.dtype), mode="drop")

    kp, vp, ksp, vsp = pools
    if quant:
        kq, ks = L.quantize_kv(k)
        vq, vs = L.quantize_kv(v)
        return put(kp, kq), put(vp, vq), put(ksp, ks), put(vsp, vs)
    return put(kp, k), put(vp, v), ksp, vsp


class PagedRunner:
    """Paged-KV execution for attention architectures."""

    def __init__(self, cfg: ModelConfig, params, kv: PagedKVConfig):
        assert cfg.arch_type in ("dense", "moe", "vlm", "audio")
        self.cfg = cfg
        self.params = params
        self.kv = kv
        self.quant = cfg.kv_cache_dtype == "int8"
        self.k_pages, self.v_pages = init_kv_pages(cfg, kv, cfg.num_layers)
        if self.quant:
            from repro.engine.kv_cache import init_kv_scale_pages
            self.k_scales, self.v_scales = init_kv_scale_pages(
                cfg, kv, cfg.num_layers)
        else:
            self.k_scales = self.v_scales = None
        self._prefill_jit = jax.jit(self._prefill_impl,
                                    donate_argnums=(1, 2, 3, 4))
        self._decode_jit = jax.jit(self._decode_impl,
                                   donate_argnums=(1, 2, 3, 4))
        # how much of the paged kernel's grid does work (host arithmetic on
        # the decode batch, whatever the backend): ``blocks_in_grid`` counts
        # its B x ceil(pp / ppb) steps per call, ``blocks_live`` those that
        # hold context and so copy and compute
        self._ppb = pa.pages_per_block(
            kv.page_size, kv.max_pages_per_seq, cfg.num_kv_heads,
            cfg.head_dim, self.k_pages.dtype.itemsize, self.quant)
        self.kernel_stats = {"calls": 0, "blocks_in_grid": 0,
                             "blocks_live": 0}
        # host-side copy of the embedding table: avoids retracing an eager
        # gather for every prompt length (hot path for token->embed lookups)
        self._embed_np = np.asarray(params["embed"], np.float32)

    # ---- embeds ---------------------------------------------------------
    def embed(self, tokens: np.ndarray) -> np.ndarray:
        return self._embed_np[np.asarray(tokens)]

    # ---- prefill chunk ---------------------------------------------------
    def _prefill_impl(self, params, k_pages, v_pages, k_scales, v_scales,
                      embeds, block_table, start, valid_len):
        """embeds: (1, C, d); block_table: (pp,); start, valid_len: scalars.
        Returns (logits (C,V), hidden (C,d), new page pools...)."""
        cfg = self.cfg
        c = embeds.shape[1]
        page = self.kv.page_size
        positions = start + jnp.arange(c)[None, :]            # (1, C)
        window = cfg.sliding_window if cfg.attn_variant == "swa" else 0

        pos_flat = start + jnp.arange(c)
        pid = jnp.where(pos_flat < start + valid_len,
                        block_table[pos_flat // page],
                        self.kv.num_pages)                    # OOB => dropped
        slot = pos_flat % page

        def body(carry, xs):
            h, pools = carry
            lp, layer = xs
            hn = L.rmsnorm(lp["ln1"], h, cfg.rmsnorm_eps)
            q, k, v = L._qkv(cfg, lp["attn"], hn)
            if cfg.rope_theta:
                q = L.rope(q, positions, cfg.rope_theta)
                k = L.rope(k, positions, cfg.rope_theta)
            pools = _write_kv(pools, layer, pid, slot, k[0], v[0], self.quant)
            kp, vp, ksp, vsp = pools
            k_all = ref.gather_pages(kp, block_table, ksp, layer=layer)
            v_all = ref.gather_pages(vp, block_table, vsp, layer=layer)
            o = ref.chunk_attention(q, k_all[None].astype(h.dtype),
                                    v_all[None].astype(h.dtype), start,
                                    window=window)
            h = h + jnp.einsum("bsqh,qhd->bsd", o, lp["attn"]["wo"])
            hn = L.rmsnorm(lp["ln2"], h, cfg.rmsnorm_eps)
            h = h + _mlp_or_moe(cfg, lp, hn)
            return (h, pools), None

        (h, (k_pages, v_pages, k_scales, v_scales)), _ = jax.lax.scan(
            body, (embeds, (k_pages, v_pages, k_scales, v_scales)),
            (params["blocks"], jnp.arange(k_pages.shape[0])))
        hidden = h[0]
        logits = T._unembed(cfg, params, h)[0]
        return logits, hidden, k_pages, v_pages, k_scales, v_scales

    def prefill_chunk(self, embeds, block_table, start, valid_len):
        (logits, hidden, self.k_pages, self.v_pages, self.k_scales,
         self.v_scales) = self._prefill_jit(
            self.params, self.k_pages, self.v_pages, self.k_scales,
            self.v_scales, embeds,
            jnp.asarray(block_table), jnp.asarray(start, jnp.int32),
            jnp.asarray(valid_len, jnp.int32))
        return logits, hidden

    # ---- prefix cache: copy-on-write page copies -------------------------
    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
    def _copy_pages_jit(self, k_pages, v_pages, src, dst):
        return (k_pages.at[:, dst].set(k_pages[:, src]),
                v_pages.at[:, dst].set(v_pages[:, src]))

    def copy_pages(self, src_pages, dst_pages) -> None:
        """Copy whole KV pages across all layers (copy-on-write: a request
        extending a shared cached page gets a private copy first).  One
        jitted donated call per pool pair — the update happens in place
        instead of materializing a full pool copy per eager ``.at.set``
        (this runs at admission, so it is on the TTFT path)."""
        src = jnp.asarray(np.asarray(src_pages, np.int32))
        dst = jnp.asarray(np.asarray(dst_pages, np.int32))
        self.k_pages, self.v_pages = self._copy_pages_jit(
            self.k_pages, self.v_pages, src, dst)
        if self.quant:
            self.k_scales, self.v_scales = self._copy_pages_jit(
                self.k_scales, self.v_scales, src, dst)

    # ---- PD disaggregation: KV extraction / injection -------------------
    def extract_kv(self, block_table, n_tokens: int):
        """Pull one request's prompt KV out of the page pool.

        Returns (k, v): (L, n_pages*page, nkv, hd) token-major host arrays
        (trailing padding past n_tokens is zeros) — the payload a prefill
        stage ships to a decode stage through the unified connector.
        """
        page = self.kv.page_size
        n_pages = -(-n_tokens // page)
        bt = jnp.asarray(block_table[:n_pages])
        shape = (self.cfg.num_layers, n_pages * page,
                 self.cfg.num_kv_heads, self.cfg.head_dim)

        def tokens(pages, scales):
            x = pages[:, bt]                        # (L, n, nkv, page, hd)
            if self.quant:
                # ship full-precision KV (the receiving stage re-quantizes)
                x = x.astype(jnp.float32) * scales[:, bt][..., None]
            return np.asarray(jnp.swapaxes(x, 2, 3).reshape(shape))

        return (tokens(self.k_pages, self.k_scales),
                tokens(self.v_pages, self.v_scales))

    def inject_kv(self, k_seed, v_seed, block_table, n_tokens: int) -> None:
        """Write transferred prompt KV into this engine's page pool."""
        page = self.kv.page_size
        n_pages = -(-n_tokens // page)
        pad = n_pages * page - k_seed.shape[1]
        if pad:
            padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
            k_seed = np.pad(k_seed, padw)
            v_seed = np.pad(v_seed, padw)
        Ln, _, nkv, hd = k_seed.shape
        kp = jnp.asarray(k_seed.reshape(Ln, n_pages, page, nkv, hd)
                         .swapaxes(2, 3))
        vp = jnp.asarray(v_seed.reshape(Ln, n_pages, page, nkv, hd)
                         .swapaxes(2, 3))
        bt = jnp.asarray(block_table[:n_pages])
        if self.quant:
            from repro.models.layers import quantize_kv
            kq, ks = quantize_kv(kp)
            vq, vs = quantize_kv(vp)
            self.k_pages = self.k_pages.at[:, bt].set(kq)
            self.v_pages = self.v_pages.at[:, bt].set(vq)
            self.k_scales = self.k_scales.at[:, bt].set(ks)
            self.v_scales = self.v_scales.at[:, bt].set(vs)
        else:
            self.k_pages = self.k_pages.at[:, bt].set(kp.astype(
                self.k_pages.dtype))
            self.v_pages = self.v_pages.at[:, bt].set(vp.astype(
                self.v_pages.dtype))

    # ---- batched decode ---------------------------------------------------
    def _decode_impl(self, params, k_pages, v_pages, k_scales, v_scales,
                     embeds, block_tables, positions, active):
        """embeds: (B,1,d); block_tables: (B,pp); positions: (B,) current
        token's write position; active: (B,) bool.
        Returns (logits (B,V), hidden (B,d), new page pools...)."""
        cfg = self.cfg
        page = self.kv.page_size
        window = cfg.sliding_window if cfg.attn_variant == "swa" else 0
        bidx = jnp.arange(embeds.shape[0])
        pid = jnp.where(active, block_tables[bidx, positions // page],
                        self.kv.num_pages)
        slot = positions % page
        seq_lens = jnp.where(active, positions + 1, 0)

        def body(carry, xs):
            h, pools = carry
            lp, layer = xs
            hn = L.rmsnorm(lp["ln1"], h, cfg.rmsnorm_eps)
            q, k, v = L._qkv(cfg, lp["attn"], hn)
            if cfg.rope_theta:
                q = L.rope(q, positions[:, None], cfg.rope_theta)
                k = L.rope(k, positions[:, None], cfg.rope_theta)
            pools = _write_kv(pools, layer, pid, slot, k[:, 0], v[:, 0],
                              self.quant)
            kp, vp, ksp, vsp = pools
            o = ops.paged_attention(q[:, 0], kp, vp, block_tables, seq_lens,
                                    window=window, k_scale_pages=ksp,
                                    v_scale_pages=vsp, layer=layer)
            h = h + jnp.einsum("bqh,qhd->bd", o.astype(h.dtype),
                               lp["attn"]["wo"])[:, None]
            hn = L.rmsnorm(lp["ln2"], h, cfg.rmsnorm_eps)
            h = h + _mlp_or_moe(cfg, lp, hn)
            return (h, pools), None

        (h, (k_pages, v_pages, k_scales, v_scales)), _ = jax.lax.scan(
            body, (embeds, (k_pages, v_pages, k_scales, v_scales)),
            (params["blocks"], jnp.arange(k_pages.shape[0])))
        hidden = h[:, 0]
        logits = T._unembed(cfg, params, h)[:, 0]
        return logits, hidden, k_pages, v_pages, k_scales, v_scales

    def decode(self, embeds, block_tables, positions, active):
        window = (self.cfg.sliding_window if self.cfg.attn_variant == "swa"
                  else 0)
        seq_lens = np.where(active, np.asarray(positions) + 1, 0)
        st = self.kernel_stats
        st["calls"] += 1
        st["blocks_in_grid"] += len(seq_lens) * -(
            -self.kv.max_pages_per_seq // self._ppb)
        st["blocks_live"] += pa.live_block_count(
            seq_lens, self._ppb * self.kv.page_size, window)
        (logits, hidden, self.k_pages, self.v_pages, self.k_scales,
         self.v_scales) = self._decode_jit(
            self.params, self.k_pages, self.v_pages, self.k_scales,
            self.v_scales, embeds,
            jnp.asarray(block_tables), jnp.asarray(positions),
            jnp.asarray(active))
        return logits, hidden


class StateRunner:
    """Recurrent-state execution for SSM / hybrid architectures.

    Slots share batched state arrays; prefill is a single scan per request
    (SSM prefill has no chunking — the scan IS the prefill), decode is a
    batched one-token step.
    """

    def __init__(self, cfg: ModelConfig, params, kv: PagedKVConfig,
                 max_batch: int):
        assert cfg.arch_type in ("ssm", "hybrid")
        self.cfg = cfg
        self.params = params
        self.kv = kv
        self.max_batch = max_batch
        self.cache = T.init_decode_cache(cfg, max_batch, kv.max_seq)
        self._prefill_jit = jax.jit(self._prefill_impl)
        self._insert_jit = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._embed_np = np.asarray(params["embed"], np.float32)

    def embed(self, tokens: np.ndarray) -> np.ndarray:
        return self._embed_np[np.asarray(tokens)]

    def _prefill_impl(self, params, embeds):
        cfg = self.cfg
        # reuse transformer prefill on a batch of 1
        logits, cache1 = _prefill_from_embeds(cfg, params, embeds,
                                              self.kv.max_seq)
        hidden = None
        return logits[0], cache1

    def _insert_impl(self, cache, cache1, slot):
        def ins(c, c1):
            return c.at[:, slot].set(c1[:, 0].astype(c.dtype))
        return jax.tree.map(ins, cache, cache1)

    def prefill(self, embeds, slot):
        logits, cache1 = self._prefill_jit(self.params, embeds)
        self.cache = self._insert_jit(self.cache, cache1, slot)
        return logits, None

    def _decode_impl(self, params, cache, embeds, positions, active):
        cfg = self.cfg
        logits, new_cache = _decode_from_embeds(cfg, params, cache, embeds,
                                                positions)
        # inactive slots must be a no-op: without the mask they run the
        # step anyway and write stale-position state/KV into the shared
        # cache (every leaf is (outer, batch, ...), batch at dim 1)
        def _sel(new, old):
            mask = active.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(mask, new, old)

        cache = jax.tree.map(_sel, new_cache, cache)
        return logits[:, 0], cache

    def decode(self, embeds, block_tables, positions, active):
        logits, self.cache = self._decode_jit(
            self.params, self.cache, embeds, jnp.asarray(positions),
            jnp.asarray(active))
        return logits, None


# ---- embed-level wrappers around transformer.py (prompts may be embeds) ----

def _prefill_from_embeds(cfg, params, embeds, max_seq):
    """transformer.forward_prefill but starting from embeddings
    (treat inputs as precomputed frames so _embed passes them through)."""
    cfg2 = cfg.replace(modality="audio_frames")
    return T.forward_prefill(cfg2, params, embeds, max_seq, remat=False)


def _decode_from_embeds(cfg, params, cache, embeds, positions):
    cfg2 = cfg.replace(modality="audio_frames")
    return T.forward_decode(cfg2, params, cache, embeds, positions)
