"""Pure-jnp reference oracles for every Pallas kernel.

These are the numerically-trusted implementations: the engines run them on
CPU (this container), the Pallas kernels are validated against them in
``tests/test_kernels.py`` with ``interpret=True``, and the dry-run lowers
them for roofline analysis.

Attention uses grouped (GQA) einsums — K/V are never materially repeated to
``num_heads``, so HLO FLOPs/bytes match what a real GQA kernel would do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -2.0 ** 30  # large-negative instead of -inf: keeps fully-masked


def _group(q: jax.Array, nkv: int) -> jax.Array:
    """(B,S,nq,hd) -> (B,S,nkv,g,hd)."""
    b, s, nq, hd = q.shape
    return q.reshape(b, s, nkv, nq // nkv, hd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None) -> jax.Array:
    """Full-sequence attention oracle.

    q: (B, Sq, nq, hd); k, v: (B, Sk, nkv, hd); nq % nkv == 0.
    window > 0 => sliding-window: key j visible to query i iff
    i - window < j <= i (plus causality).
    """
    b, sq, nq, hd = q.shape
    nkv = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    qg = _group(q, nkv).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg * scale,
                        k.astype(jnp.float32))  # (B,nkv,g,Sq,Sk)
    sk = k.shape[1]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends (prefill-extend)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, nq, hd).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int = 0,
                     scale: float | None = None,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None,
                     key_positions: jax.Array | None = None) -> jax.Array:
    """Single-token decode attention against a dense per-request KV cache.

    q: (B, 1, nq, hd); caches: (B, S, nkv, hd); pos: (B,) index of the
    current token (cache already contains it). k_scale/v_scale: optional
    (B, S, nkv) dequant scales for int8-quantized caches — HBM reads stay
    1 byte/elem; dequant fuses into the contraction. key_positions:
    optional (B, S) absolute position of every cache column (ring-buffer
    SWA caches); defaults to arange(S).
    """
    b, _, nq, hd = q.shape
    nkv = k_cache.shape[2]
    s = k_cache.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    k_cache, v_cache = kf, vf
    qg = _group(q, nkv).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg * scale,
                        k_cache.astype(jnp.float32))  # (B,nkv,g,1,S)
    if key_positions is not None:
        j = key_positions
    else:
        j = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    mask = (j <= pos[:, None]) & (j >= 0)
    if window > 0:
        mask &= j > (pos[:, None] - window)
    scores = jnp.where(mask[:, None, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, nq, hd).astype(q.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array, *,
                    window: int = 0, scale: float | None = None,
                    k_scale_pages: jax.Array | None = None,
                    v_scale_pages: jax.Array | None = None,
                    layer: jax.Array | int | None = None) -> jax.Array:
    """Decode attention over a block-paged KV cache (vLLM PagedAttention).

    q: (B, nq, hd) — one query token per sequence.
    k_pages/v_pages: (num_layers, num_pages, nkv, page_size, hd) — every
    layer's global page pool, read at ``layer`` — or, with ``layer`` None,
    one layer's (num_pages, nkv, page_size, hd); head-major so each head's
    page is one (page, hd) tile.
    block_tables: (B, pages_per_seq) int32 page ids (padded arbitrarily).
    seq_lens: (B,) int32 — number of valid tokens (incl. current).
    k/v_scale_pages: optional dequant scales for int8-quantized page pools,
    the pools' shape without ``hd``.
    """
    b, nq, hd = q.shape
    k = gather_pages(k_pages, block_tables, k_scale_pages,
                     layer=layer)                     # (B, T, nkv, hd)
    v = gather_pages(v_pages, block_tables, v_scale_pages, layer=layer)
    nkv, t = k.shape[2], k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, 1, nkv, nq // nkv, hd).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg * scale, k)
    j = jnp.arange(t)[None, :]
    mask = j < seq_lens[:, None]
    if window > 0:
        mask &= j > (seq_lens[:, None] - 1 - window)
    scores = jnp.where(mask[:, None, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, nq, hd).astype(q.dtype)


def gather_pages(pages: jax.Array, block_tables: jax.Array,
                 scale_pages: jax.Array | None = None, *,
                 layer: jax.Array | int | None = None) -> jax.Array:
    """Token-major f32 K or V of each sequence from a head-major page pool.

    pages: (L, P, nkv, page, hd), gathered at ``layer`` in one gather, or
    (P, nkv, page, hd) with ``layer`` None; block_tables: (..., pp);
    scale_pages: optional int8 dequant scales, ``pages``' shape without
    ``hd``.  Returns (..., pp * page, nkv, hd) float32.
    """
    idx = block_tables if layer is None else (layer, block_tables)
    x = pages[idx].astype(jnp.float32)            # (..., pp, nkv, page, hd)
    if scale_pages is not None:
        x = x * scale_pages[idx].astype(jnp.float32)[..., None]
    x = jnp.swapaxes(x, -3, -2)                   # (..., pp, page, nkv, hd)
    return x.reshape(*x.shape[:-4], -1, *x.shape[-2:])


def chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                    q_start: jax.Array, *, window: int = 0,
                    scale: float | None = None) -> jax.Array:
    """Chunked-prefill attention: C query tokens at absolute positions
    [q_start, q_start+C) attend over a gathered KV history.

    q: (B, C, nq, hd); k_all/v_all: (B, T, nkv, hd) with keys valid on
    [0, q_start + C) (causality masks the rest). q_start: (B,) or scalar.
    """
    b, c, nq, hd = q.shape
    nkv, t = k_all.shape[2], k_all.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    qg = _group(q, nkv).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg * scale,
                        k_all.astype(jnp.float32))
    qs = jnp.broadcast_to(jnp.asarray(q_start), (b,))
    qpos = qs[:, None, None] + jnp.arange(c)[None, :, None]   # (B,C,1)
    kpos = jnp.arange(t)[None, None, :]                       # (1,1,T)
    mask = kpos <= qpos
    if window > 0:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v_all.astype(jnp.float32))
    return out.reshape(b, c, nq, hd).astype(q.dtype)


# ----------------------------------------------------------------------------
# Mamba selective scans
# ----------------------------------------------------------------------------

def mamba1_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, h0: jax.Array | None = None):
    """Mamba1 selective scan.

    x, dt: (Bt, S, di); A: (di, n); B, C: (Bt, S, n); D: (di,).
    h0: optional initial state (Bt, di, n). Returns (y (Bt,S,di), h_last).
    """
    bt, s, di = x.shape
    n = A.shape[1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h = jnp.zeros((bt, di, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt_, ct = inp  # (Bt,di), (Bt,di), (Bt,n), (Bt,n)
        dA = jnp.exp(dtt[..., None] * Af[None])          # (Bt,di,n)
        dBx = dtt[..., None] * bt_[:, None, :] * xt[..., None]
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
          Bf.swapaxes(0, 1), Cf.swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h, xs)
    y = ys.swapaxes(0, 1) + xf * D.astype(jnp.float32)[None, None]
    return y.astype(x.dtype), h


def mamba2_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, h0: jax.Array | None = None):
    """Mamba2 (SSD) scan with scalar-per-head A.

    x: (Bt, S, nh, hp); dt: (Bt, S, nh); A, D: (nh,); B, C: (Bt, S, n).
    Returns (y (Bt,S,nh,hp), h_last (Bt,nh,hp,n)).
    """
    bt, s, nh, hp = x.shape
    n = B.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf, Af = B.astype(jnp.float32), C.astype(jnp.float32), A.astype(jnp.float32)
    h = jnp.zeros((bt, nh, hp, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt_, ct = inp  # (Bt,nh,hp), (Bt,nh), (Bt,n), (Bt,n)
        dA = jnp.exp(dtt * Af[None])                      # (Bt,nh)
        dBx = (dtt[..., None, None] * xt[..., None]) * bt_[:, None, None, :]
        h = dA[..., None, None] * h + dBx
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
          Bf.swapaxes(0, 1), Cf.swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h, xs)
    y = ys.swapaxes(0, 1) + xf * Df_broadcast(D, xf)
    return y.astype(x.dtype), h


def Df_broadcast(D: jax.Array, xf: jax.Array) -> jax.Array:
    return D.astype(jnp.float32)[None, None, :, None]
