"""Plain reference of a dense decoder with grouped-query attention
(InternLM2, Qwen1.5 and their kind), written from the published
equations and importing nothing of the program:

    h_0 = E[x]
    h'  = h + Attn(RMSNorm(h; g1)) Wo,   q,k,v = RMSNorm(h) W{q,k,v} (+ b)
    h   = h' + (silu(RMSNorm(h'; g2) Wg) * (RMSNorm(h'; g2) Wu)) Wd
    logits = RMSNorm(h_L; g) W_head

RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g.  Rotary embeddings in the
split-half ("rotate_half") convention with inv_freq = theta^(-2i/hd).
Causal softmax attention with scale 1/sqrt(hd); each group of
heads/kv_heads query heads shares one key/value head.

Everything is float32 with matmuls at ``Precision.HIGHEST``.  It runs one
sequence at a time, in chunks of queries that attend over the keys and
values kept so far, so that it fits beside the weights.  With
``quant="fp8"`` every matmul against a weight takes float8 e4m3 inputs
instead, scaled (absmax to 448) per output channel for the weights and
per token for the activations: the control, one step below the bfloat16
the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 512           # queries per step; keys are held for the bucket
_HI = jax.lax.Precision.HIGHEST


def _quant_rows(x, axis, quant):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    if quant != "fp8":
        raise ValueError(f"unknown precision {quant!r}")
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, N) in f32; lower-precision inputs when quant."""
    w = w.astype(jnp.float32)
    if quant:
        x = _quant_rows(x, -1, quant)
        w = _quant_rows(w, 0, quant)
    return jnp.matmul(x, w, precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]   # (S, hd/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, donate_argnums=(3, 4),
                   static_argnames=("nq", "nkv", "hd", "eps", "theta",
                                    "quant"))
def _layer(h, blocks, i, kc, vc, start, *, nq, nkv, hd, eps, theta, quant):
    """Layer ``i`` over one chunk of queries at positions start + [0, C),
    against the layer's keys and values so far (``kc``, ``vc``: (S, nkv,
    hd) float32, updated in place)."""
    C, d = h.shape
    S = kc.shape[0]
    lp = jax.tree.map(lambda w: w[i], blocks)
    pos = start + jnp.arange(C)
    x = _rms(h, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = _mm(x, a["wq"].reshape(d, nq * hd), quant).reshape(C, nq, hd)
    k = _mm(x, a["wk"].reshape(d, nkv * hd), quant).reshape(C, nkv, hd)
    v = _mm(x, a["wv"].reshape(d, nkv * hd), quant).reshape(C, nkv, hd)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    kc = jax.lax.dynamic_update_slice(kc, k, (start, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (start, 0, 0))
    qg = q.reshape(C, nkv, nq // nkv, hd)
    s = jnp.einsum("qkgh,tkh->kgqt", qg, kc, precision=_HI) / np.sqrt(hd)
    mask = jnp.arange(S)[None, :] <= pos[:, None]           # causal
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqt,tkh->qkgh", p, vc, precision=_HI).reshape(
        C, nq * hd)
    h = h + _mm(o, a["wo"].reshape(nq * hd, d), quant)
    x = _rms(h, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    u = jax.nn.silu(_mm(x, m["wg"], quant)) * _mm(x, m["wu"], quant)
    return h + _mm(u, m["wd"], quant), kc, vc


@functools.partial(jax.jit, donate_argnums=(0,))
def _place(hs, h, start):
    return jax.lax.dynamic_update_slice(hs, h, (start, 0))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(hs, rows, g, w, *, eps, quant):
    return _mm(_rms(hs[rows], g, eps), w, quant)


def final_logits(cfg: Dict, params, seq: np.ndarray, positions: np.ndarray,
                 quant: Optional[str] = None, seq_bucket: int = 0,
                 rows_bucket: int = 0):
    """float32 logits that the model gives after reading ``seq[:p + 1]``,
    for each p in ``positions``: an array of ``rows_bucket`` (or more)
    rows whose first len(positions) are those logits.  The sequence runs
    in chunks of _CHUNK queries, every layer of a chunk before the next
    chunk; keys and values are held for ``seq_bucket`` positions, so that
    one compiled shape serves every request of a run."""
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // nq)
    kw = dict(nq=nq, nkv=nkv, hd=hd, eps=float(cfg["rms_norm_eps"]),
              theta=float(cfg["rope_theta"]), quant=quant)
    n = len(seq)
    S = -(-max(n, seq_bucket) // _CHUNK) * _CHUNK
    toks = np.zeros(S, np.int32)
    toks[:n] = seq
    L = cfg["num_hidden_layers"]
    kcs = [jnp.zeros((S, nkv, hd), jnp.float32) for _ in range(L)]
    vcs = [jnp.zeros((S, nkv, hd), jnp.float32) for _ in range(L)]
    hs = jnp.zeros((S, d), jnp.float32)
    for start in range(0, n, _CHUNK):
        h = params["embed"][jnp.asarray(toks[start:start + _CHUNK])].astype(
            jnp.float32)
        for i in range(L):
            h, kcs[i], vcs[i] = _layer(h, params["blocks"], i, kcs[i],
                                       vcs[i], start, **kw)
        hs = _place(hs, h, start)
    del kcs, vcs
    m = len(positions)
    rows = np.zeros(max(-(-m // 128) * 128, rows_bucket), np.int32)
    rows[:m] = positions
    return _head(hs, jnp.asarray(rows), params["final_ln"]["scale"],
                 params["lm_head"], eps=kw["eps"], quant=quant)
