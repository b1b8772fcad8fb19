"""Jitted dispatch wrappers around the compute hot-spot kernels.

Backend selection:
  - "ref":     pure-jnp oracle (kernels/ref.py) — default on CPU; also what
               the multi-pod dry-run lowers (GSPMD-shardable HLO).
  - "pallas":  the Pallas TPU kernels (interpret=True off-TPU).
  - "auto":    "pallas" on TPU, else "ref".

Set globally with ``set_backend`` or per-call with ``backend=``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref

_BACKEND = "auto"


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("auto", "ref", "pallas")
    _BACKEND = name


def get_backend(override: str | None = None) -> str:
    b = override or _BACKEND
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return b


def flash_attention(q, k, v, *, causal=True, window=0, backend=None):
    if get_backend(backend) == "pallas":
        return flash_attention_trainable(q, k, v, causal, window)
    return ref.flash_attention(q, k, v, causal=causal, window=window)


# Pallas forward + recompute backward: makes the TPU kernel usable inside
# jax.grad (train_step). The backward differentiates the jnp oracle — the
# standard flash-attention recompute pattern (no O(S^2) residuals saved).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_trainable(q, k, v, causal, window):
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=jax.default_backend() != "tpu")


def _fa_fwd(q, k, v, causal, window):
    return flash_attention_trainable(q, k, v, causal, window), (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention(q_, k_, v_, causal=causal,
                                               window=window), q, k, v)
    return vjp(g)


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)


def decode_attention(q, k_cache, v_cache, pos, *, window=0, backend=None,
                     k_scale=None, v_scale=None, key_positions=None):
    # dense-cache decode: kernel-wise this is paged attention with one page
    # per sequence; we keep a dedicated ref path (used by the dry-run).
    return ref.decode_attention(q, k_cache, v_cache, pos, window=window,
                                k_scale=k_scale, v_scale=v_scale,
                                key_positions=key_positions)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    window=0, backend=None, k_scale_pages=None,
                    v_scale_pages=None, layer=None):
    if get_backend(backend) == "pallas":
        from repro.kernels import paged_attention as pa
        return pa.paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                                  window=window,
                                  k_scale_pages=k_scale_pages,
                                  v_scale_pages=v_scale_pages, layer=layer,
                                  interpret=jax.default_backend() != "tpu")
    return ref.paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                               window=window, k_scale_pages=k_scale_pages,
                               v_scale_pages=v_scale_pages, layer=layer)


def mamba1_scan(x, dt, A, B, C, D, h0=None, *, backend=None):
    if get_backend(backend) == "pallas":
        from repro.kernels import mamba_scan as ms
        return ms.mamba1_scan(x, dt, A, B, C, D, h0,
                              interpret=jax.default_backend() != "tpu")
    return ref.mamba1_scan(x, dt, A, B, C, D, h0)


def mamba2_scan(x, dt, A, B, C, D, h0=None, *, backend=None):
    return ref.mamba2_scan(x, dt, A, B, C, D, h0)
