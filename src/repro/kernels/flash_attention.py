"""Pallas TPU flash attention (prefill / full-sequence, causal + GQA + SWA).

TPU-native design notes (vs the CUDA flash-attention the paper's engines use):
  - Tiling is (BQ, head_dim) query tiles × (BK, head_dim) key tiles sized for
    VMEM; BQ/BK default 128 so the MXU matmuls are (128 × hd) @ (hd × 128) —
    fully aligned to the 128×128 systolic array.
  - The KV axis is the LAST grid dimension: on TPU the last grid dim is
    sequential, so the online-softmax running state (m, l, acc) lives in VMEM
    scratch and persists across KV steps; the output tile is written once at
    the final KV step (no atomics, no HBM round-trips — the TPU analogue of
    the warp-level reduction in the GPU kernel).
  - GQA: the kernel indexes K/V by q_head // group via the BlockSpec
    index_map, so K/V tiles are fetched once per kv-head group.
  - Lengths that are not a multiple of the block are padded up to one;
    padded keys are masked and padded query rows are dropped.

Validated against kernels/ref.py with interpret=True in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -2.0 ** 30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, bq: int, bk: int,
               sk: int, sq: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, hd)
    s = q @ k.T                                          # (BQ, BK)

    # positions for masking (query positions aligned to the end of keys)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk                                     # padded keys
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                  # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v_ref[0, 0].astype(jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, nq, hd); k, v: (B, Sk, nkv, hd) -> (B, Sq, nq, hd)."""
    b, sq, nq, hd = q.shape
    _, sk, nkv, _ = k.shape
    g = nq // nkv
    bq = min(bq, _round_up(sq, 8))
    bk = min(bk, _round_up(sk, 8))
    sqp, skp = _round_up(sq, bq), _round_up(sk, bk)
    scale = hd ** -0.5

    def heads_major(x, sp):  # (B, S, h, hd) -> (B, h, Sp, hd)
        return jnp.pad(x, ((0, 0), (0, sp - x.shape[1]), (0, 0), (0, 0))
                       ).transpose(0, 2, 1, 3)

    qt, kt, vt = heads_major(q, sqp), heads_major(k, skp), heads_major(v, skp)

    grid = (b, nq, sqp // bq, skp // bk)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, sk=sk, sq=sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :sq].transpose(0, 2, 1, 3)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m
