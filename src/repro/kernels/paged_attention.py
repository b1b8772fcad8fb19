"""Pallas TPU paged attention (single-token decode over a block-paged KV
cache) — the TPU adaptation of vLLM's PagedAttention CUDA kernel.

TPU-native design notes:
  - The GPU kernel assigns a warp per page and reduces in shared memory.
    On TPU we instead make the page axis the LAST (sequential) grid
    dimension and carry the online-softmax state in VMEM scratch — same
    dataflow, systolic-friendly.
  - Page indirection uses PrefetchScalarGridSpec: ``block_tables`` and
    ``seq_lens`` are scalar-prefetch operands, so each grid step's
    BlockSpec index_map dereferences the page id *before* the DMA is
    issued — the TPU equivalent of the GPU kernel's pointer chasing, with
    the DMA engine doing the gather.
  - The pool is head-major, (P, nkv, page, hd): one grid step DMAs a whole
    page for every kv head, and each head's (page, hd) slab is a full
    (sublane, lane) tile, so no block puts a 1 in the sublane dim.
  - GQA: the g query heads of one kv head are the rows of a (g, hd) MXU
    tile; kv heads are a static loop inside the step.
  - int8 pools keep HBM traffic at 1 B/elem: the per-token scales are
    applied to the (g, page) scores and probabilities, where they broadcast
    along sublanes, instead of to the K/V tiles.

Validated against kernels/ref.py (interpret=True) in tests/test_kernels.py
and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -2.0 ** 30


def _pa_kernel(block_tables_ref, seq_lens_ref,  # scalar prefetch
               q_ref, k_ref, v_ref, *refs, page: int, window: int,
               quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    pi = pl.program_id(1)
    nkv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    tok = pi * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
    mask = tok < seq_len
    if window > 0:
        mask &= tok > seq_len - 1 - window

    for h in range(nkv):
        q = q_ref[0, h]                                  # (g, hd) f32, scaled
        k = k_ref[0, h].astype(jnp.float32)              # (page, hd)
        s = q @ k.T                                      # (g, page)
        if quant:
            s = s * ks_ref[0, h:h + 1, :]                # (1, page) scales
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = p * vs_ref[0, h:h + 1, :]
        v = v_ref[0, h].astype(jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + p @ v
        m_ref[h] = m_new

    @pl.when(pi == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array, *,
                    k_scale_pages: jax.Array | None = None,
                    v_scale_pages: jax.Array | None = None,
                    window: int = 0, interpret: bool = False) -> jax.Array:
    """q: (B, nq, hd); k/v_pages: (P, nkv, page, hd);
    block_tables: (B, pages_per_seq) int32; seq_lens: (B,) int32.
    Optional k/v_scale_pages: (P, nkv, page) f32 — int8-quantized pool with
    in-kernel dequantization. Returns (B, nq, hd)."""
    b, nq, hd = q.shape
    _, nkv, page, _ = k_pages.shape
    pp = block_tables.shape[1]
    g = nq // nkv
    scale = hd ** -0.5
    quant = k_scale_pages is not None

    # (B, nkv, g, hd) so each kv head's query group is one tile; scaled in
    # f32, as the reference does (a bf16 product would round q)
    qg = (q.astype(jnp.float32) * scale).reshape(b, nkv, g, hd)

    def page_block(*tail):
        # dereference the page id from the prefetched block table
        return pl.BlockSpec((1, nkv) + tail,
                            lambda b_, p, bt, sl: (bt[b_, p], 0)
                            + (0,) * len(tail))

    in_specs = [
        pl.BlockSpec((1, nkv, g, hd), lambda b_, p, bt, sl: (b_, 0, 0, 0)),
        page_block(page, hd),
        page_block(page, hd),
    ]
    operands = [block_tables, seq_lens, qg, k_pages, v_pages]
    if quant:
        in_specs += [page_block(page), page_block(page)]
        operands += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, g, hd),
                               lambda b_, p, bt, sl: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel, page=page, window=window, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, nq, hd)
