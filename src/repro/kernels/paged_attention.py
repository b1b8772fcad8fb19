"""Pallas TPU paged attention (single-token decode over a block-paged KV
cache) — the TPU adaptation of vLLM's PagedAttention CUDA kernel.

TPU-native design notes:
  - The grid is ``(B, ceil(pp / ppb))``: one step covers a block of
    ``ppb`` consecutive page slots of one sequence, and the block axis is
    sequential, carrying the online-softmax state in VMEM scratch.
  - The walk is bounded by the context, not by ``pp``. A step whose block
    holds no token in context (past ``seq_len``, or wholly before a
    sliding window) issues no DMA and computes nothing, so a sequence
    costs ``ceil(seq_len / (ppb * page))`` working steps, and an inactive
    slot (``seq_len == 0``) none: it returns zeros. No page that holds no
    context is copied: table entries past a sequence's last page are
    never dereferenced.
  - The pools stay in HBM (``memory_space=pl.ANY``). A working step
    gathers the pages of its block that hold context, one async copy per
    page with the page id read from the scalar-prefetched block table,
    into a ``(ppb, nkv, page, hd)`` VMEM buffer. Buffers are doubled:
    while one block computes, the copies of the next working block (of
    this sequence or the next active one) are in flight.
  - ``ppb`` follows the shapes alone (see ``pages_per_block``): a block of
    about ``_BLOCK_TOKENS`` tokens whose double buffer fits
    ``_BUFFER_BYTES`` of VMEM. When it does not divide ``pp``, the last
    block's missing slots are masked like any slot past ``seq_len``.
  - The pool is an engine's whole pool, (L, P, nkv, page, hd), head-major:
    one copy moves a whole page of one layer for every kv head, and each
    head's (page, hd) slab is a full (sublane, lane) tile.  The layer is
    a third scalar-prefetch operand, so a layer scan hands the kernel the
    pool it carries, neither sliced nor copied.  (A 4-D pool is a
    one-layer pool.)
  - GQA: the g query heads of one kv head are the rows of a (g, hd) MXU
    tile; kv heads are a static loop inside the step.
  - int8 pools keep HBM traffic at 1 B/elem: the per-token scales are
    applied to the (g, tokens) scores and probabilities instead of to the
    K/V tiles. A (nkv, page) scale page cannot be copied alone (its
    16-lane rows are not tile-aligned in HBM), so the wrapper gathers each
    block's scales into one (nkv, ppb * page) row per head, which the
    pipeline fetches per working step.

Validated against kernels/ref.py (interpret=True) in tests/test_kernels.py
and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -2.0 ** 30
_BLOCK_TOKENS = 256          # tokens of context one grid step aims to cover
_BUFFER_BYTES = 8 << 20      # VMEM for the double-buffered K/V (and scales)


def pages_per_block(page: int, pp: int, nkv: int, hd: int, itemsize: int,
                    quant: bool = False) -> int:
    """Page slots one grid step covers: about ``_BLOCK_TOKENS`` tokens, no
    more than ``pp``, and few enough that two buffers of K and V (heads
    padded to whole 128-lane rows, and their int8 scales) fit
    ``_BUFFER_BYTES``."""
    lanes = -(-hd // 128) * 128
    per_page = 2 * nkv * page * (lanes * itemsize + (4 if quant else 0))
    fit = _BUFFER_BYTES // (2 * per_page)
    return max(1, min(pp, _BLOCK_TOKENS // page, fit))


def live_block_count(seq_lens: np.ndarray, block: int,
                     window: int = 0) -> int:
    """Blocks of ``block`` tokens that hold context, summed over sequences
    of ``seq_lens`` tokens: the grid steps the kernel's guards let
    through (host arithmetic, for counters)."""
    seq_lens = np.asarray(seq_lens, np.int64)
    last = (seq_lens - 1) // block
    first = np.maximum(seq_lens - window, 0) // block if window > 0 else 0
    return int(np.where(seq_lens > 0, last - first + 1, 0).sum())


def _pa_kernel(bt_ref, sl_ref, layer_ref,           # scalar prefetch
               q_ref, k_hbm, v_hbm, *refs, page: int, pp: int, ppb: int,
               window: int, quant: bool):
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, k_buf, v_buf, sems, state_ref, m_ref, l_ref, acc_ref = refs
    layer = layer_ref[0]
    b, i = pl.program_id(0), pl.program_id(1)
    nb, nblk = pl.num_programs(0), pl.num_programs(1)
    nkv, g, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    bk = ppb * page

    def in_context(start, end, seq_len):
        """Whether tokens [start, end) meet the context of a sequence."""
        ok = start < seq_len
        if window > 0:
            ok &= end > seq_len - window
        return ok

    def pages(b_, i_):
        """(page slot, buffer row, whether the page holds context) for the
        page slots of block ``i_`` of sequence ``b_``."""
        seq_len = sl_ref[b_]
        for j in range(ppb):
            s = i_ * ppb + j
            ok = in_context(s * page, (s + 1) * page, seq_len)
            if pp % ppb:
                ok &= s < pp
            yield s, j, ok

    def page_copies(page_id, slot, j):
        return [pltpu.make_async_copy(src.at[layer, page_id],
                                      dst.at[slot, j],
                                      sems.at[slot])
                for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf))]

    def start(b_, i_, slot):
        for s, j, ok in pages(b_, i_):
            @pl.when(ok)
            def _():
                for c in page_copies(bt_ref[b_ * pp + s], slot, j):
                    c.start()

    def wait(slot):
        # a wait needs only the destination's size and the semaphore, so
        # it names page 0 and reads no table entry
        for _, j, ok in pages(b, i):
            @pl.when(ok)
            def _():
                for c in page_copies(0, slot, j):
                    c.wait()

    @pl.when((b == 0) & (i == 0))
    def _first_step():
        state_ref[0] = 0          # buffer slot of the next working block
        state_ref[1] = 0          # whether its copies have been started
        # rows of page slots a block does not copy keep what an earlier
        # block copied there (context of some sequence, so finite) or
        # these zeros; their probabilities are 0, and 0 * finite is 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = sl_ref[b]

    @pl.when(in_context(i * bk, (i + 1) * bk, seq_len))
    def _block():
        slot = state_ref[0]

        @pl.when(state_ref[1] == 0)
        def _prime():
            start(b, i, slot)

        # the next working block: the next of this sequence, else the first
        # of the next active sequence
        same = (i + 1 < nblk) & ((i + 1) * bk < seq_len)
        nxt = jax.lax.fori_loop(
            0, nb, lambda j, c: jnp.where((c == nb) & (j > b)
                                          & (sl_ref[j] > 0), j, c), nb)
        nxt_c = jnp.minimum(nxt, nb - 1)
        first = (jnp.maximum(sl_ref[nxt_c] - window, 0) // bk
                 if window > 0 else 0)

        @pl.when(same)
        def _prefetch_same():
            start(b, i + 1, 1 - slot)

        @pl.when(jnp.logical_not(same) & (nxt < nb))
        def _prefetch_next():
            start(nxt_c, first, 1 - slot)

        state_ref[0] = 1 - slot
        state_ref[1] = (same | (nxt < nb)).astype(jnp.int32)
        wait(slot)

        tok = i * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        mask = tok < seq_len
        if window > 0:
            mask &= tok > seq_len - 1 - window

        def tokens(buf, h):
            # (ppb, page, hd) of head h -> (bk, hd) f32
            return buf[slot, :, h].astype(jnp.float32).reshape(bk, hd)

        def scale_row(ref, h):
            return ref[0, 0, h:h + 1, :]                     # (1, bk)

        for h in range(nkv):
            q = q_ref[0, h]                                  # (g, hd) f32
            s = jax.lax.dot_general(q, tokens(k_buf, h),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quant:
                s = s * scale_row(ks_ref, h)
            s = jnp.where(mask, s, _NEG_INF)                 # (g, bk)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * scale_row(vs_ref, h)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, tokens(v_buf, h), preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == nblk - 1)
    def _finalize():
        # a sequence with no context has l == 0 and acc == 0: zeros
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _block_scales(k_scale_pages, v_scale_pages, layer, block_tables,
                  seq_lens, ppb: int, nblk: int, window: int):
    """The int8 scales of ``layer``'s tokens in every block, (B, nblk, nkv,
    ppb * page) each for K and V: one lane row per kv head, as the scores
    lie. Slots that hold no context take page 0's scales, so no page past
    a sequence's context is read."""
    b, pp = block_tables.shape
    _, _, nkv, page = k_scale_pages.shape
    slot = jnp.arange(nblk * ppb)[None, :]
    live = slot * page < seq_lens[:, None]
    if window > 0:
        live &= (slot + 1) * page > seq_lens[:, None] - window
    bt = jnp.pad(block_tables, ((0, 0), (0, nblk * ppb - pp)))
    bt = jnp.where(live, bt, 0)

    def rows(scale_pages):
        # (B, nblk * ppb, nkv, page) -> (B, nblk, nkv, ppb * page)
        x = scale_pages[layer, bt].astype(jnp.float32)
        x = x.reshape(b, nblk, ppb, nkv, page).transpose(0, 1, 3, 2, 4)
        return x.reshape(b, nblk, nkv, ppb * page)

    return [rows(k_scale_pages), rows(v_scale_pages)]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array, *,
                    k_scale_pages: jax.Array | None = None,
                    v_scale_pages: jax.Array | None = None,
                    layer: jax.Array | int | None = None,
                    window: int = 0, interpret: bool = False) -> jax.Array:
    """q: (B, nq, hd); k/v_pages: (L, P, nkv, page, hd), read at ``layer``,
    or (P, nkv, page, hd) with ``layer`` None; block_tables: (B,
    pages_per_seq) int32; seq_lens: (B,) int32, at most ``pages_per_seq *
    page``. Optional k/v_scale_pages: (L, P, nkv, page), or (P, nkv, page),
    f32 — int8-quantized pool with in-kernel dequantization.
    Returns (B, nq, hd); a sequence with ``seq_len == 0`` gives zeros."""
    quant = k_scale_pages is not None
    if layer is None:                      # a one-layer pool
        k_pages, v_pages, k_scale_pages, v_scale_pages = (
            None if x is None else x[None]
            for x in (k_pages, v_pages, k_scale_pages, v_scale_pages))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    b, nq, hd = q.shape
    _, _, nkv, page, _ = k_pages.shape
    pp = block_tables.shape[1]
    g = nq // nkv
    scale = hd ** -0.5

    # (B, nkv, g, hd) so each kv head's query group is one tile; scaled in
    # f32, as the reference does (a bf16 product would round q)
    qg = (q.astype(jnp.float32) * scale).reshape(b, nkv, g, hd)
    pool_layer = layer
    if hd % 128:
        # a page copy must move whole 128-lane rows: a narrower head (the
        # tiny test widths) is zero-padded, which leaves every score and
        # output lane below hd as it was.  Only the layer read is padded,
        # at one layer's pool a call
        pad = ((0, 0),) * 4 + ((0, -hd % 128),)
        k_pages, v_pages = (
            jnp.pad(jax.lax.dynamic_slice_in_dim(x, layer, 1), pad)
            for x in (k_pages, v_pages))
        qg = jnp.pad(qg, pad[1:])
        pool_layer = jnp.zeros((), jnp.int32)
    hd_lanes = qg.shape[-1]
    ppb = pages_per_block(page, pp, nkv, hd, k_pages.dtype.itemsize, quant)
    nblk = pl.cdiv(pp, ppb)
    head_block = pl.BlockSpec((1, nkv, g, hd_lanes),
                              lambda b_, i, bt, sl, ly: (b_, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    in_specs = [head_block, hbm, hbm]
    operands = [block_tables.reshape(-1), seq_lens, pool_layer.reshape(1),
                qg, k_pages, v_pages]
    if quant:
        bk = ppb * page

        def scale_block(b_, i, bt, sl, ly):
            # steps outside the context keep a working block's index, so
            # the pipeline fetches nothing for them
            last = jnp.maximum(sl[b_] - 1, 0) // bk
            first = jnp.maximum(sl[b_] - window, 0) // bk if window else 0
            return b_, jnp.minimum(jnp.maximum(i, first), last), 0, 0

        in_specs += [pl.BlockSpec((1, 1, nkv, bk), scale_block)] * 2
        operands += _block_scales(k_scale_pages, v_scale_pages, layer,
                                  block_tables, seq_lens, ppb, nblk, window)
    scratch = [
        pltpu.VMEM((2, ppb, nkv, page, hd_lanes), k_pages.dtype),
        pltpu.VMEM((2, ppb, nkv, page, hd_lanes), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((nkv, g, 1), jnp.float32),
        pltpu.VMEM((nkv, g, 1), jnp.float32),
        pltpu.VMEM((nkv, g, hd_lanes), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=head_block,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel, page=page, pp=pp, ppb=ppb,
                          window=window, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        # the copies of one step feed the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[..., :hd].reshape(b, nq, hd)
