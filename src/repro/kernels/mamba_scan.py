"""Pallas TPU Mamba1 selective scan.

TPU-native design notes:
  - The CUDA selective-scan kernel parallelizes over channels with one
    thread block per (batch, channel-chunk) and scans sequentially in
    registers. On TPU we tile channels into (BD,) VMEM blocks (BD a
    multiple of 128 lanes) and make the sequence-chunk axis the LAST
    (sequential) grid dimension; the recurrent state persists in VMEM
    scratch across sequence chunks.
  - The state is held as (n, BD): channels on lanes, so a timestep's x and
    dt rows broadcast along sublanes and the update is dense VPU work. The
    kernel's h0/h_last operands use that layout; the wrapper transposes
    the (Bt, di, n) state at the boundary.
  - Within a chunk a lax.fori_loop walks aligned 16-step slabs
    (``pl.ds(pl.multiple_of(..., 16), 16)``: a whole bf16 tile, two f32
    tiles), unrolling the 16 steps; a dynamic single-row index would not
    be provably tile-aligned. The slab's B and C are transposed once so
    each step reads an (n, 1) column.
  - Sequences are padded to a whole chunk with dt = 0, which leaves the
    state unchanged (exp(0·A) = 1, dt·x = 0), so h_last stays exact.

Validated against kernels/ref.py (interpret=True) in tests/test_kernels.py
and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SLAB = 16


def _scan_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_ref, *, bs: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    At = at_ref[...].astype(jnp.float32)          # (n, BD)
    D = d_ref[...].astype(jnp.float32)            # (1, BD)
    rows = jax.lax.broadcasted_iota(jnp.int32, (_SLAB, At.shape[1]), 0)

    def slab(j, h):
        t0 = pl.multiple_of(j * _SLAB, _SLAB)
        win = pl.ds(t0, _SLAB)
        xs = x_ref[0, win, :].astype(jnp.float32)     # (16, BD)
        dts = dt_ref[0, win, :].astype(jnp.float32)   # (16, BD)
        Bs = b_ref[0, win, :].astype(jnp.float32).T   # (n, 16)
        Cs = c_ref[0, win, :].astype(jnp.float32).T   # (n, 16)
        ys = jnp.zeros(xs.shape, jnp.float32)
        for i in range(_SLAB):
            xt, dtt = xs[i:i + 1], dts[i:i + 1]       # (1, BD)
            h = jnp.exp(dtt * At) * h + Bs[:, i:i + 1] * (dtt * xt)
            yt = jnp.sum(h * Cs[:, i:i + 1], axis=0, keepdims=True) + D * xt
            ys = jnp.where(rows == i, yt, ys)
        y_ref[0, win, :] = ys.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs // _SLAB, slab, h_ref[...])

    @pl.when(si == pl.num_programs(2) - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def mamba1_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, h0: jax.Array | None = None, *,
                bd: int = 256, bs: int = 64, interpret: bool = False):
    """x, dt: (Bt, S, di); A: (di, n); B, C: (Bt, S, n); D: (di,).
    Returns (y (Bt,S,di) fp32-accurate, h_last (Bt,di,n) f32)."""
    bt, s, di = x.shape
    n = A.shape[1]
    bd = min(bd, di)
    bs = min(bs, -(-s // _SLAB) * _SLAB)
    if di % bd or bs % _SLAB:
        raise ValueError(f"d_inner {di} must tile by bd {bd}, and bs {bs} "
                         f"by {_SLAB}")
    sp = -(-s // bs) * bs
    if sp != s:
        pad = ((0, 0), (0, sp - s), (0, 0))
        x, dt, B, C = (jnp.pad(a, pad) for a in (x, dt, B, C))
    if h0 is None:
        h0 = jnp.zeros((bt, di, n), jnp.float32)

    grid = (bt, di // bd, sp // bs)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda b_, d_, s_: (b_, s_, d_)),  # x
            pl.BlockSpec((1, bs, bd), lambda b_, d_, s_: (b_, s_, d_)),  # dt
            pl.BlockSpec((n, bd), lambda b_, d_, s_: (0, d_)),           # A^T
            pl.BlockSpec((1, bs, n), lambda b_, d_, s_: (b_, s_, 0)),    # B
            pl.BlockSpec((1, bs, n), lambda b_, d_, s_: (b_, s_, 0)),    # C
            pl.BlockSpec((1, bd), lambda b_, d_, s_: (0, d_)),           # D
            pl.BlockSpec((1, n, bd), lambda b_, d_, s_: (b_, 0, d_)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda b_, d_, s_: (b_, s_, d_)),  # y
            pl.BlockSpec((1, n, bd), lambda b_, d_, s_: (b_, 0, d_)),    # h
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, sp, di), x.dtype),
            jax.ShapeDtypeStruct((bt, n, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, B, C, D.reshape(1, di),
      h0.astype(jnp.float32).swapaxes(1, 2))
    return y[:, :s], h.swapaxes(1, 2)
