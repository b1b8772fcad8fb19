"""Expert-parallel MoE via shard_map (§Perf optimization, beyond paper).

Why: the GSPMD formulation in moe.py sorts the GLOBAL token stream; with
tokens sharded over "data" the partitioner materializes all-gathers of the
full activation set (measured: 213 GB/device/step for qwen3-moe train_4k).

This variant keeps everything local:
  - tokens stay on their data shard (activations are replicated across the
    "model" axis, as in standard TP);
  - expert weights are sharded over the "model" axis (E_loc = E / tp);
  - each model rank dispatches ITS OWN slice of experts for the local
    tokens (local sort, local capacity) and computes partial outputs;
  - one psum over "model" combines partial expert outputs — the SAME
    collective volume as a dense TP MLP (2 * T_loc * d), instead of
    gathering the global token stream.

Capacity semantics become per-(data-shard, expert) — the standard
per-device-capacity behavior of production MoE systems.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_base
from repro.sharding.context import get_context


def _local_moe(cfg: ModelConfig, model_axis: str, dp_axes):
    """Builds the per-shard function run inside shard_map."""
    k = cfg.experts_per_token

    def fn(x, router, wg, wu, wd):
        # x: (B_loc, S, d) local tokens (replicated over model axis)
        # router: (d, E) replicated; wg/wu/wd: (E_loc, d, f) local experts
        B, S, d = x.shape
        E_loc = wg.shape[0]
        rank = jax.lax.axis_index(model_axis)
        e_lo = rank * E_loc
        T = B * S
        xf = x.reshape(T, d)

        logits = xf.astype(jnp.float32) @ router            # (T, E)
        gates = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(gates, k)
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        C = moe_base.capacity(T, cfg)
        # mask the (token, k) pairs owned by this rank's experts
        local = (topi >= e_lo) & (topi < e_lo + E_loc)       # (T, k)
        e_flat = jnp.where(local, topi - e_lo, E_loc).reshape(T * k)
        sort_idx = jnp.argsort(e_flat)
        e_sorted = e_flat[sort_idx]
        counts = jnp.bincount(e_flat, length=E_loc + 1)
        offsets = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * k) - offsets[e_sorted]
        tok = sort_idx // k

        buf = jnp.zeros((E_loc, C, d), x.dtype)
        oob = (e_sorted >= E_loc) | (pos_in_e >= C)
        buf = buf.at[jnp.where(oob, E_loc, e_sorted),
                     jnp.minimum(pos_in_e, C - 1)].set(
            jnp.where(oob[:, None], 0, xf[tok]), mode="drop")

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        y_buf = jnp.einsum("ecf,efd->ecd", h, wd)

        y_sorted = y_buf[jnp.minimum(e_sorted, E_loc - 1),
                         jnp.minimum(pos_in_e, C - 1)]
        y_sorted = jnp.where(oob[:, None], 0, y_sorted)
        y_flat = jnp.zeros((T * k, d), x.dtype).at[sort_idx].set(y_sorted)
        y = (y_flat.reshape(T, k, d)
             * topw[..., None].astype(x.dtype)).sum(axis=1)
        # combine partial expert outputs across the model axis
        y = jax.lax.psum(y, model_axis)

        # load-balance aux (global fractions via psum)
        full_counts = jnp.zeros((cfg.num_experts,), jnp.float32)
        full_counts = jax.lax.dynamic_update_slice(
            full_counts, counts[:E_loc].astype(jnp.float32), (e_lo,))
        full_counts = jax.lax.psum(full_counts, model_axis)
        # counts over all experts sum to the local T*k dispatched pairs
        # (each model rank fills only its expert slice — no double count)
        frac = full_counts / jnp.float32(T * k)
        prob = jnp.mean(gates, axis=0)           # local mean
        aux = cfg.router_aux_coef * cfg.num_experts * jnp.sum(frac * prob)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)    # replicate across data
        return y.reshape(B, S, d), aux

    return fn


def moe_forward_ep(cfg: ModelConfig, p: dict, x: jax.Array):
    """Drop-in replacement for moe.moe_forward when a DistContext is set."""
    from repro.sharding import specs as S
    ctx = get_context()
    assert ctx is not None
    dp = S.batch_spec(ctx.mesh, x.shape[0])      # None if B doesn't divide
    fn = _local_moe(cfg, ctx.model_axis, dp)
    mapped = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    return mapped(x, p["router"], p["wg"], p["wu"], p["wd"])


def ep_applicable(cfg: ModelConfig) -> bool:
    ctx = get_context()
    return (ctx is not None and ctx.moe_impl == "ep"
            and cfg.num_experts % ctx.mesh.shape[ctx.model_axis] == 0)
