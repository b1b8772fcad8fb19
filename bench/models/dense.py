"""The dense transformer kind: GQA or MHA attention (q/k/v biases where
the configuration has them) and a SwiGLU MLP in every layer.

The program's ``ModelConfig`` built from the configuration file's
published sizes, and random weights made by the benchmark (not by the
program) from ``--seed``, on the device, in the dtype they are served
in, in one jitted call.  The weights follow the parameter layout the
program's dense transformer consumes (layers stacked for ``lax.scan``).
Every leaf is random, including norm scales and, where the model has
them, the q/k/v biases, so the comparison with the plain reference
exercises each of them.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench import counts
from bench.model import jax_key


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=nq,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // nq), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], qkv_bias=bool(cfg.get("qkv_bias")),
        rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings")),
        dtype=cfg["torch_dtype"])


def param_shapes(cfg: Dict) -> Dict:
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    nkv, hd = cfg["num_key_value_heads"], cfg.get("head_dim", d // nq)
    f, v, L = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    attn = {"wq": (L, d, nq, hd), "wk": (L, d, nkv, hd),
            "wv": (L, d, nkv, hd), "wo": (L, nq, hd, d)}
    if cfg.get("qkv_bias"):
        attn.update(bq=(L, nq, hd), bk=(L, nkv, hd), bv=(L, nkv, hd))
    return {"embed": (v, d), "final_ln": {"scale": (d,)},
            "lm_head": (d, v),
            "blocks": {"ln1": {"scale": (L, d)}, "attn": attn,
                       "ln2": {"scale": (L, d)},
                       "mlp": {"wg": (L, d, f), "wu": (L, d, f),
                               "wd": (L, f, d)}}}


def kv_pool_shape(cfg: Dict, pages: int) -> Tuple[int, ...]:
    """Shape of one of a paged engine's two KV pools (K, and V alike)."""
    mc = model_config(cfg)
    return (mc.num_layers, pages, mc.num_kv_heads, cfg["serving"]["page_size"],
            mc.head_dim)


def _std(path: str, shape) -> float:
    leaf = path.split("/")[-1]
    if leaf == "scale":
        return 0.1                           # around 1, see init_params
    if leaf in ("bq", "bk", "bv"):
        return 0.5
    if leaf == "embed":
        return 1.0
    if leaf == "lm_head":
        fan_in = shape[0]
    elif leaf == "wo":
        fan_in = shape[1] * shape[2]
    else:
        fan_in = shape[1]                    # stacked (L, fan_in, ...)
    return 1.0 / np.sqrt(fan_in)


def init_params(cfg: Dict, seed: int):
    """Random weights for ``cfg`` from ``seed``: normal with std
    1/sqrt(fan-in) for matrices, 1 + N(0, 0.1) for norm scales, N(0, 0.5)
    for biases, N(0, 1) for the embedding; one jitted call on the default
    device, in ``torch_dtype``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    leaves = [s for _, s in flat]

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, path, shape in zip(keys, paths, leaves):
            x = jax.random.normal(k, shape, jnp.float32) * _std(path, shape)
            if path.endswith("scale"):
                x = x + 1.0
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(make)(jax_key(seed)))


#: the counts of bench/counts.py, as they are
dims = counts.Dims.from_config
prefill_chunk_flops = counts.prefill_chunk_flops
decode_step_flops = counts.decode_step_flops


#: CPU-sized widths; the KV heads follow from the published GQA ratio
SMOKE_WIDTHS = {"hidden_size": 256, "intermediate_size": 512,
                "num_hidden_layers": 2, "num_attention_heads": 8,
                "head_dim": 32, "vocab_size": 1024}


def smoke_widths(cfg: Dict) -> Dict:
    kv_ratio = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return dict(SMOKE_WIDTHS, num_key_value_heads=(
        SMOKE_WIDTHS["num_attention_heads"] // kv_ratio))
