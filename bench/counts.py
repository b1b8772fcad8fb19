"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the benchmark's yardstick for MFU and roofline shares: they
count what the model's mathematics requires for the tokens a call
processes, never what the program happens to compute (padding rows,
masked pages, recomputed logits).  ``bench/tests/test_counts.py`` checks
them against hand-computed values at both configurations' widths.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    qkv_bias: bool = False

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=d, heads=nq,
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim", d // nq),
                   ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   qkv_bias=bool(cfg.get("qkv_bias", False)))


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and
    the three SwiGLU matrices); biases and norms are not matmul work."""
    attn = m.d * m.head_dim * (2 * m.heads + 2 * m.kv_heads)
    return attn + 3 * m.d * m.ff


def linear_flops_per_token(m: Dims) -> int:
    """2 FLOPs per multiply-add through every layer and the LM head."""
    return 2 * (m.layers * layer_matmul_params(m) + m.d * m.vocab)


def attention_flops(m: Dims, context: int) -> int:
    """Scores and weighted sum of one query token over ``context`` keys,
    all layers: 2 * 2 * heads * head_dim * context per layer."""
    return m.layers * 4 * m.heads * m.head_dim * context


def prefill_chunk_flops(m: Dims, start: int, valid: int) -> int:
    """One chunk of ``valid`` prompt tokens at positions [start,
    start+valid): each token attends causally over position + 1 keys."""
    ctx = valid * start + valid * (valid + 1) // 2
    return valid * linear_flops_per_token(m) + attention_flops(m, 1) * ctx


def decode_step_flops(m: Dims, seq_lens: Sequence[int]) -> int:
    """One decode step: one token per active sequence, each attending over
    its ``seq_len`` keys (its context including the new token)."""
    return sum(linear_flops_per_token(m) + attention_flops(m, n)
               for n in seq_lens)


def paged_kernel_flops(m: Dims, seq_lens: Sequence[int]) -> int:
    """One call of the paged decode kernel (one layer)."""
    return sum(4 * m.heads * m.head_dim * n for n in seq_lens)


def paged_kernel_bytes(m: Dims, seq_lens: Sequence[int], page: int,
                       kv_itemsize: int = 2, q_itemsize: int = 4,
                       o_itemsize: int = 2) -> int:
    """HBM bytes one kernel call (one layer) needs: the f32 query and the
    output of each active sequence, its block-table entries for the pages
    in context and its length, and the K and V of the tokens actually in
    its context -- not of every page slot the grid visits."""
    total = 0
    for n in seq_lens:
        total += m.heads * m.head_dim * (q_itemsize + o_itemsize)
        total += 4 * (-(-n // page)) + 4
        total += 2 * n * m.kv_heads * m.head_dim * kv_itemsize
    return total


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
