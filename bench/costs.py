"""Operations and bytes of the served model's work, from shapes alone.

A dense decoder layer (GQA attention, SwiGLU MLP) at the published widths:
every count is what the algorithm needs, never what a kernel happens to do,
so a roofline share computed from them cannot pass 100% unless the time is
wrong.  Multiply-adds count as two operations.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Shapes:
    """The widths that set the costs (the config file's keys, renamed)."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    bytes_per_el: int = 2          # bfloat16 weights, activations and KV

    @classmethod
    def from_config(cls, cfg: dict) -> "Shapes":
        d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(d_model=d, n_heads=hq,
                   n_kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg.get("head_dim") or d // hq),
                   d_ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   n_layers=int(cfg["num_hidden_layers"]))


def layer_matmul_params(s: Shapes) -> int:
    """Weights one position multiplies through in one layer: Q, K, V, O and
    the three MLP matrices."""
    q = s.n_heads * s.head_dim
    kv = s.n_kv_heads * s.head_dim
    return s.d_model * (q + 2 * kv) + q * s.d_model + 3 * s.d_model * s.d_ff


def position_flops(s: Shapes, context) -> np.ndarray:
    """Operations of one model position that attends ``context`` keys: the
    block matmuls of every layer, attention (QK^T and PV) over the context,
    and the head."""
    context = np.asarray(context, np.float64)
    dense = 2.0 * (s.n_layers * layer_matmul_params(s) + s.d_model * s.vocab)
    attn = 4.0 * s.n_layers * s.n_heads * s.head_dim * context
    return dense + attn


def positions_flops(s: Shapes, start, end) -> float:
    """Operations of the positions ``start <= p < end`` of each row (position
    p attends p + 1 keys), summed over rows."""
    start = np.asarray(start, np.float64)
    end = np.maximum(np.asarray(end, np.float64), start)
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) / 2.0   # sum of p + 1
    dense = 2.0 * (s.n_layers * layer_matmul_params(s) + s.d_model * s.vocab)
    attn = 4.0 * s.n_layers * s.n_heads * s.head_dim
    return float((dense * n + attn * keys).sum())


def attention_call(s: Shapes, starts, q_len: int) -> tuple[float, float]:
    """One call of the paged mixed attention kernel for one layer: row b
    holds ``q_len`` queries at positions ``starts[b] + t``, and query t
    attends ``starts[b] + t + 1`` keys.  Bytes: Q and the output once, and
    the K and V of each row's live positions once (``starts[b] + q_len``
    tokens), with no page rounding.  Returns (operations, bytes)."""
    starts = np.asarray(starts, np.float64)
    keys = q_len * starts + q_len * (q_len + 1) / 2.0
    flops = 4.0 * s.n_heads * s.head_dim * float(keys.sum())
    qo = 2.0 * len(starts) * q_len * s.n_heads * s.head_dim
    kv = 2.0 * float((starts + q_len).sum()) * s.n_kv_heads * s.head_dim
    return flops, (qo + kv) * s.bytes_per_el


def lmhead_call(s: Shapes, rows: int) -> tuple[float, float]:
    """One call of the fused lm-head epilogue over ``rows`` hidden rows:
    the (rows, d) x (d, V) product, with the weight read once, the rows read
    once, and a token (int32) and a log-probability (float32) written per
    row.  Returns (operations, bytes)."""
    flops = 2.0 * rows * s.d_model * s.vocab
    nbytes = (s.d_model * s.vocab + rows * s.d_model) * s.bytes_per_el + rows * 8
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


__all__ = ["Shapes", "attention_call", "layer_matmul_params", "lmhead_call",
           "position_flops", "positions_flops", "roofline_seconds"]
