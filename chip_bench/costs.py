"""Operations and bytes of the measured kernels and steps, from shapes.

Counted as the algorithm needs them, whatever implements it: a step's
useful work, not what a particular kernel happens to touch. Every count
is checked against a hand count in tests/chip_bench/test_cb_costs.py.
"""
from __future__ import annotations

import numpy as np


def attention_pairs(lengths, n_valid) -> int:
    """Query-key pairs a causal step attends: row r's i-th new token (of
    n_valid[r]) sees lengths[r] + i + 1 positions."""
    lengths = np.asarray(lengths, np.int64)
    n_valid = np.asarray(n_valid, np.int64)
    return int(np.sum(n_valid * lengths + n_valid * (n_valid + 1) // 2))


def paged_attend_call(lengths, n_valid, n_heads: int, n_kv_heads: int,
                      head_dim: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one paged-attention call (one layer).

    FLOPs: q.k and p.v, 2 * 2 * pairs * heads * head_dim. Bytes: the K and
    V of every position a row attends, read once; the new tokens' K and V
    written once; q read and o written once.
    """
    lengths = np.asarray(lengths, np.int64)
    n_valid = np.asarray(n_valid, np.int64)
    pairs = attention_pairs(lengths, n_valid)
    flops = 4 * pairs * n_heads * head_dim
    ctx = int(np.sum(np.where(n_valid > 0, lengths + n_valid, 0)))
    new = int(np.sum(n_valid))
    kv = 2 * n_kv_heads * head_dim * itemsize
    qo = 2 * n_heads * head_dim * itemsize
    return flops, ctx * kv + new * kv + new * qo


def decoder_matmul_params(d_model: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, d_ff: int, n_layers: int) -> int:
    """Weights one token meets in the layer stack (q, k, v, o, SwiGLU)."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return n_layers * (attn + 3 * d_model * d_ff)


def decoder_step_flops(cfg, lengths, n_valid) -> int:
    """Model FLOPs of one paged step: 2 * N * tokens through the stack,
    attention, and the LM head at each row's one sampled position."""
    n_valid = np.asarray(n_valid, np.int64)
    tokens = int(np.sum(n_valid))
    rows = int(np.sum(n_valid > 0))
    p = decoder_matmul_params(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                              cfg.resolved_head_dim, cfg.d_ff, cfg.n_layers)
    attn = cfg.n_layers * 4 * attention_pairs(lengths, n_valid) \
        * cfg.n_heads * cfg.resolved_head_dim
    head = 2 * cfg.d_model * cfg.vocab_size * rows
    return 2 * p * tokens + attn + head


def roofline_share(work: list, seconds: float, peak_ops: float,
                   peak_bw: float) -> float:
    """Least time of `work` [(ops, bytes)] at the peaks, over `seconds`,
    in percent. Each item is bounded by its own larger term."""
    least = sum(max(o / peak_ops, b / peak_bw) for o, b in work)
    return 100.0 * least / seconds
