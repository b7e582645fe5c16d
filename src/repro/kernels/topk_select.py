"""Pallas TPU kernel: per-block local top-k (the local comparator).

Each grid step selects the top-k of one score block — the TPU analogue of
a DIRC-RAG core's local top-k comparator. The host-side global merge over
the tiny (blocks * k) candidate list is the global comparator.

Selection is k passes of (max, argmax, mask) over the 128*m lane block —
branch-free, VPU-only, no sort network. k <= 64.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._env import resolve_interpret

BLOCK_N = 512
NEG_INF = -3.0e38  # python float: becomes an immediate inside the kernel


def _topk_kernel(s_ref, vals_ref, idx_ref, *, k: int):
    b, blk = s_ref.shape
    scores = s_ref[:, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, blk), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)
    vals = jnp.zeros((b, k), jnp.float32)
    idx = jnp.zeros((b, k), jnp.int32)
    for j in range(k):
        m = jnp.max(scores, axis=1, keepdims=True)  # (b, 1)
        is_max = scores == m
        # lowest index among ties
        arg = jnp.min(jnp.where(is_max, iota, blk), axis=1, keepdims=True)
        vals = jnp.where(col == j, m, vals)
        idx = jnp.where(col == j, arg, idx)
        scores = jnp.where(iota == arg, NEG_INF, scores)
    vals_ref[0] = vals
    idx_ref[0] = idx


@functools.partial(jax.jit, static_argnames=("k", "interpret", "block_n"))
def blockwise_topk(
    scores: jax.Array, k: int, interpret: Optional[bool] = None,
    block_n: int = BLOCK_N,
) -> tuple[jax.Array, jax.Array]:
    """scores (b, n) fp32 -> (vals (nb, b, k), local idx (nb, b, k)).

    n must be a multiple of block_n; local indices are block-relative
    (caller adds `block * block_n`). The block axis leads so that each
    grid step writes one whole `(b, k)` tile, which Mosaic requires.
    """
    b, n = scores.shape
    assert n % block_n == 0 and k <= block_n
    nb = n // block_n
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(nb,),
        in_specs=[pl.BlockSpec((b, block_n), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((1, b, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, b, k), jnp.float32),
            jax.ShapeDtypeStruct((nb, b, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(scores)
    return vals, idx
