"""Pallas TPU kernel: exact per-row top-k by selection (the local comparator).

Each grid step takes a `(row_tile, block_n)` tile of scores and selects the
top-k of every row of it: k passes of (max, lowest-index argmax, mask) —
branch-free, VPU-only, no sort network. It is the TPU analogue of a
DIRC-RAG core's local top-k comparator; where a row spans several blocks,
the tiny (blocks * k) candidate list is merged by the caller
(`ops.local_topk_blocks`).

The order is `jax.lax.top_k`'s, bit for bit: scores rank in IEEE total
order (-inf < ... < -0.0 < +0.0 < ... < +inf), ties to the lower index.
The kernel ranks int32 keys that order as the floats do, so a picked entry
is masked with `TAKEN`, below the key of every score — of -inf too (only
the negative NaN whose bits are all ones maps to it). A row with fewer than
k finite scores therefore returns its -inf entries at their lowest
indices, never one entry twice. The selection does no arithmetic on the
scores: the values returned are the bits that came in.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._env import resolve_interpret

BLOCK_N = 8192       # widest block: a wider row is split into blocks
ROW_TILE = 32        # rows per grid step at the widest block (1 MiB); on
#                      a v5e 8 / 16 / 32 rows select 2,048 rows of 8,171
#                      scores in 1.18 / 0.64 / 0.50 ms
TAKEN = -(2**31)     # key of a picked entry, below every score's key
PAST_END = TAKEN + 1  # key of a lane past the row's end: below every score


def _key(x: jax.Array) -> jax.Array:
    """f32 -> int32 ordered as the floats' total order. Flipping the
    magnitude bits of negatives is its own inverse on the bits."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _unkey(key: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)


def _topk_kernel(s_ref, vals_ref, idx_ref, *, k: int, n: int):
    rows, blk = s_ref.shape
    keys = _key(s_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
    if n % blk:  # the last block runs past the row: its tail is not scores
        keys = jnp.where(lane < n - pl.program_id(1) * blk, keys, PAST_END)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    top = jnp.zeros((rows, k), jnp.int32)
    idx = jnp.zeros((rows, k), jnp.int32)
    for j in range(k):
        m = jnp.max(keys, axis=1, keepdims=True)                 # (rows, 1)
        arg = jnp.min(jnp.where(keys == m, lane, blk), axis=1, keepdims=True)
        top = jnp.where(col == j, m, top)
        idx = jnp.where(col == j, arg, idx)
        keys = jnp.where(lane == arg, TAKEN, keys)
    vals_ref[0] = _unkey(top)
    idx_ref[0] = idx


@functools.partial(jax.jit, static_argnames=("k", "interpret", "block_n",
                                             "row_tile"))
def blockwise_topk(
    scores: jax.Array, k: int, interpret: Optional[bool] = None,
    block_n: int = BLOCK_N, row_tile: int = ROW_TILE,
) -> tuple[jax.Array, jax.Array]:
    """scores (rows, n) fp32 -> (vals (nb, rows, k), local idx (nb, rows, k)).

    nb = ceil(n / block_n). Neither axis needs padding: lanes past the
    row's end rank below every score, and rows past the last are dropped.
    A last block with fewer than k scores fills its tail with the value
    of `PAST_END`, a negative NaN, which `lax.top_k` ranks below every
    score. Local indices are block-relative (caller adds `block *
    block_n`). The block axis leads so that each grid step writes one
    whole `(row_tile, k)` tile, which Mosaic requires.
    """
    rows, n = scores.shape
    nb = pl.cdiv(n, block_n)
    assert k <= block_n
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, n=n),
        grid=(pl.cdiv(rows, row_tile), nb),
        in_specs=[pl.BlockSpec((row_tile, block_n), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((1, row_tile, k), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, row_tile, k), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, rows, k), jnp.float32),
            jax.ShapeDtypeStruct((nb, rows, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="index_topk_select",
    )(scores)
    return vals, idx
