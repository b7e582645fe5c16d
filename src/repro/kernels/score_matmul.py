"""Pallas TPU kernel: MXU-path INT8 score matmul (+fused cosine norm).

The beyond-paper TPU-native retrieval path: instead of emulating the
bit-serial column arithmetic, INT8 embeddings are fed to the MXU as dense
128-aligned tiles. One pass computes `scores = q @ D^T` with int32
accumulation, optionally fused with the cosine normalization so the fp32
scores never round-trip through HBM.

Block shapes are MXU-aligned: the doc axis (lanes) is blocked at 128 and
the contraction dim is kept whole (128..1024 fits VMEM comfortably:
128 x 1024 int8 = 128 KB per block).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._env import resolve_interpret

BLOCK_N = 128


def _score_kernel(q_ref, d_ref, out_ref):
    # q: (b, dim) int8, d: (blk_n, dim) int8 -> out (b, blk_n) int32.
    # The int8 operands go to the MXU as they are: Mosaic has no int32
    # matmul, only int8 x int8 with int32 accumulation.
    out_ref[:, :] = jax.lax.dot_general(
        q_ref[:, :], d_ref[:, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _score_cosine_kernel(q_ref, d_ref, qn_ref, dn_ref, out_ref):
    q = q_ref[:, :].astype(jnp.float32)
    d = d_ref[:, :].astype(jnp.float32)
    ip = jax.lax.dot_general(
        q, d, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    denom = jnp.maximum(qn_ref[:, :] * dn_ref[:, :], 1e-12)  # (b,1)*(1,blk)
    out_ref[:, :] = ip / denom


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def score_matmul_int(
    q: jax.Array, docs: jax.Array, interpret: Optional[bool] = None,
    block_n: int = BLOCK_N,
) -> jax.Array:
    """q (b, dim) int8 x docs (n, dim) int8 -> (b, n) int32 exact scores."""
    b, dim = q.shape
    n, ddim = docs.shape
    assert ddim == dim and n % block_n == 0
    return pl.pallas_call(
        _score_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((b, dim), lambda i: (0, 0)),
            pl.BlockSpec((block_n, dim), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q, docs)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def score_matmul_cosine(
    q: jax.Array,
    docs: jax.Array,
    q_norms: jax.Array,
    doc_norms: jax.Array,
    interpret: Optional[bool] = None,
    block_n: int = BLOCK_N,
) -> jax.Array:
    """Fused cosine scores: (b, n) fp32 = (q @ D^T) / (|q| |d|).

    q (b, dim) int8; docs (n, dim) int8; q_norms (b, 1); doc_norms (1, n).
    """
    b, dim = q.shape
    n, ddim = docs.shape
    assert ddim == dim and n % block_n == 0
    assert q_norms.shape == (b, 1) and doc_norms.shape == (1, n)
    return pl.pallas_call(
        _score_cosine_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((b, dim), lambda i: (0, 0)),
            pl.BlockSpec((block_n, dim), lambda i: (i, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, docs, q_norms, doc_norms)
