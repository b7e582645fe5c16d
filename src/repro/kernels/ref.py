"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors one kernel's public semantics with straightforward
jnp — no blocking, no Pallas. Tests sweep shapes/dtypes and assert
exact (integer) or allclose (float) agreement in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane


def dirc_mac(q_values: jax.Array, d_planes_dense: jax.Array, bits: int = 8) -> jax.Array:
    """Oracle for kernels.dirc_mac: exact int32 inner products.

    q_values: (b, dim) int8 codes; d_planes_dense: (n, bits, dim) {0,1}.
    """
    return bitplane.bitserial_dot(q_values, d_planes_dense, bits=bits)


def score_matmul_int(q: jax.Array, docs: jax.Array) -> jax.Array:
    """Oracle for kernels.score_matmul_int: (b,n) int32 = q @ docs^T."""
    return jax.lax.dot_general(
        q.astype(jnp.int32),
        docs.astype(jnp.int32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def score_matmul_cosine(
    q: jax.Array, docs: jax.Array, q_norms: jax.Array, doc_norms: jax.Array
) -> jax.Array:
    ip = score_matmul_int(q, docs).astype(jnp.float32)
    return ip / jnp.maximum(q_norms * doc_norms, 1e-12)


def blockwise_topk(scores: jax.Array, k: int, block_n: int):
    """Oracle for kernels.topk_select: per-block top-k, low-index
    tie-break, block axis first ((nb, b, k) like the kernel)."""
    b, n = scores.shape
    nb = n // block_n
    s = jnp.transpose(scores.reshape(b, nb, block_n), (1, 0, 2))
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)
