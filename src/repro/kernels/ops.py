"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples and layout transposition into the
kernel layouts. Each kernel picks interpret mode from the platform while
it is traced (see `_env.resolve_interpret`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitplane
from . import dirc_mac as _dirc
from . import score_matmul as _score
from . import topk_select as _topk


def _pad_axis(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("bits", "block_n"))
def dirc_mac(q_values: jax.Array, d_planes_packed: jax.Array, bits: int = 8,
             block_n: int = _dirc.BLOCK_N) -> jax.Array:
    """q (b, dim) int8, docs packed (n, bits, nw) uint32 -> (b, n) int32.

    Accepts the natural (n, bits, nw) packed layout from
    `bitplane.pack_words(to_bitplanes(...))` and transposes to the kernel's
    (bits, nw, n) lane-major layout.
    """
    squeeze = q_values.ndim == 1
    if squeeze:
        q_values = q_values[None]
    n = d_planes_packed.shape[0]
    qp = bitplane.pack_words(bitplane.to_bitplanes(q_values, bits=bits))
    d = _pad_axis(d_planes_packed, 0, block_n)
    d_t = jnp.transpose(d, (1, 2, 0))  # (bits, nw, n_pad)
    out = _dirc.dirc_mac_packed(qp, d_t, bits=bits, block_n=block_n)[:, :n]
    return out[0] if squeeze else out


@partial(jax.jit, static_argnames=("block_n",))
def score_matmul(q: jax.Array, docs: jax.Array,
                 block_n: int = _score.BLOCK_N) -> jax.Array:
    """q (b, dim) int8 x docs (n, dim) int8 -> (b, n) int32."""
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    n = docs.shape[0]
    d = _pad_axis(docs, 0, block_n)
    out = _score.score_matmul_int(q, d, block_n=block_n)[:, :n]
    return out[0] if squeeze else out


@partial(jax.jit, static_argnames=("block_n",))
def score_matmul_cosine(q: jax.Array, docs: jax.Array, doc_norms: jax.Array,
                        block_n: int = _score.BLOCK_N) -> jax.Array:
    """Fused cosine scores (b, n) fp32; doc_norms (n,) integer-code norms."""
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    b = q.shape[0]
    n = docs.shape[0]
    d = _pad_axis(docs, 0, block_n)
    dn = _pad_axis(doc_norms, 0, block_n, value=1.0)[None, :]
    qn = jnp.sqrt(jnp.sum(q.astype(jnp.float32) ** 2, -1, keepdims=True))
    out = _score.score_matmul_cosine(
        q, d, qn, dn.astype(jnp.float32), block_n=block_n
    )[:, :n]
    return out[0] if squeeze else out


@partial(jax.jit, static_argnames=("k", "block_n"))
def local_topk_blocks(scores: jax.Array, k: int,
                      block_n: int = _topk.BLOCK_N):
    """scores (b, n) -> global top-k via per-block kernel + tiny merge.

    Returns (vals (b, k), global idx (b, k)).
    """
    b, n = scores.shape
    s = _pad_axis(scores, 1, block_n, value=_topk.NEG_INF)
    nb = s.shape[1] // block_n
    vals, idx = _topk.blockwise_topk(s, k=k, block_n=block_n)  # (nb, b, k)
    offs = (jnp.arange(nb, dtype=jnp.int32) * block_n)[:, None, None]
    gidx = jnp.transpose(idx + offs, (1, 0, 2)).reshape(b, nb * k)
    gvals = jnp.transpose(vals, (1, 0, 2)).reshape(b, nb * k)
    # Candidates are block-major, score-desc within block, low-index
    # tie-broken — top_k over them preserves the global low-index tie-break.
    fv, fpos = jax.lax.top_k(gvals, k)
    fidx = jnp.take_along_axis(gidx, fpos, axis=1)
    return fv, fidx
