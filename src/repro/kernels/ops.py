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


def _topk_tiles(rows: int, n: int) -> tuple[int, int, int]:
    """(blocks per row, block width, rows per tile) for a (rows, n) top-k.

    A row of up to `BLOCK_N` scores is one block, so no candidate merge
    follows; a wider row splits into the fewest blocks of at most
    `BLOCK_N`, 128-lane aligned. Tiles hold up to ROW_TILE * BLOCK_N
    scores (1 MiB), rows in multiples of 8."""
    nb = -(-n // _topk.BLOCK_N)
    block_n = -(-n // nb)
    block_n += (-block_n) % 128
    per_tile = max(8, _topk.ROW_TILE * _topk.BLOCK_N // block_n // 8 * 8)
    row_tile = min(per_tile, rows + (-rows) % 8)
    return -(-n // block_n), block_n, row_tile


@partial(jax.jit, static_argnames=("k",))
def local_topk_blocks(scores: jax.Array, k: int):
    """scores (..., n) -> exact top-k of each row: (vals (..., k), idx
    (..., k) int32), `jax.lax.top_k`'s results bit for bit.

    Every row of every leading axis goes through one kernel launch, which
    reads the scores where they lie: nothing is padded or copied.
    """
    *lead, n = scores.shape
    assert k <= n, f"k={k} exceeds the row's {n} scores"
    s = scores.reshape(-1, n)
    nb, block_n, row_tile = _topk_tiles(s.shape[0], n)
    vals, idx = _topk.blockwise_topk(s, k=k, block_n=block_n,
                                     row_tile=row_tile)  # (nb, rows, k)
    if nb == 1:
        fv, fidx = vals[0], idx[0]
    else:
        offs = (jnp.arange(nb, dtype=jnp.int32) * block_n)[:, None, None]
        gidx = jnp.transpose(idx + offs, (1, 0, 2)).reshape(-1, nb * k)
        gvals = jnp.transpose(vals, (1, 0, 2)).reshape(-1, nb * k)
        # Candidates are block-major, in selection order within a block:
        # top_k's position tie-break over them is the low-index one.
        fv, fpos = jax.lax.top_k(gvals, k)
        fidx = jnp.take_along_axis(gidx, fpos, axis=1)
    return fv.reshape(*lead, k), fidx.reshape(*lead, k)
