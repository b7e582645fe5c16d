"""Pallas kernel: fused paged-attention decode (flash-decoding split-KV).

The gather path in `models.attention.paged_attend` materializes every
row's full logical KV window — `(b, max_blocks * block_size, kh, hd)` of
activation per step — before attending: paged HBM *residency* with
dense-window *compute*. This kernel removes the materialization by
walking the block table inside the kernel.

Split-KV dataflow (flash-decoding):

    grid = (row, kv_chunk)   # one program per (b, chunk of the block table)

Each program
  1. copies its `chunk_blocks` physical blocks from the HBM pools into a
     VMEM buffer, one DMA per block, addressed through the block table;
  2. writes the new-token K/V that land inside its chunk both into that
     buffer and, by DMA, into the shared pools (the `_paged_write` fold-in
     — pools are aliased input/outputs, so the write is in place and
     rows' chunks are disjoint by construction; invalid lanes simply skip
     the write instead of scribbling the NULL scratch block);
  3. computes scores for all `t` query positions against its chunk with
     a causal + true-length mask, keeping *local* softmax statistics:
     chunk max `m`, unnormalized weight sum `denom`, and weighted-value
     accumulator `acc` in fp32.

The per-chunk `(acc, m, denom)` partials are reduced in a second pass
(plain jnp in the jitted wrapper): with `M = max_c m_c` and
`alpha_c = exp(m_c - M)`, the exact softmax-weighted output is
`sum_c acc_c * alpha_c / sum_c denom_c * alpha_c` — the standard
online-softmax rescale, so long contexts parallelize over the KV axis
instead of serializing per row.

Layout notes: the block table, the per-row length/n_valid scalars and
the layer index are scalar-prefetched into SMEM; the K/V pools stay
unblocked in HBM (`pl.ANY`) and move only by DMA. The pools may be one
layer's `(n_blocks, bs, kh, hd)` or the whole stack's
`(L, n_blocks, bs, kh, hd)` with a layer index: a layer scan then hands
the kernel the stacked pools it carries, the DMAs address
`pool[layer, block]`, and the aliased stack is updated in place instead
of being sliced out per layer and written back. Scores are one 2-D
matmul of every query head against every (token, KV head) row of the
chunk, with the rows of other KV heads masked out: that keeps each dot
two-dimensional, which is what Mosaic lowers, at `n_kv_heads` times the
score FLOPs of a per-head loop — small next to the bytes a decode step
moves.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._env import resolve_interpret

NEG_INF = -1.0e30
# Target tokens per chunk: one program's KV tile. 128 keeps the score
# matmul lane-aligned while bounding per-program VMEM.
CHUNK_TOKENS = 128
# Scoped VMEM the kernel may use: a prefill chunk's fp32 score tile
# (t*h, chunk_tokens*kh) and its temporaries outgrow the default limit.
VMEM_LIMIT_BYTES = 64 * 2**20


def _paged_attend_kernel(table_ref, len_ref, nv_ref, layer_ref,
                         q_ref, kn_ref, vn_ref, kpool_ref, vpool_ref,
                         acc_ref, m_ref, den_ref, kout_ref, vout_ref,
                         k_buf, v_buf, sem,
                         *, block_size: int, chunk_blocks: int,
                         table_width: int, n_heads: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    t, kh, hd = kn_ref.shape[1], kn_ref.shape[2], kn_ref.shape[3]
    h = n_heads
    g = h // kh
    bs, cb = block_size, chunk_blocks
    ct = cb * bs
    row0 = i * table_width
    length = len_ref[i]
    n_valid = nv_ref[i]
    layer = layer_ref[0]

    # -- gather this chunk's physical blocks through the block table.
    copies = []
    for c in range(cb):
        phys = table_ref[row0 + j * cb + c]
        copies.append(pltpu.make_async_copy(kpool_ref.at[layer, phys],
                                            k_buf.at[c], sem))
        copies.append(pltpu.make_async_copy(vpool_ref.at[layer, phys],
                                            v_buf.at[c], sem))
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()

    # -- fused `_paged_write`: each new token owned by this chunk goes into
    # the VMEM copy (so this program attends it) and into the pool. Each
    # logical position belongs to exactly one (row, chunk) program, and
    # live rows' physical blocks are disjoint (CoW barriers guarantee
    # shared blocks are never write targets), so the writes never race.
    def write_token(ti, carry):
        pos = length + ti
        lb = pos // bs
        own = (lb >= j * cb) & (lb < (j + 1) * cb) & (ti < n_valid)

        @pl.when(own)
        def _():
            phys = table_ref[row0 + lb]
            off = pos % bs
            c = lb - j * cb
            k_buf[c, off] = kn_ref[0, ti]
            v_buf[c, off] = vn_ref[0, ti]
            for src, dst in ((kn_ref, kout_ref), (vn_ref, vout_ref)):
                cp = pltpu.make_async_copy(src.at[0, ti],
                                           dst.at[layer, phys, off], sem)
                cp.start()
                cp.wait()

        return carry

    jax.lax.fori_loop(0, t, write_token, 0)

    # -- local online-softmax statistics for this chunk. Rows are
    # (query position, head), columns (chunk token, KV head).
    kc = k_buf[...].astype(jnp.float32).reshape(ct * kh, hd)
    vc = v_buf[...].astype(jnp.float32).reshape(ct * kh, hd)
    q = q_ref[0].astype(jnp.float32) * scale              # (t*h, hd)
    s = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = ((row % h) // g == col % kh) & (
        j * ct + col // kh <= length + row // h)
    s = jnp.where(visible, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)                 # (t*h, 1)
    p = jnp.where(visible, jnp.exp(s - m), 0.0)
    den = jnp.sum(p, axis=1, keepdims=True)
    acc = jnp.dot(p.astype(v_buf.dtype), vc.astype(v_buf.dtype),
                  preferred_element_type=jnp.float32)     # (t*h, hd)
    acc_ref[0, 0] = acc
    m_ref[0, 0] = m
    den_ref[0, 0] = den


@functools.partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def paged_attend_fused(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                       k_pool: jax.Array, v_pool: jax.Array,
                       block_table: jax.Array, length: jax.Array,
                       n_valid: jax.Array,
                       chunk_blocks: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       layer: Optional[jax.Array] = None):
    """Fused scatter + block-table attention over `t` new positions.

    q (b, t, h, hd) post-RoPE queries; k_new/v_new (b, t, kh, hd) the new
    K/V for logical positions `length[b] .. length[b] + t - 1` (entries
    past `n_valid[b]` are padding and are neither written nor attended);
    pools (n_blocks, block_size, kh, hd), or the stacked
    (L, n_blocks, block_size, kh, hd) with `layer` the int32 scalar that
    picks the layer attended and written (the other layers pass through
    untouched); block_table (b, max_blocks) int32. Returns (out
    (b, t, h, hd) in q.dtype, k_pool', v_pool') with identical semantics
    to the gather path in `models.attention`, except invalid lanes skip
    the scatter entirely instead of writing the NULL_BLOCK scratch (both
    leave scratch content unspecified).
    """
    if layer is None:
        out, kp, vp = paged_attend_fused(
            q, k_new, v_new, k_pool[None], v_pool[None], block_table,
            length, n_valid, chunk_blocks=chunk_blocks, interpret=interpret,
            layer=jnp.int32(0))
        return out, kp[0], vp[0]
    b, t, h, hd = q.shape
    _, _, bs, kh, _ = k_pool.shape
    mb = block_table.shape[1]
    cb = min(mb, chunk_blocks or max(1, CHUNK_TOKENS // bs))
    # Pad the table to a chunk multiple with NULL_BLOCK: the padded
    # logical positions sit past every row's capacity, so the mask
    # already hides whatever the scratch block holds.
    mb_p = (mb + cb - 1) // cb * cb
    if mb_p != mb:
        block_table = jnp.pad(block_table, ((0, 0), (0, mb_p - mb)))
    nc = mb_p // cb

    row_block = lambda i, j, *_: (i, 0, 0)          # noqa: E731
    new_block = lambda i, j, *_: (i, 0, 0, 0)       # noqa: E731
    part_block = lambda i, j, *_: (i, j, 0, 0)      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, t * h, hd), row_block),
            pl.BlockSpec((1, t, kh, hd), new_block),
            pl.BlockSpec((1, t, kh, hd), new_block),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t * h, hd), part_block),
            pl.BlockSpec((1, 1, t * h, 1), part_block),
            pl.BlockSpec((1, 1, t * h, 1), part_block),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((cb, bs, kh, hd), k_pool.dtype),
            pltpu.VMEM((cb, bs, kh, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    acc, m, den, kp, vp = pl.pallas_call(
        functools.partial(_paged_attend_kernel, block_size=bs,
                          chunk_blocks=cb, table_width=mb_p, n_heads=h,
                          scale=hd**-0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, t * h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, t * h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, t * h, 1), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={7: 3, 8: 4},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(block_table.reshape(-1).astype(jnp.int32), length.astype(jnp.int32),
      n_valid.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape(b, t * h, hd),
      k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype), k_pool, v_pool)

    # -- second pass: flash-decoding combine of the per-chunk partials.
    big = jnp.max(m, axis=1)                              # (b, t*h, 1)
    alpha = jnp.exp(m - big[:, None])                     # (b, nc, t*h, 1)
    den_tot = jnp.sum(den * alpha, axis=1)
    out = jnp.sum(acc * alpha, axis=1)
    out = out / jnp.maximum(den_tot, 1e-30)
    return out.reshape(b, t, h, hd).astype(q.dtype), kp, vp
