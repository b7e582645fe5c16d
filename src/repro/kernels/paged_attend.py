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
  2. merges the new-token K/V that land inside its chunk into that
     buffer, and DMAs each block they touch back to the shared pools
     whole (the `_paged_write` fold-in — pools are aliased
     input/outputs, so the write is in place and rows' chunks are
     disjoint by construction; invalid lanes simply skip the write
     instead of scribbling the NULL scratch block);
  3. computes scores for all `t` query positions against its chunk with
     a causal + true-length mask, each KV head against the query heads
     of its group, keeping *local* softmax statistics: chunk
     max `m`, unnormalized weight sum `denom`, and weighted-value
     accumulator `acc` in fp32.

The per-chunk `(acc, m, denom)` partials are reduced in a second pass
(plain jnp in the jitted wrapper): with `M = max_c m_c` and
`alpha_c = exp(m_c - M)`, the exact softmax-weighted output is
`sum_c acc_c * alpha_c / sum_c denom_c * alpha_c` — the standard
online-softmax rescale, so long contexts parallelize over the KV axis
instead of serializing per row.

Layout notes: the block table, the per-row length/n_valid scalars and
the layer index are scalar-prefetched into SMEM; the K/V pools stay
unblocked in HBM (`pl.ANY`) and move only by DMA. The engine's pools are
the whole stack, head-major: `(L, n_blocks, kh, block_size, hd)`, so one
(block, head) tile is `(block_size, hd)` and no copy, slice or reshape
ever tiles the KV-head axis — any KV-head count compiles, with no head
padded. The kernel takes the layer index, its DMAs address
`pool[layer, block]`, and the aliased stack is updated in place instead
of being sliced out per layer and written back. Queries come grouped
by KV head, so each head scores only the `h // kh` query heads that
read it, in one dot batched over the KV heads; the VMEM copy is laid
out `(kh, chunk_blocks, block_size, hd)` to match. A program
whose chunk lies past every query position copies and scores nothing.
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
# Scoped VMEM the kernel may use: a prefill chunk's fp32 partials and
# score tiles, double-buffered, outgrow the default limit.
VMEM_LIMIT_BYTES = 64 * 2**20


def _paged_attend_kernel(table_ref, len_ref, nv_ref, layer_ref,
                         q_ref, kn_ref, vn_ref, kpool_ref, vpool_ref,
                         acc_ref, m_ref, den_ref, kout_ref, vout_ref,
                         k_buf, v_buf, sem,
                         *, block_size: int, chunk_blocks: int,
                         table_width: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    kh, tg = q_ref.shape[1], q_ref.shape[2]
    t = kn_ref.shape[2]
    g = tg // t
    bs, cb = block_size, chunk_blocks
    ct = cb * bs
    row0 = i * table_width
    length = len_ref[i]
    n_valid = nv_ref[i]
    layer = layer_ref[0]
    lo = j * ct                      # first logical position of the chunk
    pairs = ((kpool_ref, kout_ref, k_buf), (vpool_ref, vout_ref, v_buf))

    def phys(c):
        return table_ref[row0 + j * cb + c]

    # A chunk past every query position (`length + t - 1`) has nothing
    # visible: it moves and scores nothing, and its partials are the
    # empty softmax (m = NEG_INF, no weight) that the combine drops.
    live = lo <= length + t - 1

    @pl.when(jnp.logical_not(live))
    def _empty():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        den_ref[...] = jnp.zeros(den_ref.shape, den_ref.dtype)

    @pl.when(live)
    def _attend():
        # -- gather this chunk's physical blocks through the block table.
        copies = [pltpu.make_async_copy(pool.at[layer, phys(c)], buf.at[:, c], sem)
                  for c in range(cb) for pool, _, buf in pairs]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()

        # -- fused `_paged_write`: the new tokens that fall in this chunk are
        # merged into the VMEM copy of their blocks (so this program attends
        # them), and every block they touch goes back to the pool whole. Each
        # logical position belongs to exactly one (row, chunk) program, and
        # live rows' physical blocks are disjoint (CoW barriers guarantee
        # shared blocks are never write targets), so the write-backs never
        # race.
        def touched(c):
            start = lo + c * bs
            return (n_valid > 0) & (length < start + bs) & (length + n_valid > start)

        def write_backs(c):
            return [pltpu.make_async_copy(buf.at[:, c], out.at[layer, phys(c)], sem)
                    for _, out, buf in pairs]

        def merge(c, carry):
            @pl.when(touched(c))
            def _():
                # each block position's index among the new tokens
                rel = lo + c * bs - length + jax.lax.broadcasted_iota(
                    jnp.int32, (bs, 1), 0)
                hit = (rel >= 0) & (rel < n_valid)
                # position takes new token `rel`: a one-hot per KV head
                onehot = jnp.broadcast_to(
                    rel == jax.lax.broadcasted_iota(jnp.int32, (bs, t), 1),
                    (kh, bs, t)).astype(jnp.float32)
                for new_ref, buf in ((kn_ref, k_buf), (vn_ref, v_buf)):
                    new = jax.lax.dot_general(          # (kh, bs, hd)
                        onehot, new_ref[0].astype(jnp.float32),
                        (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
                    buf[:, c] = jnp.where(
                        hit[None], new,
                        buf[:, c].astype(jnp.float32)).astype(buf.dtype)
                for cp in write_backs(c):
                    cp.start()
            return carry

        jax.lax.fori_loop(0, cb, merge, 0)

        # -- local online-softmax statistics for this chunk, batched over
        # the KV heads. Rows are (query position, query head of the group),
        # columns the chunk's tokens.
        row = jax.lax.broadcasted_iota(jnp.int32, (tg, ct), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tg, ct), 1)
        visible = lo + col <= length + row // g
        kc = k_buf[...].astype(jnp.float32).reshape(kh, ct, -1)
        vc = v_buf[...].reshape(kh, ct, -1)
        q = q_ref[0].astype(jnp.float32) * scale           # (kh, t*g, hd)
        s = jax.lax.dot_general(q, kc, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = jnp.where(visible[None], s, NEG_INF)
        m = jnp.max(s, axis=2, keepdims=True)
        p = jnp.where(visible[None], jnp.exp(s - m), 0.0)
        acc = jax.lax.dot_general(p.astype(v_buf.dtype), vc,
                                  (((2,), (1,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        den = jnp.sum(p, axis=2, keepdims=True)
        parts = [(acc[hk], m[hk], den[hk]) for hk in range(kh)]
        # one store each, rows (KV head, query position, group head): a
        # (t*g, hd) tile per head would pad t*g = 3 rows to 8
        for ref, part in zip((acc_ref, m_ref, den_ref), zip(*parts)):
            ref[0, 0] = jnp.concatenate(part, axis=0)

        def wait(c, carry):
            @pl.when(touched(c))
            def _():
                for cp in write_backs(c):
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, cb, wait, 0)


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
    pools the head-major stack (L, n_blocks, kh, block_size, hd) with
    `layer` the int32 scalar that picks the layer attended and written
    (the other layers pass through untouched); block_table
    (b, max_blocks) int32. Returns (out (b, t, h, hd) in q.dtype,
    k_pool', v_pool') with identical semantics to the gather path in
    `models.attention`, except invalid lanes skip the scatter entirely
    instead of writing the NULL_BLOCK scratch (both leave scratch content
    unspecified).

    Without `layer`, one layer's token-major (n_blocks, block_size, kh,
    hd) pools, transposed around the call: only the benchmark's compile
    test (`tests/chip_bench/test_cb_tpu_compile.py`) passes that form,
    and it goes with that test's next revision.
    """
    if layer is None:
        head_major = lambda x: jnp.swapaxes(x, 1, 2)[None]  # noqa: E731
        out, kp, vp = paged_attend_fused(
            q, k_new, v_new, head_major(k_pool), head_major(v_pool),
            block_table, length, n_valid, chunk_blocks=chunk_blocks,
            interpret=interpret, layer=jnp.int32(0))
        return out, jnp.swapaxes(kp[0], 1, 2), jnp.swapaxes(vp[0], 1, 2)
    b, t, h, hd = q.shape
    _, _, kh, bs, _ = k_pool.shape
    g = h // kh
    mb = block_table.shape[1]
    cb = min(mb, chunk_blocks or max(1, CHUNK_TOKENS // bs))
    # Pad the table to a chunk multiple with NULL_BLOCK: the padded
    # logical positions sit past every row's capacity, so the mask
    # already hides whatever the scratch block holds.
    mb_p = (mb + cb - 1) // cb * cb
    if mb_p != mb:
        block_table = jnp.pad(block_table, ((0, 0), (0, mb_p - mb)))
    nc = mb_p // cb

    row_block = lambda i, j, *_: (i, 0, 0, 0)       # noqa: E731
    part_block = lambda i, j, *_: (i, j, 0, 0)      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, kh, t * g, hd), row_block),
            pl.BlockSpec((1, kh, t, hd), row_block),
            pl.BlockSpec((1, kh, t, hd), row_block),
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
            pltpu.VMEM((kh, cb, bs, hd), k_pool.dtype),
            pltpu.VMEM((kh, cb, bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    # queries grouped by the KV head they read: row (position, group head)
    q_grouped = q.reshape(b, t, kh, g, hd).transpose(0, 2, 1, 3, 4)
    k_in = jnp.swapaxes(k_new, 1, 2).astype(k_pool.dtype)   # (b, kh, t, hd)
    v_in = jnp.swapaxes(v_new, 1, 2).astype(v_pool.dtype)
    acc, m, den, kp, vp = pl.pallas_call(
        functools.partial(_paged_attend_kernel, block_size=bs,
                          chunk_blocks=cb, table_width=mb_p,
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
      q_grouped.reshape(b, kh, t * g, hd),
      k_in, v_in, k_pool, v_pool)

    # -- second pass: flash-decoding combine of the per-chunk partials.
    big = jnp.max(m, axis=1)                        # (b, t*h, 1)
    alpha = jnp.exp(m - big[:, None])               # (b, nc, t*h, 1)
    den_tot = jnp.sum(den * alpha, axis=1)
    out = jnp.sum(acc * alpha, axis=1)
    out = out / jnp.maximum(den_tot, 1e-30)         # (b, kh*t*g, hd)
    out = out.reshape(b, kh, t, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, h, hd).astype(q.dtype), kp, vp
