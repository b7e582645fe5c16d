"""GQA attention: chunked-flash training/prefill + KV-cache decode.

Memory-safe causal attention in pure JAX: an outer `lax.scan` over query
chunks and an inner rematerialized scan over KV chunks with online
softmax (running max / denominator), so peak activation is
O(chunk_q * chunk_kv) per head instead of O(S^2). GQA never materializes
repeated KV heads — queries are reshaped to (kv_head, group) so the score
einsum contracts against the compact KV tensor directly.

Decode attends a single query step against a (possibly sequence-sharded)
KV cache with a position mask — flash-decoding's partial-softmax combine
is expressed through GSPMD sharding constraints in the model layer.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import layers

NEG_INF = -1.0e30


class KVCache(NamedTuple):
    k: jax.Array       # (b, S, kv_heads, hd)
    v: jax.Array       # (b, S, kv_heads, hd)
    length: jax.Array  # (b,) int32 — valid prefix length


def init_attention(cfg: ModelConfig, key, d_model: Optional[int] = None,
                   n_heads: Optional[int] = None,
                   n_kv_heads: Optional[int] = None,
                   n_layers_scale: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    h = n_heads or cfg.effective_n_heads
    kh = n_kv_heads or cfg.n_kv_heads
    hd = cfg.resolved_head_dim if d_model is None else d // h
    L = n_layers_scale or cfg.n_layers
    pdt = layers.dt(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d**-0.5
    std_o = (h * hd) ** -0.5 / (2 * L) ** 0.5
    p = {
        "wq": layers.normal(k1, (d, h * hd), std, pdt),
        "wk": layers.normal(k2, (d, kh * hd), std, pdt),
        "wv": layers.normal(k3, (d, kh * hd), std, pdt),
        "wo": layers.normal(k4, (h * hd, d), std_o, pdt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h * hd,), pdt)
        p["bk"] = jnp.zeros((kh * hd,), pdt)
        p["bv"] = jnp.zeros((kh * hd,), pdt)
    return p


def _project_qkv(cfg: ModelConfig, params: dict, x: jax.Array,
                 h: int, kh: int, hd: int):
    cdt = layers.dt(cfg.compute_dtype)
    x = x.astype(cdt)
    q = x @ params["wq"].astype(cdt)
    k = x @ params["wk"].astype(cdt)
    v = x @ params["wv"].astype(cdt)
    if "bq" in params:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    from . import sharding

    b, s, _ = x.shape
    return (
        sharding.constrain(q.reshape(b, s, h, hd),
                           ("batch", None, "heads", None)),
        sharding.constrain(k.reshape(b, s, kh, hd),
                           ("batch", None, "heads", None)),
        sharding.constrain(v.reshape(b, s, kh, hd),
                           ("batch", None, "heads", None)),
    )


def _maybe_repeat_kv(q, k, v):
    """GQA sharding repair: a (kh, g) head split is GSPMD-shardable only
    if kh or g divides tp. When the FLAT head count divides tp but kh does
    not, repeat K/V to full heads (g=1) — extra HBM for repeated KV, but
    the score tensors stay head-sharded instead of replicated+gathered."""
    from . import sharding

    tp = sharding.tp_size()
    h, kh = q.shape[2], k.shape[2]
    g = h // kh
    if tp > 1 and g > 1 and h % tp == 0 and kh % tp and g % tp:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = sharding.constrain(k, ("batch", None, "heads", None))
        v = sharding.constrain(v, ("batch", None, "heads", None))
    return k, v


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       chunk_q: int, chunk_kv: int,
                       causal: bool = True, unroll: bool = False) -> jax.Array:
    """(b, sq, h, d) x (b, skv, kh, d) -> (b, sq, h, d), online softmax."""
    k, v = _maybe_repeat_kv(q, k, v)
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    scale = d**-0.5
    cq = min(chunk_q, sq) if chunk_q else sq
    ckv = min(chunk_kv, skv) if chunk_kv else skv
    # Non-divisible lengths are padded to chunk multiples and masked —
    # never densified: padded KV columns are hidden by the validity mask,
    # padded query rows are sliced off the output. The first KV chunk is
    # always fully valid (ckv <= skv), so the running max is finite
    # before any padded column is scanned.
    pq, pkv = (-sq) % cq, (-skv) % ckv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    sq_p, skv_p = sq + pq, skv + pkv
    nq, nkv = sq_p // cq, skv_p // ckv

    qc = (q * scale).reshape(b, nq, cq, kh, g, d)
    kc = k.reshape(b, nkv, ckv, kh, d)
    vc = v.reshape(b, nkv, ckv, kh, d)
    q_pos = jnp.arange(sq_p).reshape(nq, cq)
    k_pos = jnp.arange(skv_p).reshape(nkv, ckv)

    def kv_step(carry, inp):
        acc, m, denom, qi, qb = carry
        kb, vb, kp = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                       preferred_element_type=jnp.float32)
        mask = None
        if causal:
            mask = q_pos[qi][:, None] >= kp[None, :]
        if pkv:
            kv_ok = (kp < skv)[None, :]
            mask = kv_ok if mask is None else mask & kv_ok
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(qb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None].transpose(0, 3, 1, 2, 4) + pv
        return (acc, m_new, denom, qi, qb), None

    def q_step(_, inp):
        qi, qb = inp
        acc0 = jnp.zeros((b, cq, kh, g, d), jnp.float32)
        m0 = jnp.full((b, kh, g, cq), NEG_INF, jnp.float32)
        d0 = jnp.zeros((b, kh, g, cq), jnp.float32)
        (acc, m, denom, _, _), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, d0, qi, qb),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), k_pos),
            unroll=unroll,
        )
        out = acc / denom.transpose(0, 3, 1, 2)[..., None]
        return None, out

    _, outs = jax.lax.scan(
        q_step, None, (jnp.arange(nq), qc.swapaxes(0, 1)), unroll=unroll
    )  # (nq, b, cq, kh, g, d)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq_p, h, d)
    return out[:, :sq].astype(q.dtype)


def _dense_attention(q, k, v, causal):
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    qg = q.reshape(b, sq, kh, g, d) * d**-0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(b, sq, h, d)


def attend_train(cfg: ModelConfig, params: dict, x: jax.Array,
                 angles: Optional[jax.Array],
                 h: Optional[int] = None, kh: Optional[int] = None,
                 causal: bool = True) -> jax.Array:
    """Full-sequence attention (training / encoder / prefill compute)."""
    from . import rope as rope_mod

    h = h or cfg.effective_n_heads
    kh = kh or cfg.n_kv_heads
    hd = params["wq"].shape[1] // h
    q, k, v = _project_qkv(cfg, params, x, h, kh, hd)
    if angles is not None:
        q = rope_mod.apply_rotary(q, angles)
        k = rope_mod.apply_rotary(k, angles)
    out = _chunked_attention(q, k, v, cfg.attn_chunk, cfg.attn_chunk,
                             causal=causal, unroll=cfg.scan_unroll)
    cdt = layers.dt(cfg.compute_dtype)
    b, s, _, _ = out.shape
    return out.reshape(b, s, h * hd).astype(cdt) @ params["wo"].astype(cdt)


def prefill(cfg: ModelConfig, params: dict, x: jax.Array,
            angles: Optional[jax.Array], cache_len: int,
            h: Optional[int] = None, kh: Optional[int] = None):
    """Prefill: causal attention + populate a KV cache of size cache_len."""
    h = h or cfg.effective_n_heads
    kh = kh or cfg.n_kv_heads
    hd = params["wq"].shape[1] // h
    from . import rope as rope_mod

    q, k, v = _project_qkv(cfg, params, x, h, kh, hd)
    if angles is not None:
        q = rope_mod.apply_rotary(q, angles)
        k = rope_mod.apply_rotary(k, angles)
    out = _chunked_attention(q, k, v, cfg.attn_chunk, cfg.attn_chunk, True,
                             unroll=cfg.scan_unroll)
    b, s, _, _ = out.shape
    cdt = layers.dt(cfg.compute_dtype)
    y = out.reshape(b, s, h * hd).astype(cdt) @ params["wo"].astype(cdt)
    pad = cache_len - s
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cache = KVCache(k=kc, v=vc, length=jnp.full((b,), s, jnp.int32))
    return y, cache


def decode_step(cfg: ModelConfig, params: dict, x: jax.Array,
                cache: KVCache, angles: Optional[jax.Array],
                h: Optional[int] = None, kh: Optional[int] = None):
    """One-token decode: x (b, 1, d) against the cache; returns (y, cache').

    New K/V are written at position cache.length; attention masks positions
    >= length+1. Works with a sequence-sharded cache (SP decode): the
    einsum + masked softmax over S lower to partial reductions + collectives
    under GSPMD.
    """
    from . import rope as rope_mod

    h = h or cfg.effective_n_heads
    kh = kh or cfg.n_kv_heads
    hd = params["wq"].shape[1] // h
    q, k_new, v_new = _project_qkv(cfg, params, x, h, kh, hd)
    if angles is not None:
        q = rope_mod.apply_rotary(q, angles)
        k_new = rope_mod.apply_rotary(k_new, angles)
    b = x.shape[0]
    S = cache.k.shape[1]
    # Overwrite the new K/V at per-batch positions (dynamic per-b). An
    # overwrite, not an additive one-hot: the target cell may hold stale
    # nonzero data (e.g. a reused slot's retired cache), which an additive
    # scatter would fold into the new entry.
    hit = jnp.arange(S)[None, :] == cache.length[:, None]          # (b, S)
    k = jnp.where(hit[:, :, None, None], k_new.astype(cache.k.dtype), cache.k)
    v = jnp.where(hit[:, :, None, None], v_new.astype(cache.v.dtype), cache.v)
    g = h // kh
    qg = q.reshape(b, 1, kh, g, hd) * hd**-0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(S)[None, :]  # (1, S)
    valid = pos <= cache.length[:, None]  # (b, S) — includes the new token
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, 1, h * hd)
    cdt = layers.dt(cfg.compute_dtype)
    y = out.astype(cdt) @ params["wo"].astype(cdt)
    new_cache = KVCache(k=k, v=v, length=cache.length + 1)
    return y, new_cache


class PagedKVCache(NamedTuple):
    """View of the block-pooled KV cache (serving memory model).

    Unlike `KVCache`, where row b owns a contiguous (S, kh, hd) region,
    the pool is shared by every sequence: row b's logical positions live
    in the physical blocks its `block_table` row names, in order. Block
    `paged_cache.NULL_BLOCK` (physical 0) is scratch — inactive lanes
    point every table entry at it so their writes never touch live data.
    Allocation/free/backpressure bookkeeping is host-side
    (`serving.paged_cache.PagedCacheManager`); this view is what the
    jitted step consumes. The pools are every layer's, stacked and
    head-major, so a (block, head) tile is `(block_size, hd)` whatever
    the KV-head count; `paged_attend` is told which layer to use.
    """

    k_pool: jax.Array       # (L, n_blocks, kh, block_size, hd)
    v_pool: jax.Array       # (L, n_blocks, kh, block_size, hd)
    block_table: jax.Array  # (b, max_blocks) int32 physical block ids
    length: jax.Array       # (b,) int32 — tokens already written


def _paged_write(pool: jax.Array, new: jax.Array, cache: PagedKVCache,
                 n_valid: jax.Array, layer: jax.Array) -> jax.Array:
    """Scatter `new` (b, t, kh, hd) into layer `layer` of the stacked,
    head-major pool at each row's next `n_valid[b]` logical positions;
    invalid lanes land in NULL_BLOCK."""
    b, t = new.shape[:2]
    block_size = pool.shape[3]
    mb = cache.block_table.shape[1]
    pos = cache.length[:, None] + jnp.arange(t)[None, :]          # (b, t)
    valid = jnp.arange(t)[None, :] < n_valid[:, None]             # (b, t)
    blk = jnp.take_along_axis(
        cache.block_table, jnp.clip(pos // block_size, 0, mb - 1), axis=1)
    blk = jnp.where(valid, blk, 0)   # NULL_BLOCK scratch
    off = jnp.where(valid, pos % block_size, 0)
    # every axis but hd indexed by an array: with a slice between the
    # index arrays XLA's TPU scatter copies the whole donated pool
    heads = jnp.arange(pool.shape[2])[None, None, :]
    return pool.at[layer, blk[..., None], heads, off[..., None]].set(
        new.astype(pool.dtype))


def paged_attend(cfg: ModelConfig, params: dict, x: jax.Array,
                 cache: PagedKVCache, angles: Optional[jax.Array],
                 n_valid: jax.Array,
                 h: Optional[int] = None, kh: Optional[int] = None,
                 paged_kernel: Optional[bool] = None, *,
                 layer: jax.Array):
    """Block-table attention over `t` new positions per row of layer
    `layer` (an int32 scalar).

    x (b, t, d) holds each row's next `n_valid[b] <= t` tokens starting
    at logical position `cache.length[b]` (t == 1 is the decode step,
    t == prefill_chunk is one chunked-prefill piece; same trace, two
    compiled shapes). New K/V are scattered into the shared pools at
    those positions, then the row's full logical window is attended with
    a causal + true-length mask — position j is visible to query i iff
    j <= length + i. Returns (y (b, t, d), k_pool', v_pool'); rows
    beyond n_valid produce garbage outputs the caller must ignore (the
    pools stay clean outside the scratch block).

    This layer's K/V are read and written at `pool[layer]` of the
    stacked pools, the other layers come back untouched, and nothing
    copies a layer out of the stack, so a layer scan that carries the
    pools updates them in place.

    Two dispatch paths, selected by `paged_kernel` (falling back to
    `cfg.paged_kernel`):

    * gather reference (default): scatter via `_paged_write`, then
      gather the full window — (b, max_blocks * block_size, kh, hd) of
      activation per step. Paged HBM *residency* with dense-window
      compute; kept as the parity oracle.
    * fused kernel: `kernels.paged_attend.paged_attend_fused` walks the
      block table inside a flash-decoding Pallas kernel (split-KV
      partials + combine) with the new-token scatter folded into the
      same launch, so the dense window is never materialized.
    """
    from . import rope as rope_mod

    h = h or cfg.effective_n_heads
    kh = kh or cfg.n_kv_heads
    hd = params["wq"].shape[1] // h
    q, k_new, v_new = _project_qkv(cfg, params, x, h, kh, hd)
    if angles is not None:
        q = rope_mod.apply_rotary(q, angles)
        k_new = rope_mod.apply_rotary(k_new, angles)
    b, t = x.shape[:2]
    cdt = layers.dt(cfg.compute_dtype)
    use_kernel = cfg.paged_kernel if paged_kernel is None else paged_kernel
    if use_kernel:
        from repro.kernels.paged_attend import paged_attend_fused

        out, k_pool, v_pool = paged_attend_fused(
            q, k_new, v_new, cache.k_pool, cache.v_pool,
            cache.block_table, cache.length, n_valid, layer=layer)
        y = out.reshape(b, t, h * hd).astype(cdt) @ params["wo"].astype(cdt)
        return y, k_pool, v_pool
    k_pool = _paged_write(cache.k_pool, k_new, cache, n_valid, layer)
    v_pool = _paged_write(cache.v_pool, v_new, cache, n_valid, layer)
    block_size = k_pool.shape[3]
    mb = cache.block_table.shape[1]
    S = mb * block_size
    # each row's window, block by block: (b, mb, kh, bs, hd)
    k = k_pool[layer, cache.block_table]
    v = v_pool[layer, cache.block_table]
    g = h // kh
    qg = q.reshape(b, t, kh, g, hd) * hd**-0.5
    s = jnp.einsum("bqkgd,bmksd->bkgqms", qg, k,
                   preferred_element_type=jnp.float32).reshape(
                       b, kh, g, t, S)
    q_pos = cache.length[:, None] + jnp.arange(t)[None, :]        # (b, t)
    kv_pos = jnp.arange(S)[None, None, :]                         # (1, 1, S)
    visible = kv_pos <= q_pos[:, :, None]                         # (b, t, S)
    s = jnp.where(visible[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    p = p.reshape(b, kh, g, t, mb, block_size)
    out = jnp.einsum("bkgqms,bmksd->bqkgd", p, v).reshape(b, t, h * hd)
    y = out.astype(cdt) @ params["wo"].astype(cdt)
    return y, k_pool, v_pool


def cross_attention(cfg: ModelConfig, params: dict, x: jax.Array,
                    kv_src: jax.Array, h: int, kh: int) -> jax.Array:
    """Encoder-decoder cross attention (whisper): no RoPE, no mask."""
    hd = params["wq"].shape[1] // h
    cdt = layers.dt(cfg.compute_dtype)
    b, s, _ = x.shape
    q = (x.astype(cdt) @ params["wq"].astype(cdt)).reshape(b, s, h, hd)
    k = (kv_src.astype(cdt) @ params["wk"].astype(cdt)).reshape(b, -1, kh, hd)
    v = (kv_src.astype(cdt) @ params["wv"].astype(cdt)).reshape(b, -1, kh, hd)
    out = _chunked_attention(q, k, v, cfg.attn_chunk, cfg.attn_chunk,
                             causal=False, unroll=cfg.scan_unroll)
    return out.reshape(b, s, h * hd).astype(cdt) @ params["wo"].astype(cdt)
