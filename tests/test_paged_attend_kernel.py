"""Fused Pallas paged-attention kernel vs the gather reference path.

The gather path in `models.attention.paged_attend` is the parity oracle:
the kernel (`kernels.paged_attend.paged_attend_fused`) must reproduce its
outputs and pool writes within tight fp32 tolerance across decode (t=1)
and chunked-prefill (t>1) shapes, including the block-boundary edge
cases (lengths at block edges, inactive lanes, CoW-shared partial
blocks, all-NULL table tails). Pool comparisons exclude physical block 0
(NULL_BLOCK): it is scratch with unspecified content on both paths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.paged_attend import paged_attend_fused
from repro.models import attention as A
from repro.models import build_model, rope
from repro.serving.continuous_batching import ContinuousBatchingEngine

TOL = dict(rtol=2e-5, atol=1e-5)


def _mini_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                head_dim=8, attn_chunk=16, compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _mk_case(rng, cfg, b, t, bs, mb, lengths, n_valid, tables=None,
             null_garbage=False, dtype=np.float32):
    """A PagedKVCache + inputs; row r owns blocks 1 + r*mb .. unless an
    explicit `tables` layout (for shared/CoW cases) is given."""
    kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    n_blocks = b * mb + 2
    kp = rng.normal(size=(n_blocks, bs, kh, hd)).astype(dtype)
    vp = rng.normal(size=(n_blocks, bs, kh, hd)).astype(dtype)
    if null_garbage:  # prove NULL_BLOCK content never leaks into outputs
        kp[0] = 1e6
        vp[0] = -1e6
    if tables is None:
        tables = np.zeros((b, mb), np.int32)
        for r in range(b):
            need = -(-int(lengths[r] + t) // bs)
            for i in range(min(need, mb)):
                tables[r, i] = 1 + r * mb + i
    cache = A.PagedKVCache(
        k_pool=jnp.asarray(kp), v_pool=jnp.asarray(vp),
        block_table=jnp.asarray(tables, jnp.int32),
        length=jnp.asarray(lengths, jnp.int32))
    x = jnp.asarray(rng.normal(size=(b, t, cfg.d_model)).astype(np.float32))
    pos = cache.length[:, None] + jnp.arange(t)[None, :]
    angles = rope.rope_angles(pos, hd, cfg.rope_theta)
    return x, cache, angles, jnp.asarray(n_valid, jnp.int32)


def _both_paths(cfg, params, x, cache, angles, nv):
    yg, kg, vg = A.paged_attend(cfg, params, x, cache, angles, nv,
                                paged_kernel=False)
    yk, kk, vk = A.paged_attend(cfg, params, x, cache, angles, nv,
                                paged_kernel=True)
    return (yg, kg, vg), (yk, kk, vk)


def _assert_parity(gather, kernel, nv, **tol):
    tol = tol or TOL
    (yg, kg, vg), (yk, kk, vk) = gather, kernel
    rows = np.asarray(nv) > 0
    np.testing.assert_allclose(np.asarray(yk)[rows], np.asarray(yg)[rows],
                               **tol)
    # every pool block except the NULL scratch must match exactly: the
    # kernel's fused scatter writes the same cells the reference does
    np.testing.assert_array_equal(np.asarray(kk)[1:], np.asarray(kg)[1:])
    np.testing.assert_array_equal(np.asarray(vk)[1:], np.asarray(vg)[1:])


@pytest.fixture(scope="module")
def mini():
    cfg = _mini_cfg()
    params = A.init_attention(cfg, jax.random.key(0))
    return cfg, params


# ------------------------------------------------------------- core parity
@pytest.mark.parametrize("chunk_blocks", [None, 1, 2])
def test_parity_decode_t1(rng, mini, chunk_blocks):
    cfg, params = mini
    x, cache, angles, nv = _mk_case(rng, cfg, b=4, t=1, bs=4, mb=6,
                                    lengths=[5, 8, 0, 23], n_valid=[1, 1, 0, 1])
    yg, kg, vg = A.paged_attend(cfg, params, x, cache, angles, nv,
                                paged_kernel=False)
    q, kn, vn = A._project_qkv(cfg, params, x, 4, 2, 8)
    q = rope.apply_rotary(q, angles)
    kn = rope.apply_rotary(kn, angles)
    out, kk, vk = paged_attend_fused(
        q, kn, vn, cache.k_pool, cache.v_pool, cache.block_table,
        cache.length, nv, chunk_blocks=chunk_blocks)
    yk = out.reshape(4, 1, -1) @ params["wo"]
    rows = np.asarray(nv) > 0
    np.testing.assert_allclose(np.asarray(yk)[rows], np.asarray(yg)[rows],
                               **TOL)
    np.testing.assert_array_equal(np.asarray(kk)[1:], np.asarray(kg)[1:])
    np.testing.assert_array_equal(np.asarray(vk)[1:], np.asarray(vg)[1:])


@pytest.mark.parametrize("t,lengths,n_valid", [
    (8, [2, 0], [8, 5]),          # chunked prefill, mixed fill
    (8, [0, 0], [8, 8]),          # first chunk from empty
    (5, [9, 3], [5, 2]),          # odd t, partial validity
])
def test_parity_chunked_prefill(rng, mini, t, lengths, n_valid):
    cfg, params = mini
    x, cache, angles, nv = _mk_case(rng, cfg, b=2, t=t, bs=4, mb=8,
                                    lengths=lengths, n_valid=n_valid)
    _assert_parity(*_both_paths(cfg, params, x, cache, angles, nv), nv)


def test_parity_bf16_pools(rng):
    cfg = _mini_cfg(compute_dtype="bfloat16")
    params = A.init_attention(cfg, jax.random.key(1))
    x, cache, angles, nv = _mk_case(rng, cfg, b=2, t=1, bs=4, mb=4,
                                    lengths=[5, 9], n_valid=[1, 1],
                                    dtype=np.dtype(jnp.bfloat16.dtype))
    g, k = _both_paths(cfg, params, x, cache, angles, nv)
    (yg, kg, vg), (yk, kk, vk) = g, k
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yg, np.float32), rtol=3e-2,
                               atol=3e-2)
    np.testing.assert_array_equal(np.asarray(kk, np.float32)[1:],
                                  np.asarray(kg, np.float32)[1:])


# --------------------------------------- block-boundary edge-case suite
def test_edge_length_exactly_at_block_boundary(rng, mini):
    """Decode whose write opens a fresh block (length % bs == 0), and one
    whose window ends exactly at a block edge."""
    cfg, params = mini
    x, cache, angles, nv = _mk_case(rng, cfg, b=3, t=1, bs=4, mb=6,
                                    lengths=[4, 8, 12], n_valid=[1, 1, 1])
    _assert_parity(*_both_paths(cfg, params, x, cache, angles, nv), nv)


def test_edge_prefill_fills_block_exactly(rng, mini):
    """Chunked prefill whose last token lands on the final slot of a
    block (length + n_valid == multiple of bs)."""
    cfg, params = mini
    x, cache, angles, nv = _mk_case(rng, cfg, b=2, t=6, bs=4, mb=6,
                                    lengths=[2, 6], n_valid=[6, 6])
    _assert_parity(*_both_paths(cfg, params, x, cache, angles, nv), nv)


def test_edge_inactive_lanes(rng, mini):
    """n_valid < b: inactive lanes (all-NULL table, length 0) must not
    disturb live rows' outputs or pools."""
    cfg, params = mini
    tables = np.zeros((4, 5), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[2, :2] = [4, 5]
    x, cache, angles, nv = _mk_case(rng, cfg, b=4, t=1, bs=4, mb=5,
                                    lengths=[9, 0, 4, 0],
                                    n_valid=[1, 0, 1, 0], tables=tables)
    _assert_parity(*_both_paths(cfg, params, x, cache, angles, nv), nv)


def test_edge_prefill_crosses_cow_shared_partial_block(rng, mini):
    """Two rows share full prefix blocks; the writer's table then points
    at its private CoW copy of the shared partial block, and its prefill
    chunk crosses from that copy into the next owned block. The still-
    shared blocks must come through bit-identical on both paths."""
    cfg, params = mini
    bs, mb = 4, 6
    # rows share block 1 (full); row 0 continues in its CoW copy (5) of
    # block 2, then its own block 6; row 1 still points at block 2.
    tables = np.zeros((2, mb), np.int32)
    tables[0, :3] = [1, 5, 6]
    tables[1, :3] = [1, 2, 7]
    x, cache, angles, nv = _mk_case(rng, cfg, b=2, t=4, bs=bs, mb=mb,
                                    lengths=[bs + 2, 2 * bs],
                                    n_valid=[4, 1], tables=tables)
    # seed the CoW copy with the shared block's content, as prepare_write
    # would have
    cache = cache._replace(
        k_pool=cache.k_pool.at[5].set(cache.k_pool[2]),
        v_pool=cache.v_pool.at[5].set(cache.v_pool[2]))
    shared_k = np.asarray(cache.k_pool)[[1, 2]]
    g, k = _both_paths(cfg, params, x, cache, angles, nv)
    _assert_parity(g, k, nv)
    for _, kp, _vp in (g, k):
        np.testing.assert_array_equal(np.asarray(kp)[[1, 2]], shared_k)


def test_edge_null_tail_garbage_masked(rng, mini):
    """A table row whose tail padding is all NULL_BLOCK, with the scratch
    block poisoned: the garbage must never leak into outputs (it is
    masked by the true-length window on both paths)."""
    cfg, params = mini
    x, cache, angles, nv = _mk_case(rng, cfg, b=2, t=1, bs=4, mb=12,
                                    lengths=[5, 2], n_valid=[1, 1],
                                    null_garbage=True)
    g, k = _both_paths(cfg, params, x, cache, angles, nv)
    _assert_parity(g, k, nv)
    assert np.all(np.abs(np.asarray(k[0])) < 1e4)


# ------------------------------------------------ stacked-pool (L, ...) form
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("t,lengths,n_valid,mb", [
    (1, [5, 8, 0, 23], [1, 1, 0, 1], 7),   # decode, several rows
    (32, [6], [32], 12),                   # one prefill chunk
], ids=["decode", "prefill"])
def test_stacked_pools_match_one_layer_form(rng, mini, layer, t, lengths,
                                            n_valid, mb):
    """The kernel and the gather path given the layer stack and a layer
    index attend and write exactly what they do given that layer alone,
    and leave every other layer bit-identical."""
    cfg, params = mini
    b, n_layers = len(lengths), 3
    x, cache, angles, nv = _mk_case(rng, cfg, b=b, t=t, bs=4, mb=mb,
                                    lengths=lengths, n_valid=n_valid)
    shape = (n_layers,) + cache.k_pool.shape
    k_stack = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    v_stack = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    k_stack = k_stack.at[layer].set(cache.k_pool)
    v_stack = v_stack.at[layer].set(cache.v_pool)
    stacked = cache._replace(k_pool=k_stack, v_pool=v_stack)
    li = jnp.int32(layer)
    others = [i for i in range(n_layers) if i != layer]

    q, kn, vn = A._project_qkv(cfg, params, x, cfg.n_heads, cfg.n_kv_heads,
                               cfg.resolved_head_dim)
    q = rope.apply_rotary(q, angles)
    kn = rope.apply_rotary(kn, angles)
    args = (cache.block_table, cache.length, nv)
    out4, k4, v4 = paged_attend_fused(q, kn, vn, cache.k_pool, cache.v_pool,
                                      *args)
    out5, k5, v5 = paged_attend_fused(q, kn, vn, k_stack, v_stack, *args,
                                      layer=li)
    np.testing.assert_array_equal(np.asarray(out5), np.asarray(out4))
    np.testing.assert_array_equal(np.asarray(k5[layer]), np.asarray(k4))
    np.testing.assert_array_equal(np.asarray(v5[layer]), np.asarray(v4))

    gather = A.paged_attend(cfg, params, x, cache, angles, nv,
                            paged_kernel=False)
    kernel = (out5.reshape(b, t, -1) @ params["wo"], k5[layer], v5[layer])
    _assert_parity(gather, kernel, nv)
    yg5, kg5, vg5 = A.paged_attend(cfg, params, x, stacked, angles, nv,
                                   paged_kernel=False, layer=li)
    np.testing.assert_array_equal(np.asarray(yg5), np.asarray(gather[0]))
    np.testing.assert_array_equal(np.asarray(kg5[layer])[1:],
                                  np.asarray(gather[1])[1:])
    np.testing.assert_array_equal(np.asarray(vg5[layer])[1:],
                                  np.asarray(gather[2])[1:])
    for new_k, new_v in ((k5, v5), (kg5, vg5)):
        np.testing.assert_array_equal(np.asarray(new_k)[others],
                                      np.asarray(k_stack)[others])
        np.testing.assert_array_equal(np.asarray(new_v)[others],
                                      np.asarray(v_stack)[others])


# --------------------------------------------------- engine-level parity
def test_engine_greedy_parity_kernel_vs_gather():
    """ContinuousBatchingEngine(paged_kernel=True) emits token-for-token
    what the gather engine emits, on a real model with chunked prefill
    and staggered admission."""
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b", smoke=True),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    lens = [3, 17, 6, 24, 2]
    max_news = [5, 3, 4, 3, 6]
    reqs = [(rng.integers(0, cfg.vocab_size, size=n), m)
            for n, m in zip(lens, max_news)]

    def run(paged_kernel):
        eng = ContinuousBatchingEngine(
            model, params, n_slots=2, cache_len=32, paged=True,
            block_size=8, prefill_chunk=8, paged_kernel=paged_kernel)
        tickets = [eng.submit(p, max_new_tokens=m) for p, m in reqs[:3]]
        eng.step()  # staggered admission
        tickets += [eng.submit(p, max_new_tokens=m) for p, m in reqs[3:]]
        eng.run_until_drained()
        return [np.asarray(t.result()) for t in tickets], eng.stats()

    gather_outs, gstats = run(False)
    kernel_outs, kstats = run(True)
    for a, b in zip(gather_outs, kernel_outs):
        assert np.array_equal(a, b)
    assert kstats["paged_kernel"] is True
    assert gstats["paged_kernel"] is False
    assert kstats["pool"]["free_blocks"] == kstats["pool"]["n_usable_blocks"]


def test_engine_paged_kernel_requires_paged():
    cfg = _mini_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="paged_kernel"):
        ContinuousBatchingEngine(model, params, n_slots=1, cache_len=16,
                                 paged_kernel=True)
