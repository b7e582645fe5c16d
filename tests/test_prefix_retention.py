"""Tiered prefix retention (PR 7): LRU pinning, pressure eviction and
the host-RAM tier — allocator behaviour plus ContinuousBatchingEngine
integration.

The allocator invariants (pins in the refcount ledger, budget ceilings,
host-byte accounting) are fuzzed in tests/test_paged_cache.py; this file
pins down the *semantics*: retained prefixes survive their publisher and
serve suffix-only hits, eviction is LRU-ordered and attach-touched,
pinned blocks are never handed to new reservations, retained entries
yield to pool pressure BEFORE live sequences feel backpressure, and a
host-tier round trip restores the exact KV bytes it offloaded (checksum
script model for engine semantics, a real fp32 dense model for the
bit-identical acceptance property).
"""

import dataclasses
import hashlib
import itertools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, supports_paged_kv
from repro.serving import (
    ContinuousBatchingEngine,
    EngineConfig,
    GenerationEngine,
    OutOfBlocks,
    PagedCacheManager,
)


# --------------------------------------------------------- allocator helpers
def _pcm(n_blocks=9, block_size=4, width=6, retain=0, host=0, store=None):
    """Pool with an engine-stand-in host store (key -> nbytes)."""
    if host and store is None:
        store = {}

    def on_evict(key, blocks, n_tokens):
        store[key] = 4 * n_tokens
        return store[key]

    def on_swapin(key, blocks, n_tokens):
        store.pop(key)

    def on_host_drop(key):
        store.pop(key)

    return PagedCacheManager(
        n_blocks, block_size, width,
        retain_blocks=retain, host_blocks=host,
        on_evict=on_evict if host else None,
        on_swapin=on_swapin if host else None,
        on_host_drop=on_host_drop if host else None,
    )


def _publish(pcm, key, seq, n_tokens):
    """Reserve + materialize + publish + retire a publisher in one go."""
    pcm.reserve(seq, n_tokens)
    pcm.ensure(seq, n_tokens)
    assert pcm.register_prefix(key, seq, n_tokens)
    pcm.free(seq)


# ------------------------------------------------------- allocator semantics
def test_retained_prefix_survives_publisher_and_serves_hit():
    pcm = _pcm(retain=4)
    _publish(pcm, "ctx", "own", 8)  # 2 full blocks, publisher retires
    assert pcm.has_prefix("ctx") and pcm.retained_keys() == ["ctx"]
    assert pcm.stats()["n_registry_invalidations"] == 0
    # a later identical prefix attaches suffix-only
    assert pcm.reserve("att", 12, prefix_key="ctx") == 1
    assert pcm.shared_tokens("att") == 8
    st = pcm.stats()
    assert st["n_device_hits"] == 1 and st["n_host_hits"] == 0
    assert st["device_hit_rate"] == 1.0 and st["prefix_hit_rate"] == 1.0
    pcm.free("att")
    assert pcm.has_prefix("ctx")  # the pin keeps the entry alive
    assert pcm.clear_retained() == 1
    assert not pcm.has_prefix("ctx")
    assert pcm.stats()["free_blocks"] == pcm.n_usable_blocks


def test_without_retention_registry_entry_dies_with_publisher():
    pcm = _pcm(retain=0)
    _publish(pcm, "ctx", "own", 8)
    assert not pcm.has_prefix("ctx")  # PR 5 non-owning semantics
    assert pcm.stats()["n_registry_invalidations"] == 1


def test_lru_eviction_order_and_attach_touch():
    # 16 usable blocks; budget fits exactly two 4-block entries
    pcm = _pcm(n_blocks=17, block_size=4, width=8, retain=8)
    _publish(pcm, "k1", "a", 16)
    _publish(pcm, "k2", "b", 16)
    assert pcm.retained_keys() == ["k1", "k2"]
    # a third publication budget-evicts the coldest (k1)
    _publish(pcm, "k3", "c", 16)
    assert pcm.retained_keys() == ["k2", "k3"]
    assert pcm.stats()["n_evictions"] == 1
    # an attach touches k2 -> k3 becomes the LRU victim
    pcm.reserve("att", 20, prefix_key="k2")
    assert pcm.retained_keys() == ["k3", "k2"]
    _publish(pcm, "k4", "d", 16)
    assert pcm.retained_keys() == ["k2", "k4"]


def test_pinned_blocks_never_handed_out():
    pcm = _pcm(n_blocks=9, block_size=4, width=6, retain=2)
    _publish(pcm, "ctx", "own", 8)
    pinned = set(pcm._prefix_index["ctx"].blocks)
    # fill most of the remaining pool; nothing may land on a pinned block
    pcm.reserve("a", 16)
    pcm.ensure("a", 16)
    pcm.reserve("b", 8)
    pcm.ensure("b", 8)
    assert not pinned & set(pcm.allocated("a") + pcm.allocated("b"))
    assert pcm.retained_keys() == ["ctx"]  # still resident under load


def test_eviction_yields_before_backpressure():
    """A reservation that fits only if retained entries are reclaimed
    must be admitted (retention is cache, not capacity) — and the same
    reservation without retention is genuine backpressure."""
    pcm = _pcm(n_blocks=9, block_size=4, width=8, retain=4)
    _publish(pcm, "ctx", "own", 16)  # 4 blocks pinned, 4 free
    assert pcm.can_reserve(32)  # needs all 8: reclaims the pinned entry
    assert pcm.reserve("big", 32) == 8
    st = pcm.stats()
    assert st["n_evictions"] == 1 and st["n_oob_events"] == 0
    assert not pcm.retained_keys()
    # control: real pool pressure (no retained entries) still backpressures
    with pytest.raises(OutOfBlocks):
        pcm.reserve("more", 4)
    assert pcm.stats()["n_oob_events"] == 1


def test_eviction_offloads_to_host_and_swapin_round_trips():
    store = {}
    pcm = _pcm(n_blocks=9, block_size=4, width=8, retain=2, host=4,
               store=store)
    _publish(pcm, "ctx", "own", 6)  # 2 blocks, partial last (6 % 4)
    _publish(pcm, "hot", "own2", 8)  # budget-evicts ctx -> host tier
    assert pcm.retained_keys() == ["hot"] and pcm.host_keys() == ["ctx"]
    assert store == {"ctx": 24} and pcm.host_bytes == 24
    # a later request for ctx swaps it back in (host hit, suffix-only:
    # 3 blocks - 2 shared = 1 budgeted, plus an unreturned CoW credit)
    assert pcm.reserve("att", 10, prefix_key="ctx") == 1
    assert pcm.shared_tokens("att") == 6
    st = pcm.stats()
    assert st["n_host_hits"] == 1 and st["n_device_hits"] == 0
    assert st["host_hit_rate"] == 1.0
    # ctx's bytes were consumed by the swap-in; the displaced hot entry
    # (LRU-evicted for retained-budget room) took its place host-side
    assert pcm.retained_keys() == ["ctx"] and pcm.host_keys() == ["hot"]
    assert store == {"hot": 32} and pcm.host_bytes == 32


def test_host_budget_evicts_lru_host_entry():
    store = {}
    pcm = _pcm(n_blocks=17, block_size=4, width=8, retain=2, host=2,
               store=store)
    _publish(pcm, "k1", "a", 8)
    _publish(pcm, "k2", "b", 8)  # k1 -> host
    _publish(pcm, "k3", "c", 8)  # k2 -> host, k1 dropped (budget 2 blocks)
    assert pcm.host_keys() == ["k2"] and set(store) == {"k2"}
    assert pcm.reserve("att", 12, prefix_key="k1") == 3  # k1 is a plain miss
    assert pcm.stats()["n_host_hits"] == 0


def test_host_hit_falls_back_to_miss_without_headroom():
    """can_reserve prices a host hit as a plain miss; reserve must not
    promise more: when the pool lacks swap-in + attach headroom the
    request proceeds as a miss instead of raising post-gate."""
    store = {}
    pcm = _pcm(n_blocks=9, block_size=4, width=8, retain=2, host=4,
               store=store)
    _publish(pcm, "ctx", "own", 6)
    _publish(pcm, "hot", "own2", 8)  # ctx -> host (2 blocks + 24 bytes)
    pcm.reserve("fill1", 28)  # 7 of 8 blocks: hot is pressure-evicted too
    pcm.free("fill1")
    assert not pcm.retained_keys() and pcm.host_keys() == ["ctx", "hot"]
    pcm.reserve("fill2", 20)  # 5 blocks: 3 free remain
    # a 12-token attach is a 3-block miss, but the swap-in path needs
    # n + credit = 4 free up front — it must degrade, not raise
    assert pcm.can_reserve(12, prefix_key="ctx")
    assert pcm.reserve("att", 12, prefix_key="ctx") == 3
    assert pcm.shared_tokens("att") == 0
    st = pcm.stats()
    assert st["n_host_hits"] == 0 and st["n_prefix_misses"] >= 1
    assert pcm.host_keys() == ["ctx", "hot"]  # the host copies untouched


# ----------------------------------------------- engine: checksum script model
class ChecksumScriptModel:
    """Next token = (sum of every token seen so far) % vocab — any KV
    corruption anywhere in the window changes the output immediately."""

    def __init__(self, vocab: int = 97):
        self.cfg = SimpleNamespace(vocab_size=vocab)
        self.vocab = vocab

    def init_caches(self, batch, cache_len, prefix_len):
        return {
            "sum": jnp.zeros((batch,), jnp.int32),
            "length": jnp.full((batch,), prefix_len, jnp.int32),
        }

    def decode_step(self, params, caches, token):
        s = caches["sum"] + token[:, 0]
        logits = jax.nn.one_hot(s % self.vocab, self.vocab, dtype=jnp.float32)
        return logits, {"sum": s, "length": caches["length"] + 1}


class ChecksumPagedScriptModel(ChecksumScriptModel):
    """Checksum model over a REAL block-pooled store (redeclared from
    test_prefix_sharing to keep this module import-independent)."""

    def init_paged_caches(self, n_blocks, block_size):
        return jnp.zeros((n_blocks, block_size), jnp.int32)

    def paged_step(self, params, pools, tables, lengths, tokens, n_valid):
        b, t = tokens.shape
        bs = pools.shape[1]
        mb = tables.shape[1]
        pos = lengths[:, None] + jnp.arange(t)[None, :]
        valid = jnp.arange(t)[None, :] < n_valid[:, None]
        blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, mb - 1), axis=1)
        blk = jnp.where(valid, blk, 0)
        off = jnp.where(valid, pos % bs, 0)
        pools = pools.at[blk, off].set(tokens)
        window = pools[tables]
        wpos = (jnp.arange(mb)[:, None] * bs + jnp.arange(bs)[None, :])[None]
        mask = wpos < (lengths + jnp.maximum(n_valid, 1))[:, None, None]
        total = jnp.sum(jnp.where(mask, window, 0), axis=(1, 2))
        logits = jax.nn.one_hot(
            total % self.vocab, self.vocab, dtype=jnp.float32)
        return logits, pools


def _baseline(prompt, max_new, vocab=97):
    out = GenerationEngine(ChecksumScriptModel(vocab), {}).generate(
        jnp.asarray(prompt, jnp.int32)[None],
        max_new_tokens=max_new,
        cache_len=64,
    )
    return np.asarray(out)[0]


def _retained_engine(*, retain, host=0, n_blocks=9, clock=None):
    cfg = EngineConfig(
        n_slots=2, cache_len=48, paged=True, block_size=8,
        n_blocks=n_blocks, prefill_chunk=8, prefix_sharing=True,
        retain_blocks=retain, host_blocks=host)
    kw = {"clock": clock} if clock is not None else {}
    return ContinuousBatchingEngine(
        ChecksumPagedScriptModel(vocab=97), {}, cfg, **kw)


def test_engine_hit_after_publisher_retires():
    """The PR 7 headline: a prefix published by a request that has fully
    retired still serves a suffix-only device hit."""
    ctx = list(range(1, 11))  # 10 tokens: partial second block
    eng = _retained_engine(retain=2)
    pub = eng.submit(ctx + [40, 41], max_new_tokens=3, prefix_len=10)
    eng.run_until_drained()  # publisher is gone before the attacher arrives
    assert np.array_equal(pub.result(), _baseline(ctx + [40, 41], 3))
    chunks = eng.stats()["n_prefill_chunks"]
    att = eng.submit(ctx + [60, 61], max_new_tokens=3, prefix_len=10)
    eng.run_until_drained()
    assert np.array_equal(att.result(), _baseline(ctx + [60, 61], 3))
    st = eng.stats()["pool"]
    assert st["n_device_hits"] == 1 and st["n_host_hits"] == 0
    assert eng.stats()["n_prefill_chunks"] == chunks + 1  # suffix only
    assert eng.clear_prefix_cache() == 1
    assert eng.stats()["pool"]["free_blocks"] == st["n_usable_blocks"]


def test_engine_host_round_trip_checksum_parity():
    """Retain -> pressure-evict to host -> swap back in on a later hit;
    the checksum model proves the restored KV window is exact."""
    ctx = list(range(1, 18))  # 17 tokens: 3 blocks pinned (partial third)
    eng = _retained_engine(retain=3, host=3)
    pub = eng.submit(ctx + [91, 92], max_new_tokens=3, prefix_len=17)
    eng.run_until_drained()  # 3 blocks stay pinned after the publisher
    assert np.array_equal(pub.result(), _baseline(ctx + [91, 92], 3))
    big = eng.submit(list(range(50, 90)), max_new_tokens=4)  # needs 6 of 8
    eng.run_until_drained()
    assert np.array_equal(big.result(), _baseline(list(range(50, 90)), 4))
    st = eng.stats()["pool"]
    assert st["n_evictions"] == 1 and st["n_host_entries"] == 1
    assert st["host_bytes"] > 0
    att = eng.submit(ctx + [60, 61], max_new_tokens=3, prefix_len=17)
    eng.run_until_drained()
    assert np.array_equal(att.result(), _baseline(ctx + [60, 61], 3))
    st = eng.stats()["pool"]
    assert st["n_host_hits"] == 1 and st["host_bytes"] == 0
    assert not eng._host_kv  # saved bytes consumed by the swap-in
    assert eng.clear_prefix_cache() == 1
    st = eng.stats()["pool"]
    assert st["free_blocks"] == st["n_usable_blocks"]


def test_engine_zipf_fake_clock_retention_lifts_hit_rate():
    """Sequential Zipf-shared-context traffic on a fake clock: with a
    retention budget the repeated contexts hit across publisher
    lifetimes; without one (PR 5 semantics) every arrival is a miss."""
    rng = np.random.default_rng(3)
    ctxs = [list(rng.integers(1, 90, size=10)) for _ in range(6)]
    weights = np.array([1 / (i + 1) ** 1.5 for i in range(6)])
    picks = rng.choice(6, size=24, p=weights / weights.sum())

    def run(retain):
        tick = itertools.count()
        eng = _retained_engine(
            retain=retain, n_blocks=17,
            clock=lambda: next(tick) * 1e-3)
        for i in picks:
            sfx = [90 + int(i), 91]
            t = eng.submit(ctxs[i] + sfx, max_new_tokens=2, prefix_len=10)
            eng.run_until_drained()  # publisher retired before the next
            assert np.array_equal(t.result(), _baseline(ctxs[i] + sfx, 2))
        return eng.stats()["pool"]

    cold = run(retain=0)
    warm = run(retain=6)  # room for ~3 of the 6 two-block contexts
    assert cold["n_prefix_hits"] == 0
    assert warm["n_device_hits"] >= 8  # the hot contexts stay resident
    assert warm["n_evictions"] >= 1  # the tail churns through the LRU
    assert warm["prefix_hit_rate"] > cold["prefix_hit_rate"]


# --------------------------------------- engine: real-model bit-identical KV
def test_host_round_trip_bit_identical_fp32_real_model():
    """Acceptance: on a real dense model at fp32, the KV bytes gathered
    after a host-tier swap-in equal the bytes offloaded at eviction,
    bit for bit, and the attacher's greedy output matches per-query
    generate."""
    cfg = dataclasses.replace(
        get_config("phi4-mini-3.8b", smoke=True), compute_dtype="float32")
    model = build_model(cfg)
    assert supports_paged_kv(model)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(9)
    ctx = rng.integers(0, cfg.vocab_size, size=19).astype(np.int32)
    pub_prompt = np.concatenate([ctx, rng.integers(0, cfg.vocab_size, 5)])
    att_prompt = np.concatenate([ctx, rng.integers(0, cfg.vocab_size, 4)])
    eng = ContinuousBatchingEngine(
        model, params,
        EngineConfig(n_slots=2, cache_len=48, paged=True, block_size=8,
                     n_blocks=9, prefill_chunk=8, prefix_sharing=True,
                     retain_blocks=3, host_blocks=3))
    eng.submit(pub_prompt, max_new_tokens=3, prefix_len=19)
    eng.run_until_drained()
    key = hashlib.sha1(np.asarray(ctx, np.int32).tobytes()).hexdigest()
    entry = eng._pcm._prefix_index[key]
    axes = eng._pool_block_axes
    idx = jnp.asarray(list(entry.blocks), jnp.int32)
    before = [np.asarray(jnp.take(leaf, idx, axis=ax))
              for leaf, ax in zip(jax.tree_util.tree_leaves(eng._pools), axes)]
    # pressure: a 5-block request against 4 free blocks evicts ctx to host
    eng.submit(rng.integers(0, cfg.vocab_size, 36), max_new_tokens=4)
    eng.run_until_drained()
    st = eng.stats()["pool"]
    assert st["n_evictions"] == 1 and st["n_host_entries"] == 1
    att = eng.submit(att_prompt, max_new_tokens=4, prefix_len=19)
    eng.run_until_drained()
    assert eng.stats()["pool"]["n_host_hits"] == 1
    entry = eng._pcm._prefix_index[key]  # fresh blocks after the swap-in
    idx = jnp.asarray(list(entry.blocks), jnp.int32)
    after = [np.asarray(jnp.take(leaf, idx, axis=ax))
             for leaf, ax in zip(jax.tree_util.tree_leaves(eng._pools), axes)]
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    ref = GenerationEngine(model, params).generate(
        jnp.asarray(att_prompt, jnp.int32)[None],
        max_new_tokens=4, cache_len=48)
    assert np.array_equal(att.result(), np.asarray(ref)[0])


# ------------------------------------------ engine: donated pools stay safe
def test_donated_pools_with_cow_and_swapin_match_unshared_run():
    """Every program that takes the pools (the step, the CoW block copy,
    the host-tier swap-in write) donates them. Copy-on-write and a
    swap-in interleaved with donated steps give the tokens of an
    unshared, non-retaining engine, and every pool handed to a program
    is consumed there, never read again."""
    cfg = dataclasses.replace(
        get_config("phi4-mini-3.8b", smoke=True), compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(5)
    ctx = rng.integers(0, cfg.vocab_size, size=19)  # partial third block

    def after_ctx(n):
        return np.concatenate([ctx, rng.integers(0, cfg.vocab_size, n)])

    pub, att, late = after_ctx(5), after_ctx(4), after_ctx(3)
    filler = rng.integers(0, cfg.vocab_size, 36)

    def run(shared):
        tiers = dict(prefix_sharing=True, retain_blocks=3,
                     host_blocks=3) if shared else {}
        eng = ContinuousBatchingEngine(model, params, EngineConfig(
            n_slots=2, cache_len=48, paged=True, block_size=8, n_blocks=9,
            prefill_chunk=8, **tiers))
        handed = []
        for name, at in (("_paged_step", 1), ("_copy_block", 0),
                         ("_write_block", 0)):
            def call(*a, _inner=getattr(eng, name), _name=name, _at=at):
                handed.append((_name, jax.tree_util.tree_leaves(a[_at])))
                return _inner(*a)
            setattr(eng, name, call)
        first = eng.submit(pub, max_new_tokens=6, prefix_len=19)
        for _ in range(3):  # 3 chunks: published, still decoding
            eng.step()
        tickets = [first, eng.submit(att, max_new_tokens=4, prefix_len=19)]
        eng.run_until_drained()
        # 5 blocks against 4 free: evicts the retained ctx to the host
        tickets.append(eng.submit(filler, max_new_tokens=4))
        eng.run_until_drained()
        tickets.append(eng.submit(late, max_new_tokens=4, prefix_len=19))
        eng.run_until_drained()
        assert all(leaf.is_deleted() for _, leaves in handed
                   for leaf in leaves)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(eng._pools))
        return ([np.asarray(t.result()) for t in tickets], eng.stats()["pool"],
                {name for name, _ in handed})

    outs, st, programs = run(shared=True)
    assert st["n_cow_copies"] >= 1 and st["n_host_hits"] == 1
    assert programs == {"_paged_step", "_copy_block", "_write_block"}
    ref_outs, ref_st, _ = run(shared=False)
    assert ref_st["n_prefix_hits"] == 0
    for a, b in zip(outs, ref_outs):
        assert np.array_equal(a, b)
