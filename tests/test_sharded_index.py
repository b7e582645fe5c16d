"""ShardedDircIndex: sharded-vs-monolithic parity and incremental updates."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dirc_rag import RETRIEVAL_INT8
from repro.core import error_model as E
from repro.core import retrieval, topk
from repro.core.retrieval import DircRagIndex, RetrievalConfig
from repro.core.sharded_index import ShardedDircIndex
from repro.data.synthetic import make_ir_dataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chip_bench.reference import search as ref_search  # noqa: E402


@pytest.fixture(scope="module")
def ds():
    return make_ir_dataset(n_docs=512, dim=128, n_queries=8,
                           n_clusters=16, seed=7)


def _assert_parity(mono, sharded, atol=0.0, rtol=0.0):
    assert np.array_equal(np.asarray(mono.indices), np.asarray(sharded.indices))
    np.testing.assert_allclose(np.asarray(mono.scores),
                               np.asarray(sharded.scores),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("path", retrieval.PATHS)
@pytest.mark.parametrize("n_shards", [1, 4])
def test_parity_all_paths(ds, path, n_shards):
    """Every compute path: sharded search == monolithic search (bit-exact
    ranks; scores exact on integer paths, fp-reduction-tolerant on
    reference)."""
    cfg = RetrievalConfig(bits=8, metric="cosine", path=path)
    emb = jnp.asarray(ds.doc_embeddings)
    q = jnp.asarray(ds.query_embeddings)
    mono = DircRagIndex.build(emb, cfg).search(q, k=5)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=n_shards).search(q, k=5)
    tol = 1e-6 if path == "reference" else 0.0
    _assert_parity(mono, sh, atol=tol, rtol=1e-5 if tol else 0.0)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_parity_error_channel_with_detection(ds, n_shards):
    """The error-channel + Sigma-D detection path stays shard-invariant.

    p=0 keeps the channel deterministic (the full sense/detect/re-sense
    machinery still runs per macro), so parity is exact."""
    err = E.ErrorModelConfig(enabled=True, p_min=0.0, p_max=0.0)
    cfg = RetrievalConfig(bits=8, path="bitserial", mapping="error_aware",
                          error=err, detect=True, max_retries=2)
    emb = jnp.asarray(ds.doc_embeddings)
    q = jnp.asarray(ds.query_embeddings)
    key = jax.random.key(3)
    mono = DircRagIndex.build(emb, cfg).search(q, k=5, key=key)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=n_shards).search(
        q, k=5, key=key)
    _assert_parity(mono, sh)


def test_parity_mips_metric(ds):
    cfg = RetrievalConfig(bits=8, metric="mips", path="int_exact")
    emb = jnp.asarray(ds.doc_embeddings)
    q = jnp.asarray(ds.query_embeddings)
    mono = DircRagIndex.build(emb, cfg).search(q, k=5)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4).search(q, k=5)
    _assert_parity(mono, sh, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("parallelism", ["vmap", "map", "shard_map"])
def test_parallelism_modes_agree(ds, parallelism):
    cfg = RetrievalConfig(bits=8, path="int_exact")
    emb = jnp.asarray(ds.doc_embeddings)
    q = jnp.asarray(ds.query_embeddings)
    mono = DircRagIndex.build(emb, cfg).search(q, k=5)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4,
                                parallelism=parallelism).search(q, k=5)
    _assert_parity(mono, sh)


def test_ragged_corpus_shards(ds):
    """A corpus size not divisible by n_shards still matches monolithic."""
    cfg = RetrievalConfig(bits=8, path="int_exact")
    emb = jnp.asarray(ds.doc_embeddings[:509])  # prime-ish, ragged shards
    q = jnp.asarray(ds.query_embeddings)
    mono = DircRagIndex.build(emb, cfg).search(q, k=5)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4).search(q, k=5)
    _assert_parity(mono, sh)


def _corpus_ragged(rng):
    """2,363 documents: 37 slots a macro, the last slots never written."""
    return rng.normal(size=(64 * 37 - 5, 64)).astype(np.float32), []


def _corpus_few_live(rng):
    """Two live slots a macro, fewer than k, the rest tombstones."""
    docs = rng.normal(size=(64 * 12, 64)).astype(np.float32)
    return docs, [i for i in range(len(docs)) if i % 12 >= 2]


def _corpus_nearly_empty(rng):
    """Fewer live documents than k in the whole index: ids -1 follow."""
    docs = rng.normal(size=(64 * 12, 64)).astype(np.float32)
    return docs, [i for i in range(len(docs)) if i % 97]


def _corpus_tombstoned(rng):
    docs = rng.normal(size=(64 * 40 + 3, 64)).astype(np.float32)
    return docs, np.flatnonzero(rng.random(len(docs)) < 0.3).tolist()


def _corpus_duplicated(rng):
    """40 distinct documents, each copied ~59 times: every top-k is a tie,
    within macros and across them, broken by the lower id."""
    base = rng.normal(size=(40, 64)).astype(np.float32)
    return base[rng.integers(0, 40, size=64 * 37 - 5)], []


@pytest.mark.parametrize("make", [_corpus_ragged, _corpus_few_live,
                                  _corpus_nearly_empty, _corpus_tombstoned,
                                  _corpus_duplicated],
                         ids=lambda f: f.__name__[len("_corpus_"):])
def test_macro_topk_is_exact(make):
    """Over 64 macros, each macro's top-k is `jax.lax.top_k`'s bit for
    bit, and the search returns exact int8 search's top-10 ids
    (`chip_bench/reference/search.py`), -1 past the live documents."""
    rng = np.random.default_rng(17)
    docs, deleted = make(rng)
    sh = ShardedDircIndex.build(jnp.asarray(docs), RETRIEVAL_INT8,
                                n_shards=64)
    sh.delete_docs(deleted)
    q = docs[rng.integers(0, len(docs), size=8)]
    q = q + 0.3 * rng.normal(size=q.shape).astype(np.float32)
    k = 10
    s = sh.scores(jnp.asarray(q))                       # (64, 8, cap)
    kk = min(k, sh.capacity)
    got = topk.select_topk(s, kk)
    want_v, want_i = jax.lax.top_k(s, kk)
    assert np.array_equal(np.asarray(got.indices), np.asarray(want_i))
    assert np.array_equal(np.asarray(got.scores).view(np.int32),
                          np.asarray(want_v).view(np.int32))

    live = np.setdiff1d(np.arange(len(docs)), deleted)
    ref = live[ref_search.ExactIndex(docs[live]).topk(q, k)]
    ids = np.asarray(sh.search(jnp.asarray(q), k).indices)
    n = min(k, len(live))
    assert np.array_equal(ids[:, :n], ref[:, :n])
    assert (ids[:, n:] == -1).all()


def test_add_docs_balances_and_retrieves(ds):
    cfg = RetrievalConfig(bits=8, path="int_exact")
    sh = ShardedDircIndex.build(jnp.asarray(ds.doc_embeddings), cfg,
                                n_shards=4)
    n0 = sh.n_docs
    new = sh.add_docs(jnp.asarray(ds.query_embeddings[:3]))
    assert list(new) == [n0, n0 + 1, n0 + 2]  # stable append-ordered ids
    assert sh.n_docs == n0 + 3
    # An added document is its own nearest neighbour.
    res = sh.search(jnp.asarray(ds.query_embeddings[:3]), k=1)
    assert np.array_equal(np.asarray(res.indices).ravel(), new)
    # Load stays balanced: max-min live docs per shard <= 1 after appends.
    loads = sh.shard_loads()
    assert loads.max() - loads.min() <= 1


def test_delete_docs_tombstones(ds):
    cfg = RetrievalConfig(bits=8, path="int_exact")
    sh = ShardedDircIndex.build(jnp.asarray(ds.doc_embeddings), cfg,
                                n_shards=4)
    new = sh.add_docs(jnp.asarray(ds.query_embeddings[:3]))
    assert sh.delete_docs(new.tolist()) == 3
    assert sh.delete_docs(new.tolist()) == 0  # idempotent
    res = sh.search(jnp.asarray(ds.query_embeddings), k=10)
    assert not np.isin(np.asarray(res.indices), new).any()


def test_tombstone_slot_reuse_and_growth(ds):
    cfg = RetrievalConfig(bits=8, path="int_exact")
    emb = jnp.asarray(ds.doc_embeddings)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4)
    cap0 = sh.capacity
    # Delete two docs; the next adds must reuse their slots (no growth).
    sh.delete_docs([0, 1])
    ids = sh.add_docs(jnp.asarray(ds.query_embeddings[:2]))
    assert sh.capacity == cap0  # built full, so the adds reused tombstones
    assert sh.n_docs == 512
    # Filling every remaining slot forces capacity growth, search survives.
    free = sh.n_shards * sh.capacity - sh.n_docs
    sh.add_docs(jnp.tile(jnp.asarray(ds.query_embeddings[:1]), (free + 2, 1)))
    assert sh.capacity > cap0
    res = sh.search(jnp.asarray(ds.query_embeddings[:2]), k=3)
    assert (np.asarray(res.indices) >= 0).all()
    assert np.isin(ids, np.asarray(sh.ids)).all()


def test_deleted_ids_never_reused(ds):
    cfg = RetrievalConfig(bits=8, path="int_exact")
    sh = ShardedDircIndex.build(jnp.asarray(ds.doc_embeddings[:64]), cfg,
                                n_shards=4)
    a = sh.add_docs(jnp.asarray(ds.query_embeddings[:1]))
    sh.delete_docs(a.tolist())
    b = sh.add_docs(jnp.asarray(ds.query_embeddings[1:2]))
    assert b[0] > a[0]


def test_storage_accounting(ds):
    cfg = RetrievalConfig(bits=8)
    sh = ShardedDircIndex.build(jnp.asarray(ds.doc_embeddings), cfg,
                                n_shards=4)
    sb = sh.storage_bytes()
    assert sb["embeddings"] == 512 * 128  # slots * dim * 1 byte
    assert sb["live_docs"] == 512


# ------------------------------------------------------ per-macro channels
def test_shards_draw_independent_flips_for_the_same_query():
    """Regression: two macros holding IDENTICAL rows must sample
    different transient flips for the same query key (per-shard
    `fold_in` keys), while the whole draw stays deterministic per key."""
    rng = np.random.default_rng(0)
    half = rng.normal(size=(64, 32)).astype(np.float32)
    emb = jnp.asarray(np.concatenate([half, half]))  # shard 0 == shard 1
    err = E.ErrorModelConfig(enabled=True, p_min=0.05, p_max=0.05)
    cfg = RetrievalConfig(bits=8, path="bitserial", mapping="grouped",
                          error=err, detect=False)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=2)
    assert np.array_equal(np.asarray(sh.planes[0]), np.asarray(sh.planes[1]))
    key = jax.random.key(7)
    sensed = np.asarray(sh._sensed_planes(key))
    assert not np.array_equal(sensed[0], sensed[1])  # independent channels
    again = np.asarray(sh._sensed_planes(key))
    np.testing.assert_array_equal(sensed, again)  # deterministic per key


def test_calibration_jitter_diversifies_per_shard_mappings(ds):
    """With cell-to-cell jitter each macro gets its own calibration map,
    so the error-aware remapping differs per shard; without jitter every
    macro is identical (the parity regime)."""
    err = E.ErrorModelConfig(enabled=True, p_min=1e-3, p_max=5e-2,
                             jitter_sigma=1.0, seed=5)
    cfg = RetrievalConfig(bits=8, path="bitserial", mapping="error_aware",
                          error=err)
    emb = jnp.asarray(ds.doc_embeddings)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4)
    assert not np.array_equal(sh.believed_maps[0], sh.believed_maps[1])
    assert any(
        not np.array_equal(sh.mapping[0], sh.mapping[s]) for s in range(1, 4)
    )
    flat = E.ErrorModelConfig(enabled=True, p_min=1e-3, p_max=5e-2,
                              jitter_sigma=0.0, seed=5)
    cfg0 = RetrievalConfig(bits=8, path="bitserial", mapping="error_aware",
                           error=flat)
    sh0 = ShardedDircIndex.build(emb, cfg0, n_shards=4)
    for s in range(1, 4):
        np.testing.assert_array_equal(sh0.mapping[0], sh0.mapping[s])


def test_stats_reports_per_shard_error_counters(ds):
    err = E.ErrorModelConfig(enabled=True, p_min=2e-3, p_max=2e-2,
                             jitter_sigma=0.5, seed=5)
    cfg = RetrievalConfig(bits=8, path="bitserial", mapping="error_aware",
                          error=err, detect=True, max_retries=2)
    emb = jnp.asarray(ds.doc_embeddings)
    q = jnp.asarray(ds.query_embeddings)
    sh = ShardedDircIndex.build(emb, cfg, n_shards=4)
    for wave in range(3):
        sh.search(q, k=5, key=jax.random.key(wave))
    st = sh.stats()
    assert st["error_enabled"] and not st["drift_enabled"]
    assert st["total_senses"] == 4 * 3
    assert st["total_detected"] > 0
    assert len(st["shards"]) == 4
    for row in st["shards"]:
        assert row["senses"] == 3
        assert 0.0 <= row["detected_rate"] <= 1.0
        assert row["residual_rate"] <= row["detected_rate"] + 1e-9
        assert row["recal_events"] == 0
        assert row["exposure"] > 0.0
