"""Hierarchical local/global top-k (paper Fig. 3a).

Sixteen DIRC-RAG cores each hold a shard of the database and run a local
top-k comparator; the tiny (score, index) candidate lists land in an SRAM
buffer and a global comparator merges them. The same structure scales to a
TPU pod: per-device local top-k + all-gather of candidates + global merge
(see the flat-index searcher in `core/sharded_index.py`).

Which comparator runs where: `DircRagIndex` (one macro) runs
`hierarchical_topk`, or `local_topk` on a ragged database; every macro of
a `ShardedDircIndex` runs `select_topk`, the Pallas selection kernel of
`kernels/topk_select.py`, which finds each row's top-k in k passes with no
sort, whatever the macro's capacity. All of them return `lax.top_k`'s ids
and scores bit for bit.

`jax.lax.top_k` breaks ties toward the LOWER index; the hierarchical merge
preserves that order because core-local indices are offset monotonically.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class TopK(NamedTuple):
    scores: jax.Array   # (..., k) fp32, descending
    indices: jax.Array  # (..., k) int32, global document ids


@partial(jax.jit, static_argnames=("k",))
def local_topk(scores: jax.Array, k: int) -> TopK:
    """Plain top-k over the last axis."""
    v, i = jax.lax.top_k(scores, k)
    return TopK(scores=v, indices=i.astype(jnp.int32))


def select_topk(scores: jax.Array, k: int) -> TopK:
    """Top-k over the last axis by exact selection: every row of every
    leading axis in one launch of the kernel (interpreted off the TPU)."""
    from repro.kernels import ops as kops

    v, i = kops.local_topk_blocks(scores, k)
    return TopK(scores=v, indices=i)


@partial(jax.jit, static_argnames=("k", "n_cores"))
def hierarchical_topk(scores: jax.Array, k: int, n_cores: int = 16) -> TopK:
    """Split the score vector into `n_cores` shards, local top-k per shard,
    then a global top-k over the n_cores*k candidates.

    scores: (..., n) with n divisible by n_cores.
    Exactly equals `local_topk(scores, k)` (same tie-break) — property-tested.
    """
    *lead, n = scores.shape
    assert n % n_cores == 0, f"n={n} not divisible by n_cores={n_cores}"
    per = n // n_cores
    s = scores.reshape(*lead, n_cores, per)
    lv, li = jax.lax.top_k(s, min(k, per))           # (..., cores, k)
    offset = (jnp.arange(n_cores, dtype=jnp.int32) * per)[:, None]
    gi = li.astype(jnp.int32) + offset                # global doc ids
    flat_v = lv.reshape(*lead, -1)
    flat_i = gi.reshape(*lead, -1)
    # Global merge. Ties must resolve by ascending doc id: top_k on the
    # candidate list resolves by candidate position, and candidate position
    # is ordered (core-major, score-desc) — re-sort by (-score, id) keys.
    # Candidates are core-major and score-descending within a core, so for
    # equal scores the lower candidate position also has the lower doc id —
    # top_k's position tie-break therefore matches plain top-k over scores.
    gv, gpos = jax.lax.top_k(flat_v, k)
    gid = jnp.take_along_axis(flat_i, gpos, axis=-1)
    return TopK(scores=gv, indices=gid)


def merge_candidates(scores: jax.Array, indices: jax.Array, k: int) -> TopK:
    """Top-k over a (..., m) candidate list by (-score, index).

    The double stable argsort reproduces `jax.lax.top_k`'s lower-index
    tie-break for candidates in ANY order — the global comparator shared by
    `merge_topk` and the cross-macro merge in `sharded_index`."""
    key = jnp.argsort(indices, axis=-1, stable=True)
    v = jnp.take_along_axis(scores, key, axis=-1)
    i = jnp.take_along_axis(indices, key, axis=-1)
    order = jnp.argsort(-v, axis=-1, stable=True)
    v = jnp.take_along_axis(v, order, axis=-1)[..., :k]
    i = jnp.take_along_axis(i, order, axis=-1)[..., :k]
    return TopK(scores=v, indices=i)


@partial(jax.jit, static_argnames=("k",))
def merge_topk(a: TopK, b: TopK, k: int) -> TopK:
    """Merge two candidate lists into a single top-k (global comparator)."""
    v = jnp.concatenate([a.scores, b.scores], axis=-1)
    i = jnp.concatenate([a.indices, b.indices], axis=-1)
    return merge_candidates(v, i, k)


def precision_at_k(retrieved: jax.Array, relevant: jax.Array, k: int) -> jax.Array:
    """P@k: fraction of the top-k retrieved ids that are relevant.

    retrieved: (q, >=k) int ids; relevant: (q, r) int ids (pad with -1).
    """
    top = retrieved[..., :k]                       # (q, k)
    hit = (top[..., :, None] == relevant[..., None, :]) & (relevant[..., None, :] >= 0)
    return jnp.mean(jnp.sum(hit.any(axis=-1), axis=-1) / k)
