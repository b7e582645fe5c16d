"""A retrieval corpus and its query pool, made from the seed.

The benchmark's own copy of the clustered model of
`repro.data.synthetic.make_ir_dataset`, so that the yardstick cannot move
with the program: each document is a cluster centre plus Gaussian noise,
normalized to unit length. `make_ir_dataset` draws in `dim` plus hidden
dimensions and keeps the first `dim`, renormalized; with independent
coordinates that is the same distribution as drawing in `dim` alone,
which is what is done here. No relevance judgments are made: the check
compares against exact search, not against labels.

Each query is a perturbed copy of one corpus document (BEIR Quora's test
questions are near-duplicates of corpus questions), so its top hits are
its source and that source's cluster mates, then the nearest of the rest.

The documents are drawn on the device in one jitted call from a key of
the seed, in float32, and copied to the host once; the query pool is
drawn on the host.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import jax_seed, seeded_rng


@dataclasses.dataclass
class IrCorpus:
    docs: np.ndarray       # (n_docs, dim) float32, unit rows
    queries: np.ndarray    # (n_queries, dim) float32, unit rows
    sources: np.ndarray    # (n_queries,) the document each query copies


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw_docs(key, n_docs: int, dim: int, n_clusters: int,
               doc_noise: float):
    k_centre, k_assign, k_noise = jax.random.split(key, 3)
    centres = jax.random.normal(k_centre, (n_clusters, dim), jnp.float32)
    assign = jax.random.randint(k_assign, (n_docs,), 0, n_clusters)
    x = centres[assign] + doc_noise * jax.random.normal(
        k_noise, (n_docs, dim), jnp.float32)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def make(n_docs: int, dim: int, n_queries: int, seed: int,
         docs_per_cluster: float, doc_noise: float,
         query_noise: float) -> IrCorpus:
    """`n_docs` clustered unit documents and `n_queries` perturbed copies.

    Clusters: `n_docs / docs_per_cluster` centres, each document's drawn
    uniformly. A document is `centre + doc_noise * N(0, I)`; a query is
    its source document plus `query_noise * N(0, I / dim)`, both then
    normalized (so a query sits at cosine ~1 / sqrt(1 + query_noise**2)
    from its source)."""
    n_clusters = max(1, int(round(n_docs / docs_per_cluster)))
    docs = np.asarray(_draw_docs(jax.random.key(jax_seed(seed)), n_docs,
                                 dim, n_clusters, float(doc_noise)))
    rng = seeded_rng(seed, 11)
    sources = rng.choice(n_docs, size=n_queries, replace=False)
    noise = rng.standard_normal((n_queries, dim), dtype=np.float32)
    queries = _unit(docs[sources]
                    + np.float32(query_noise / np.sqrt(dim)) * noise)
    return IrCorpus(docs=docs, queries=queries.astype(np.float32),
                    sources=sources)
