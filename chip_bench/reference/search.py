"""Plain reference of the RAG configurations' retrieval: exact int8 search.

The paper's INT8 operating point: each document and each query is
quantized symmetrically, one scale per vector (code = round(x / s),
s = max|x| / 127), and scored by cosine over the integer codes:
ip(q, d) / (|q| |d|). The exact top-k is the k largest scores, ties to
the lower document id. Runs on the host in numpy, in blocks of queries;
integer dot products of dim <= 1024 are exact in float32.

The hashing embedder of the RAG configurations is written out here too
(byte 4-grams bucketed by seeded FNV-1a, a seeded Gaussian projection,
unit norm): the reference embeds queries and passages itself.
"""
from __future__ import annotations

import numpy as np

FNV_PRIME = np.uint32(16777619)
FNV_BASIS = np.uint32(2166136261)


class HashEmbedder:
    def __init__(self, dim: int, seed: int = 0, buckets: int = 8192):
        self.dim, self.buckets = dim, buckets
        rng = np.random.default_rng(seed)
        self.basis = np.uint32(FNV_BASIS ^ np.uint32(seed & 0xFFFFFFFF))
        proj = rng.normal(size=(buckets, dim)).astype(np.float32)
        self.proj = proj / np.linalg.norm(proj, axis=-1, keepdims=True)

    def features(self, text: str) -> np.ndarray:
        data = text.encode("utf-8", errors="replace")
        if len(data) < 4:
            data = data.ljust(4, b"\x00")
        grams = np.lib.stride_tricks.sliding_window_view(
            np.frombuffer(data, np.uint8), 4)
        h = np.full((grams.shape[0],), self.basis, np.uint32)
        with np.errstate(over="ignore"):
            for col in range(4):
                h = (h ^ grams[:, col]) * FNV_PRIME
        feats = np.zeros((self.buckets,), np.float32)
        np.add.at(feats, h % np.uint32(self.buckets), 1.0)
        return feats

    def embed(self, texts) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), np.float32)
        for i, t in enumerate(texts):
            v = self.features(t) @ self.proj
            n = np.linalg.norm(v)
            out[i] = v / n if n > 0 else v
        return out


def quantize(x: np.ndarray) -> np.ndarray:
    """Per-vector symmetric int8 codes, as float32 (exact small integers)."""
    qmax = 127.0
    x = np.asarray(x, np.float32)
    absmax = np.max(np.abs(x), axis=-1, keepdims=True)
    scale = np.where(absmax > 0, absmax / np.float32(qmax),
                     np.float32(1.0)).astype(np.float32)
    return np.clip(np.rint(x / scale), -qmax, qmax).astype(np.float32)


class ExactIndex:
    """Int8 codes and norms of a corpus."""

    def __init__(self, docs: np.ndarray):
        self.codes = quantize(docs)
        self.norms = np.sqrt(np.sum(self.codes * self.codes, axis=-1))

    def scores(self, queries: np.ndarray) -> np.ndarray:
        q = quantize(queries)
        qn = np.sqrt(np.sum(q * q, axis=-1, keepdims=True))
        ip = q @ self.codes.T
        return ip / np.maximum(qn * self.norms[None, :], 1e-12)

    def topk(self, queries: np.ndarray, k: int) -> np.ndarray:
        s = self.scores(queries)
        order = np.lexsort((np.broadcast_to(np.arange(s.shape[1]), s.shape),
                            -s), axis=-1)
        return order[:, :k]

