"""The RAG corpus, made from the seed: passages as text.

RAG passages come in topic groups of `top_k`: the passages of a group
repeat the group's topic words over their first third and are filled
with common words to their exact token length (the tokenizer is
byte-level, so a passage of n bytes is n tokens). A request asks about
one group with a question made of its topic words, so its prompt is
that group's passages, of the lengths the traffic generator gave it.
"""
from __future__ import annotations

import dataclasses

from .traffic import quantiles, seeded_rng

TOPIC_LETTERS = 6
FILLER_WORDS = 997


@dataclasses.dataclass
class RagCorpus:
    texts: list            # passage texts, ids are positions
    queries: list          # one question per window request
    groups: list           # passage ids of each window request's group
    warm_groups: list      # passage ids of the warm-up requests' groups


def _topic(rng, n_words: int) -> list:
    letters = rng.integers(0, 26, size=(n_words, TOPIC_LETTERS))
    return ["".join(chr(97 + c) for c in row) for row in letters]


def _passage(rng, topic: list, n_bytes: int) -> str:
    head = (" ".join(topic) + " ") * (1 + n_bytes // (3 * 7 * len(topic)))
    head = head[: max(n_bytes // 3, 7 * len(topic))]
    words = rng.integers(0, FILLER_WORDS, size=n_bytes // 2 + 8)
    text = head + " ".join(f"w{w}" for w in words)
    return text[:n_bytes]


def question(topic: list) -> str:
    return "question: " + " ".join(topic) + "?"


def rag_corpus(n_passages: int, requests: list, mix: dict, seed: int,
               n_warm: int) -> RagCorpus:
    """Passages for `requests` (traffic.RagRequest) plus the rest of the
    corpus, and `n_warm` further groups for warm-up requests."""
    k = mix["top_k"]
    n_groups = n_passages // k
    rng = seeded_rng(seed, 3)
    picked = rng.choice(n_groups, size=len(requests) + n_warm, replace=False)
    lengths = quantiles(mix["passage_tokens"], n_passages)
    lengths = rng.permutation(lengths)
    topics = [_topic(rng, mix["topic_words"]) for _ in range(n_groups)]
    for g, req in zip(picked, requests):
        lengths[g * k:(g + 1) * k] = req.passages
    texts = []
    for i in range(n_passages):
        g = i // k
        topic = topics[g] if g < n_groups else _topic(rng, mix["topic_words"])
        texts.append(_passage(rng, topic, int(lengths[i])))
    ids = [list(range(g * k, (g + 1) * k)) for g in picked]
    return RagCorpus(texts=texts,
                     queries=[question(topics[g]) for g in picked],
                     groups=ids[:len(requests)],
                     warm_groups=ids[len(requests):])

