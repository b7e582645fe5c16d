"""Driver: closed-loop top-k search through the program's index and scheduler.

Retrieval alone, as a search service runs it: `clients` callers, each
with one query outstanding and no think time, submit to the program's
`AsyncBatchScheduler`, whose flushes search a `ShardedDircIndex` of the
configuration's corpus (each flush padded to `max_batch` rows, one
program shape, as `launch/serve._padded_search` pads). A client submits
its next query from its previous ticket's done callback. Each query is
stamped on the host clock when submitted and when its callback runs.

The query embedder is bypassed: the corpus and the query pool are
embeddings made from the seed (`chip_bench/ir_corpus.py`), as a
deployment that embeds with its own encoder hands them over.
"""
from __future__ import annotations

import functools
import gc
import threading
import time

import numpy as np

from chip_bench import ir_corpus, scan_cost, trace, traffic
from chip_bench.drivers.rag_generate import _CompileCounter
from chip_bench.reference import search as ref_search

DRAIN_S = 60.0          # how long queries submitted in the window may come late
STREAM_LEN = 1 << 17    # queries drawn ahead per client; the stream repeats
MAX_QPS = 200_000       # room for the window's records, far above the chip's


class State:
    pass


def padded_search(index, queries: np.ndarray, max_batch: int, k: int):
    """(ids, scores) of `queries` (at most `max_batch` rows), the batch
    padded with copies of its first row to one static shape."""
    n = len(queries)
    pad = np.repeat(queries[:1], max_batch - n, axis=0)
    res = index.search(np.concatenate([queries, pad]), k)
    return np.asarray(res.indices)[:n], np.asarray(res.scores)[:n]


def setup(cell, seed: int, seconds: float) -> State:
    import repro.configs.dirc_rag as dirc_rag
    from repro.core.sharded_index import ShardedDircIndex
    from repro.serving import AsyncBatchScheduler

    c, mix = cell.config, cell.traffic
    idx, gen = c["index"], c["corpus"]
    st = State()
    st.cell, st.seed, st.k = cell, seed, c["corpus"]["top_k"]
    st.corpus = ir_corpus.make(
        idx["n_docs"], idx["dim"], gen["n_queries"], seed,
        docs_per_cluster=gen["docs_per_cluster"],
        doc_noise=gen["doc_noise"], query_noise=gen["query_noise"])
    st.index = ShardedDircIndex.build(
        st.corpus.docs, getattr(dirc_rag, idx["retrieval"]),
        n_shards=idx["n_shards"])
    sch = c["scheduler"]
    st.max_batch = sch["max_batch"]
    st.traced = False

    def search(qids, k):
        queries = st.corpus.queries[np.asarray(qids)]
        if not st.traced:
            return padded_search(st.index, queries, st.max_batch, k)
        with trace.span("cb.search"):
            return padded_search(st.index, queries, st.max_batch, k)

    st.sched = AsyncBatchScheduler(search, max_batch=st.max_batch,
                                   max_wait_ms=sch["max_wait_ms"], start=True)
    st.streams = np.stack([
        traffic.seeded_rng(seed, 100 + i).integers(
            0, gen["n_queries"], size=STREAM_LEN, dtype=np.int32)
        for i in range(mix["clients"])])
    for _ in range(2):      # the one shape, then a flush from the cache
        tickets = [st.sched.submit(int(q), k=st.k)
                   for q in st.streams[:, -1][:st.max_batch]]
        for t in tickets:
            t.result(timeout=900)
    return st


class _Records:
    """Per-query host records of a window, in arrays sized up front, so
    that the window allocates no Python object per query that outlives
    its ticket. Zeroed arrays take memory only where written; a query is
    done once `done` is stamped, and answered once its ids are copied."""

    def __init__(self, cap: int, k: int):
        self.cap, self.n = cap, 0
        self.qid = np.zeros(cap, np.int32)
        self.submitted = np.zeros(cap)
        self.done = np.zeros(cap)
        self.answered = np.zeros(cap, bool)
        self.ids = np.zeros((cap, k), np.int32)
        self.scores = np.zeros((cap, k), np.float32)
        self.errors: list = []
        self.lock = threading.Lock()

    def take(self) -> int:
        with self.lock:
            i, self.n = self.n, self.n + 1
        return i


def window(st: State, seconds: float, traced: bool) -> dict:
    """Drive the window; returns the host records."""
    st.traced = traced
    n_clients, k = st.streams.shape[0], st.k
    recs = _Records(int(seconds * MAX_QPS) + n_clients, k)
    pos = [0] * n_clients           # next position in each client's stream
    t_end = [float("inf")]

    def send(client: int) -> None:
        i = recs.take()
        if i >= recs.cap:
            recs.errors.append("more queries than the records hold")
            return
        q = int(st.streams[client, pos[client] % STREAM_LEN])
        pos[client] += 1
        recs.qid[i] = q
        recs.submitted[i] = time.perf_counter()
        ticket = st.sched.submit(q, k=k)
        ticket.add_done_callback(functools.partial(finish, client, i))

    def finish(client: int, i: int, ticket) -> None:
        now = time.perf_counter()
        try:
            if ticket.doc_ids is not None:
                recs.ids[i] = ticket.doc_ids
                recs.scores[i] = ticket.doc_scores
                recs.answered[i] = True
            recs.done[i] = now
            if now < t_end[0]:
                send(client)
        except Exception as e:  # noqa: BLE001 - recorded, fails the check
            recs.errors.append(f"client {client}: {e!r}")

    gc.collect()
    stats0 = st.sched.stats()
    with _CompileCounter() as compiles, trace.span("cb.window"):
        t0 = time.perf_counter()
        t_end[0] = t0 + seconds
        for client in range(n_clients):
            send(client)
        time.sleep(max(0.0, t_end[0] - time.perf_counter()))
    stats1 = st.sched.stats()
    deadline = t_end[0] + DRAIN_S
    while time.perf_counter() < deadline:
        with recs.lock:
            n = min(recs.n, recs.cap)
        if recs.done[:n].all():
            break
        time.sleep(0.01)
    st.traced = False
    n = min(recs.n, recs.cap)
    return {"t0": t0, "t_end": t_end[0], "n": n, "qid": recs.qid[:n].copy(),
            "submitted": recs.submitted[:n].copy(),
            "done": recs.done[:n].copy(), "ids": recs.ids[:n].copy(),
            "scores": recs.scores[:n].copy(),
            "answered": recs.answered[:n].copy(),
            "errors": list(recs.errors), "sched0": stats0, "sched1": stats1,
            "compiles_in_window": compiles.n}


def _in_window(rec: dict) -> np.ndarray:
    """Positions of the queries submitted in the window."""
    s = rec["submitted"]
    return np.flatnonzero((s >= rec["t0"]) & (s < rec["t_end"]))


def _served(rec: dict) -> np.ndarray:
    """Per query: it came back with a whole top-k of distinct ids."""
    ids = rec["ids"]
    whole = (ids >= 0).all(axis=1)
    distinct = (np.diff(np.sort(ids, axis=1), axis=1) != 0).all(axis=1)
    return rec["answered"] & whole & distinct


def counts(rec: dict) -> tuple:
    w = _in_window(rec)
    return len(w), int((~_served(rec)[w]).sum()) + len(rec["errors"])


def end_to_end(st: State, rec: dict) -> dict:
    w = _in_window(rec)
    done = rec["done"]
    lat = np.where(done[w] > 0, done[w] - rec["submitted"][w], np.inf)
    n_done = int(((done >= rec["t0"]) & (done <= rec["t_end"])).sum())
    return {"retrieval_qps": n_done / (rec["t_end"] - rec["t0"]),
            "retrieval_p99_ms": 1e3 * float(np.percentile(lat, 99))
            if len(lat) else np.inf}


def release(st: State) -> None:
    """Close the scheduler and drop the index, so that the reference runs
    on a device that holds nothing of the program."""
    st.sched.close(drain=True)
    st.sched = st.index = None
    gc.collect()


def _sample(st: State, rec: dict) -> np.ndarray:
    """Positions of the queries the check compares: the slowest one
    served in the window and others drawn from the seed."""
    w = _in_window(rec)
    w = w[_served(rec)[w]]
    if not len(w):
        return w
    lat = rec["done"][w] - rec["submitted"][w]
    slowest = w[int(np.argmax(lat))]
    others = w[w != slowest]
    n = min(st.cell.traffic["check_queries"], len(others))
    rng = traffic.seeded_rng(st.seed, 5)
    return np.concatenate([[slowest], rng.choice(others, size=n,
                                                 replace=False)])


def _reference(st: State, rec: dict, sample: np.ndarray) -> np.ndarray:
    """The exact int8 top-k of the sample's queries, computed once."""
    if getattr(st, "ref", None) is None:
        st.ref_index = ref_search.ExactIndex(st.corpus.docs)
        queries = st.corpus.queries[rec["qid"][sample]]
        st.ref = st.ref_index.topk(queries, st.k)
    return st.ref


def _agreement(st: State, rec: dict, sample: np.ndarray) -> dict:
    """How near the sample comes to a tie that rounding could turn: the
    widest gap, in float32 spacings, between a served score and the
    reference's score of the same document, and the narrowest gap, in
    the same units, between the reference's k-th and (k+1)-th scores."""
    want = _reference(st, rec, sample)
    scores = st.ref_index.scores(st.corpus.queries[rec["qid"][sample]])
    served, got = rec["ids"][sample], rec["scores"][sample]
    ref = np.take_along_axis(scores, served, axis=1)
    ulps = np.abs(got - ref) / np.spacing(np.abs(ref))
    top = -np.sort(-scores, axis=1)[:, :st.k + 1]
    kth = np.take_along_axis(scores, want[:, -1:], axis=1)[:, 0]
    gap = (top[:, -2] - top[:, -1]) / np.spacing(np.abs(kth))
    return {"score_ulps_max": float(ulps.max()),
            "kth_gap_ulps_min": float(gap.min())}


def _mismatches(served: np.ndarray, want: np.ndarray) -> float:
    """Queries whose served id set is not the reference's top-k set."""
    return float(sum(set(s.tolist()) != set(w.tolist())
                     for s, w in zip(served, want)))


def check(st: State, rec: dict) -> list:
    """[(name, value, limit)]: failed queries, and the served top-k of a
    sample of the window's queries against exact int8 search (compared
    exactly: the limit is 0)."""
    limits = st.cell.config["correct"]
    _, failed = counts(rec)
    sample = _sample(st, rec)
    miss = _mismatches(rec["ids"][sample], _reference(st, rec, sample)) \
        if len(sample) else np.inf
    return [("failed_queries", float(failed), limits["failed_queries"]),
            ("retrieval_mismatches", miss, limits["retrieval_mismatches"])]


def control(st: State, rec: dict) -> dict:
    """The control's reading on the check's own sample: the same queries
    searched, in batches of the window's shape, through the program's
    int4 index of the same corpus."""
    import repro.configs.dirc_rag as dirc_rag
    from repro.core.sharded_index import ShardedDircIndex

    sample = _sample(st, rec)
    queries = st.corpus.queries[rec["qid"][sample]]
    index = ShardedDircIndex.build(
        st.corpus.docs, dirc_rag.RETRIEVAL_INT4,
        n_shards=st.cell.config["index"]["n_shards"])
    served = np.concatenate([
        padded_search(index, queries[i:i + st.max_batch], st.max_batch,
                      st.k)[0]
        for i in range(0, len(queries), st.max_batch)])
    del index
    gc.collect()
    return {"retrieval_mismatches": _mismatches(
        served, _reference(st, rec, sample)),
        **_agreement(st, rec, sample)}


def layer_context(st: State, rec: dict) -> dict:
    """What the per-layer readers take from this driver: the scheduler's
    counters at the window's edges and the least work of one flush."""
    idx = st.cell.config["index"]
    return {"sched0": rec["sched0"], "sched1": rec["sched1"],
            "max_batch": st.max_batch,
            "search_work": scan_cost.search_work(idx["n_docs"], idx["dim"],
                                                 st.max_batch)}
