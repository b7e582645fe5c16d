"""End-to-end edge RAG pipeline (paper Fig. 1).

    user query --embed--> query embedding
      --DIRC retrieve--> top-k document ids (quantized CIM search)
      --augment--> [doc1 SEP doc2 ... SEP query] prompt
      --generate--> answer tokens

The embedding model is a self-contained stub (seeded random projection of
byte 4-gram features) standing in for all-MiniLM-L6-v2: deterministic
across processes (stable FNV-1a bucketing, not Python's salted `hash()`),
dimension-correct, and collision-behaved enough that identical texts map
to identical embeddings — the retrieval math downstream is the real
DIRC-RAG engine from repro.core.

Streaming serving (PR 3): `query_stream` submits single queries into the
async dual-trigger scheduler and, with `generate=True`, chains each
completed retrieval straight into a `ContinuousBatchingEngine` decode
slot — retrieval batches and decode slots share one open-loop pipeline.
`generate_stream` is the retrieval-free variant; `decode_engine()` hands
out the underlying engine for direct use.

Serving memory (PR 4): `decode_engine(paged=True)` swaps the fixed
per-slot cache regions for the shared block pool in
`serving.paged_cache` with chunked prefill — RAG's bimodally-sized
augmented prompts are exactly the workload fixed regions waste HBM on
(see the module docstrings of `continuous_batching` / `paged_cache` and
ROADMAP.md "Serving memory model").

Prefix sharing (PR 5): RAG traffic repeats itself — the same retrieved
documents head many augmented prompts. Under `paged=True` the pipeline
derives a prefix hint from the prompt layout (`encode_prompt_with_prefix`
splits the `[BOS] docs SEP` context header from the user query), so
`query_stream(generate=True, paged=True)` automatically maps concurrent
queries that retrieved the same documents onto the SAME physical KV
blocks, copy-on-write protecting their divergent answers
(`prefix_sharing=None` resolves to "on whenever the model's KV is
paged"; pass False to opt out).

Fleet serving (PR 8): `decode_engine(n_replicas=N)` (or
`router=RouterConfig(...)`) returns an `EngineRouter` over N replicated
engines instead of one — `query_stream`/`generate_stream` accept the
same knobs, and prefix-affinity placement keeps the sharing hit-rate
intact across the fleet (see serving/router.py).

SLO control plane (PR 10): requests carry an optional priority
((tenant, text, priority) triples) that rides retrieval onto the decode
submit, and `query_stream(generate=True, slo=SLOConfig(...))` attaches
an `SLOController` that is polled as the stream drains — tightening the
flush deadline and admission lookahead, rebalancing tenant weights, and
preempting low-priority decodes under pool pressure when the measured
per-tenant p95s miss their targets (see serving/slo_controller.py).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.device_physics import DriftConfig
from repro.core.recalibration import (
    RecalibrationConfig,
    RecalibrationController,
)
from repro.core.retrieval import DircRagIndex, RetrievalConfig
from repro.core.sharded_index import ShardedDircIndex
from repro.models import supports_paged_kv
from repro.core.simulator import simulate_query
from repro.data.tokenizer import ByteTokenizer
from .async_scheduler import DEFAULT_TENANT, AsyncBatchScheduler, SchedulerError
from .config import (EngineConfig, RouterConfig, SLOConfig, resolve_config,
                     resolve_router_config)
from .continuous_batching import ContinuousBatchingEngine, GenerationTicket
from .engine import GenerationEngine
from .router import EngineRouter
from .slo_controller import SLOController


_FNV_PRIME = np.uint32(16777619)
_FNV_BASIS = np.uint32(2166136261)


class HashEmbedder:
    """Deterministic byte-4-gram hashing embedder (frontend stub).

    4-grams are bucketed with seeded FNV-1a over their bytes, NOT Python's
    built-in `hash()`: bytes hashing is salted per process (PYTHONHASHSEED),
    which silently broke cross-process reproducibility — an index built in
    one process disagreed with queries embedded in another. FNV-1a is
    stable across processes, platforms, and Python versions, and the
    vectorized uint32 arithmetic is also much faster than a Python loop.
    """

    def __init__(self, dim: int = 512, seed: int = 0, buckets: int = 8192):
        self.dim = dim
        self.buckets = buckets
        rng = np.random.default_rng(seed)
        # mix the seed into the FNV basis so different embedders bucket
        # differently but every process agrees
        self._basis = np.uint32(_FNV_BASIS ^ np.uint32(seed & 0xFFFFFFFF))
        self.proj = rng.normal(size=(buckets, dim)).astype(np.float32)
        self.proj /= np.linalg.norm(self.proj, axis=-1, keepdims=True)

    def _bucket_4grams(self, data: bytes) -> np.ndarray:
        """Bucket ids of every byte 4-gram (short inputs are NUL-padded)."""
        if len(data) < 4:
            data = data.ljust(4, b"\x00")
        arr = np.frombuffer(data, np.uint8)
        grams = np.lib.stride_tricks.sliding_window_view(arr, 4)
        h = np.full((grams.shape[0],), self._basis, np.uint32)
        with np.errstate(over="ignore"):  # uint32 wraparound is the point
            for col in range(4):
                h = (h ^ grams[:, col]) * _FNV_PRIME
        return h % np.uint32(self.buckets)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), np.float32)
        for i, t in enumerate(texts):
            feats = np.zeros((self.buckets,), np.float32)
            np.add.at(feats, self._bucket_4grams(
                t.encode("utf-8", errors="replace")), 1.0)
            v = feats @ self.proj
            n = np.linalg.norm(v)
            out[i] = v / n if n > 0 else v
        return out


@dataclasses.dataclass
class RagResult:
    doc_ids: np.ndarray
    doc_scores: np.ndarray
    retrieved_texts: list
    answer_text: Optional[str]
    answer_tokens: Optional[np.ndarray]
    sim_latency_us: float
    sim_energy_uj: float


class RagPipeline:
    def __init__(
        self,
        doc_texts: Sequence[str],
        retrieval_config: RetrievalConfig,
        model=None,
        params=None,
        embedder: Optional[HashEmbedder] = None,
        dim: int = 512,
        max_prompt_len: int = 512,
        n_shards: int = 0,
        clock: Callable[[], float] = time.monotonic,
        drift: Optional[DriftConfig] = None,
        recal=None,
    ):
        """n_shards=0 builds the monolithic single-macro DircRagIndex;
        n_shards>=1 builds a ShardedDircIndex, which also unlocks
        add_docs/delete_docs (incremental corpus updates). `clock` is the
        monotonic-seconds source for every pipeline deadline (and the
        engines it builds) — injectable for deterministic tests.

        Device physics (sharded index only): `drift` configures each
        macro's temporal error-map drift over `clock`; `recal=True` (or a
        `RecalibrationConfig`) attaches a `RecalibrationController` that
        the retrieval path polls after every batch, so shards whose
        detection counters drift past baseline get re-extracted and
        re-encoded online, mid-serving."""
        self.tokenizer = ByteTokenizer()
        self.embedder = embedder or HashEmbedder(dim=dim)
        self.doc_texts = list(doc_texts)
        embs = self.embedder.embed(self.doc_texts)
        if n_shards > 0:
            self.index = ShardedDircIndex.build(
                jnp.asarray(embs), retrieval_config, n_shards=n_shards,
                drift=drift, clock=clock)
        else:
            if drift is not None or recal:
                raise TypeError(
                    "drift/recal require n_shards >= 1 (per-macro device "
                    "physics lives on ShardedDircIndex)")
            self.index = DircRagIndex.build(jnp.asarray(embs), retrieval_config)
        self.recal_controller = None
        if recal:
            cfg = recal if isinstance(recal, RecalibrationConfig) else None
            self.recal_controller = RecalibrationController(self.index, cfg)
        self.engine = (
            GenerationEngine(model, params) if model is not None else None
        )
        self.max_prompt_len = max_prompt_len
        self._clock = clock
        # final SLOController counters from the last query_stream(slo=...)
        self.last_slo_stats: Optional[dict] = None

    # ------------------------------------------------------------ retrieval
    def search_batch(
        self, texts: Sequence[str], k: int,
        key: Optional[jax.Array] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Embed + search a whole batch as one (b, dim) call.

        Returns (ids (b, k) int32, scores (b, k) fp32). This is the unit
        the BatchScheduler flushes. Traced as `rag.embed` (the host
        embedder) and `rag.search` (the index search up to its results on
        the host)."""
        with TraceAnnotation("rag.embed"):
            emb = self.embedder.embed(list(texts))
        with TraceAnnotation("rag.search"):
            res = self.index.search(jnp.asarray(emb), k=k, key=key)
            if self.recal_controller is not None:
                # Cheap when no detection window has filled; fires online
                # per-shard re-extraction + re-encode when one has drifted.
                self.recal_controller.poll()
            return np.asarray(res.indices), np.asarray(res.scores)

    def retrieval_stats(self) -> dict:
        """Per-shard error/recal counters + the controller's view.

        Monolithic indexes (n_shards=0) report {} — device physics lives
        on the sharded index."""
        stats: dict = {}
        if isinstance(self.index, ShardedDircIndex):
            stats = self.index.stats()
            if self.recal_controller is not None:
                stats["recalibration"] = self.recal_controller.stats()
        return stats

    def scheduler(self, max_batch: int = 32,
                  key: Optional[jax.Array] = None,
                  max_wait_ms: Optional[float] = None,
                  tenant_quantum: int = 1,
                  tenant_weights: Optional[dict] = None,
                  start: Optional[bool] = None) -> AsyncBatchScheduler:
        """An AsyncBatchScheduler whose flushes run through this pipeline.

        Default (max_wait_ms=None) is the PR 1 pull-based behaviour:
        manual mode, batches form on flush()/result(). Passing
        max_wait_ms starts the background flush loop: batches then form
        on the dual trigger (max_batch reached OR oldest ticket older
        than max_wait_ms) with no caller blocking, and per-tenant queues
        are drained weighted-deficit-round-robin (`tenant_quantum *
        weight` tickets per visit; `tenant_weights` maps tenant name ->
        weight, default 1.0). `start` overrides the thread choice
        explicitly."""
        if start is None:
            start = max_wait_ms is not None
        return AsyncBatchScheduler(
            lambda texts, k: self.search_batch(texts, k, key=key),
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            quantum=tenant_quantum,
            tenant_weights=tenant_weights,
            start=start,
        )

    def decode_engine(self, config: Optional[EngineConfig] = None, *,
                      router: Optional[RouterConfig] = None,
                      n_replicas: Optional[int] = None,
                      affinity: Optional[bool] = None,
                      max_imbalance: Optional[int] = None,
                      n_slots: Optional[int] = None,
                      cache_len: Optional[int] = None,
                      max_new_tokens: int = 32,
                      temperature: float = 0.0,
                      paged: Optional[bool] = None,
                      block_size: Optional[int] = None,
                      n_blocks: Optional[int] = None,
                      prefill_chunk: Optional[int] = None,
                      prefix_sharing: Optional[bool] = None,
                      paged_kernel: Optional[bool] = None,
                      retain_blocks: Optional[int] = None,
                      host_blocks: Optional[int] = None,
                      start: bool = True):
        """A ContinuousBatchingEngine — or a routed fleet — over this
        pipeline's model.

        The generation twin of `scheduler()`: requests join and leave the
        `n_slots`-wide decode batch at token boundaries, so streaming
        generation keeps the batch full the way the async scheduler keeps
        retrieval batches full. Pass the engine shape as
        `config=EngineConfig(...)`; the per-knob keywords are a
        deprecated shim that builds the same config (DeprecationWarning;
        see serving/config.py for the migration path). `max_new_tokens`,
        `temperature`, and `start` are pipeline-runtime parameters, not
        engine shape, and stay ordinary keywords.

        Fleet mode: passing `router=RouterConfig(...)` or any fleet knob
        (`n_replicas`, `affinity`, `max_imbalance` — supported sugar,
        no deprecation) returns an `EngineRouter` over that many
        replicas of the SAME resolved config, with prefix-affinity
        placement; its submit/stats/close surface matches the engine's,
        so `query_stream`/`generate_stream` work over either. With no
        fleet knob the single engine comes back exactly as before.

        Two `EngineConfig` fields resolve pipeline-side: `cache_len=None`
        becomes `max_prompt_len + max_new_tokens` (every augmented
        prompt fits) and `prefix_sharing=None` turns copy-on-write
        prefix sharing on exactly when the model's KV is paged
        (attention families under `paged=True`). Everything else —
        pool geometry, the fused kernel, the retention/host tiers
        (`retain_blocks`/`host_blocks`) — passes through to the engine
        unchanged.
        """
        if self.engine is None:
            raise TypeError("decode_engine requires a model "
                            "(RagPipeline(..., model=, params=))")
        fleet = None
        if (router is not None or n_replicas is not None
                or affinity is not None or max_imbalance is not None):
            fleet = resolve_router_config(router, dict(
                n_replicas=n_replicas, affinity=affinity,
                max_imbalance=max_imbalance))
        config = resolve_config(config, dict(
            n_slots=n_slots, cache_len=cache_len, paged=paged,
            block_size=block_size, n_blocks=n_blocks,
            prefill_chunk=prefill_chunk, prefix_sharing=prefix_sharing,
            paged_kernel=paged_kernel, retain_blocks=retain_blocks,
            host_blocks=host_blocks))
        resolved = {}
        if config.cache_len is None:
            resolved["cache_len"] = self.max_prompt_len + max_new_tokens
        if config.prefix_sharing is None:
            resolved["prefix_sharing"] = config.paged and supports_paged_kv(
                self.engine.model)
        if resolved:
            config = config.replace(**resolved)
        eos = self.tokenizer.eos_id
        vocab = self.engine.model.cfg.vocab_size
        eos_id = eos if eos < vocab else None
        if fleet is not None:
            return EngineRouter(
                self.engine.model, self.engine.params, config, fleet,
                eos_id=eos_id, temperature=temperature,
                clock=self._clock, start=start)
        return ContinuousBatchingEngine(
            self.engine.model, self.engine.params, config,
            eos_id=eos_id,
            temperature=temperature,
            clock=self._clock,
            start=start,
        )

    def encode_prompt(self, text: str, retrieved_texts: Sequence[str]) -> list:
        """Augmented-prompt token ids, folded into the model vocab."""
        return self.encode_prompt_with_prefix(text, retrieved_texts)[0]

    def encode_prompt_with_prefix(
            self, text: str, retrieved_texts: Sequence[str],
    ) -> tuple[list, int]:
        """(augmented-prompt token ids, shareable prefix length).

        The prefix is the `[BOS] doc1 SEP doc2 ... SEP` context header —
        everything before the user query — which is a pure function of
        the retrieved doc ids + prompt template, so concurrent queries
        that retrieved the same documents produce bit-identical prefixes
        and share their context KV under `prefix_sharing`. When
        `max_prompt_len` truncation cuts into the header (the template
        keeps the prompt TAIL), the surviving header is still shared;
        0 means nothing shareable survived. Traced as `rag.prompt`.
        """
        with TraceAnnotation("rag.prompt"):
            prompt = self.tokenizer.encode_rag_prompt(
                text, list(retrieved_texts), self.max_prompt_len)
            n_query = len(self.tokenizer.encode(text, bos=False))
            prefix_len = max(len(prompt) - n_query, 0)
            vocab = self.engine.model.cfg.vocab_size
            return [t % vocab for t in prompt], prefix_len

    def query_stream(self, requests, k: int = 3, max_batch: int = 32,
                     max_wait_ms: float = 5.0,
                     key: Optional[jax.Array] = None,
                     generate: bool = False, max_new_tokens: int = 32,
                     temperature: float = 0.0,
                     config: Optional[EngineConfig] = None,
                     router: Optional[RouterConfig] = None,
                     n_replicas: Optional[int] = None,
                     affinity: Optional[bool] = None,
                     n_slots: Optional[int] = None,
                     paged: Optional[bool] = None,
                     block_size: Optional[int] = None,
                     n_blocks: Optional[int] = None,
                     prefill_chunk: Optional[int] = None,
                     prefix_sharing: Optional[bool] = None,
                     retain_blocks: Optional[int] = None,
                     host_blocks: Optional[int] = None,
                     slo: Optional[SLOConfig] = None):
        """Stream results as they are served (completion order).

        `requests` is an iterable of query strings, (tenant, text)
        pairs, or (tenant, text, priority) triples. Each request is
        submitted to a live AsyncBatchScheduler (background flush loop,
        dual trigger) and completed tickets are yielded as soon as their
        batch lands — callers never block the batch formation. A
        request's priority (default 0) rides through retrieval onto its
        decode submission: under pool pressure higher priorities are
        admitted first and can preempt lower ones (see
        `serving.continuous_batching`).

        With generate=False yields AsyncTicket objects: `.text`,
        `.tenant`, `.doc_ids`, `.doc_scores`, `.wait_s`, `.batch_size`.

        With generate=True (requires a model) each completed retrieval
        ticket's augmented prompt is submitted straight into a
        ContinuousBatchingEngine decode slot, so retrieval batches and
        decode slots share one open-loop pipeline; yields
        GenerationTicket objects as generation completes: `.text`,
        `.tenant`, `.tokens`, `.answer_text`, `.retrieval` (the retrieval
        ticket), `.first_token_s` (TTFT), `.wait_s` (end-to-end). If
        retrieval failed for a request — or its generation could not be
        started — the retrieval AsyncTicket is yielded instead, with its
        `result()` re-raising the error.

        Under `paged=True` the decode engine also gets a shareable-prefix
        hint per prompt (the retrieved-context header from
        `encode_prompt_with_prefix`), so concurrent queries hitting the
        same documents share their context KV automatically;
        `prefix_sharing` forces the engine knob (None: on iff the
        model's KV is paged). Engine shape knobs are best passed as
        `config=EngineConfig(...)`; the per-knob keywords are the usual
        deprecated shim. `router=`/`n_replicas=`/`affinity=` put an
        `EngineRouter` fleet behind the stream instead of one engine —
        same-context queries then land on the replica already holding
        their prefix KV (see serving/router.py).

        `slo=SLOConfig(...)` (requires generate=True) closes the control
        loop: an `SLOController` wired to this stream's scheduler and
        engine is polled as the stream drains, tightening/relaxing the
        flush deadline and admission lookahead, rebalancing tenant
        weights, and firing priority preemption against the configured
        targets. Its final counters land on `self.last_slo_stats`.
        """
        import queue as _queue

        if generate and self.engine is None:
            raise TypeError("query_stream(generate=True) requires a model")
        if slo is not None and not generate:
            raise TypeError("query_stream(slo=...) requires generate=True")
        config = resolve_config(config, dict(
            n_slots=n_slots, paged=paged, block_size=block_size,
            n_blocks=n_blocks, prefill_chunk=prefill_chunk,
            prefix_sharing=prefix_sharing, retain_blocks=retain_blocks,
            host_blocks=host_blocks))
        done_q: "_queue.Queue" = _queue.Queue()
        sched = engine = controller = None
        try:
            # engine first: if its cache-layout probe raises, no thread
            # has started yet; the finally closes whatever did start
            engine = self.decode_engine(
                config, router=router, n_replicas=n_replicas,
                affinity=affinity, max_new_tokens=max_new_tokens,
                temperature=temperature,
                start=True) if generate else None
            sched = self.scheduler(max_batch=max_batch, key=key,
                                   max_wait_ms=max_wait_ms, start=True)
            if slo is not None:
                controller = SLOController(slo, engine=engine,
                                           scheduler=sched,
                                           clock=self._clock)

            def on_retrieved(ticket):
                """Scheduler-thread callback: chain retrieval into decode."""
                try:
                    texts_k = [self.doc_texts[i]
                               for i in ticket.doc_ids if i >= 0]
                    prompt, prefix_len = self.encode_prompt_with_prefix(
                        ticket.text, texts_k)
                    gen = engine.submit(
                        prompt, max_new_tokens=max_new_tokens,
                        tenant=ticket.tenant, prefix_len=prefix_len,
                        priority=getattr(ticket, "priority", 0))
                    gen.text = ticket.text
                    gen.retrieval = ticket
                    gen.add_done_callback(done_q.put)
                except Exception as e:  # noqa: BLE001 - retrieval/engine failed
                    if ticket._error is None:
                        # retrieval succeeded but the decode submit failed
                        # (e.g. engine died/closed): graft the error onto
                        # the yielded ticket so result() re-raises instead
                        # of masquerading as a pure-retrieval success
                        err = SchedulerError(f"generation submit failed: {e}")
                        err.__cause__ = e
                        ticket._error = err
                    done_q.put(ticket)  # surface the failing ticket

            def submit(tenant, text, priority):
                ticket = sched.submit(text, k=k, tenant=tenant)
                # ride the priority through retrieval to the decode submit
                ticket.priority = priority
                ticket.add_done_callback(
                    on_retrieved if generate else done_q.put)

            yield from self._drain_stream(
                requests, submit, done_q,
                poll=controller.poll if controller is not None else None)
        finally:
            if controller is not None:
                self.last_slo_stats = controller.stats()
                controller.close()
            if sched is not None:
                sched.close(drain=True)
            if engine is not None:
                engine.close(drain=True)

    def _drain_stream(self, requests, submit, done_q, poll=None):
        """Shared submit/drain loop for the streaming generators.

        Submits each request via `submit(tenant, text, priority)` (which
        must arrange for exactly one finished ticket per request to land
        on `done_q`), opportunistically yielding completions while
        submitting and draining the remainder afterwards. Requests are
        bare strings, (tenant, text) pairs, or (tenant, text, priority)
        triples. `poll`, when given, is invoked between completions
        (the SLO controller's poll hook) — during the final drain the
        queue wait is chopped so the controller keeps actuating even
        while no ticket lands."""
        import queue as _queue

        n_submitted = n_yielded = 0
        for req in requests:
            priority = 0
            if isinstance(req, tuple):
                tenant, text = req[0], req[1]
                if len(req) > 2:
                    priority = int(req[2])
            else:
                tenant, text = DEFAULT_TENANT, req
            submit(tenant, text, priority)
            n_submitted += 1
            if poll is not None:
                poll()
            while True:  # opportunistically drain while submitting
                try:
                    yield self._finalize_stream_item(done_q.get_nowait())
                    n_yielded += 1
                except _queue.Empty:
                    break
        while n_yielded < n_submitted:
            if poll is None:
                ticket = done_q.get()
            else:
                poll()
                try:
                    ticket = done_q.get(timeout=0.05)
                except _queue.Empty:
                    continue
            yield self._finalize_stream_item(ticket)
            n_yielded += 1

    def _finalize_stream_item(self, ticket):
        """Attach decoded text to finished generation tickets."""
        if isinstance(ticket, GenerationTicket) and ticket._error is None:
            ticket.answer_text = self.tokenizer.decode(ticket.tokens)
        return ticket

    def generate_stream(self, requests, max_new_tokens: int = 32,
                        temperature: float = 0.0,
                        config: Optional[EngineConfig] = None,
                        router: Optional[RouterConfig] = None,
                        n_replicas: Optional[int] = None,
                        affinity: Optional[bool] = None,
                        n_slots: Optional[int] = None,
                        cache_len: Optional[int] = None,
                        paged: Optional[bool] = None,
                        block_size: Optional[int] = None,
                        n_blocks: Optional[int] = None,
                        prefill_chunk: Optional[int] = None,
                        prefix_sharing: Optional[bool] = None,
                        retain_blocks: Optional[int] = None,
                        host_blocks: Optional[int] = None):
        """Stream plain (retrieval-free) generations in completion order.

        `requests` is an iterable of prompt strings, (tenant, text)
        pairs, or (tenant, text, priority) triples; each is tokenized
        and submitted into a continuous-batching decode slot. Yields GenerationTicket objects as sequences retire:
        `.text`, `.tokens`, `.answer_text`, `.first_token_s`, `.wait_s`.
        Use `ticket.token_stream()` from another thread for live
        per-token consumption. Engine shape knobs are best passed as
        `config=EngineConfig(...)`; the per-knob keywords are the usual
        deprecated shim. `router=`/`n_replicas=`/`affinity=` run the
        stream over an `EngineRouter` fleet instead of one engine."""
        import queue as _queue

        if self.engine is None:
            raise TypeError("generate_stream requires a model")
        config = resolve_config(config, dict(
            n_slots=n_slots, cache_len=cache_len, paged=paged,
            block_size=block_size, n_blocks=n_blocks,
            prefill_chunk=prefill_chunk, prefix_sharing=prefix_sharing,
            retain_blocks=retain_blocks, host_blocks=host_blocks))
        done_q: "_queue.Queue" = _queue.Queue()
        if config.cache_len is not None \
                and config.cache_len <= max_new_tokens:
            # the truncation below keeps the LAST (cache_len - max_new)
            # prompt tokens; with no room for even one, every submit
            # would be rejected — fail fast with the real constraint
            raise ValueError(
                f"cache_len ({config.cache_len}) must exceed "
                f"max_new_tokens ({max_new_tokens}) to leave room for "
                "the prompt")
        engine = self.decode_engine(
            config, router=router, n_replicas=n_replicas,
            affinity=affinity, max_new_tokens=max_new_tokens,
            temperature=temperature, start=True)
        vocab = self.engine.model.cfg.vocab_size

        def submit(tenant, text, priority):
            toks = [t % vocab for t in self.tokenizer.encode(text)]
            toks = toks[-(engine.cache_len - max_new_tokens):]
            ticket = engine.submit(toks, max_new_tokens=max_new_tokens,
                                   tenant=tenant, priority=priority)
            ticket.text = text
            ticket.add_done_callback(done_q.put)

        try:
            yield from self._drain_stream(requests, submit, done_q)
        finally:
            engine.close(drain=True)

    async def aquery_stream(self, requests, k: int = 3, max_batch: int = 32,
                            max_wait_ms: float = 5.0,
                            key: Optional[jax.Array] = None,
                            close_timeout: float = 30.0):
        """Async-generator twin of `query_stream` for asyncio servers.

        The blocking waits happen on worker threads via
        `asyncio.to_thread`, so the event loop stays free while the
        background scheduler forms batches. Closing this generator early
        (break / `aclose()`) closes the underlying `query_stream`, whose
        `finally` shuts down the background scheduler thread — consumers
        that bail out never leak the flush loop. `close_timeout` bounds
        (in injected-clock seconds) how long that shutdown retries a
        still-executing generator before warning."""
        it = self.query_stream(requests, k=k, max_batch=max_batch,
                               max_wait_ms=max_wait_ms, key=key)
        sentinel = object()
        try:
            import asyncio

            while True:
                ticket = await asyncio.to_thread(next, it, sentinel)
                if ticket is sentinel:
                    return
                yield ticket
        finally:
            await self._aclose_stream(it, close_timeout)

    async def _aclose_stream(self, it, close_timeout: float) -> None:
        """Close a running `query_stream` generator from async context.

        Close on a worker thread: generator close() runs query_stream's
        finally (sched.close(drain=True)), which blocks on the flush
        thread. If a cancelled next() still has the generator running
        (blocked until its next completion lands, <= one flush away),
        retry until it suspends; a stuck generator is warned about
        loudly rather than silently leaking the scheduler thread. The
        deadline runs on the pipeline's injected clock, so fake-clock
        tests neither wall-hang nor flake under load.
        """
        import asyncio

        deadline = self._clock() + close_timeout
        while True:
            try:
                await asyncio.to_thread(it.close)
                break
            except ValueError:  # generator already executing
                if self._clock() > deadline:
                    warnings.warn(
                        "aquery_stream could not close its query_stream "
                        f"(still executing after {close_timeout:g}s); the "
                        "background scheduler thread may leak",
                        RuntimeWarning, stacklevel=1)
                    break
                await asyncio.sleep(0.02)

    # ------------------------------------------------------ corpus updates
    def add_docs(self, texts: Sequence[str]) -> np.ndarray:
        """Embed and append new documents (sharded index only)."""
        if not isinstance(self.index, ShardedDircIndex):
            raise TypeError("add_docs requires n_shards >= 1 "
                            "(ShardedDircIndex); the monolithic ReRAM image "
                            "is build-once")
        texts = list(texts)
        if not texts:
            return np.zeros((0,), np.int32)
        # Stable ids are append-ordered, so position in doc_texts == id.
        # Reject BEFORE mutating the index, or the new batch would land in
        # the index with no doc_texts entries.
        if self.index.next_id != len(self.doc_texts):
            raise RuntimeError(
                "doc_texts out of sync with index ids (documents were added "
                "directly on pipe.index, bypassing pipe.add_docs)")
        ids = self.index.add_docs(jnp.asarray(self.embedder.embed(texts)))
        self.doc_texts.extend(texts)
        return ids

    def delete_docs(self, doc_ids: Sequence[int]) -> int:
        """Tombstone documents by id (sharded index only)."""
        if not isinstance(self.index, ShardedDircIndex):
            raise TypeError("delete_docs requires n_shards >= 1")
        return self.index.delete_docs(doc_ids)

    # --------------------------------------------------------------- query
    def query(self, text: str, k: int = 3, max_new_tokens: int = 32,
              key: Optional[jax.Array] = None) -> RagResult:
        return self.query_many([text], k=k, max_new_tokens=max_new_tokens,
                               key=key)[0]

    def query_many(self, texts: Sequence[str], k: int = 3,
                   max_new_tokens: int = 32,
                   key: Optional[jax.Array] = None) -> list:
        """Serve a batch of queries with ONE embed + ONE batched search.

        Equals per-query `query` results row for row (same index, same
        key); generation (if a model is attached) still runs per query
        since prompt lengths differ."""
        ids_b, scores_b = self.search_batch(texts, k, key=key)

        # DIRC hardware supports dims 128..1024 (paper Table I); round the
        # simulated dim up to the nearest supported column folding.
        sim_dim = min(max((self.index.dim + 127) // 128 * 128, 128), 1024)
        sim = simulate_query(self.index.n_docs, sim_dim,
                             bits=self.index.config.bits)

        results = []
        for text, ids, scores in zip(texts, ids_b, scores_b):
            texts_k = [self.doc_texts[i] for i in ids if i >= 0]
            answer_text = answer_tokens = None
            if self.engine is not None and max_new_tokens > 0:
                prompt = self.encode_prompt(text, texts_k)
                toks = jnp.asarray(prompt, jnp.int32)[None]
                answer_tokens = self.engine.generate(
                    toks, max_new_tokens=max_new_tokens)
                answer_text = self.tokenizer.decode(answer_tokens[0])
            results.append(RagResult(
                doc_ids=ids,
                doc_scores=scores,
                retrieved_texts=texts_k,
                answer_text=answer_text,
                answer_tokens=answer_tokens,
                sim_latency_us=sim.latency_s * 1e6,
                sim_energy_uj=sim.energy_j * 1e6,
            ))
        return results
