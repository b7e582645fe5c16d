"""Continuous-batching decode engine: iteration-level scheduling for generation.

PR 2 put *retrieval* behind the streaming front door (`AsyncBatchScheduler`)
but generation still ran one prompt at a time inside `RagPipeline.query_many`,
so the answer stage threw away every batch the front door formed. This module
closes that gap with Orca-style continuous batching (the scheduling model
vLLM adopted): requests join and leave the decode batch at TOKEN boundaries
instead of waiting for the slowest sequence in a static batch.

`ContinuousBatchingEngine` holds a fixed decode batch of `n_slots` sequences
over ONE jitted `decode_step` program — the static `(n_slots, 1)` token and
cache shapes compile exactly once, the query-stationary discipline the
retrieval path already uses. Between decode steps the engine:

* **admits** waiting requests into free slots;
* **decodes** one token for every occupied slot in a single batched step;
* **retires** slots whose sequence emitted `eos_id` or reached its own
  `max_new_tokens`, freeing the slot for the next waiting request — mixed
  lengths never stall the batch.

Two *cache memory models* sit under the slots (PR 4):

* **Fixed-slot (default, `paged=False`).** Every slot owns a private
  `(cache_len, ...)` cache region for its whole lifetime; admission
  prefills the whole prompt at b=1 and copies its cache into the slot
  (`dynamic_update_slice` along auto-detected batch axes — dense/MoE
  `DecodeCaches` and Mamba state trees both work). Simple, but a 16-token
  query costs the same HBM as a 900-token RAG prompt, and a long prompt's
  whole-sequence prefill stalls every other slot.
* **Paged (`paged=True`).** Attention KV lives in a shared pool of
  `(n_blocks, block_size)` blocks handed out by
  `paged_cache.PagedCacheManager` (free-list allocate/append/free,
  worst-case budget reserved at admission, `OutOfBlocks` backpressure);
  the jitted step gathers each row's window through its block table
  (`models/attention.paged_attend`). `submit()` then rejects only
  requests that could NEVER fit the pool — a temporarily exhausted pool
  queues the request and admission retries at the next token boundary
  with bounded skip-ahead: up to `admit_lookahead` later requests that
  fit NOW are admitted past a deferred head, and after `max_head_skips`
  skips admission falls back to strict FIFO so the head is never
  starved. Prompts prefill in `prefill_chunk`-sized pieces *interleaved
  with decode* (one chunk per engine step), so a long prompt no longer
  freezes every running sequence. Models without a pageable KV cache —
  Mamba's O(1) SSM state — keep their state slot-resident under
  `paged=True` and still get chunked (b=1, `prefill_chunk` tokens per
  step) admission. See ROADMAP.md "Serving memory model".

With `prefix_sharing=True` (paged attention only) the engine becomes a
copy-on-write prefix cache over that pool: `submit(prefix_len=...)`
hashes the prompt's shareable prefix into a content key, the first
sequence to prefill it publishes its blocks under that key
(`PagedCacheManager.register_prefix`), and every later identical prefix
maps onto the SAME physical blocks — refcount++ instead of allocation,
and chunked prefill skips straight to the unique suffix (the shared KV
is already resident). Requests whose key is mid-publication are briefly
deferred in the queue (skip-ahead lets unrelated requests pass) and
attach on the next boundary. Before any scatter, the engine asks the
allocator for a copy-on-write barrier (`prepare_write`): a block still
shared by someone else is detached onto a fresh block, copied
device-side (one jitted block copy, `_copy_block`), and swapped in the
table, so divergent continuations never corrupt shared KV. The gather
path (`models/attention.paged_attend`) is untouched by design — sharing
is purely a block-table/allocator concern, which the three-way parity
suite in tests/test_prefix_sharing.py demonstrates.

Tickets mirror the `AsyncBatchScheduler` futures API (`result(timeout)`,
`done()`, `add_done_callback`) and add `token_stream()`: a blocking iterator
over tokens as they are emitted, for incremental client streaming.

Like the scheduler, the engine runs in two modes: `start=True` spawns a
background decode loop (submit never blocks; tokens appear as the loop
turns), while manual mode exposes `step()` — admit + one decode step — so
tests drive admission/retirement deterministically on a fake clock with zero
sleeps and zero threads.

The engine writes host spans into the JAX profiler's trace at its phase
boundaries (`jax.profiler.TraceAnnotation`; about a microsecond each
when no trace is recording): `engine.step` around one step, with args
`prefill_chunks` and `decode_rows`, and inside it `engine.admit`,
`engine.prefill` (args `req`, the ticket's `request_id`, and `pos`),
`engine.decode` (host prep and dispatch), `engine.sample` (the blocking
copy of the next tokens) and `engine.emit`; the background loop adds
`engine.idle` while it waits for work. A request's lifetime crosses
threads, so it is stamped on its ticket (`queue_s`, `first_token_s`,
`wait_s`) rather than spanned.

The KV pools are updated in place: every jit that takes them
(`_paged_step`, `_copy_block`, `_write_block`) donates them, and the
engine keeps no reference to pools it has handed over — `self._pools`
is replaced by each call's result under the step lock.

Greedy decoding is row-independent in every model here (attention, SSM scan
and dense MLPs act per batch row), so for fixed prompts the emitted tokens
are token-for-token identical to per-query `GenerationEngine.generate` —
property-tested in tests/test_continuous_batching.py and
tests/test_paged_cache.py, including staggered admission, mixed per-request
`max_new_tokens`, and paged-vs-fixed-vs-baseline three-way parity under
chunked prefill. Temperature sampling draws one key per decode step shared
across rows (like `GenerationEngine`), so sampled outputs depend on slot
placement; use greedy when reproducibility across admission orders matters.
"""

from __future__ import annotations

import hashlib
import itertools
import queue as _queue
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.model_api import supports_paged_kv

from .async_scheduler import DEFAULT_TENANT, SchedulerError
from .config import EngineConfig, resolve_config
from .paged_cache import PagedCacheManager, blocks_for, pow2_at_least

_DONE = object()  # token_stream sentinel


def _single_device(tree):
    """The one device every array leaf of `tree` lives on, else None."""
    devices = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            devices |= leaf.devices()
    return devices.pop() if len(devices) == 1 else None


def paged_step_program(model, paged_kernel: Optional[bool] = None):
    """The engine's jitted paged step, `(params, pools, tables, lengths,
    tokens, n_valid) -> (logits, pools')`, donating `pools`: with the
    model's layer loop carrying the pools, no program copies a pool.
    `paged_kernel` None defers to the model (`cfg.paged_kernel`) and
    keeps duck-typed models whose `paged_step` lacks the knob working."""
    if paged_kernel is None:
        def step(p, pools, tbl, ln, tok, nv):
            return model.paged_step(p, pools, tbl, ln, tok, nv)
    else:
        def step(p, pools, tbl, ln, tok, nv):
            return model.paged_step(p, pools, tbl, ln, tok, nv,
                                    paged_kernel=paged_kernel)
    return jax.jit(step, donate_argnums=(1,))


class GenerationTicket:
    """Future-style handle for one generation request.

    Filled in by the engine as decoding progresses: `tokens` grows one id
    per emitted token, `queue_s` is the submit->first-admission wait for
    a slot, `first_token_s` the submit->first-token latency (TTFT) and
    `wait_s` the submit->finish latency, all on the engine's clock.
    `request_id` is the engine's submission counter, carried as the `req`
    argument of the request's `engine.prefill` trace spans. `slot` is the
    decode slot the request occupied. `priority`
    orders admission and shields the request from preemption
    (`n_preempted` counts how often it was preempted; TTFT/e2e stamps
    span the whole request, preemptions included).
    """

    def __init__(self, engine: "ContinuousBatchingEngine", prompt: np.ndarray,
                 max_new_tokens: int, tenant: str, priority: int = 0):
        self._engine = engine
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tenant = tenant
        self.priority = priority
        self.submit_time = engine._clock()
        self.request_id: Optional[int] = None
        # set once, at the first admission: a preempted request keeps it
        self.queue_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        self.wait_s: Optional[float] = None
        self.slot: Optional[int] = None
        self.prefix_key: Optional[str] = None  # content hash of the
        self.prefix_span: int = 0  # shareable prompt prefix (paged mode)
        self.n_preempted = 0
        # after a preemption: prompt + tokens emitted so far — what a
        # re-admission must make resident before decoding can continue
        self._resume_prompt: Optional[np.ndarray] = None
        self.tokens: list[int] = []
        self._token_q: _queue.SimpleQueue = _queue.SimpleQueue()
        self._event = threading.Event()
        self._error: Optional[BaseException] = None
        self._callbacks: list = []

    @property
    def seq_prompt(self) -> np.ndarray:
        """The token sequence admission must prefill: the original
        prompt, or (after a preemption) prompt + already-emitted tokens —
        resumption re-materializes the whole sequence, attaching the
        republished prefix where the pool still holds it."""
        return self.prompt if self._resume_prompt is None else self._resume_prompt

    def done(self) -> bool:
        """True once finished or failed (result() will not block)."""
        return self._event.is_set()

    def add_done_callback(self, fn: Callable[["GenerationTicket"], None]) -> None:
        """Run `fn(ticket)` when done; immediately if already done."""
        run_now = False
        with self._engine._cv:
            if self._event.is_set():
                run_now = True
            else:
                self._callbacks.append(fn)
        if run_now:
            fn(self)

    def token_stream(self, timeout: Optional[float] = None):
        """Yield token ids incrementally as the engine emits them.

        Ends when the sequence retires (EOS or max_new_tokens); re-raises
        the engine error if the request failed. Single consumer: tokens
        are handed over exactly once. In manual mode (no background
        thread) each `get` first drives `engine.step()` so the stream
        makes progress without an external driver.
        """
        while True:
            if not self._engine._has_thread():
                while self._token_q.empty() and not self._event.is_set():
                    if self._engine.step() == 0 and not self._event.is_set():
                        raise SchedulerError(
                            "engine made no progress for this ticket")
            try:
                item = self._token_q.get(timeout=timeout)
            except _queue.Empty:
                raise TimeoutError(
                    f"no token within {timeout}s "
                    f"(tenant={self.tenant!r}, emitted={len(self.tokens)})"
                ) from None
            if item is _DONE:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """All generated token ids as an int32 vector; blocks until done.

        In manual mode (no background thread) an unfinished ticket drives
        `engine.step()` itself, mirroring `AsyncTicket.result`'s pull-based
        flush. Raises `SchedulerError` if the request failed,
        `TimeoutError` on timeout.
        """
        while not self._event.is_set() and not self._engine._has_thread():
            if self._engine.step() == 0 and not self._event.is_set():
                raise SchedulerError("engine made no progress for this ticket")
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"generation not finished within {timeout}s "
                f"(tenant={self.tenant!r}, emitted={len(self.tokens)})"
            )
        if self._error is not None:
            raise self._error
        return np.asarray(self.tokens, np.int32)

    # -- internal: called by the engine ---------------------------------
    def _emit(self, tok: int) -> None:
        if self.first_token_s is None:
            self.first_token_s = self._engine._clock() - self.submit_time
        self.tokens.append(tok)
        self._token_q.put(tok)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        # set + swap under the engine lock so a concurrent
        # add_done_callback either sees done() and runs immediately or
        # lands in the list we are about to drain — never in between.
        with self._engine._cv:
            self._error = error
            self.wait_s = self._engine._clock() - self.submit_time
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
            if error is None and self.first_token_s is not None:
                # latency sample for the SLO control plane (slo_controller)
                self._engine._completions.append((
                    self._engine._clock(), self.tenant, self.priority,
                    self.first_token_s, self.wait_s))
        self._token_q.put(_DONE)
        for fn in callbacks:
            try:
                fn(self)
            except Exception:  # noqa: BLE001 - callbacks must not kill the loop
                pass


class _Prefill:
    """In-flight chunked prefill of one admitted sequence (paged mode)."""

    __slots__ = ("ticket", "pos", "caches1", "publish_key", "publish_span")

    def __init__(self, ticket: GenerationTicket, caches1=None):
        self.ticket = ticket
        self.pos = 0          # prompt tokens processed so far
        self.caches1 = caches1  # b=1 cache tree (slot-resident models only)
        self.publish_key = None   # prefix key to register once pos >= span
        self.publish_span = 0


class ContinuousBatchingEngine:
    """Slot-based continuous-batching decode over one jitted decode_step.

    model/params: any Model-protocol object (prefill optional; SSM models
        are prefilled by streaming the prompt through decode_step at b=1).
    config: an `EngineConfig` holding every shape/policy knob — batch
        width, cache geometry, paged-pool layout, sharing, retention.
        The per-knob keyword parameters below mirror its fields as a
        DEPRECATED shim: passing any of them emits DeprecationWarning
        and builds the equivalent config (config= plus knobs is an
        error). See serving/config.py for the field reference and the
        migration path; only the config-resolved semantics are described
        here.
    n_slots: decode batch width — the number of sequences in flight.
    cache_len: per-sequence token capacity (None: 256). Fixed-slot mode
        allocates `n_slots` private regions of this size up front and
        `submit()` rejects `len(prompt) + max_new_tokens > cache_len`.
        Paged mode uses it only as the block-table width cap
        (`max_seq_len` of one sequence); memory is the shared pool.
    eos_id: retire a slot when it emits this id (None: length-only).
    temperature: 0 == greedy (argmax, reproducible); > 0 samples with one
        key per decode step shared across slots.
    paged: use the block-pooled KV memory model (see module docstring).
    block_size / n_blocks: paged-pool geometry. `n_blocks` defaults to
        the fixed-slot footprint (`n_slots * cache_len` tokens' worth of
        blocks, plus the reserved null block), i.e. paged-by-default uses
        the SAME cache HBM as fixed-slot and turns it into admission
        headroom for short sequences.
    prefill_chunk: paged-mode admission granularity — prompt tokens
        advanced per engine step per admitting sequence (default 32).
    prefix_sharing: map identical prompt prefixes onto the same physical
        blocks with copy-on-write divergence (paged attention models
        only; see module docstring). `submit(prefix_len=...)` bounds the
        shareable span; without a hint the whole prompt (minus the final
        token, which is always recomputed for logits) is the candidate.
    admit_lookahead: paged admission skip-ahead bound — how many queued
        requests past a deferred head are examined for one that fits the
        pool right now (default 4; 0 restores strict FIFO).
    max_head_skips: starvation guard — after the same head request has
        been skipped this many times, admission reverts to strict FIFO
        until it gets in (default 16).
    paged_kernel: route paged attention through the fused Pallas
        flash-decoding kernel (`kernels.paged_attend`) instead of the
        dense-window gather path. None (default) defers to the model
        (`cfg.paged_kernel`) and keeps duck-typed models whose
        `paged_step` lacks the knob working; True/False force it.
    retain_blocks: device retention budget (pool blocks) for published
        prefixes that outlive their publisher — the tiered prefix cache
        (see paged_cache.py; 0/None keeps PR 5 non-owning semantics).
    host_blocks: host-RAM tier budget (pool blocks): prefixes evicted
        from the device tier park their KV in host numpy buffers and
        swap back in on a later hit. Requires retain_blocks.
    replica_id: this engine's position in an `EngineRouter` fleet (see
        serving/router.py); None outside a fleet. Identity only — it
        never changes engine behaviour or the stats() schema.
    clock: monotonic-seconds callable, injectable for deterministic tests.
    start: spawn the background decode loop. With start=False the engine
        is in *manual mode*: call `step()` yourself (or let
        `ticket.result()` / `token_stream()` drive it).

    `clock`, `start`, `eos_id`, `temperature`, `key` and `replica_id`
    are runtime parameters, not engine shape — they stay keywords and
    are NOT deprecated.

    Fixed-slot prefill compiles once per distinct prompt length (b=1
    shapes); paged mode compiles a BOUNDED set of step shapes regardless
    of prompt-length mix — `(w, 1)` decode and `(1, prefill_chunk)`
    prefill pieces, where batch width w and the prefill gather window
    are bucketed to powers of two (compaction: a half-empty engine
    doesn't pay full-width attention, a short prompt doesn't attend the
    full table window).
    """

    def __init__(
        self,
        model,
        params,
        config: Optional[EngineConfig] = None,
        *,
        n_slots: Optional[int] = None,
        cache_len: Optional[int] = None,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        key: Optional[jax.Array] = None,
        paged: Optional[bool] = None,
        block_size: Optional[int] = None,
        n_blocks: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_sharing: Optional[bool] = None,
        admit_lookahead: Optional[int] = None,
        max_head_skips: Optional[int] = None,
        paged_kernel: Optional[bool] = None,
        retain_blocks: Optional[int] = None,
        host_blocks: Optional[int] = None,
        replica_id: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        start: bool = False,
    ):
        config = resolve_config(config, dict(
            n_slots=n_slots, cache_len=cache_len, paged=paged,
            block_size=block_size, n_blocks=n_blocks,
            prefill_chunk=prefill_chunk, prefix_sharing=prefix_sharing,
            admit_lookahead=admit_lookahead, max_head_skips=max_head_skips,
            paged_kernel=paged_kernel, retain_blocks=retain_blocks,
            host_blocks=host_blocks))
        self.config = config
        n_slots = config.n_slots
        cache_len = 256 if config.cache_len is None else config.cache_len
        paged = config.paged
        block_size = config.block_size
        n_blocks = config.n_blocks
        prefill_chunk = config.prefill_chunk
        prefix_sharing = bool(config.prefix_sharing)
        admit_lookahead = config.admit_lookahead
        max_head_skips = config.max_head_skips
        paged_kernel = config.paged_kernel
        retain_blocks = config.retain_blocks or 0
        host_blocks = config.host_blocks or 0
        self.model = model
        self.params = params
        # the device the weights live on: caches and pools are made there
        # too, so a replica placed on its own chip keeps all its state there
        self.device = _single_device(params)
        self.replica_id = replica_id
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.paged = paged
        self.paged_kernel: Optional[bool] = None
        self._key = key if key is not None else jax.random.key(0)
        self._clock = clock
        self._decode = jax.jit(
            lambda p, caches, tok: model.decode_step(p, caches, tok))
        if hasattr(model, "prefill"):
            self._prefill = jax.jit(
                lambda p, toks: model.prefill(p, tokens=toks,
                                              cache_len=cache_len))
        else:
            self._prefill = None

        # -- cache memory model -----------------------------------------
        self._kv_paged = paged and supports_paged_kv(model)
        self._pcm: Optional[PagedCacheManager] = None
        if paged:
            if not self._kv_paged and (block_size is not None
                                       or n_blocks is not None
                                       or paged_kernel is not None
                                       or prefix_sharing
                                       or retain_blocks or host_blocks):
                # slot-resident state has no pool: explicit pool geometry,
                # sharing, retention, or the fused kernel would silently
                # vanish — say so instead
                import warnings

                warnings.warn(
                    f"{type(model).__name__} has no pageable KV cache; "
                    "block_size/n_blocks/prefix_sharing/paged_kernel/"
                    "retain_blocks/host_blocks are ignored (state stays "
                    "slot-resident, only chunked admission applies)",
                    RuntimeWarning, stacklevel=2)
            block_size = block_size or 16
            self.block_size = block_size
            self.prefill_chunk = prefill_chunk or 32
            self.admit_lookahead = 4 if admit_lookahead is None \
                else admit_lookahead
            self.max_head_skips = 16 if max_head_skips is None \
                else max_head_skips
        self.prefix_sharing = bool(prefix_sharing) and self._kv_paged
        self.retain_blocks = retain_blocks if self._kv_paged else 0
        self.host_blocks = host_blocks if self._kv_paged else 0
        self._host_kv: dict = {}  # prefix key -> host-tier KV leaf list
        if self._kv_paged:
            if n_blocks is None:
                n_blocks = blocks_for(n_slots * cache_len, block_size) + 1
            self._pcm = PagedCacheManager(
                n_blocks, block_size,
                max_blocks_per_seq=blocks_for(cache_len, block_size),
                retain_blocks=self.retain_blocks,
                host_blocks=self.host_blocks,
                on_evict=self._offload_prefix if self.host_blocks else None,
                on_swapin=self._swapin_prefix if self.host_blocks else None,
                on_host_drop=(
                    self._drop_host_prefix if self.host_blocks else None))
            with jax.default_device(self.device):
                self._pools = model.init_paged_caches(n_blocks, block_size)
            self.paged_kernel = paged_kernel
            self._paged_step = paged_step_program(model, paged_kernel)
            self._pool_block_axes = self._detect_block_axes(block_size)
            self._copy_block = jax.jit(self._copy_block_impl,
                                       donate_argnums=(0,))
            self._write_block = jax.jit(self._write_block_impl,
                                        donate_argnums=(0,))
            self._lengths = np.zeros((n_slots,), np.int64)
            self._caches = None
        else:
            self._batch_axes = self._detect_batch_axes()
            self._write_slot = jax.jit(self._write_slot_impl)
            with jax.default_device(self.device):
                self._caches = model.init_caches(n_slots, cache_len, 0)

        self._pad_id = eos_id if eos_id is not None else 0
        self._cur = np.full((n_slots, 1), self._pad_id, np.int32)
        self._slots: list[Optional[GenerationTicket]] = [None] * n_slots
        self._emitted = np.zeros((n_slots,), np.int64)
        self._prefills: dict[int, _Prefill] = {}  # slot -> chunked prefill
        self._waiting: deque[GenerationTicket] = deque()
        self._request_ids = itertools.count()
        self._cv = threading.Condition()
        # serializes step() bodies: several threads may drive a manual-mode
        # engine via ticket.result()/token_stream() at once, and the cache
        # read-modify-write must not interleave
        self._step_lock = threading.Lock()
        self._closed = False
        self._drain_on_close = True
        # stats (guarded by _cv for cross-thread reads)
        self.n_decode_steps = 0
        self.n_prefills = 0
        self.n_prefill_chunks = 0
        self.n_tokens = 0
        self.n_finished = 0
        self.n_failed = 0
        self.n_backpressure = 0  # admissions deferred by pool exhaustion
        self.n_skip_ahead = 0  # admissions that jumped a deferred head
        self.n_preemptions = 0  # running sequences released + re-queued
        self.n_resumes = 0  # preempted sequences re-admitted
        self.peak_active = 0
        # finished-request latency samples for the SLO control plane:
        # (finish clock, tenant, priority, ttft_s, e2e_s); bounded so an
        # undrained engine never grows without bound
        self._completions: deque = deque(maxlen=4096)
        # prefix keys being published: key -> owning slot. Requests with a
        # matching key are deferred in the queue (skip-ahead lets others
        # pass) and attach the registered blocks on a later boundary.
        self._publishing: dict[str, int] = {}
        self._head_ticket: Optional[GenerationTicket] = None
        self._head_skips = 0
        self._occupancy_counts: dict[int, int] = {}
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._loop, name="ContinuousBatchingEngine", daemon=True
            )
            self._thread.start()

    # ------------------------------------------------------- cache plumbing
    @staticmethod
    def _unique_diff_axes(big, small, what: str):
        """Per-leaf axis on which two pytrees of shapes differ — the
        shape-diff trick behind both batch-axis and block-axis detection;
        raises when any leaf has no single distinguishing axis."""
        axes = []
        for b_l, s_l in zip(jax.tree_util.tree_leaves(big),
                            jax.tree_util.tree_leaves(small)):
            diff = [i for i, (a, c) in enumerate(zip(b_l.shape, s_l.shape))
                    if a != c]
            if len(diff) != 1:
                raise ValueError(
                    f"unsupported {what} layout: leaf "
                    f"{b_l.shape} vs {s_l.shape} has no unique axis")
            axes.append(diff[0])
        return axes

    def _detect_batch_axes(self):
        """Per-leaf batch axis of the decode-cache pytree, found by shape
        diffing init_caches at two batch sizes — model-agnostic, so dense
        DecodeCaches (batch on axis 1 of k/v, axis 0 of length) and Mamba
        state trees both slot-write correctly."""
        big = jax.eval_shape(lambda: self.model.init_caches(2, self.cache_len, 0))
        one = jax.eval_shape(lambda: self.model.init_caches(1, self.cache_len, 0))
        return self._unique_diff_axes(big, one, "cache")

    def _detect_block_axes(self, block_size: int):
        """Per-leaf physical-block axis of the paged-pool pytree, found by
        shape diffing init_paged_caches at two pool sizes — model-agnostic
        the same way `_detect_batch_axes` is, so the jitted copy-on-write
        block copy works for dense `(L, n_blocks, bs, kh, hd)` pools and
        the flat test pools alike."""
        big = jax.eval_shape(
            lambda: self.model.init_paged_caches(3, block_size))
        two = jax.eval_shape(
            lambda: self.model.init_paged_caches(2, block_size))
        return self._unique_diff_axes(big, two, "paged-pool")

    def _copy_block_impl(self, pools, src, dst):
        """Copy physical block `src` onto `dst` in every pool leaf — the
        device half of a copy-on-write detachment."""
        leaves, treedef = jax.tree_util.tree_flatten(pools)
        out = [
            jax.lax.dynamic_update_slice_in_dim(
                leaf, jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax),
                dst, axis=ax)
            for leaf, ax in zip(leaves, self._pool_block_axes)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _cow_barrier(self, seq: int, start: int, end: int) -> None:
        """Detach + device-copy every shared block a scatter into
        positions [start, end) of `seq` would touch."""
        for src, dst in self._pcm.prepare_write(seq, start, end):
            self._pools = self._copy_block(
                self._pools, jnp.int32(src), jnp.int32(dst))

    def _write_block_impl(self, pools, pieces, dst):
        """Write one block's worth of per-leaf KV (`pieces`, each shaped
        like a single-block slice) into physical block `dst` — the
        device half of a host-tier swap-in."""
        leaves, treedef = jax.tree_util.tree_flatten(pools)
        out = [
            jax.lax.dynamic_update_slice_in_dim(
                leaf, piece.astype(leaf.dtype), dst, axis=ax)
            for leaf, piece, ax in zip(leaves, pieces, self._pool_block_axes)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _offload_prefix(self, key, blocks, n_tokens: int) -> int:
        """Host-tier `on_evict` callback: gather the victim prefix's KV
        blocks out of the device pools into host numpy buffers (one
        `(k, ...)`-shaped array per pool leaf, k = len(blocks)); returns
        the bytes parked. Runs synchronously under the step lock while
        the blocks are still resident."""
        idx = jnp.asarray(list(blocks), jnp.int32)
        saved = []
        nbytes = 0
        for leaf, ax in zip(jax.tree_util.tree_leaves(self._pools),
                            self._pool_block_axes):
            piece = np.asarray(jnp.take(leaf, idx, axis=ax))
            saved.append(piece)
            nbytes += piece.nbytes
        self._host_kv[key] = saved
        return nbytes

    def _swapin_prefix(self, key, blocks, n_tokens: int) -> None:
        """Host-tier `on_swapin` callback: scatter the saved KV back into
        freshly reserved device blocks, one jitted single-block write per
        block (one compiled shape total — every piece is a one-block
        slice)."""
        saved = self._host_kv.pop(key)
        for k, dst in enumerate(blocks):
            pieces = [np.take(piece, [k], axis=ax)
                      for piece, ax in zip(saved, self._pool_block_axes)]
            self._pools = self._write_block(
                self._pools, pieces, jnp.int32(dst))

    def _drop_host_prefix(self, key) -> None:
        """Host-tier `on_host_drop` callback: discard parked KV bytes."""
        self._host_kv.pop(key, None)

    def clear_prefix_cache(self) -> int:
        """Drop every retained prefix pin and host-tier entry; returns
        entries dropped. Restores non-owning registry semantics until
        the next publication (bench warm-up / test isolation)."""
        with self._step_lock:
            if self._pcm is None:
                return 0
            n = self._pcm.clear_retained()
            self._host_kv.clear()
            return n

    def _write_slot_impl(self, full, one, slot):
        """Write a b=1 cache tree into slot `slot` of the batched tree."""
        flat_full, treedef = jax.tree_util.tree_flatten(full)
        flat_one = jax.tree_util.tree_leaves(one)
        out = [
            jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), slot, axis=ax)
            for f, o, ax in zip(flat_full, flat_one, self._batch_axes)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _prefill_one(self, prompt: np.ndarray):
        """Prefill one prompt at b=1; returns (last logits (1, V), caches)."""
        toks = jnp.asarray(prompt, jnp.int32)[None]
        if self._prefill is not None:
            return self._prefill(self.params, toks)
        caches = self.model.init_caches(1, self.cache_len, 0)
        logits = None
        for t in range(toks.shape[1]):
            logits, caches = self._decode(self.params, caches,
                                          toks[:, t : t + 1])
        return logits, caches

    def _sample(self, logits: jax.Array) -> np.ndarray:
        """(b, V) -> (b,) int32 next tokens."""
        with TraceAnnotation("engine.sample"):
            if self.temperature <= 0:
                return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            self._key, sub = jax.random.split(self._key)
            return np.asarray(
                jax.random.categorical(sub, logits / self.temperature,
                                       axis=-1),
                np.int32)

    # --------------------------------------------------------------- submit
    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 32,
        tenant: str = DEFAULT_TENANT,
        prefix_len: Optional[int] = None,
        priority: int = 0,
    ) -> GenerationTicket:
        """Enqueue one prompt; returns immediately with a GenerationTicket.

        The request is admitted into a decode slot at the next token
        boundary with a free slot (paged mode: and enough free pool
        blocks to reserve its worst-case budget — a temporarily
        exhausted pool queues the request instead of rejecting it, and
        bounded skip-ahead may admit later queued requests that fit
        now). Raises SchedulerError if the engine is closed or the
        request could NEVER be served: fixed-slot mode when `len(prompt)
        + max_new_tokens > cache_len`, paged mode when the worst case
        exceeds the block-table width or the whole pool.

        `prefix_len` bounds the shareable prompt prefix under
        `prefix_sharing=True`: the first `prefix_len` tokens (e.g. the
        retrieved-document context of a RAG prompt) are hashed into a
        content key, and identical prefixes share physical KV blocks
        with copy-on-write divergence. Ignored when sharing is off;
        `None` offers the whole prompt. The final prompt token is never
        shared — it is always recomputed to produce the first logits.

        `priority` (default 0, higher wins) orders paged admission
        within the skip-ahead window and shields the request from
        `preempt()`: only a strictly lower-priority running sequence may
        be preempted on its behalf. Equal priorities reduce to the
        FIFO-with-skip-ahead behaviour exactly.
        """
        prompt = np.asarray(list(prompt), np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token sequence")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        need = int(prompt.size) + max_new_tokens
        if self._kv_paged:
            blocks = self._pcm.blocks_needed(need)
            if blocks > self._pcm.max_blocks_per_seq \
                    or blocks > self._pcm.n_usable_blocks:
                raise SchedulerError(
                    f"request needs {blocks} blocks of {self.block_size} "
                    f"tokens but the pool serves at most "
                    f"{min(self._pcm.max_blocks_per_seq, self._pcm.n_usable_blocks)} "
                    f"per sequence")
        elif need > self.cache_len:
            raise SchedulerError(
                f"request needs {prompt.size} prompt + {max_new_tokens} new "
                f"tokens but cache_len is {self.cache_len}")
        t = GenerationTicket(self, prompt, max_new_tokens, tenant, priority)
        t.prefix_key, t.prefix_span = self.compute_prefix_key(
            prompt, prefix_len)
        with self._cv:
            if self._closed:
                raise SchedulerError("engine is closed")
            t.request_id = next(self._request_ids)
            self._waiting.append(t)
            self._cv.notify_all()
        return t

    def compute_prefix_key(
        self, prompt: np.ndarray, prefix_len: Optional[int] = None
    ) -> tuple[Optional[str], int]:
        """(content key, span) a `submit(prompt, prefix_len=...)` would
        carry, or (None, 0) when the span is sub-block or sharing is off.

        The single source of the key derivation, shared with
        `EngineRouter` so placement hashes exactly what admission will:
        the shareable span is the whole prompt minus the final token
        (always recomputed for logits), clipped to `prefix_len`, and the
        key is the SHA-1 of those token bytes — content-addressed, so
        two prompts share iff their shareable spans are bit-identical.
        """
        if not self.prefix_sharing:
            return None, 0
        prompt = np.asarray(prompt, np.int32)
        span = int(prompt.size) - 1
        if prefix_len is not None:
            span = min(int(prefix_len), span)
        if span < self.block_size:
            return None, 0
        return hashlib.sha1(prompt[:span].tobytes()).hexdigest(), span

    def holds_prefix(self, key: str) -> bool:
        """True when this engine already holds (or is about to hold)
        prefix `key`: published in the pool registry, pinned in the
        retained tier, parked in the host tier, mid-publication in an
        admitted slot, or carried by a queued/active ticket. The
        external-placement hook `EngineRouter` routes on — a request
        sent here attaches (or waits to attach) instead of re-prefilling.
        """
        if not self.prefix_sharing:
            return False
        if self._pcm.has_prefix_any(key) or key in self._publishing:
            return True
        with self._cv:
            return any(
                t is not None and t.prefix_key == key
                for t in itertools.chain(self._slots, self._waiting))

    def pending(self) -> int:
        """Requests waiting for a slot (admitted ones count as active)."""
        with self._cv:
            return len(self._waiting)

    def active(self) -> int:
        """Occupied decode slots (decoding or mid-prefill)."""
        with self._cv:
            return sum(t is not None for t in self._slots)

    def load(self) -> int:
        """Queued + active requests, read atomically — the placement
        signal `EngineRouter` balances on."""
        with self._cv:
            return len(self._waiting) + sum(
                t is not None for t in self._slots)

    def stats(self) -> dict:
        """Engine counters. Full schema:

        Always present (int/float): `n_slots`, `n_decode_steps`,
        `n_prefills` (completed prompt prefills), `n_tokens`,
        `n_finished`, `n_failed`, `peak_active`, `mean_occupancy`.
        Always present (non-scalar): `occupancy_hist` — occupied slots
        at a decode step -> how many steps ran like that.

        Paged mode only (int): `n_prefill_chunks`, `n_backpressure`
        (admissions deferred by pool exhaustion), `n_skip_ahead`
        (admissions that jumped a deferred head), `prefill_chunk`,
        `n_preemptions` (running sequences released + re-queued) and
        `n_resumes` (preempted sequences re-admitted).

        Pageable-KV mode only: `prefix_sharing` (bool), `paged_kernel`
        (bool or None — None defers to the model config), and `pool`,
        the nested `PagedCacheManager.stats()` dict (see its docstring
        for the pool-side schema, including the retention/host-tier
        counters)."""
        with self._cv:
            occ = dict(sorted(self._occupancy_counts.items()))
            steps = self.n_decode_steps
            occ_tokens = sum(k * v for k, v in occ.items())
            out = {
                "n_slots": self.n_slots,
                "n_decode_steps": steps,
                "n_prefills": self.n_prefills,
                "n_tokens": self.n_tokens,
                "n_finished": self.n_finished,
                "n_failed": self.n_failed,
                "peak_active": self.peak_active,
                "occupancy_hist": occ,
                "mean_occupancy": occ_tokens / steps if steps else 0.0,
            }
            if self.paged:
                out["n_prefill_chunks"] = self.n_prefill_chunks
                out["n_backpressure"] = self.n_backpressure
                out["n_skip_ahead"] = self.n_skip_ahead
                out["prefill_chunk"] = self.prefill_chunk
                out["n_preemptions"] = self.n_preemptions
                out["n_resumes"] = self.n_resumes
            if self._kv_paged:
                out["prefix_sharing"] = self.prefix_sharing
                out["paged_kernel"] = self.paged_kernel
                out["pool"] = self._pcm.stats()
            return out

    # ------------------------------------------------------- the decode loop
    def _has_thread(self) -> bool:
        return self._thread is not None

    def _free_slots_locked(self) -> list[int]:
        return [i for i, t in enumerate(self._slots) if t is None]

    def _release_slot(self, slot: int) -> None:
        """Drop per-slot serving resources (prefill state, pool blocks).

        Called under the step lock (pool bookkeeping is not thread-safe);
        slot-table mutation happens separately under `_cv`.
        """
        self._prefills.pop(slot, None)
        if self._kv_paged:
            if slot in self._pcm:
                self._pcm.free(slot)
            self._lengths[slot] = 0
            # a failed/retired publisher unblocks deferred same-key
            # requests: the next one to admit becomes the new owner
            for key in [k for k, s in self._publishing.items() if s == slot]:
                del self._publishing[key]

    def _retire_locked(self, slot: int) -> None:
        self._slots[slot] = None
        self._cur[slot, 0] = self._pad_id
        self._emitted[slot] = 0
        self.n_finished += 1
        self._release_slot(slot)

    def _fail_all_locked(self) -> list[GenerationTicket]:
        """Collect every waiting + in-flight ticket and clear the engine
        state (close/abort paths). Caller finishes the tickets."""
        fail = list(self._waiting)
        fail.extend(t for t in self._slots if t is not None)
        self._waiting.clear()
        for slot, t in enumerate(self._slots):
            if t is not None:
                self._release_slot(slot)
        self._slots = [None] * self.n_slots
        self.n_failed += len(fail)
        return fail

    # ------------------------------------------------ fixed-slot admission
    def _admit(self) -> int:
        """Move waiting requests into free slots; returns tokens emitted.

        Fixed-slot path: each admission prefills the WHOLE prompt (b=1),
        writes its cache into the slot region (copy-on-admit), and emits
        the first sampled token. A request whose first token already
        retires it (EOS, or max_new_tokens=1) never occupies the slot.
        """
        emitted = 0
        while True:
            with self._cv:
                free = self._free_slots_locked()
                if not free or not self._waiting:
                    return emitted
                ticket = self._waiting.popleft()
                slot = free[0]
                # reserve while prefilling outside the lock
                self._slots[slot] = ticket
                self._stamp_queue_locked(ticket)
            try:
                logits, caches1 = self._prefill_one(ticket.prompt)
                self._caches = self._write_slot(self._caches, caches1,
                                                jnp.int32(slot))
                tok = int(self._sample(logits)[0])
            except Exception as e:  # noqa: BLE001 - fail just this ticket
                err = SchedulerError(f"prefill failed: {e}")
                err.__cause__ = e
                with self._cv:
                    self._slots[slot] = None
                    self.n_failed += 1
                ticket._finish(error=err)
                continue
            ticket.slot = slot
            emitted += self._emit_first_token(slot, ticket, tok)

    def _stamp_queue_locked(self, ticket: GenerationTicket) -> None:
        """Stamp the submit->first-admission wait; a preempted ticket
        re-admitted later keeps its first stamp."""
        if ticket.queue_s is None:
            ticket.queue_s = self._clock() - ticket.submit_time

    def _emit_first_token(self, slot: int, ticket: GenerationTicket,
                          tok: int) -> int:
        """Shared post-prefill bookkeeping: emit the first token and
        either retire immediately or enter the decode rotation."""
        with TraceAnnotation("engine.emit"):
            ticket._emit(tok)
            with self._cv:
                self.n_prefills += 1
                self.n_tokens += 1
                # len(tokens), not 1: a resumed sequence re-enters here
                # with its pre-preemption output already emitted
                if (self.eos_id is not None and tok == self.eos_id) \
                        or len(ticket.tokens) >= ticket.max_new_tokens:
                    self._retire_locked(slot)
                    finish = True
                else:
                    self._cur[slot, 0] = tok
                    self._emitted[slot] = len(ticket.tokens)
                    finish = False
            if finish:
                ticket._finish()
        return 1

    # ----------------------------------------------------- paged admission
    def _admit_paged(self) -> int:
        """Assign waiting requests to free slots, reserving their
        worst-case pool budget; returns the number admitted.

        No tokens are emitted here — prompts stream through
        `_advance_prefills` one `prefill_chunk` per step. Admission is
        FIFO with bounded skip-ahead: when the head request cannot
        reserve right now (pool exhaustion bumps `n_backpressure`; a
        prefix mid-publication defers without counting), up to
        `admit_lookahead` later requests are examined and the first
        that fits is admitted in its place (`n_skip_ahead`). After
        `max_head_skips` skips of the same head, admission reverts to
        strict FIFO until that head gets in — bounded lookahead, so a
        big request is delayed but never starved.
        """
        admitted = 0
        head_counted = False  # bump n_backpressure once per step, like PR 4
        while True:
            with self._cv:
                free = self._free_slots_locked()
                if not free or not self._waiting:
                    return admitted
                # peek only what admission can examine, not the whole queue
                waiting = list(itertools.islice(
                    self._waiting, 1 + self.admit_lookahead))
            head = waiting[0]
            if head is not self._head_ticket:
                self._head_ticket, self._head_skips = head, 0
            lookahead = (self.admit_lookahead
                         if self._head_skips < self.max_head_skips else 0)
            ticket = None
            head_deferred = False
            window = waiting[: 1 + lookahead]
            # probe highest priority first (stable within a priority, so
            # the all-default-priority case reduces to FIFO order and
            # probes exactly the candidates the pre-priority engine did)
            order = sorted(range(len(window)),
                           key=lambda i: (-window[i].priority, i))
            for i in order:
                cand = window[i]
                if self._kv_paged:
                    if (cand.prefix_key is not None
                            and cand.prefix_key in self._publishing):
                        continue  # prefix mid-publication: attach later
                    need = (int(cand.seq_prompt.size)
                            + cand.max_new_tokens - len(cand.tokens))
                    if not self._pcm.can_reserve(
                            need, prefix_key=cand.prefix_key):
                        if cand is head:
                            head_deferred = True
                        continue
                ticket = cand
                break
            with self._cv:
                if head_deferred and not head_counted:
                    self.n_backpressure += 1
                    head_counted = True
                if ticket is None:
                    return admitted
                if ticket is not head:
                    self._head_skips += 1
                    self.n_skip_ahead += 1
                else:
                    self._head_ticket, self._head_skips = None, 0
                try:
                    self._waiting.remove(ticket)
                except ValueError:  # failed/closed concurrently
                    continue
                slot = free[0]
                self._slots[slot] = ticket
                self._stamp_queue_locked(ticket)
                if ticket._resume_prompt is not None:
                    self.n_resumes += 1
            if self._kv_paged:
                need = (int(ticket.seq_prompt.size)
                        + ticket.max_new_tokens - len(ticket.tokens))
                self._pcm.reserve(slot, need, prefix_key=ticket.prefix_key)
                shared = self._pcm.shared_tokens(slot)
                self._lengths[slot] = shared
                pre = _Prefill(ticket)
                # prefix hit: the shared KV is already resident — chunked
                # prefill starts at the unique suffix
                pre.pos = shared
                if shared == 0 and ticket.prefix_key is not None:
                    self._publishing[ticket.prefix_key] = slot
                    pre.publish_key = ticket.prefix_key
                    pre.publish_span = ticket.prefix_span
            else:
                # slot-resident state (SSM / no pageable KV): chunked
                # admission streams into a private b=1 cache, written
                # into the slot on completion (copy-on-admit)
                pre = _Prefill(
                    ticket, caches1=self.model.init_caches(
                        1, self.cache_len, 0))
            self._prefills[slot] = pre
            ticket.slot = slot
            admitted += 1

    def _advance_prefills(self) -> int:
        """Advance every in-flight prefill by one `prefill_chunk` piece;
        returns pieces processed. Completed prompts emit their first
        token and join the decode rotation at this step's decode."""
        work = 0
        for slot in sorted(self._prefills):
            pre = self._prefills[slot]
            ticket = pre.ticket
            try:
                with TraceAnnotation("engine.prefill", req=ticket.request_id,
                                     pos=pre.pos):
                    done, logits = self._prefill_chunk_once(slot, pre)
                tok = int(self._sample(logits)[0]) if done else None
            except Exception as e:  # noqa: BLE001 - fail just this ticket
                err = SchedulerError(f"chunked prefill failed: {e}")
                err.__cause__ = e
                with self._cv:
                    self._release_slot(slot)
                    self._slots[slot] = None
                    self.n_failed += 1
                ticket._finish(error=err)
                continue
            work += 1
            with self._cv:
                self.n_prefill_chunks += 1
            if pre.publish_key is not None and pre.pos >= pre.publish_span:
                # the prefix KV is fully resident: publish it so identical
                # prefixes map onto these blocks from now on
                self._pcm.register_prefix(
                    pre.publish_key, slot, pre.publish_span)
                self._publishing.pop(pre.publish_key, None)
                pre.publish_key = None
            if done:
                del self._prefills[slot]
                self._emit_first_token(slot, ticket, tok)
        return work

    def _prefill_chunk_once(self, slot: int, pre: _Prefill):
        """Process the next prompt piece of one admitted sequence.

        Returns (done, logits) where `logits` is only meaningful at
        completion (the model's output at the prompt's last position).
        """
        prompt = pre.ticket.seq_prompt
        n = min(self.prefill_chunk, int(prompt.size) - pre.pos)
        if self._kv_paged:
            self._pcm.ensure(slot, pre.pos + n)
            self._cow_barrier(slot, pre.pos, pre.pos + n)
            toks = np.zeros((1, self.prefill_chunk), np.int32)
            toks[0, :n] = prompt[pre.pos : pre.pos + n]
            # narrow the gather window to the blocks this chunk can see,
            # bucketed to powers of two so at most log2(max_blocks)
            # prefill shapes ever compile — without this every chunk
            # attends (and gathers) the full table-width window, which
            # is where a paged engine would lose prefill throughput to
            # the fixed-slot one
            table = self._pcm.tables([slot])
            need = blocks_for(pre.pos + n, self.block_size)
            table = table[:, : min(pow2_at_least(need), table.shape[1])]
            logits, self._pools = self._paged_step(
                self.params, self._pools,
                jnp.asarray(table),
                jnp.asarray([pre.pos], jnp.int32),
                jnp.asarray(toks),
                jnp.asarray([n], jnp.int32))
            pre.pos += n
            self._lengths[slot] = pre.pos
        else:
            logits = None
            for t in range(pre.pos, pre.pos + n):
                logits, pre.caches1 = self._decode(
                    self.params, pre.caches1,
                    jnp.asarray(prompt[None, t : t + 1], jnp.int32))
            pre.pos += n
        done = pre.pos == int(prompt.size)
        if done and not self._kv_paged:
            self._caches = self._write_slot(self._caches, pre.caches1,
                                            jnp.int32(slot))
        return done, logits

    # ---------------------------------------------------------- decode step
    def _decode_once(self) -> int:
        """One batched decode step over every occupied, non-prefilling
        slot.

        Paged KV lanes carry no per-slot device state (everything lives
        in the shared pools, addressed through block tables), so the
        decode batch is COMPACTED host-side: only active rows are fed,
        padded up to a power-of-two width — a half-empty engine stops
        paying full-width attention, at the cost of at most
        log2(n_slots) compiled decode shapes. Slot-resident caches are
        positional, so that mode always decodes the full width.
        """
        with TraceAnnotation("engine.decode"):
            with self._cv:
                active = [(i, t) for i, t in enumerate(self._slots)
                          if t is not None and i not in self._prefills]
                if not active:
                    return 0
                cur = self._cur.copy()
            if self._kv_paged:
                idx = [i for i, _ in active]
                for i in idx:
                    # lazy append: take a block only when the next position
                    # crosses into one (guaranteed by the reservation); then
                    # detach any block a later prefix hit is still sharing
                    # (the mid-decode divergence half of copy-on-write)
                    li = int(self._lengths[i])
                    self._pcm.ensure(i, li + 1)
                    self._cow_barrier(i, li, li + 1)
                width = min(pow2_at_least(len(idx)), self.n_slots)
                tables = self._pcm.tables(idx + [None] * (width - len(idx)))
                lengths = np.zeros((width,), np.int32)
                lengths[: len(idx)] = self._lengths[idx]
                toks = np.full((width, 1), self._pad_id, np.int32)
                toks[: len(idx), 0] = cur[idx, 0]
                n_valid = np.zeros((width,), np.int32)
                n_valid[: len(idx)] = 1
                logits, self._pools = self._paged_step(
                    self.params, self._pools, jnp.asarray(tables),
                    jnp.asarray(lengths), jnp.asarray(toks),
                    jnp.asarray(n_valid))
            else:
                logits, self._caches = self._decode(
                    self.params, self._caches, jnp.asarray(cur))
        nxt = self._sample(logits)
        with TraceAnnotation("engine.emit"):
            finished: list[GenerationTicket] = []
            emitted = 0
            with self._cv:
                self.n_decode_steps += 1
                n_active = len(active)
                self._occupancy_counts[n_active] = \
                    self._occupancy_counts.get(n_active, 0) + 1
                for row, (slot, ticket) in enumerate(active):
                    if self._slots[slot] is not ticket:  # failed concurrently
                        continue
                    if self._kv_paged:
                        self._lengths[slot] += 1
                    tok = int(nxt[row if self._kv_paged else slot])
                    ticket._emit(tok)
                    emitted += 1
                    self.n_tokens += 1
                    self._emitted[slot] += 1
                    if (self.eos_id is not None and tok == self.eos_id) or \
                            self._emitted[slot] >= ticket.max_new_tokens:
                        self._retire_locked(slot)
                        finished.append(ticket)
                    else:
                        self._cur[slot, 0] = tok
            for ticket in finished:
                ticket._finish()
        return emitted

    # ------------------------------------------------- priority preemption
    def _preempt_locked(self, priority_below: Optional[int] = None) -> bool:
        """Preempt one running sequence; caller holds the step lock.

        Victim: the decode-phase slot (mid-prefill sequences are never
        preempted — their first token is imminent) with the LOWEST
        priority, tie-broken by smallest resident length (cheapest to
        resume). With `priority_below`, only a victim of strictly lower
        priority qualifies. Returns True when a sequence was preempted.

        Pageable-KV mode publishes the victim's resident KV span under
        its content hash BEFORE freeing the blocks, so with retention
        enabled resumption is a prefix re-attach + one-token suffix
        prefill instead of a full re-prefill (bit-identical KV either
        way — the span is re-derived from the same tokens).
        """
        with self._cv:
            cands = [
                (t.priority,
                 int(self._lengths[s]) if self._kv_paged else 0, s, t)
                for s, t in enumerate(self._slots)
                if t is not None and s not in self._prefills]
        if not cands:
            return False
        pri, _, slot, ticket = min(cands)
        if priority_below is not None and pri >= priority_below:
            return False
        full = np.concatenate(
            [ticket.prompt, np.asarray(ticket.tokens, np.int32)])
        key, span = None, 0
        if self._kv_paged:
            # resident KV covers full[:lengths] (the newest token's KV is
            # written on its NEXT decode step) — exactly the default
            # shareable span of the resume prompt, so the re-admission's
            # content key matches this publication
            span = int(self._lengths[slot])
            if span >= self.block_size:
                key = hashlib.sha1(full[:span].tobytes()).hexdigest()
                self._pcm.register_prefix(key, slot, span)
        self._release_slot(slot)
        with self._cv:
            self._slots[slot] = None
            self._cur[slot, 0] = self._pad_id
            self._emitted[slot] = 0
            ticket._resume_prompt = full
            ticket.prefix_key, ticket.prefix_span = key, span
            ticket.slot = None
            ticket.n_preempted += 1
            self.n_preemptions += 1
            self._waiting.append(ticket)
            self._cv.notify_all()
        return True

    def preempt(self, priority_below: Optional[int] = None) -> bool:
        """Release the lowest-priority running sequence's slot and pool
        blocks and re-queue it to resume later (see `_preempt_locked`).
        Paged engines only — fixed-slot mode has no block pool to
        release into and returns False. Returns True when a sequence
        was preempted."""
        if not self.paged:
            return False
        with self._step_lock:
            return self._preempt_locked(priority_below)

    def preempt_for_waiting(self, max_preemptions: int = 1) -> int:
        """Preempt lower-priority running sequences so the best waiting
        request can admit; returns preemptions performed (<=
        `max_preemptions`).

        The policy half of preemption (the SLO controller's actuator):
        take the highest-priority request in the admission window; when
        it is blocked — no free slot, or (pageable KV) its reservation
        cannot be covered — preempt a strictly lower-priority running
        sequence and re-check, so preemption fires only under real
        pressure and never on behalf of an equal-or-lower priority.
        """
        if not self.paged:
            return 0
        done = 0
        while done < max_preemptions:
            with self._step_lock:
                with self._cv:
                    window = list(itertools.islice(
                        self._waiting, 1 + self.admit_lookahead))
                    free = self._free_slots_locked()
                if not window:
                    return done
                top = max(window, key=lambda t: t.priority)
                if top.prefix_key is not None \
                        and top.prefix_key in self._publishing:
                    return done  # attaches once publication lands
                blocked = not free
                if not blocked and self._kv_paged:
                    need = (int(top.seq_prompt.size)
                            + top.max_new_tokens - len(top.tokens))
                    blocked = not self._pcm.can_reserve(
                        need, prefix_key=top.prefix_key)
                if not blocked:
                    return done
                if not self._preempt_locked(priority_below=top.priority):
                    return done
            done += 1
        return done

    def set_admit_lookahead(self, n: int) -> None:
        """Retune the paged admission skip-ahead bound live (an SLO
        controller actuator). No-op on non-paged engines."""
        if n < 0:
            raise ValueError("admit_lookahead must be >= 0")
        if not self.paged:
            return
        with self._cv:
            self.admit_lookahead = int(n)

    def pop_completions(self) -> list[tuple]:
        """Drain finished-request latency samples: a list of
        `(finish_clock, tenant, priority, ttft_s, e2e_s)` tuples, oldest
        first. Successful requests only; each sample is handed out
        exactly once (the SLO controller's measurement feed)."""
        with self._cv:
            out = list(self._completions)
            self._completions.clear()
        return out

    def step(self) -> int:
        """Admit waiting requests, advance prefills, run one decode step.

        Returns the work done: tokens emitted plus (paged mode) prefill
        pieces processed. 0 means the engine is idle. Manual-mode entry
        point; the background loop calls the same path.
        """
        with self._step_lock, TraceAnnotation("engine.step") as span:
            if self.paged:
                with TraceAnnotation("engine.admit"):
                    self._admit_paged()
                chunks = self._advance_prefills()
            else:
                with TraceAnnotation("engine.admit"):
                    chunks = self._admit()  # whole-prompt prefills
            with self._cv:
                self.peak_active = max(
                    self.peak_active,
                    sum(t is not None for t in self._slots))
            rows = self._decode_once()
            span.set_metadata(prefill_chunks=chunks, decode_rows=rows)
            return chunks + rows

    def run_until_drained(self, max_steps: Optional[int] = None) -> int:
        """step() until no work remains; returns total work units."""
        total = 0
        steps = 0
        while True:
            got = self.step()
            total += got
            steps += 1
            if got == 0:
                with self._cv:
                    if not self._waiting and \
                            all(t is None for t in self._slots):
                        return total
            if max_steps is not None and steps >= max_steps:
                return total

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._closed and not self._waiting \
                        and all(t is None for t in self._slots):
                    with TraceAnnotation("engine.idle"):
                        self._cv.wait()
                if self._closed:
                    idle = not self._waiting and \
                        all(t is None for t in self._slots)
                    if idle or not self._drain_on_close:
                        fail = self._fail_all_locked()
                        self._cv.notify_all()
                        closing = True
                    else:
                        closing = False
                else:
                    closing = False
            if closing:
                err = SchedulerError("engine closed without draining")
                for t in fail:
                    t._finish(error=err)
                return
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 - decode died: fail loudly
                # a decode/sample error must not kill the daemon thread
                # silently — every in-flight and waiting consumer would
                # block forever. Fail every ticket and shut down.
                err = SchedulerError(f"decode loop failed: {e}")
                err.__cause__ = e
                with self._cv:
                    self._closed = True
                    fail = self._fail_all_locked()
                    self._cv.notify_all()
                for t in fail:
                    t._finish(error=err)
                return

    def close(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Stop accepting work and shut down; idempotent.

        drain=True finishes every admitted and waiting request first;
        drain=False fails them with SchedulerError. In manual mode
        draining runs `run_until_drained()` on the calling thread.
        """
        with self._cv:
            self._closed = True
            self._drain_on_close = drain
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        elif drain:
            self.run_until_drained()
        else:
            with self._step_lock, self._cv:
                fail = self._fail_all_locked()
            err = SchedulerError("engine closed without draining")
            for t in fail:
                t._finish(error=err)

    def __enter__(self) -> "ContinuousBatchingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))
