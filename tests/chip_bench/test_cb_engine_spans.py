"""The program's own trace spans and request stamps: a tiny paged engine
and a retrieval scheduler traced with `chip_bench.trace.profile`, read
back with `chip_bench.attribute.load_program_spans`; the engine's
`request_id` and `queue_s` stamps on a fake clock, through a preemption
and its resume."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench import attribute, trace
from repro.serving import (AsyncBatchScheduler, ContinuousBatchingEngine,
                           EngineConfig)

ENGINE_NAMES = {"engine.step", "engine.admit", "engine.prefill",
                "engine.decode", "engine.sample", "engine.emit",
                "engine.idle"}


class PagedSumModel:
    """Next token = (sum of the whole history read back from the pool)
    % vocab: a paged model small enough to compile in a moment."""

    def __init__(self, vocab: int = 97):
        self.cfg = SimpleNamespace(vocab_size=vocab)
        self.vocab = vocab

    def init_caches(self, batch, cache_len, prefix_len):
        return {"sum": jnp.zeros((batch,), jnp.int32)}

    def decode_step(self, params, caches, token):
        s = caches["sum"] + token[:, 0]
        return jax.nn.one_hot(s % self.vocab, self.vocab), {"sum": s}

    def init_paged_caches(self, n_blocks, block_size):
        return jnp.zeros((n_blocks, block_size), jnp.int32)

    def paged_step(self, params, pools, tables, lengths, tokens, n_valid):
        b, t = tokens.shape
        bs, mb = pools.shape[1], tables.shape[1]
        pos = lengths[:, None] + jnp.arange(t)[None, :]
        valid = jnp.arange(t)[None, :] < n_valid[:, None]
        blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, mb - 1),
                                  axis=1)
        pools = pools.at[jnp.where(valid, blk, 0),
                         jnp.where(valid, pos % bs, 0)].set(tokens)
        wpos = (jnp.arange(mb)[:, None] * bs + jnp.arange(bs)[None, :])[None]
        mask = wpos < (lengths + jnp.maximum(n_valid, 1))[:, None, None]
        total = jnp.sum(jnp.where(mask, pools[tables], 0), axis=(1, 2))
        return jax.nn.one_hot(total % self.vocab, self.vocab), pools


CFG = EngineConfig(n_slots=2, cache_len=64, paged=True, block_size=4,
                   n_blocks=33, prefill_chunk=4, retain_blocks=4,
                   prefix_sharing=True)
PROMPTS = [np.arange(1, 14, dtype=np.int32), np.arange(20, 26, dtype=np.int32),
           np.arange(30, 39, dtype=np.int32)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three requests through a background engine loop, traced."""
    tmp = tmp_path_factory.mktemp("spans")
    eng = ContinuousBatchingEngine(PagedSumModel(), {}, CFG, start=True)
    eng.submit(PROMPTS[1] + 50, max_new_tokens=2).result(timeout=120)
    with trace.profile(str(tmp)):
        tickets = [eng.submit(PROMPTS[0], max_new_tokens=5)]
        tickets[0].result(timeout=120)
        time.sleep(0.05)            # the loop waits for work meanwhile
        tickets += [eng.submit(p, max_new_tokens=5) for p in PROMPTS[1:]]
        for t in tickets:
            t.result(timeout=120)
        eng.close()
    return tickets, attribute.load_program_spans(trace.find_xplane(str(tmp)))


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(child, parent):
    return (child["thread"] == parent["thread"]
            and parent["start"] <= child["start"]
            and child["start"] + child["dur"]
            <= parent["start"] + parent["dur"])


def test_every_engine_span_is_in_the_trace(traced):
    _, spans = traced
    assert {s["name"] for s in spans} >= ENGINE_NAMES


def test_every_sample_lies_inside_a_step_on_its_thread(traced):
    _, spans = traced
    steps = _named(spans, "engine.step")
    samples = _named(spans, "engine.sample")
    assert samples
    assert all(any(_inside(s, p) for p in steps) for s in samples)


def test_prefill_spans_carry_the_ticket_request_id(traced):
    tickets, spans = traced
    by_req: dict = {}
    for s in _named(spans, "engine.prefill"):
        by_req.setdefault(s["args"]["req"], []).append(s["args"]["pos"])
    assert set(by_req) == {t.request_id for t in tickets}
    for t, prompt in zip(tickets, PROMPTS):
        assert by_req[t.request_id] == list(range(0, prompt.size, 4))


def test_step_args_count_the_chunks_and_rows_it_ran(traced):
    tickets, spans = traced
    steps = _named(spans, "engine.step")
    prefills = _named(spans, "engine.prefill")
    for st in steps:
        assert st["args"]["prefill_chunks"] == sum(
            _inside(p, st) for p in prefills)
    # every token but a request's first comes from a decode row
    assert sum(st["args"]["decode_rows"] for st in steps) == sum(
        len(t.tokens) - 1 for t in tickets)


def test_scheduler_flush_span_counts_its_rows(tmp_path):
    def search(texts, k):
        return np.zeros((len(texts), k), np.int32), np.zeros((len(texts), k))

    sched = AsyncBatchScheduler(search, max_batch=8)
    with trace.profile(str(tmp_path)):
        tickets = [sched.submit(f"q{i}", k=2) for i in range(3)]
        sched.flush()
    assert all(t.done() for t in tickets)
    flushes = _named(attribute.load_program_spans(
        trace.find_xplane(str(tmp_path))), "sched.flush")
    assert [s["args"]["rows"] for s in flushes] == [3]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "fixed"])
def test_stamps_are_ordered_on_a_fake_clock(paged):
    clock = FakeClock()
    cfg = CFG if paged else EngineConfig(n_slots=1, cache_len=64,
                                         paged=False)
    eng = ContinuousBatchingEngine(PagedSumModel(), {}, cfg, clock=clock)
    tickets = [eng.submit(p, max_new_tokens=3) for p in PROMPTS]
    assert [t.request_id for t in tickets] == [0, 1, 2]
    assert all(t.queue_s is None for t in tickets)
    while not all(t.done() for t in tickets):
        clock.t += 0.25
        eng.step()
    eng.close()
    for t in tickets:
        assert 0 <= t.queue_s <= t.first_token_s <= t.wait_s
    assert max(t.queue_s for t in tickets) > 0  # one waited for a slot


def test_queue_stamp_survives_a_preemption_and_resume():
    clock = FakeClock()
    eng = ContinuousBatchingEngine(PagedSumModel(), {}, CFG, clock=clock)
    t = eng.submit(PROMPTS[0], max_new_tokens=12)
    clock.t = 1.0
    for _ in range(6):          # 4 prefill chunks, then decode
        eng.step()
    assert t.queue_s == 1.0 and len(t.tokens) >= 2
    assert eng.preempt() is True
    clock.t = 3.0
    eng.run_until_drained()
    assert eng.stats()["n_resumes"] == 1 and t.n_preempted == 1
    assert t.queue_s == 1.0
    assert t.queue_s <= t.first_token_s <= t.wait_s == 3.0
    eng.close()
