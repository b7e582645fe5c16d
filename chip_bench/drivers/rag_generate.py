"""Driver: open-loop RAG requests through the program's serving path.

The window drives what `launch/serve.serve_rag_open_loop_generate`
wires together, with the program's own pieces: an `AsyncBatchScheduler`
over `RagPipeline.search_batch` (padded by `launch/serve._padded_search`
to one static batch), `RagPipeline.encode_prompt_with_prefix` on each
completed retrieval, `ContinuousBatchingEngine.submit` with the
retrieved-context prefix, and every token read through
`GenerationTicket.token_stream()` and stamped on the host clock. Each
request is timed from when it was due, not from when it was sent.

The engine is paged (`EngineConfig(paged=True)`) with the configuration's
slots and pool; the fused kernel comes from the configuration's
`program.paged_kernel`; every other knob is the program's default.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time
from typing import Optional

import numpy as np

from chip_bench import corpus as corpus_mod
from chip_bench import costs, trace, traffic, weights
from chip_bench.reference import decoder_lm, search as ref_search

DRAIN_S = 60.0        # how long answers due in the window may come late
N_WARM_GROUPS = 2


def as_run(c: dict) -> dict:
    """The configuration with its `departures` (what the program runs in
    place of the source's values) put over the published keys."""
    run = {**c, **c.get("departures", {})}
    if run.get("partial_rotary_factor", 1.0) != 1.0 or run.get(
            "rope_scaling"):
        raise ValueError("the program rotates every head dimension with "
                         "plain RoPE; the configuration asks for "
                         f"{run.get('partial_rotary_factor')} with "
                         f"{run.get('rope_scaling')}")
    return run


def model_config(c: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig

    c = as_run(c)
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"], norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu",
        rope_style="standard", rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"], param_dtype="bfloat16",
        compute_dtype="bfloat16",
        paged_kernel=bool(c["program"]["paged_kernel"]))


@dataclasses.dataclass
class Request:
    spec: traffic.RagRequest
    query: str
    due: float = 0.0
    submitted: float = 0.0
    retrieval: Optional[object] = None   # AsyncTicket
    prompt: Optional[list] = None
    gen: Optional[object] = None         # GenerationTicket
    stamps: list = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    consumer: Optional[threading.Thread] = None
    # what the check reads, copied off the tickets once the window is over
    doc_ids: Optional[np.ndarray] = None
    wait_ms: Optional[float] = None
    tokens: Optional[list] = None
    finished: bool = False


class State:
    pass


def setup(cell, seed: int, seconds: float) -> State:
    import jax

    import repro.configs.dirc_rag as dirc_rag
    from repro.launch.serve import _padded_search
    from repro.models import build_model
    from repro.serving import (AsyncBatchScheduler, EngineConfig,
                               HashEmbedder, RagPipeline)

    c, mix = cell.config, cell.traffic
    st = State()
    st.cell, st.seed = cell, seed
    st.reqs_spec = traffic.rag_requests(mix, seconds)
    idx = c["index"]
    st.corpus = corpus_mod.rag_corpus(idx["n_docs"], st.reqs_spec, mix, seed,
                                      n_warm=N_WARM_GROUPS)
    st.model_cfg = model_config(c)
    model = build_model(st.model_cfg)
    params = weights.make(c, seed)
    weights.check_layout(params, model)
    jax.block_until_ready(params)
    st.pipe = RagPipeline(
        st.corpus.texts, getattr(dirc_rag, idx["retrieval"]), model=model,
        params=params,
        dim=idx["dim"], embedder=HashEmbedder(dim=idx["dim"]),
        max_prompt_len=mix["max_prompt_len"], n_shards=idx["n_shards"])
    del params
    sch = c["scheduler"]
    st.max_batch = sch["max_batch"]
    padded = _padded_search(st.pipe, st.max_batch)

    def search(texts, k):
        if not st.traced:
            return padded(texts, k)
        with trace.span("cb.search"):
            return padded(texts, k)

    st.traced = False
    st.sched = AsyncBatchScheduler(search, max_batch=st.max_batch,
                                   max_wait_ms=sch["max_wait_ms"], start=True)
    eng = c["engine"]
    config = EngineConfig(n_slots=eng["n_slots"], paged=True,
                          n_blocks=eng["pool_tokens"] // 16 + 1)
    st.engine = st.pipe.decode_engine(
        config, max_new_tokens=mix["answer_tokens"]["max"], start=True)
    st.steps = []
    _instrument(st)
    _warm_up(st)
    return st


def _instrument(st: State) -> None:
    """Wrap the engine's step program so a traced run can name each
    launch (prefill chunk or decode step) and count its work. The
    wrapper only adds host spans and host copies of two small inputs,
    and only while the run is traced."""
    inner = st.engine._paged_step

    def step(params, pools, table, lengths, toks, n_valid):
        if not st.traced:
            return inner(params, pools, table, lengths, toks, n_valid)
        kind = "cb.prefill" if toks.shape[1] > 1 else "cb.decode"
        st.steps.append((time.perf_counter(), kind, np.asarray(lengths),
                         np.asarray(n_valid)))
        with trace.span(kind):
            return inner(params, pools, table, lengths, toks, n_valid)

    st.engine._paged_step = step


def _warm_up(st: State) -> None:
    """Compile every shape the window uses, through the window's objects:
    the padded search, each prefill window up to the longest prompt, and
    each decode width up to the slot count."""
    pipe, eng = st.pipe, st.engine
    tok = pipe.tokenizer
    group = st.corpus.warm_groups[0]
    q = corpus_mod.question(["warmup"])
    st.sched.submit(q, k=st.cell.traffic["top_k"]).result(timeout=600)
    longest = max(1 + sum(r.passages) + len(r.passages)
                  + len(tok.encode(q, bos=False)) for r in st.reqs_spec)
    longest = min(longest, st.cell.traffic["max_prompt_len"])
    base, _ = pipe.encode_prompt_with_prefix(
        q, [pipe.doc_texts[i] for i in group])
    long_prompt = (base * (1 + longest // len(base)))[:longest]
    eng.submit(long_prompt, max_new_tokens=2).result(timeout=900)
    n = eng.n_slots
    short = base[:24]
    tickets = [eng.submit([(t + i) % 250 for t in short],
                          max_new_tokens=2 + i) for i in range(n)]
    for t in tickets:
        t.result(timeout=900)


def _consume(req: Request) -> None:
    try:
        for _ in req.gen.token_stream():
            req.stamps.append(time.perf_counter())
    except Exception as e:  # noqa: BLE001 - recorded, fails the run's check
        req.error = f"generation failed: {e}"


def window(st: State, seconds: float, traced: bool) -> dict:
    """Drive the window; returns the host records."""
    st.traced = traced
    pipe, eng, mix = st.pipe, st.engine, st.cell.traffic
    reqs = [Request(spec=s, query=q)
            for s, q in zip(st.reqs_spec, st.corpus.queries)]
    pacer = traffic.Pacer()
    stats0 = st.sched.stats()
    compiles = _CompileCounter()

    def on_retrieved(req: Request, rt) -> None:
        try:
            texts = [pipe.doc_texts[i] for i in rt.doc_ids if i >= 0]
            prompt, prefix_len = pipe.encode_prompt_with_prefix(rt.text,
                                                                texts)
            req.prompt = prompt
            req.gen = eng.submit(prompt, max_new_tokens=req.spec.answer,
                                 prefix_len=prefix_len)
            req.consumer = threading.Thread(target=_consume, args=(req,),
                                            daemon=True)
            req.consumer.start()
        except Exception as e:  # noqa: BLE001 - recorded, fails the check
            req.error = f"retrieval or submit failed: {e}"

    def submit(i: int) -> None:
        req = reqs[i]
        req.due, req.submitted = pacer.due[i], pacer.submitted[i]
        req.retrieval = st.sched.submit(req.query, k=mix["top_k"])
        req.retrieval.add_done_callback(lambda rt: on_retrieved(req, rt))

    with compiles, trace.span("cb.window"):
        t0 = pacer.run([r.spec.due for r in reqs], submit)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    t_end = t0 + seconds
    stats1 = st.sched.stats()
    deadline = t_end + DRAIN_S
    for req in reqs:
        while req.consumer is None and req.error is None \
                and time.perf_counter() < deadline:
            time.sleep(0.01)
        if req.consumer is not None:
            req.consumer.join(max(0.0, deadline - time.perf_counter()))
            if req.consumer.is_alive():
                req.error = "no answer within the drain time"
        elif req.error is None:
            req.error = "never reached the engine"
    st.traced = False
    eos = eng.eos_id
    for req in reqs:
        _settle(req, eos)
    return {"reqs": reqs, "t0": t0, "t_end": t_end, "lag_ms": pacer.lag_ms(),
            "sched0": stats0, "sched1": stats1,
            "compiles_in_window": compiles.n, "steps": list(st.steps)}


class _CompileCounter:
    """Counts backend compilations between enter and exit."""

    def __enter__(self):
        import jax

        self.n = 0
        self._on = True

        def listen(event, duration, **kw):
            if self._on and event.endswith("backend_compile_duration"):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        self._on = False


def _settle(req: Request, eos) -> None:
    """Copy what the check needs off the request's tickets and drop them,
    so that nothing of the window keeps the program's state alive. A
    request is finished when it produced its tokens (fewer only after an
    EOS) and every one of them reached the client."""
    rt, gen = req.retrieval, req.gen
    if rt is not None and rt.wait_s is not None:
        req.wait_ms = 1e3 * rt.wait_s
        req.doc_ids = np.asarray(getattr(rt, "doc_ids", []))
    if gen is not None and gen.done() and gen._error is None:
        req.tokens = [int(t) for t in gen.tokens]
        n = len(req.tokens)
        req.finished = (req.error is None and n > 0
                        and len(req.stamps) == n
                        and (n == req.spec.answer or req.tokens[-1] == eos))
    req.retrieval = req.gen = req.consumer = None


def _failed(req: Request) -> bool:
    return not req.finished


def end_to_end(st: State, rec: dict) -> dict:
    reqs, t0, t_end = rec["reqs"], rec["t0"], rec["t_end"]
    ttft = [1e3 * (r.stamps[0] - r.due) if r.stamps else np.inf
            for r in reqs]
    gaps = [1e3 * (b - a) for r in reqs for a, b in zip(r.stamps,
                                                      r.stamps[1:])
            if t0 <= b <= t_end]
    n_tok = sum(1 for r in reqs for s in r.stamps if t0 <= s <= t_end)
    return {"ttft_p90_ms": float(np.percentile(ttft, 90)),
            "itl_p95_ms": float(np.percentile(gaps, 95)) if gaps else np.inf,
            "output_tok_s": n_tok / (t_end - t0)}


def backlog(rec: dict) -> dict:
    """Whether the queue grew across the window, for the sweep that finds
    the knee: requests due but without a first token at the window's
    end, and the median TTFT of the window's first and second halves."""
    reqs, t0, t_end = rec["reqs"], rec["t0"], rec["t_end"]
    mid = (t0 + t_end) / 2
    waiting = sum(1 for r in reqs if r.due <= t_end
                  and (not r.stamps or r.stamps[0] > t_end))

    def med(rs):
        v = [1e3 * (r.stamps[0] - r.due) for r in rs if r.stamps]
        return float(np.median(v)) if v else None

    return {"waiting_at_end": waiting,
            "ttft_p50_first_half_ms": med([r for r in reqs if r.due < mid]),
            "ttft_p50_second_half_ms": med([r for r in reqs
                                            if r.due >= mid])}


def counts(rec: dict) -> tuple:
    reqs = rec["reqs"]
    return len(reqs), sum(_failed(r) for r in reqs)


def release(st: State) -> None:
    """Close the program's objects and drop them, so that the reference
    runs on a device that holds nothing of the program."""
    st.sched.close(drain=True)
    st.engine.close(drain=True)
    st.sched = st.engine = st.pipe = None
    gc.collect()


def _sample(st: State, rec: dict) -> list:
    """The finished requests the check compares: the one with the most
    served tokens and others drawn from the seed."""
    done = [r for r in rec["reqs"] if not _failed(r)]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    others = [i for i in range(len(done)) if i != longest]
    n = min(st.cell.traffic["check_requests"], len(done))
    rng = traffic.seeded_rng(st.seed, 5)
    pick = [longest] + list(rng.choice(others, size=n - 1, replace=False))
    return [done[i] for i in pick]


def _logit_gap(st: State, sample: list, precision: str) -> float:
    """Widest gap, over the sample's served tokens, of a token's float32
    reference logit below the reference's best. With `precision="fp8"`
    (the control) the served token is the fp8 reference's first choice
    at each position of the same prompt and tokens."""
    c = as_run(st.cell.config)
    params = weights.make(c, st.seed)
    lgap = 0.0
    for r in sample:
        toks = list(r.prompt) + r.tokens[:-1]
        first = len(r.prompt) - 1
        ref = decoder_lm.logits(c, params, toks, first)
        served = r.tokens
        if precision != "f32":
            served = decoder_lm.logits(c, params, toks, first,
                                       precision=precision).argmax(-1)
        lgap = max(lgap, float(decoder_lm.served_gaps(ref, served).max()))
    return lgap


def _mismatches(st: State, sample: list) -> float:
    """Sampled requests whose retrieved ids are not the exact int8 top-k
    of the reference's own embedding of the corpus and the question."""
    c, k = st.cell.config, st.cell.traffic["top_k"]
    emb = ref_search.HashEmbedder(dim=c["index"]["dim"])
    docs = emb.embed(st.corpus.texts)
    queries = emb.embed([r.query for r in sample])
    want = ref_search.ExactIndex(docs).topk(queries, k)
    return float(sum(set(np.asarray(r.doc_ids).tolist()) != set(w.tolist())
                     for r, w in zip(sample, want)))


def check(st: State, rec: dict) -> list:
    """[(name, value, limit)]: the retrieval top-k and the served tokens
    of a sample of the window's requests against the plain reference.
    Retrieval is compared exactly (PERF.md gives why no gap is compared
    there)."""
    limits = st.cell.config["correct"]
    failed = sum(_failed(r) for r in rec["reqs"])
    out = [("failed_requests", float(failed), 0.0)]
    sample = _sample(st, rec)
    miss = _mismatches(st, sample) if sample else np.inf
    lgap = _logit_gap(st, sample, "f32") if sample else np.inf
    return out + [("retrieval_mismatches", miss, 0.0),
                  ("logit_gap", lgap, limits["logit_gap"])]


def control(st: State, rec: dict) -> dict:
    """The control's reading on the check's own sample."""
    return {"logit_gap": _logit_gap(st, _sample(st, rec), "fp8")}


def layer_context(st: State, rec: dict) -> dict:
    """What the per-layer readers take from this driver: host records
    and the work of each traced step."""
    t0, t_end = rec["t0"], rec["t_end"]
    cfg = st.model_cfg
    steps = [(kind, ln, nv) for t, kind, ln, nv in rec["steps"]
             if t0 <= t <= t_end]
    attn = [costs.paged_attend_call(ln, nv, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.resolved_head_dim)
            for kind, ln, nv in steps for _ in range(cfg.n_layers)]
    return {
        "lag_ms": rec["lag_ms"],
        "retrieval_wait_ms": np.array([r.wait_ms for r in rec["reqs"]
                                       if r.wait_ms is not None]),
        "paged_attend_work": attn,
        "step_flops": sum(costs.decoder_step_flops(cfg, ln, nv)
                          for kind, ln, nv in steps),
        "n_steps": {k: sum(1 for s in steps if s[0] == k)
                    for k in ("cb.prefill", "cb.decode")},
    }
