"""Chip smoke test: the RAG serving path at phi4-mini's full width on a TPU.

    python3 chip_smoke.py                # one chip (the default)
    python3 chip_smoke.py --four-chips   # the paths that span four chips

One chip: builds a `RagPipeline` through `launch.serve.build_rag_pipeline`
with phi4-mini-3.8b at its published widths (32 layers, d_model 3072,
seeded random bf16 weights) over the paper's 4 MB INT8 corpus operating
point (8,192 documents at dim 512, 4 shards), then

  1. checks the fused Pallas paged-attention kernel against the gather
     path (`paged_kernel=False`) on one prompt: logits after the chunked
     prefill and after the first decode step;
  2. serves 16 requests through `serve_rag_open_loop_generate` — async
     retrieval scheduler, paged continuous-batching engine, the kernel
     compiled by Mosaic — greedy, 32 new tokens each, and requires every
     request to finish;
  3. checks that every probed corpus document retrieves itself first.

Four chips (`--four-chips`): an `EngineRouter` of four full-width
replicas, one per chip, against a one-replica run of the same 16
requests (greedy tokens must match), and `ShardedDircIndex` shard_map
retrieval over a four-device macro mesh against a monolithic
`DircRagIndex` (top-k ids must match). Nothing else runs.

Every phase runs in this one process, which holds the chip(s). Any
failure exits non-zero; the last line of a passing run is one JSON
object naming the device. Rates printed here are smoke figures, not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARCH = "phi4-mini-3.8b"
# The paper's operating point (configs/dirc_rag.py): a 4 MB INT8 database
# of dim-512 embeddings is 8,192 documents.
N_DOCS, DIM, N_SHARDS = 8192, 512, 4
N_REQUESTS, NEW_TOKENS, TOP_K = 16, 32, 3
MAX_PROMPT_LEN = 512
BLOCK_SIZE, PREFILL_CHUNK = 16, 32
# KV pool: 8,192 tokens x 128 KiB (32 layers x 8 KV heads x 128 x K,V x
# bf16) = 1 GiB. No pool jit donates, so each step holds two copies next
# to the 7.2 GiB of weights.
POOL_TOKENS = 8192
# Kernel-vs-gather logit tolerance, as a fraction of the largest |logit|
# of the gather path: 2**-4 of it is 8 bf16 ulps of the largest logit.
# Both paths round the residual stream to bf16 after each of the 32
# layers and emit bf16 logits; they differ only in attention arithmetic
# (fp32 q.k in the kernel, bf16 q.k with fp32 accumulation in the gather
# path), which moves each layer's output by about one bf16 ulp. Such
# discrepancies add up over 32 layers like a random walk, to about
# sqrt(32) ~ 6 ulps. A kernel that reads a wrong block or mask changes
# the attention output itself, not its last bits.
LOGIT_TOL_FRAC = 2.0**-4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def load_repo():
    """Put the checkout's `src/` on the path; fail without it."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's default device is {dev.platform!r} "
             f"({dev.device_kind}); this check runs on a TPU only")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, JAX sees {len(devices)}")
    log(f"device: {dev.device_kind} x{len(devices)} "
        f"(platform {dev.platform})")
    return devices


def build_pipeline(seed: int):
    from repro.launch.serve import build_rag_pipeline

    t0 = time.perf_counter()
    pipe = build_rag_pipeline(
        n_docs=N_DOCS, n_shards=N_SHARDS, dim=DIM, seed=seed, arch=ARCH,
        smoke=False, max_prompt_len=MAX_PROMPT_LEN)
    import jax

    jax.block_until_ready(pipe.engine.params)
    cfg = pipe.engine.model.cfg
    log(f"config: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"~{cfg.param_count() / 1e9:.2f}B params")
    log(f"phase build: {time.perf_counter() - t0:.1f} s (corpus "
        f"{N_DOCS} docs x dim {DIM}, {N_SHARDS} shards; weight init "
        f"compile included)")
    return pipe


def engine_config(kernel: bool = True):
    from repro.serving import EngineConfig

    return EngineConfig(
        n_slots=N_REQUESTS, paged=True, block_size=BLOCK_SIZE,
        n_blocks=POOL_TOKENS // BLOCK_SIZE + 1,
        prefill_chunk=PREFILL_CHUNK, paged_kernel=kernel)


def rag_prompts(pipe, seed: int):
    """(prompt ids, shareable prefix length) for N_REQUESTS corpus queries,
    retrieved through the pipeline's own index."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    qids = rng.choice(len(pipe.doc_texts), size=N_REQUESTS, replace=False)
    texts = [pipe.doc_texts[i] for i in qids]
    ids, _ = pipe.search_batch(texts, TOP_K)
    return [pipe.encode_prompt_with_prefix(
        t, [pipe.doc_texts[i] for i in row if i >= 0])
        for t, row in zip(texts, ids)]


def kernel_parity(pipe, prompt) -> None:
    """Fused kernel vs gather path: logits after prefill and one decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.paged_cache import blocks_for

    model, params = pipe.engine.model, pipe.engine.params
    vocab = model.cfg.vocab_size  # slots past it are padding, -1e30
    prompt = np.asarray(prompt, np.int32)
    n = prompt.size
    mb = blocks_for(n + 1, BLOCK_SIZE)
    table = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]
    step = jax.jit(model.paged_step, static_argnames=("paged_kernel",))

    def run(kernel: bool, first_token=None):
        pools = model.init_paged_caches(mb + 1, BLOCK_SIZE)
        logits = None
        for pos in range(0, n, PREFILL_CHUNK):
            m = min(PREFILL_CHUNK, n - pos)
            toks = np.zeros((1, PREFILL_CHUNK), np.int32)
            toks[0, :m] = prompt[pos:pos + m]
            logits, pools = step(params, pools, table,
                                 jnp.asarray([pos], jnp.int32),
                                 jnp.asarray(toks), jnp.asarray([m], jnp.int32),
                                 paged_kernel=kernel)
        prefill = np.asarray(logits[0, :vocab], np.float32)
        tok = int(np.argmax(prefill)) if first_token is None else first_token
        logits, _ = step(params, pools, table, jnp.asarray([n], jnp.int32),
                         jnp.asarray([[tok]], jnp.int32),
                         jnp.asarray([1], jnp.int32), paged_kernel=kernel)
        return prefill, np.asarray(logits[0, :vocab], np.float32), tok

    t0 = time.perf_counter()
    g_pre, g_dec, tok = run(kernel=False)
    t1 = time.perf_counter()
    k_pre, k_dec, _ = run(kernel=True, first_token=tok)
    t2 = time.perf_counter()
    log(f"phase parity: gather {t1 - t0:.1f} s, kernel {t2 - t1:.1f} s "
        f"(prompt {n} tokens in {PREFILL_CHUNK}-token chunks + 1 decode "
        f"step; compile included)")
    for name, ref, got in (("prefill", g_pre, k_pre),
                           ("decode", g_dec, k_dec)):
        if not (np.all(np.isfinite(ref)) and np.all(np.isfinite(got))):
            fail(f"non-finite logits at {name}")
        delta = float(np.max(np.abs(got - ref)))
        tol = LOGIT_TOL_FRAC * float(np.max(np.abs(ref)))
        same = int(np.argmax(got)) == int(np.argmax(ref))
        log(f"kernel vs gather {name}: max|dlogit| {delta:.6g} "
            f"(tol {tol:.6g} = 2^-4 x max|logit| {np.max(np.abs(ref)):.6g}), "
            f"argmax {'agrees' if same else 'differs'}")
        if delta > tol:
            fail(f"kernel logits differ from the gather path at {name}: "
                 f"{delta} > {tol}")


def kernel_is_compiled(pipe) -> None:
    """The fused kernel must lower to a Mosaic custom call here."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attend import paged_attend_fused

    cfg = pipe.engine.model.cfg
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bf = jnp.bfloat16
    hlo = jax.jit(paged_attend_fused).lower(
        jax.ShapeDtypeStruct((1, 1, h, hd), bf),
        jax.ShapeDtypeStruct((1, 1, kh, hd), bf),
        jax.ShapeDtypeStruct((1, 1, kh, hd), bf),
        jax.ShapeDtypeStruct((4, BLOCK_SIZE, kh, hd), bf),
        jax.ShapeDtypeStruct((4, BLOCK_SIZE, kh, hd), bf),
        jax.ShapeDtypeStruct((1, 2), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    if "tpu_custom_call" not in hlo:
        fail("paged_attend_fused did not lower to a Mosaic kernel")
    log("fused kernel: compiled by Mosaic (tpu_custom_call), not interpreted")


def serve_requests(pipe, seed: int) -> None:
    from repro.launch.serve import serve_rag_open_loop_generate

    t0 = time.perf_counter()
    out = serve_rag_open_loop_generate(
        pipe=pipe, n_queries=N_REQUESTS, max_batch=N_REQUESTS, k=TOP_K,
        max_new_tokens=NEW_TOKENS, config=engine_config(kernel=True),
        offered_qps=50.0, seed=seed)
    wall = time.perf_counter() - t0
    log(f"phase serve: {wall:.1f} s wall incl. warm-up and compilation; "
        f"{out['n_finished']}/{out['n_queries']} requests finished, "
        f"{out['n_tokens']} tokens, {out['n_prefill_chunks']} prefill "
        f"chunks, {out['n_decode_steps']} decode steps")
    log(f"smoke figures (not benchmark metrics): e2e p50 "
        f"{out['p50_ms']:.1f} ms, TTFT p50 {out['ttft_p50_ms']:.1f} ms, "
        f"decode {out['decode_tok_per_s']:.1f} tok/s, per-token "
        f"{out['per_token_ms_mean']:.2f} ms mean")
    if out["paged_kernel"] is not True:
        fail(f"engine ran paged_kernel={out['paged_kernel']}")
    if out["n_failed"] or out["n_chain_failed"] \
            or out["n_finished"] != N_REQUESTS:
        fail(f"{out['n_failed']} failed, {out['n_chain_failed']} chain "
             f"failures, {out['n_finished']}/{N_REQUESTS} finished")
    if out["n_tokens"] != N_REQUESTS * NEW_TOKENS:
        fail(f"{out['n_tokens']} tokens, want {N_REQUESTS * NEW_TOKENS}")


def self_retrieval(pipe, n: int = 64) -> None:
    import numpy as np

    ids = np.arange(0, len(pipe.doc_texts), len(pipe.doc_texts) // n)[:n]
    hits = 0
    for lo in range(0, n, N_REQUESTS):
        batch = ids[lo:lo + N_REQUESTS]
        got, _ = pipe.search_batch([pipe.doc_texts[i] for i in batch], 1)
        hits += int(np.sum(got[:, 0] == batch))
    log(f"self-retrieval (smoke figure): {hits}/{n}")
    if hits != n:
        fail(f"self-retrieval {hits}/{n}: a document did not retrieve itself")


def memory(device, after: str) -> None:
    """Device memory in use now and at its peak so far (the peak is
    cumulative over the process)."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        log(f"device memory after {after}: not reported by this backend")
        return
    gib = lambda k: stats.get(k, 0) / 2**30  # noqa: E731
    log(f"device memory after {after} ({device}): in use "
        f"{gib('bytes_in_use'):.2f} GiB, peak so far "
        f"{gib('peak_bytes_in_use'):.2f} GiB of {gib('bytes_limit'):.2f} GiB")


def one_chip(seed: int) -> list:
    devices = require_tpu(1)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    pipe = build_pipeline(seed)
    memory(devices[0], "build")
    kernel_is_compiled(pipe)
    prompt, _ = rag_prompts(pipe, seed)[0]
    kernel_parity(pipe, prompt)
    memory(devices[0], "parity")
    serve_requests(pipe, seed)
    memory(devices[0], "serve")
    self_retrieval(pipe)
    return devices


def _generate(engine, prompts) -> list:
    """Greedy tokens of every prompt, after draining `engine`."""
    tickets = [engine.submit(p, max_new_tokens=NEW_TOKENS, prefix_len=pl)
               for p, pl in prompts]
    engine.close(drain=True)
    return [list(t.result()) for t in tickets]


def four_chips(seed: int) -> list:
    devices = require_tpu(4)
    import numpy as np

    from repro.core import DircRagIndex, RetrievalConfig, ShardedDircIndex
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_macro_mesh

    log(f"compile cache: {enable_compile_cache()}")
    pipe = build_pipeline(seed)
    memory(devices[0], "build")
    prompts = rag_prompts(pipe, seed)

    t0 = time.perf_counter()
    single = pipe.decode_engine(engine_config(), max_new_tokens=NEW_TOKENS)
    want = _generate(single, prompts)
    del single
    gc.collect()  # the engine's thread and tickets form cycles: free its pool
    t1 = time.perf_counter()
    fleet = pipe.decode_engine(engine_config(), n_replicas=4,
                               max_new_tokens=NEW_TOKENS)
    placed = [str(e.device) for e in fleet.engines]
    log("replica devices: " + ", ".join(
        f"replica {i} -> {d}" for i, d in enumerate(placed)))
    got = _generate(fleet, prompts)
    t2 = time.perf_counter()
    log(f"phase router: one replica {t1 - t0:.1f} s, four replicas "
        f"{t2 - t1:.1f} s (compile included); per-replica submits "
        f"{fleet.per_replica_submits}")
    if len(set(placed)) != 4:
        fail(f"replicas share devices: {placed}")
    same = sum(a == b for a, b in zip(want, got))
    log(f"routed vs one-replica greedy tokens: {same}/{len(want)} "
        f"requests identical")
    if same != len(want):
        fail("routed greedy tokens differ from the one-replica run")

    embs = pipe.embedder.embed(pipe.doc_texts)
    rng = np.random.default_rng(seed + 2)
    qids = rng.choice(len(embs), size=N_REQUESTS, replace=False)
    queries = (embs[qids] + 0.01 * rng.normal(size=(N_REQUESTS, DIM))
               ).astype(np.float32)
    mesh = make_macro_mesh(4)
    for path in ("int_exact", "kernel_mxu"):
        cfg = RetrievalConfig(bits=8, metric="cosine", path=path)
        t0 = time.perf_counter()
        mono = DircRagIndex.build(embs, cfg).search(queries, 10)
        idx = ShardedDircIndex.build(embs, cfg, n_shards=8,
                                     parallelism="shard_map", mesh=mesh)
        res = idx.search(queries, 10)
        ok = np.array_equal(np.asarray(res.indices), np.asarray(mono.indices))
        log(f"shard_map ({path}, 8 shards over "
            f"{mesh.devices.size} devices) vs monolithic top-10 ids: "
            f"{'identical' if ok else 'DIFFER'} "
            f"({time.perf_counter() - t0:.1f} s)")
        if not ok:
            fail(f"shard_map top-k differs from monolithic ({path})")
    for d in devices[:4]:
        memory(d, "router and shard_map")
    return devices


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the replica-fleet and shard_map "
                         "retrieval paths across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, corpus and requests")
    args = ap.parse_args()
    load_repo()
    devices = four_chips(args.seed) if args.four_chips \
        else one_chip(args.seed)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
