"""phi3-medium-14b's configuration against its source, its cell as the
harness resolves it, and the served path against the plain reference.

The configuration is one stage of a four-stage pipeline: 10 of the
source's 40 layers, every width and the whole vocabulary as published.
On the CPU, at a small size with the source's 4:1 query-to-KV group at 5
KV heads (a count no sublane tile divides, as 10 does not) and an untied
head, the engine's paged path with the fused kernel in interpret mode
(chunked prefill, then decode) serves tokens that the float32
reference's full forward pass ranks first within the harness's
`logit_gap`.
"""
import json
import os

import numpy as np
import pytest

from chip_bench import spec, weights
from chip_bench.drivers.rag_generate import as_run, model_config
from chip_bench.reference import decoder_lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "phi3-medium-14b", "phi3medium-rag-poisson"

# Quoted from the source's config.json (the configuration's `source`):
# every key the program's shapes depend on.
SOURCE = {
    "hidden_size": 5120, "intermediate_size": 17920,
    "num_hidden_layers": 40, "num_attention_heads": 40,
    "num_key_value_heads": 10, "vocab_size": 32064, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "rope_scaling": None,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "max_position_embeddings": 4096, "sliding_window": 2047,
    "torch_dtype": "bfloat16"}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size")
# the per-layer metrics of the RAG cells, each read in this cell too
RAG_LAYER_METRICS = {
    "gen_lag_p99_ms", "retrieval_wait_p90_ms", "search_device_ms.rag",
    "step_gap_ms", "decode_step_ms", "prefill_chunk_ms",
    "paged_attend_roofline", "step_mfu", "device_idle_share.gen"}
SEED = 2**33 + 29     # larger than 32 signed bits hold
# the harness's tiny-cell limit (cb_tiny.py): bf16 serving at this size
# ranks the reference's best token first, or within 0.005 of it
LOGIT_GAP = 0.005


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chip_bench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_only_the_depth_is_cut(bench, config):
    changed = {k for k in SOURCE if config.get(k) != SOURCE[k]}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 10
    assert config["published"] == {"num_hidden_layers": 40}
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"chip_bench/configs/{CONFIG}.json"


def test_widths_are_the_source_s(config):
    assert {k: config[k] for k in WIDTHS if k != "head_dim"} == {
        k: SOURCE[k] for k in WIDTHS if k != "head_dim"}
    assert config["head_dim"] * config["num_attention_heads"] \
        == SOURCE["hidden_size"]
    run = as_run(config)
    assert {k: run[k] for k in WIDTHS} == {k: config[k] for k in WIDTHS}
    cfg = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.tie_embeddings) == (
        5120, 40, 10, 128, 17920, 32064, False)
    assert cfg.paged_kernel


def test_program_registry_matches_the_source():
    from repro.configs import get_config

    full = get_config(CONFIG)
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.resolved_head_dim, full.d_ff, full.vocab_size,
            full.tie_embeddings) == (40, 5120, 40, 10, 128, 17920, 32064,
                                     False)
    smoke = get_config(CONFIG, smoke=True)
    assert smoke.n_heads // smoke.n_kv_heads == 4
    assert smoke.n_kv_heads % 8 and not smoke.tie_embeddings


def test_harness_resolves_the_cell(bench):
    cell = spec.resolve(CELL, ROOT)
    assert cell.chips == 1
    assert cell.config["name"] == CONFIG
    assert cell.traffic["driver"] == "rag_generate"
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p90_ms", "itl_p95_ms", "output_tok_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == RAG_LAYER_METRICS
    for m in cell.per_layer:
        assert callable(spec.metric_reader(ROOT, m["name"]))
    assert callable(spec.driver(ROOT, cell.traffic["driver"]).setup)


def test_longest_sequence_fits_the_window(config):
    mix = spec.resolve(CELL, ROOT).traffic
    longest = mix["max_prompt_len"] + mix["answer_tokens"]["max"]
    assert longest <= 2047 == config["sliding_window"]
    assert longest <= config["max_position_embeddings"]
    # the pool holds every slot's longest sequence
    eng = config["engine"]
    assert eng["pool_tokens"] >= eng["n_slots"] * longest


def _tiny_config() -> dict:
    """The registry's smoke widths in the configuration file's keys."""
    from repro.configs import get_config

    s = get_config(CONFIG, smoke=True)
    return {
        "name": "phi3-medium-tiny", "hidden_size": s.d_model,
        "intermediate_size": s.d_ff, "num_hidden_layers": s.n_layers,
        "num_attention_heads": s.n_heads, "num_key_value_heads": s.n_kv_heads,
        "head_dim": s.resolved_head_dim, "vocab_size": s.vocab_size,
        "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "tie_word_embeddings": s.tie_embeddings,
        "program": {"paged_kernel": True}}


def test_served_tokens_match_the_reference_at_five_kv_heads(monkeypatch):
    import jax

    from repro.kernels import paged_attend
    from repro.models import build_model
    from repro.serving import EngineConfig
    from repro.serving.continuous_batching import ContinuousBatchingEngine

    traced = []   # the steps' traces go through the fused kernel
    fused = paged_attend.paged_attend_fused
    monkeypatch.setattr(paged_attend, "paged_attend_fused",
                        lambda *a, **kw: traced.append(a[0].shape)
                        or fused(*a, **kw))

    c = _tiny_config()
    cfg = model_config(c)
    model = build_model(cfg)
    params = weights.make(c, SEED)
    weights.check_layout(params, model)
    pools = jax.eval_shape(lambda: model.init_paged_caches(2, 16))
    assert pools.k_pool.shape == (2, 2, 5, 16, 16)   # 5 heads, none padded
    eng = ContinuousBatchingEngine(model, params, config=EngineConfig(
        n_slots=2, cache_len=64, paged=True, block_size=16,
        prefill_chunk=16))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, c["vocab_size"], size=n).tolist()
               for n in (37, 21)]
    tickets = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, (9, 14))]
    eng.run_until_drained()
    ref_params = weights.make(c, SEED)
    for prompt, ticket in zip(prompts, tickets):
        served = [int(t) for t in ticket.result()]
        toks = prompt + served[:-1]
        ref = decoder_lm.logits(c, ref_params, toks, len(prompt) - 1)
        gaps = decoder_lm.served_gaps(ref, served)
        assert gaps.max() <= LOGIT_GAP, gaps
    # a prefill chunk and a decode step, each at 20 query heads of 16
    assert {shape[1:] for shape in traced} == {(16, 20, 16), (1, 20, 16)}
