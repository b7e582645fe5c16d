"""A tiny copy of the benchmark for CPU tests: the repository's harness
with small configurations and mixes, in a temporary checkout."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_LM = {
    "name": "tiny-lm", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 4096, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "program": {"paged_kernel": True},
    "index": {"n_docs": 96, "dim": 64, "n_shards": 2,
              "retrieval": "RETRIEVAL_INT8"},
    "scheduler": {"max_batch": 4, "max_wait_ms": 5.0},
    "engine": {"n_slots": 4, "pool_tokens": 1024},
    "correct": {"logit_gap": 0.005},
}
TINY_RAG = {
    "driver": "rag_generate", "loop": "open", "arrivals": "poisson",
    "rate_per_s": 3.0, "top_k": 3, "topic_words": 6,
    "passage_tokens": {"median": 40, "sigma": 0.5, "min": 20, "max": 80},
    "answer_tokens": {"median": 10, "sigma": 0.3, "min": 6, "max": 14},
    "max_prompt_len": 200, "check_requests": 4,
}


def make_root(tmp: str) -> str:
    """A checkout in `tmp`: the harness, the program (linked), and a
    BENCHMARK.json of one tiny cell, `tiny-rag`, with every metric reader
    the harness has."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(REPO, "chip_bench"),
                    os.path.join(root, "chip_bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    for name, body in (("configs/tiny-lm.json", TINY_LM),
                       ("traffic/tiny-rag.json", TINY_RAG)):
        with open(os.path.join(root, "chip_bench", name), "w") as f:
            json.dump(body, f)
    rag = ["tiny-rag"]

    def metric(name, unit, moves, cells, source="device_trace"):
        return {"name": name, "unit": unit, "better": "lower",
                "source": source, "layer": "test", "moves": moves,
                "workloads": cells}

    bench = {
        "command": ["python3", "chip_bench/run.py"],
        "paths": ["chip_bench"], "run_seconds": 51,
        "configs": [
            {"name": "tiny-lm", "source": "test",
             "file": "chip_bench/configs/tiny-lm.json", "reduced": [],
             "why": "test"}],
        "workloads": [
            {"name": "tiny-rag", "config": "tiny-lm", "traffic": "tiny-rag",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            dict(metric("ttft_p90_ms", "ms", None, rag, "host_clock"),
                 bound=0.25),
            dict(metric("itl_p95_ms", "ms", None, rag, "host_clock"),
                 bound=0.25),
            dict(metric("output_tok_s", "tokens/s", None, rag,
                        "host_clock"), bound=0.25),
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            metric("gen_lag_p99_ms", "ms", "ttft_p90_ms", rag, "host_clock"),
            metric("retrieval_wait_p90_ms", "ms", "ttft_p90_ms", rag,
                   "host_clock"),
            metric("search_device_ms.rag", "ms", "ttft_p90_ms", rag),
            metric("step_gap_ms", "ms", "itl_p95_ms", rag),
            metric("decode_step_ms", "ms", "itl_p95_ms", rag),
            metric("prefill_chunk_ms", "ms", "ttft_p90_ms", rag),
            metric("paged_attend_roofline", "%", "itl_p95_ms", rag),
            metric("step_mfu", "%", "itl_p95_ms", rag),
            metric("device_idle_share.gen", "%", "output_tok_s", rag)],
    }
    for m in bench["end_to_end"]:
        m.pop("moves", None)
        m.pop("layer", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
