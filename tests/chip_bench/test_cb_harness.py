"""The harness end to end on the CPU at a tiny size.

A tiny RAG cell (cb_tiny.py: open loop, scheduler, index, paged engine
with the fused kernel in interpret mode) runs through `run.run_cell` with
the chip check skipped. A run with the timed path intact comes out
correct; a run with a fault planted where an answer is produced (a
served token altered, a retrieved id altered) comes out not correct, and
so does a traced run in which a declared per-layer metric reads nothing.
Without a TPU the command exits non-zero and prints no result.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

import cb_tiny
from chip_bench import run, spec

SEED = 2**33 + 17     # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_tiny.make_root(str(tmp_path_factory.mktemp("cb")))


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(root, cell, seconds=2.0, traced=False, seed=SEED):
    return run.run_cell(spec.resolve(cell, root), seed, seconds, traced,
                        require_tpu=False)


def test_command_without_a_tpu_exits_nonzero_and_prints_nothing():
    proc = subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload",
         "phi4mini-rag-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cb_tiny.REPO, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_outside_a_checkout_exits_nonzero(tmp_path):
    import shutil

    shutil.copytree(cb_tiny.REPO + "/chip_bench", tmp_path / "chip_bench")
    shutil.copy(cb_tiny.REPO + "/BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload",
         "phi4mini-rag-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_rag_cell_runs_and_is_correct(root):
    out = _run(root, "tiny-rag")
    assert out["correct"], out
    assert out["attempted"] == 6 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"failed_requests",
                                    "retrieval_mismatches", "logit_gap"}
    json.dumps(out)


HOST_METRICS = ("gen_lag_p99_ms", "retrieval_wait_p90_ms")


@pytest.mark.parametrize("declared,missing", [
    (HOST_METRICS, 0), (HOST_METRICS + ("nothing_to_read",), 1)],
    ids=["all-read", "one-empty"])
def test_traced_rag_cell_counts_declared_metrics(root, declared, missing):
    """A traced run reports its declared per-layer metrics; one whose
    reader finds nothing makes the run not correct. (Device-time readers
    find nothing on the CPU, so the cell declares host metrics here.)"""
    with open(f"{root}/chip_bench/metrics/nothing_to_read.py", "w") as f:
        f.write("def read(ctx):\n    return None\n")
    cell = spec.resolve("tiny-rag", root)
    cell.per_layer = [dict(cell.per_layer[0], name=n) for n in declared]
    out = run.run_cell(cell, SEED, 2.0, True, require_tpu=False)
    assert out["correct"] == (missing == 0), out
    assert set(out["metrics"]) == set(HOST_METRICS)
    assert out["compared"]["missing_layer_metrics"] == {
        "value": float(missing), "limit": 0.0}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in out["device"] and "busy_s" in out["device"]


def test_altered_token_fails_the_rag_check(root, monkeypatch):
    from repro.serving.continuous_batching import ContinuousBatchingEngine

    sample = ContinuousBatchingEngine._sample

    def altered(self, logits):
        toks = sample(self, logits)
        return (toks + 1) % self.model.cfg.vocab_size

    monkeypatch.setattr(ContinuousBatchingEngine, "_sample", altered)
    out = _run(root, "tiny-rag")
    assert not out["correct"]
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_retrieval_fails_the_rag_check(root, monkeypatch):
    from repro.core import topk
    from repro.core.sharded_index import ShardedDircIndex

    search = ShardedDircIndex.search

    def altered(self, queries, k, key=None):
        res = search(self, queries, k, key=key)
        ids = np.asarray(res.indices).copy()
        ids[:, -1] = (ids[:, -1] + 1) % self.n_docs
        return topk.TopK(scores=res.scores, indices=ids)

    monkeypatch.setattr(ShardedDircIndex, "search", altered)
    out = _run(root, "tiny-rag")
    assert not out["correct"]
    miss = out["compared"]["retrieval_mismatches"]
    assert miss["value"] > miss["limit"]
