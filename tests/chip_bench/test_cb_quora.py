"""The retrieval cell `quora-dirc-search` and its parts, on the CPU.

A tiny copy of the cell (4,096 documents in 8 shards, 8 closed-loop
clients, a 2 s window) runs through `run.run_cell` with the chip check
skipped and comes out correct; with a fault planted where an answer is
produced it comes out not correct, and the program's int4 index (the
control) fails the limit where the int8 index passes. The full cell's
configuration is checked against its source, the corpus generator for
its seeding, the least scan time against a hand count, and each new
per-layer reader against a small synthetic trace.
"""
import json
import os
import shutil

import numpy as np
import pytest

import cb_tiny
from chip_bench import calibrate, run, scan_cost, spec
from chip_bench.peaks import PEAKS

SEED = 2**33 + 29     # larger than 32 signed bits hold
CELL = "quora-dirc-search"
TINY_CELL = "tiny-quora"
METRICS = ("batch_fill.corpus", "flush_ms.corpus", "search_device_ms.corpus",
           "scan_roofline.corpus", "search_mfu.corpus",
           "device_idle_share.corpus")
HOST_METRICS = ("batch_fill.corpus", "flush_ms.corpus")


def _tiny_config():
    with open(os.path.join(cb_tiny.REPO, "chip_bench", "configs",
                           "dirc-int8-quora.json")) as f:
        c = json.load(f)
    c["name"] = "tiny-dirc"
    c["corpus"] = dict(c["corpus"], n_docs=4096, n_queries=512)
    c["index"] = dict(c["index"], n_docs=4096, n_shards=8)
    c["scheduler"] = dict(c["scheduler"], max_batch=4)
    return c


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the repository's BENCHMARK.json plus a tiny copy of
    the retrieval cell that reports the same metrics."""
    root = str(tmp_path_factory.mktemp("cbq") / "checkout")
    shutil.copytree(os.path.join(cb_tiny.REPO, "chip_bench"),
                    os.path.join(root, "chip_bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(cb_tiny.REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(root, "chip_bench/configs/tiny-dirc.json"),
              "w") as f:
        json.dump(_tiny_config(), f)
    with open(os.path.join(cb_tiny.REPO, "chip_bench", "traffic",
                           "quora-closed-64.json")) as f:
        mix = json.load(f)
    with open(os.path.join(root, "chip_bench/traffic/tiny-closed.json"),
              "w") as f:
        json.dump(dict(mix, clients=8), f)
    with open(os.path.join(cb_tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-dirc", "source": "test",
                             "file": "chip_bench/configs/tiny-dirc.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-dirc",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(root, traced=False, cell=None):
    cell = cell or spec.resolve(TINY_CELL, root)
    return run.run_cell(cell, SEED, 2.0, traced, require_tpu=False)


# ----------------------------------------------------------- the full cell
def test_cell_resolves_by_name():
    cell = spec.resolve(CELL, cb_tiny.REPO)
    assert cell.chips == 1
    assert cell.config["name"] == "dirc-int8-quora"
    assert cell.traffic["driver"] == "search_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "retrieval_qps", "retrieval_p99_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(METRICS)
    for m in cell.per_layer:
        assert callable(spec.metric_reader(cb_tiny.REPO, m["name"]))


def test_config_matches_the_source():
    """BEIR Quora (Thakur et al. 2021, Table 1): 522,931 documents, 10,000
    test queries, nDCG@10; the paper's dim 512 and 4 MiB macros."""
    cell = spec.resolve(CELL, cb_tiny.REPO)
    c = cell.config
    assert c["corpus"]["n_docs"] == c["index"]["n_docs"] == 522_931
    assert c["corpus"]["n_queries"] == 10_000
    assert c["corpus"]["top_k"] == 10
    assert c["index"]["dim"] == 512 and c["index"]["n_shards"] == 64
    assert c["index"]["retrieval"] == "RETRIEVAL_INT8"
    largest = -(-522_931 // 64)
    assert largest == 8_171
    # 64 is the fewest shards of at most one 4 MiB macro of codes each
    assert largest * 512 <= 4 * 2**20
    assert -(-522_931 * 512 // (4 * 2**20)) == 64
    assert c["reduced"] == []
    assert cell.traffic["clients"] == 2 * c["scheduler"]["max_batch"] == 64
    assert c["correct"] == {"failed_queries": 0, "retrieval_mismatches": 0}


# ------------------------------------------------------ the tiny cell runs
def test_tiny_cell_runs_and_is_correct(root):
    out = _run(root)
    assert out["correct"], out
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"retrieval_qps", "retrieval_p99_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"
    assert out["compared"] == {
        "failed_queries": {"value": 0.0, "limit": 0},
        "retrieval_mismatches": {"value": 0.0, "limit": 0}}


def test_traced_tiny_cell_reads_its_host_metrics(root):
    """Device-time readers find nothing on the CPU, so the traced cell
    declares the host metrics here."""
    cell = spec.resolve(TINY_CELL, root)
    cell.per_layer = [m for m in cell.per_layer if m["name"] in HOST_METRICS]
    out = _run(root, traced=True, cell=cell)
    assert out["correct"], out
    assert set(out["metrics"]) == set(HOST_METRICS)
    fill = out["metrics"]["batch_fill.corpus"]["value"]
    assert 50.0 < fill <= 100.0
    assert out["metrics"]["flush_ms.corpus"]["value"] > 0


def _altered_id(search):
    def altered(self, queries, k, key=None):
        from repro.core import topk

        res = search(self, queries, k, key=key)
        ids = np.asarray(res.indices).copy()
        ids[:, -1] = (ids[:, -1] + 1) % self.n_docs
        return topk.TopK(scores=res.scores, indices=ids)
    return altered


def _half_batch(search):
    """Answers of the batch's first half handed to its second half."""
    def halved(self, queries, k, key=None):
        from repro.core import topk

        res = search(self, queries, k, key=key)
        ids = np.asarray(res.indices).copy()
        half = len(ids) // 2
        ids[half:2 * half] = ids[:half]
        return topk.TopK(scores=res.scores, indices=ids)
    return halved


@pytest.mark.parametrize("fault", [_altered_id, _half_batch],
                         ids=["altered-id", "half-batch"])
def test_planted_fault_fails_the_check(root, monkeypatch, fault):
    from repro.core.sharded_index import ShardedDircIndex

    monkeypatch.setattr(ShardedDircIndex, "search",
                        fault(ShardedDircIndex.search))
    out = _run(root)
    assert not out["correct"]
    miss = out["compared"]["retrieval_mismatches"]
    assert miss["value"] > miss["limit"]


def test_int4_control_fails_where_int8_passes(root):
    cell = spec.resolve(TINY_CELL, root)
    out = calibrate.readings(cell, 31337, 2.0, control=True,
                             require_tpu=False)
    limit = cell.config["correct"]["retrieval_mismatches"]
    assert out["program"]["retrieval_mismatches"] <= limit
    assert out["control"]["retrieval_mismatches"] > limit


# ---------------------------------------------------------------- its parts
def test_corpus_is_made_from_the_seed():
    from chip_bench import ir_corpus

    a = ir_corpus.make(300, 512, 20, SEED, 8, 0.7, 0.5)
    b = ir_corpus.make(300, 512, 20, SEED, 8, 0.7, 0.5)
    c = ir_corpus.make(300, 512, 20, SEED + 1, 8, 0.7, 0.5)
    np.testing.assert_array_equal(a.docs, b.docs)
    np.testing.assert_array_equal(a.queries, b.queries)
    assert not np.array_equal(a.docs, c.docs)
    assert a.docs.dtype == a.queries.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(a.docs, axis=1), 1, atol=1e-5)
    # a query's nearest document is the one it copies
    assert (np.argmax(a.queries @ a.docs.T, axis=1) == a.sources).all()


def test_least_scan_time_by_hand():
    # 2 queries over 3 documents of 4 dims: 2 x 3 x 4 multiply-adds;
    # 12 code bytes + 3 float32 norms + 2 x 4 float32 queries
    assert scan_cost.search_work(3, 4, 2) == (48, 12 + 12 + 32)
    # the full cell: 267.7 MB of codes, bytes-bound at 0.33 ms
    ops, nbytes = scan_cost.search_work(522_931, 512, 32)
    assert ops == 2 * 32 * 522_931 * 512 == 17_135_403_008
    assert nbytes == 267_740_672 + 2_091_724 + 65_536
    p = PEAKS["TPU v5 lite"]
    least = scan_cost.least_seconds((ops, nbytes), p.int8_ops, p.hbm_bw)
    assert least == pytest.approx(nbytes / 819e9)
    assert 0.32e-3 < least < 0.33e-3 and ops / p.int8_ops < least
    # ops-bound when the bandwidth is high
    assert scan_cost.least_seconds((100, 10), 50.0, 1e9) == 2.0


# ------------------------------------------------------ readers on a trace
def _trace():
    """Two `cb.search` spans in the window, each enqueuing one program of
    2 and 4 ms; a third after the window; one device op per program."""
    ms = 1e6
    spans = [("cb.window", 0, 100 * ms), ("cb.search", 10 * ms, 3 * ms),
             ("cb.search", 20 * ms, 6 * ms), ("cb.search", 150 * ms, 5 * ms)]
    runs = [(11 * ms, 11.5 * ms, 2 * ms, 1), (21 * ms, 21.5 * ms, 4 * ms, 2),
            (151 * ms, 151.5 * ms, 4 * ms, 3)]
    return {
        "spans": [dict(name=n, start=s, dur=d, thread="t")
                  for n, s, d in spans],
        "launches": [dict(name="DoEnqueueProgram", start=e, dur=1.0,
                          thread="t", run_id=r) for e, _, _, r in runs],
        "modules": [dict(name="search", start=s, dur=d,
                         device="/device:TPU:0", run_id=r)
                    for _, s, d, r in runs],
        "device": [dict(name="%fusion.1", start=s, dur=d,
                        device="/device:TPU:0", module="m", program_id=1,
                        run_id=r) for _, s, d, r in runs],
    }


def _ctx(**host):
    work = scan_cost.search_work(1000, 64, 4)
    return {"events": _trace(), "window": (0.0, 100e6),
            "device": "/device:TPU:0", "peaks": PEAKS["TPU v5 lite"],
            "host": dict({"search_work": work, "max_batch": 4}, **host)}


def test_readers_on_a_synthetic_trace():
    read = lambda name, ctx: spec.metric_reader(cb_tiny.REPO, name)(ctx)  # noqa: E731
    ctx = _ctx(sched0={"n_flushes": 10, "n_served": 40},
               sched1={"n_flushes": 20, "n_served": 70})
    assert read("batch_fill.corpus", ctx) == pytest.approx(75.0)
    assert read("flush_ms.corpus", ctx) == pytest.approx(4.5)
    assert read("search_device_ms.corpus", ctx) == pytest.approx(3.0)
    ops, nbytes = ctx["host"]["search_work"]
    least = max(ops / 393e12, nbytes / 819e9)
    assert read("scan_roofline.corpus", ctx) == pytest.approx(
        100 * 2 * least / 6e-3)
    assert read("search_mfu.corpus", ctx) == pytest.approx(
        100 * 2 * ops / (6e-3 * 393e12))
    assert read("device_idle_share.corpus", ctx) == pytest.approx(94.0)


def test_readers_find_nothing_to_read():
    read = lambda name, ctx: spec.metric_reader(cb_tiny.REPO, name)(ctx)  # noqa: E731
    empty = _ctx(sched0={"n_flushes": 5, "n_served": 20},
                 sched1={"n_flushes": 5, "n_served": 20})
    empty["events"] = dict(empty["events"], spans=[
        s for s in empty["events"]["spans"] if s["name"] != "cb.search"])
    for name in METRICS[:-1]:
        assert read(name, empty) is None, name
    no_peaks = dict(_ctx(), peaks=None)
    assert read("scan_roofline.corpus", no_peaks) is None
    assert read("search_mfu.corpus", no_peaks) is None
