"""Cells resolve by name to their configuration, mix, driver and metric
readers; a configuration, a mix and a metric are added by adding files
and entries, with no edit to a file that is there."""
import hashlib
import json
import os

import cb_tiny
from chip_bench import spec


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chip_bench")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha1(
                        fh.read()).hexdigest()
    return out


def test_every_cell_resolves():
    with open(os.path.join(cb_tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], cb_tiny.REPO)
        assert cell.config["name"] == w["config"]
        assert hasattr(spec.driver(cb_tiny.REPO, cell.traffic["driver"]),
                       "window")
        for m in cell.per_layer:
            assert callable(spec.metric_reader(cb_tiny.REPO, m["name"]))


def test_adding_a_config_mix_and_metric_edits_nothing(tmp_path):
    root = cb_tiny.make_root(str(tmp_path))
    before = _digest(root)
    c = dict(cb_tiny.TINY_LM, name="tiny-lm-wide", hidden_size=96)
    with open(os.path.join(root, "chip_bench/configs/tiny-lm-wide.json"),
              "w") as f:
        json.dump(c, f)
    with open(os.path.join(root, "chip_bench/traffic/tiny-rag-slow.json"),
              "w") as f:
        json.dump(dict(cb_tiny.TINY_RAG, rate_per_s=1.0), f)
    with open(os.path.join(root, "chip_bench/metrics/answer_count.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 7.0\n")
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-lm-wide", "source": "test",
                             "file": "chip_bench/configs/tiny-lm-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-rag-wide",
                               "config": "tiny-lm-wide",
                               "traffic": "tiny-rag-slow", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answer_count", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "client", "moves": "output_tok_s",
                               "workloads": ["tiny-rag-wide"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    cell = spec.resolve("tiny-rag-wide", root)
    assert cell.config["hidden_size"] == 96
    assert cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer][-1] == "answer_count"
    assert spec.metric_reader(root, "answer_count")({}) == 7.0
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 3
