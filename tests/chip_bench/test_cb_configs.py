"""BENCHMARK.json against the benchmark's contract, and each model
configuration's sizes against the values quoted from its source."""
import json
import os
import re

import pytest

from chip_bench import spec
from chip_bench.drivers.rag_generate import as_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

# Quoted from each source's config.json (see the configuration's
# `source`): every key the program's shapes depend on.
SOURCES = {
    "phi4-mini-3.8b": {
        "hidden_size": 3072, "intermediate_size": 8192,
        "num_hidden_layers": 32, "num_attention_heads": 24,
        "num_key_value_heads": 8, "vocab_size": 200064,
        "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "hidden_act": "silu",
        "partial_rotary_factor": 0.75, "max_position_embeddings": 131072,
        "torch_dtype": "bfloat16"},
}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(SOURCES))
def test_model_sizes_match_the_source(bench, name):
    with open(os.path.join(ROOT, "chip_bench", "configs",
                           name + ".json")) as f:
        c = json.load(f)
    src = SOURCES[name]
    changed = {k for k in src if c.get(k) != src[k]}
    assert changed == set(c["reduced"]), changed
    assert not set(c["reduced"]) & set(WIDTHS)
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    # what the program runs in place of the source's values changes no
    # width, and the harness runs it
    run = as_run(c)
    assert not set(c.get("departures", {})) & set(WIDTHS)
    assert {k: run[k] for k in WIDTHS} == {k: c[k] for k in WIDTHS}
    assert run["partial_rotary_factor"] == 1.0
    for entry in bench["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == c["reduced"]
            assert entry["source"] == c["source"]


def test_benchmark_follows_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chip_bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert 2 + 14 * 24 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            ROOT, "chip_bench", "metrics", m["name"] + ".py"))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    for name, w in cells.items():
        cell = spec.resolve(name, ROOT)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported
        assert len(w["why"]) <= 200
    used = {w["config"] for w in cells.values()}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith(tuple(bench["paths"])) for f in files)
