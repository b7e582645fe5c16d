"""The launch surface: full-width selection, the compile-cache location,
failure exits, and the per-chip peak table. Nothing here initializes a
full-width model: weights are `jax.eval_shape` stand-ins."""
import os
import re
import sys
import types

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache, serve
from repro.perf import roofline

FULL = get_config("phi4-mini-3.8b", smoke=False)
SMOKE = get_config("phi4-mini-3.8b", smoke=True)


@pytest.fixture
def shapes_only(monkeypatch):
    """Weights as shapes: build_rag_pipeline never materializes them."""
    monkeypatch.setattr(
        serve, "init_params",
        lambda model, seed: jax.eval_shape(model.init, jax.random.key(seed)))


@pytest.mark.parametrize("smoke,want", [(False, FULL), (True, SMOKE)])
def test_build_rag_pipeline_selects_config(shapes_only, smoke, want):
    pipe = serve.build_rag_pipeline(n_docs=8, n_shards=2, dim=32,
                                    arch="phi4-mini-3.8b", smoke=smoke)
    assert pipe.engine.model.cfg == want
    embed = pipe.engine.params["embedding"]["embed"]
    assert embed.shape == (want.padded_vocab_size, want.d_model)
    assert pipe.engine.params["blocks"]["attn"]["wq"].shape == (
        want.n_layers, want.d_model, want.n_heads * want.resolved_head_dim)


def _run_main(monkeypatch, argv, target):
    """Run `serve.main` with `target` replaced by a recorder; returns the
    kwargs it was called with."""
    seen = {}

    def record(*args, **kwargs):
        seen["args"], seen["kwargs"] = args, kwargs
        raise SystemExit(0)

    monkeypatch.setattr(serve, target, record)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["serve.py", *argv])
    with pytest.raises(SystemExit):
        serve.main()
    return seen["kwargs"]


@pytest.mark.parametrize("flag,want", [("--no-smoke", FULL),
                                       ("--smoke", SMOKE), (None, SMOKE)])
def test_cli_smoke_switch_reaches_serve(monkeypatch, flag, want):
    argv = ["--arch", "phi4-mini-3.8b"] + ([flag] if flag else [])
    kwargs = _run_main(monkeypatch, argv, "serve")
    assert get_config("phi4-mini-3.8b", smoke=kwargs["smoke"]) == want


def test_cli_no_smoke_reaches_rag_generate(monkeypatch):
    kwargs = _run_main(
        monkeypatch, ["--rag", "--open-loop", "--generate", "--no-smoke"],
        "serve_rag_open_loop_generate")
    assert kwargs["smoke"] is False
    assert kwargs["arch"] == "phi4-mini-3.8b"


@pytest.mark.parametrize("out,message", [
    ({"n_failed": 0, "n_chain_failed": 0}, None),
    ({"n_failed": 1, "n_chain_failed": 0}, "1 request(s) failed"),
    ({"n_failed": 0, "n_chain_failed": 2}, "2 request(s) failed"),
])
def test_failed_requests_exit_nonzero(out, message):
    if message is None:
        serve._exit_on_failures(out)
        return
    with pytest.raises(SystemExit, match=re.escape(message)):
        serve._exit_on_failures(out)


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_is_written(monkeypatch, tmp_path,
                                          restore_cache_dir):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    try:
        jax.jit(lambda x: x * 3 + 1).lower(
            jax.ShapeDtypeStruct((7, 5), "float32")).compile()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()
    assert any(tmp_path.iterdir()), "nothing was written to the cache dir"


def test_roofline_peaks_keyed_by_device_kind():
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks.flops == 197e12 and peaks.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peak table entry"):
        roofline.peaks_for("cpu")
    cell = roofline.CellAnalysis(flops=197e12, hbm_bytes=819e9,
                                 collective_bytes=0.0, collectives={})
    assert cell.compute_s == pytest.approx(1.0)
    assert cell.memory_s == pytest.approx(1.0)


def test_benchmark_harness_exits_nonzero_on_failed_section(monkeypatch):
    import importlib

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    run = importlib.import_module("benchmarks.run")

    def boom():
        raise RuntimeError("section broke")

    ok = types.SimpleNamespace(main=lambda: None)
    monkeypatch.setattr(run, "SECTIONS", [("fine", ok),
                                          ("broken", types.SimpleNamespace(
                                              main=boom))])
    with pytest.raises(SystemExit, match="1 section"):
        run.main()
