"""Every Pallas kernel compiles for a TPU v5e at real widths.

The kernels run in interpret mode everywhere else in the suite; these
tests hand them to Mosaic ahead of time, from shapes only, against a
described `v5e:2x2` topology (no chip attached), so a kernel the chip's
compiler would refuse fails here. Widths: phi4-mini-3.8b attention (24
query / 8 KV heads, head_dim 128, 16-token blocks) and phi3-medium-14b's
(40 / 10, a KV-head count no sublane tile divides) for `paged_attend`,
the paper's retrieval point (8,192 documents at dim 512) for the scoring
kernels, and the Quora cell's flush (64 macros of 8,171 slots, 32
queries) for each macro's top-k selection. The topology is described
inside a fixture, never at import: only one process may load the TPU
library at a time.

The engine's paged step program is compiled too, at phi4-mini widths
with 4 layers and a 1,025-block pool, to show from `memory_analysis()`
that it updates the KV pools in place: the donated pools alias the
output and no temp buffer holds even one layer's pool. (XLA:CPU keeps a
full-size temp for a scatter inside a loop even when donated, so only a
compile for the TPU can show this.) phi3-medium's step, cut to 4
layers the same way, is held to the same check on the fused kernel's
path, the one its cell runs.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sharded_index
from repro.kernels import dirc_mac, paged_attend, score_matmul, topk_select

H, KH, HD, BS = 24, 8, 128, 16
PREFILL_CHUNK = 32
N_DOCS, DIM, BATCH = 8192, 512, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _paged(t, b, mb, h=H, kh=KH):
    """The kernel as the step calls it: a head-major two-layer stack of
    512 blocks and a layer index."""
    def args(s):
        bf = jnp.bfloat16
        return (s((b, t, h, HD), bf), s((b, t, kh, HD), bf),
                s((b, t, kh, HD), bf), s((2, 512, kh, BS, HD), bf),
                s((2, 512, kh, BS, HD), bf), s((b, mb), jnp.int32),
                s((b,), jnp.int32), s((b,), jnp.int32), s((), jnp.int32))
    return (lambda *a: paged_attend.paged_attend_fused(
        *a[:-1], layer=a[-1], interpret=False), args)


CASES = {
    # decode: 16 rows, one new token, a 34-block table (544 tokens)
    "paged_attend_decode": _paged(t=1, b=16, mb=34),
    # one chunked-prefill piece at the engine's prefill_chunk
    "paged_attend_prefill": _paged(t=PREFILL_CHUNK, b=1, mb=32),
    # phi3-medium-14b: 40 query / 10 KV heads
    "paged_attend_decode_40_10": _paged(t=1, b=16, mb=34, h=40, kh=10),
    "paged_attend_prefill_40_10": _paged(t=PREFILL_CHUNK, b=1, mb=32, h=40,
                                         kh=10),
    "score_matmul_int": (
        lambda q, d: score_matmul.score_matmul_int(q, d, interpret=False),
        lambda s: (s((BATCH, DIM), jnp.int8), s((N_DOCS, DIM), jnp.int8))),
    "score_matmul_cosine": (
        lambda q, d, qn, dn: score_matmul.score_matmul_cosine(
            q, d, qn, dn, interpret=False),
        lambda s: (s((BATCH, DIM), jnp.int8), s((N_DOCS, DIM), jnp.int8),
                   s((BATCH, 1), jnp.float32), s((1, N_DOCS), jnp.float32))),
    "dirc_mac_packed": (
        lambda q, d: dirc_mac.dirc_mac_packed(q, d, interpret=False),
        lambda s: (s((BATCH, 8, DIM // 32), jnp.uint32),
                   s((8, DIM // 32, N_DOCS), jnp.uint32))),
    "blockwise_topk": (
        lambda x: topk_select.blockwise_topk(x, k=10, interpret=False),
        lambda s: (s((BATCH, N_DOCS), jnp.float32),)),
    # the Quora cell's per-macro top-k: 64 macros of 8,171 slots, 32 queries
    "index_topk_select_quora": (
        lambda s, ids, alive: sharded_index._merge_impl(s, ids, alive,
                                                        k=10, kk=10),
        lambda s: (s((64, 32, 8171), jnp.float32), s((64, 8171), jnp.int32),
                   s((64, 8171), jnp.bool_))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, monkeypatch, name):
    fn, make_args = CASES[name]
    # programs that call a kernel interpret it when the default backend is
    # not a TPU, as it is here; compile what the chip runs, and let no
    # trace of it outlive the test
    monkeypatch.setattr(topk_select, "resolve_interpret", lambda _: False)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jax.clear_caches()
    try:
        compiled = jax.jit(fn).lower(*make_args(shaped)).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} did not lower to a Mosaic kernel"


def test_index_topk_compiles_over_a_mesh(topo, monkeypatch):
    """A `shard_map` index over four chips: XLA cannot partition a Mosaic
    kernel, so each chip selects among its own macros."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(topk_select, "resolve_interpret", lambda _: False)
    mesh = Mesh(np.array(topo.devices), ("macro",))
    rep = NamedSharding(mesh, P())

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    jax.clear_caches()
    try:
        compiled = sharded_index._merge_impl.lower(
            shaped((4, 16, 2048), jnp.float32), shaped((4, 2048), jnp.int32),
            shaped((4, 2048), jnp.bool_), k=5, kk=5, mesh=mesh).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()


# phi4-mini's step program with the cell's pool, cut to 4 layers
STEP_LAYERS, POOL_BLOCKS, TABLE = 4, 1025, 128


def _compile_paged_step(one_chip, monkeypatch, arch, paged_kernel, b, t):
    """The paged step of `arch`, cut to STEP_LAYERS layers, compiled for
    the chip; asserts that the pools are updated in place."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.continuous_batching import paged_step_program

    # the kernel interprets when the default backend is not a TPU, as it
    # is here; compile what the chip runs
    monkeypatch.setattr(paged_attend, "resolve_interpret", lambda _: False)
    cfg = dataclasses.replace(get_config(arch),
                              n_layers=STEP_LAYERS, paged_kernel=paged_kernel)
    model = build_model(cfg)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_caches(POOL_BLOCKS, BS)))
    i32 = jnp.int32
    args = (params, pools, on_chip(jax.ShapeDtypeStruct((b, TABLE), i32)),
            on_chip(jax.ShapeDtypeStruct((b,), i32)),
            on_chip(jax.ShapeDtypeStruct((b, t), i32)),
            on_chip(jax.ShapeDtypeStruct((b,), i32)))
    compiled = paged_step_program(model).lower(*args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(pools))
    one_layer_k = pools.k_pool.size // STEP_LAYERS * pools.k_pool.dtype.itemsize
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < one_layer_k
    assert ("tpu_custom_call" in compiled.as_text()) == paged_kernel


@pytest.mark.parametrize("paged_kernel", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("b,t", [(16, 1), (1, PREFILL_CHUNK)],
                         ids=["decode", "prefill"])
def test_paged_step_updates_pools_in_place(one_chip, monkeypatch,
                                           paged_kernel, b, t):
    _compile_paged_step(one_chip, monkeypatch, "phi4-mini-3.8b",
                        paged_kernel, b, t)


@pytest.mark.parametrize("b,t", [(16, 1), (1, PREFILL_CHUNK)],
                         ids=["decode", "prefill"])
def test_phi3_medium_paged_step_updates_pools_in_place(one_chip, monkeypatch,
                                                       b, t):
    _compile_paged_step(one_chip, monkeypatch, "phi3-medium-14b", True, b, t)
