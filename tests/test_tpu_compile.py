"""Every Pallas kernel compiles for a TPU v5e at real widths.

The kernels run in interpret mode everywhere else in the suite; these
tests hand them to Mosaic ahead of time, from shapes only, against a
described `v5e:2x2` topology (no chip attached), so a kernel the chip's
compiler would refuse fails here. Widths: phi4-mini-3.8b attention (24
query / 8 KV heads, head_dim 128, 16-token blocks) for `paged_attend`,
the paper's retrieval point (8,192 documents at dim 512) for the scoring
kernels. The topology is described inside a fixture, never at import:
only one process may load the TPU library at a time.

The engine's paged step program is compiled too, at phi4-mini widths
with 4 layers and a 1,025-block pool, to show from `memory_analysis()`
that it updates the KV pools in place: the donated pools alias the
output and no temp buffer holds even one layer's pool. (XLA:CPU keeps a
full-size temp for a scatter inside a loop even when donated, so only a
compile for the TPU can show this.)
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


def _paged(t, b, mb):
    def args(s):
        bf = jnp.bfloat16
        return (s((b, t, H, HD), bf), s((b, t, KH, HD), bf),
                s((b, t, KH, HD), bf), s((512, BS, KH, HD), bf),
                s((512, BS, KH, HD), bf), s((b, mb), jnp.int32),
                s((b,), jnp.int32), s((b,), jnp.int32))
    return (lambda *a: paged_attend.paged_attend_fused(*a, interpret=False),
            args)


CASES = {
    # decode: 16 rows, one new token, a 34-block table (544 tokens)
    "paged_attend_decode": _paged(t=1, b=16, mb=34),
    # one chunked-prefill piece at the engine's prefill_chunk
    "paged_attend_prefill": _paged(t=PREFILL_CHUNK, b=1, mb=32),
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
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, make_args = CASES[name]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn).lower(*make_args(shaped)).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} did not lower to a Mosaic kernel"


# phi4-mini's step program with the cell's pool, cut to 4 layers
STEP_LAYERS, POOL_BLOCKS, TABLE = 4, 1025, 128


@pytest.mark.parametrize("paged_kernel", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("b,t", [(16, 1), (1, PREFILL_CHUNK)],
                         ids=["decode", "prefill"])
def test_paged_step_updates_pools_in_place(one_chip, monkeypatch,
                                           paged_kernel, b, t):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.continuous_batching import paged_step_program

    # the kernel interprets when the default backend is not a TPU, as it
    # is here; compile what the chip runs
    monkeypatch.setattr(paged_attend, "resolve_interpret", lambda _: False)
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"),
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
