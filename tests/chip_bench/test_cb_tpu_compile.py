"""The cells' step programs compile for a TPU v5e (described chip, no chip
attached).

phi4-mini-3.8b's cell runs the fused paged-attention kernel: 24 query / 8
KV heads, decode at the cell's slot count over the full block table and
one prefill chunk; and the whole paged step around it at the cell's
widths (d_model 3072, the 200,064-row output head), one layer of the
scanned stack. The topology is described inside a module fixture, never
at import.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attend

HD, BS, TABLE = 128, 16, 128      # head_dim, block size, blocks per row
SLOTS, CHUNK, POOL = 16, 32, 1025


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize("b,t", [(SLOTS, 1), (1, CHUNK)],
                         ids=["decode", "prefill"])
def test_phi4_paged_attend_kernel_compiles(one_chip, b, t):
    h, kh = 24, 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    args = (s((b, t, h, HD), bf), s((b, t, kh, HD), bf),
            s((b, t, kh, HD), bf), s((POOL, BS, kh, HD), bf),
            s((POOL, BS, kh, HD), bf), s((b, TABLE), jnp.int32),
            s((b,), jnp.int32), s((b,), jnp.int32))
    fn = jax.jit(lambda *a: paged_attend.paged_attend_fused(
        *a, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,t", [(SLOTS, 1), (1, CHUNK)],
                         ids=["decode", "prefill"])
def test_phi4_paged_step_compiles(one_chip, monkeypatch, b, t):
    from chip_bench.drivers.rag_generate import model_config
    from repro.kernels import paged_attend as kernel_mod
    from repro.models import build_model

    # the kernel interprets when the default backend is not a TPU, as it
    # is here; compile what the chip runs
    monkeypatch.setattr(kernel_mod, "resolve_interpret", lambda _: False)

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chip_bench", "configs",
                           "phi4-mini-3.8b.json")) as f:
        c = json.load(f)
    assert c["program"]["paged_kernel"] is True
    c["num_hidden_layers"] = 1
    model = build_model(model_config(c))

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_caches(POOL, BS)))
    i32 = jnp.int32
    args = (params, pools,
            jax.ShapeDtypeStruct((b, TABLE), i32, sharding=one_chip),
            jax.ShapeDtypeStruct((b,), i32, sharding=one_chip),
            jax.ShapeDtypeStruct((b, t), i32, sharding=one_chip),
            jax.ShapeDtypeStruct((b,), i32, sharding=one_chip))
    step = jax.jit(lambda *a: model.paged_step(*a, paged_kernel=True))
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()
