"""Sharding rules + multi-device semantics (8 fake CPU devices via a
subprocess so the main test process keeps its single real device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build_model
from repro.models import sharding as sh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spec_rules():
    assert sh.spec_for_path("blocks/mlp/w_gate", (8, 16, 32)) == \
        (None, "fsdp", "tp")
    assert sh.spec_for_path("blocks/attn/wq", (8, 16, 32),
                            attn_q_tp=True) == (None, "fsdp", "tp")
    assert sh.spec_for_path("blocks/attn/wq", (8, 16, 32),
                            attn_q_tp=False) == (None, "fsdp", None)
    assert sh.spec_for_path("blocks/moe/w_gate", (8, 4, 16, 32)) == \
        (None, "expert", "fsdp", None)
    assert sh.spec_for_path("embedding/embed", (100, 64)) == ("tp", "fsdp")
    assert sh.spec_for_path("final_norm/scale", (64,)) == (None,)
    assert sh.spec_for_path("blocks/mamba/A_log", (8, 80)) == (None, "tp")


def test_divisibility_guard_single_device():
    mesh = jax.make_mesh((1,), ("data",))
    with sh.sharding_ctx(mesh):
        spec = sh._physical(("batch", None, "tp"), (8, 4, 30))
    assert spec == P(None, None, None)  # nothing to shard on 1 device


def test_param_shardings_tree_matches_structure():
    cfg = get_config("arctic-480b", smoke=True)
    model = build_model(cfg)
    shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    mesh = jax.make_mesh((1,), ("data",))
    shards = sh.param_shardings(mesh, shape, cfg=cfg)
    assert jax.tree_util.tree_structure(shards) == \
        jax.tree_util.tree_structure(shape)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    # 1) distributed retrieval: local-topk + gather == flat topk
    from repro.core import sharded_index as D
    from repro.core import quantization as Q
    from repro.core._compat import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    emb = rng.normal(size=(512, 128)).astype(np.float32)
    docs = Q.quantize(jnp.asarray(emb), bits=8)
    norms = Q.doc_int_norms(docs)
    dv, nv = D.shard_index_arrays(mesh, docs.values, norms)
    search = D.make_distributed_searcher(mesh, k=8, metric="cosine")
    q = Q.quantize_query(jnp.asarray(emb[:4] + 0.05 * rng.normal(size=(4, 128)).astype(np.float32)))
    res = search(q.values, dv, nv)
    ip = Q.int_inner_product(q.values, docs.values).astype(jnp.float32)
    qn = jnp.sqrt(jnp.sum(q.values.astype(jnp.float32) ** 2, -1, keepdims=True))
    flat = ip / jnp.maximum(qn * norms[None, :], 1e-12)
    want_v, want_i = jax.lax.top_k(flat, 8)
    ok1 = bool((res.indices == want_i).all())

    # 2) sharded train step == single-device train step (loss bitwise-ish)
    from repro.configs import get_config
    from repro.launch.steps import build_train_step, batch_shardings
    from repro.models import input_specs
    from repro.configs import SHAPES
    import dataclasses
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    from repro.models import build_model
    from repro.optim import adamw
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt = adamw.init(params)
    toks = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    art = build_train_step(cfg, mesh, adamw.AdamWConfig(), grad_accum=1)
    with mesh:
        p2, o2, m2 = jax.jit(art.fn)(params, opt, batch)
    loss_sharded = float(m2["loss"])
    # single-device reference
    mesh1 = make_mesh((1, 1), ("data", "model"))
    art1 = build_train_step(cfg, mesh1, adamw.AdamWConfig(), grad_accum=1)
    with mesh1:
        p1, o1, m1 = jax.jit(art1.fn)(params, opt, batch)
    loss_single = float(m1["loss"])
    ok2 = abs(loss_sharded - loss_single) < 1e-3

    # 3) grad compression inside shard_map
    from repro.optim.grad_compression import compressed_psum
    gmesh = make_mesh((8,), ("data",))
    g = {"w": jnp.arange(8.0).reshape(8, 1) * jnp.ones((8, 4))}
    e = {"w": jnp.zeros((8, 4))}
    def body(gl, el):
        return compressed_psum(gl, el, ("data",))
    out, new_e = jax.shard_map(
        body, mesh=gmesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=True)(g, e)
    # mean over 8 shards of rows 0..7 -> 3.5 everywhere (within int8 quant)
    ok3 = bool(np.allclose(np.asarray(out["w"]), 3.5, atol=0.05))

    print(json.dumps({"ok1": ok1, "ok2": ok2, "ok3": ok3,
                      "loss_sharded": loss_sharded,
                      "loss_single": loss_single}))
""") % os.path.join(REPO, "src")


@pytest.mark.slow
def test_multidevice_semantics_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok1"], "distributed retrieval != flat top-k"
    assert out["ok2"], f"sharded vs single loss: {out}"
    assert out["ok3"], "compressed psum wrong"
