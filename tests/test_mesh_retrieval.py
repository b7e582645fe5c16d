"""Real device-mesh retrieval (PR 8): `ShardedDircIndex` shard_map on an
explicit multi-device mesh with exact monolithic parity, the flat-index
searcher folded into sharded_index, and the `core.distributed`
deprecation shim. Multi-device runs in a subprocess (4 fake CPU devices
via XLA_FLAGS) so the main test process keeps its single real device."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DircRagIndex, RetrievalConfig, ShardedDircIndex
from repro.core._compat import make_mesh
from repro.launch.mesh import make_macro_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- single device
def test_make_mesh_compat_shapes_and_subset():
    import jax

    m = make_mesh((1,), ("macro",))
    assert m.axis_names == ("macro",) and m.devices.shape == (1,)
    m2 = make_mesh((1,), ("macro",), devices=jax.devices())
    assert m2.devices.shape == (1,)
    with pytest.raises(ValueError, match="needs 2 devices"):
        make_mesh((2,), ("macro",), devices=jax.devices()[:1])


def test_explicit_mesh_single_device_parity():
    rng = np.random.default_rng(0)
    emb = rng.normal(size=(48, 24)).astype(np.float32)
    cfg = RetrievalConfig()
    idx = ShardedDircIndex.build(
        emb, cfg, n_shards=4, parallelism="shard_map",
        mesh=make_macro_mesh())
    mono = DircRagIndex.build(emb, cfg)
    q = jnp.asarray(emb[:3] + 0.01 * rng.normal(size=(3, 24)), jnp.float32)
    got, want = idx.search(q, 5), mono.search(q, 5)
    assert np.array_equal(np.asarray(got.indices), np.asarray(want.indices))


def test_mesh_requires_shard_map():
    emb = np.zeros((8, 8), np.float32)
    with pytest.raises(ValueError, match="shard_map"):
        ShardedDircIndex.build(emb, RetrievalConfig(), n_shards=2,
                               parallelism="vmap", mesh=make_macro_mesh())


def test_distributed_shim_warns_and_forwards():
    import repro.core.distributed as D
    import repro.core.sharded_index as SI

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        fn = D.make_distributed_searcher
        arrs = D.shard_index_arrays
    assert fn is SI.make_distributed_searcher
    assert arrs is SI.shard_index_arrays
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 2 and "sharded_index" in str(dep[0].message)
    with pytest.raises(AttributeError):
        D.no_such_name


# ------------------------------------------------------------ multi device
_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, json
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import DircRagIndex, RetrievalConfig, ShardedDircIndex
    from repro.core import quantization as Q
    from repro.core.sharded_index import (make_distributed_searcher,
                                          shard_index_arrays)
    from repro.launch.mesh import make_macro_mesh

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(0)
    emb = rng.normal(size=(128, 32)).astype(np.float32)
    cfg = RetrievalConfig()
    q = jnp.asarray(emb[:4] + 0.01 * rng.normal(size=(4, 32)), jnp.float32)
    mono = DircRagIndex.build(emb, cfg)
    want = mono.search(q, 8)

    # 1) stacked macro images on an explicit 4-device mesh: exact score
    #    AND top-k parity with the monolithic index
    mesh = make_macro_mesh(4)
    assert mesh.devices.shape == (4,)
    idx = ShardedDircIndex.build(emb, cfg, n_shards=4,
                                 parallelism="shard_map", mesh=mesh)
    got = idx.search(q, 8)
    ok_topk = bool(np.array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices)))
    flat_sharded = np.asarray(idx.scores(q))      # (S, b, cap)
    flat_mono = np.asarray(mono.scores(q))        # (b, n)
    per_doc = np.transpose(flat_sharded, (1, 0, 2)).reshape(4, -1)
    ok_scores = bool(np.array_equal(per_doc[:, : flat_mono.shape[1]],
                                    flat_mono))

    # 2) default mesh (None -> all devices) matches too
    idx2 = ShardedDircIndex.build(emb, cfg, n_shards=4,
                                  parallelism="shard_map")
    ok_default = bool(np.array_equal(np.asarray(idx2.search(q, 8).indices),
                                     np.asarray(want.indices)))

    # 3) folded flat-index searcher == flat top-k on the same mesh
    docs = Q.quantize(jnp.asarray(emb), bits=8)
    norms = Q.doc_int_norms(docs)
    dv, nv = shard_index_arrays(mesh, docs.values, norms)
    search = make_distributed_searcher(mesh, k=8, metric="cosine")
    qq = Q.quantize_query(q)
    res = search(qq.values, dv, nv)
    ip = Q.int_inner_product(qq.values, docs.values).astype(jnp.float32)
    qn = jnp.sqrt(jnp.sum(qq.values.astype(jnp.float32) ** 2, -1,
                          keepdims=True))
    fv, fi = jax.lax.top_k(ip / jnp.maximum(qn * norms[None, :], 1e-12), 8)
    ok_flat = bool((res.indices == fi).all())

    # 4) a mesh whose size does not divide n_shards is refused, never
    #    quietly scored as a one-device vmap
    try:
        ShardedDircIndex.build(emb, cfg, n_shards=6,
                               parallelism="shard_map", mesh=mesh)
        ok_refuse = False
    except ValueError:
        ok_refuse = True

    # 5) replica fleet: one replica (weights and pool) per device, greedy
    #    tokens identical to one engine's
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               EngineRouter, RouterConfig)
    mcfg = get_config("phi4-mini-3.8b", smoke=True)
    model = build_model(mcfg)
    params = model.init(jax.random.key(0))
    ecfg = EngineConfig(n_slots=2, paged=True, cache_len=64, block_size=8,
                        prefill_chunk=8)
    prompts = [rng.integers(0, mcfg.vocab_size, size=n)
               for n in (5, 12, 9, 20)]
    single = ContinuousBatchingEngine(model, params, ecfg)
    want_t = [single.submit(p, max_new_tokens=4) for p in prompts]
    single.run_until_drained()
    fleet = EngineRouter(model, params, ecfg, RouterConfig(n_replicas=4))
    got_t = [fleet.submit(p, max_new_tokens=4) for p in prompts]
    fleet.run_until_drained()
    placed = [e.device for e in fleet.engines]
    ok_devices = len(set(placed)) == 4 and all(
        leaf.devices() == {e.device}
        for e in fleet.engines
        for leaf in jax.tree_util.tree_leaves((e.params, e._pools)))
    ok_router = all(list(a.result()) == list(b.result())
                    for a, b in zip(want_t, got_t))

    print(json.dumps({"ok_topk": ok_topk, "ok_scores": ok_scores,
                      "ok_default": ok_default, "ok_flat": ok_flat,
                      "ok_refuse": ok_refuse, "ok_devices": ok_devices,
                      "ok_router": ok_router}))
""") % os.path.join(REPO, "src")


def test_shard_map_multidevice_parity_subprocess():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok_topk"], "mesh search != monolithic top-k"
    assert out["ok_scores"], "mesh scores != monolithic scores"
    assert out["ok_default"], "default mesh != monolithic top-k"
    assert out["ok_flat"], "folded flat searcher != flat top-k"
    assert out["ok_refuse"], "shard_map accepted a mesh not dividing shards"
    assert out["ok_devices"], "replicas do not each hold their own device"
    assert out["ok_router"], "routed greedy tokens != one engine's"
