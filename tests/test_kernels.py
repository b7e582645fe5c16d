"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitplane as B
from repro.kernels import ops, ref


@pytest.mark.parametrize("b,n,dim,bits", [
    (1, 128, 128, 8), (2, 300, 128, 8), (4, 515, 512, 4),
    (3, 130, 1024, 8), (1, 64, 256, 4), (8, 256, 128, 8),
])
def test_dirc_mac_sweep(rng, b, n, dim, bits):
    lo, hi = (-8, 8) if bits == 4 else (-128, 128)
    q = jnp.asarray(rng.integers(lo, hi, size=(b, dim)), jnp.int8)
    d = jnp.asarray(rng.integers(lo, hi, size=(n, dim)), jnp.int8)
    planes = B.to_bitplanes(d, bits=bits)
    got = np.asarray(ops.dirc_mac(q, B.pack_words(planes), bits=bits))
    want = np.asarray(ref.dirc_mac(q, planes, bits=bits))
    assert (got == want).all()


def test_dirc_mac_1d_query(rng):
    q = jnp.asarray(rng.integers(-128, 128, size=(128,)), jnp.int8)
    d = jnp.asarray(rng.integers(-128, 128, size=(100, 128)), jnp.int8)
    packed = B.pack_words(B.to_bitplanes(d))
    got = np.asarray(ops.dirc_mac(q, packed))
    assert got.shape == (100,)
    want = np.asarray(q, np.int64) @ np.asarray(d, np.int64).T
    assert (got == want).all()


@pytest.mark.parametrize("b,n,dim", [(1, 128, 128), (3, 257, 384),
                                     (2, 1000, 512)])
def test_score_matmul_sweep(rng, b, n, dim):
    q = jnp.asarray(rng.integers(-128, 128, size=(b, dim)), jnp.int8)
    d = jnp.asarray(rng.integers(-128, 128, size=(n, dim)), jnp.int8)
    got = np.asarray(ops.score_matmul(q, d))
    want = np.asarray(ref.score_matmul_int(q, d))
    assert (got == want).all()


def test_score_matmul_cosine(rng):
    q = jnp.asarray(rng.integers(-128, 128, size=(2, 128)), jnp.int8)
    d = jnp.asarray(rng.integers(-128, 128, size=(300, 128)), jnp.int8)
    dn = jnp.sqrt(jnp.sum(d.astype(jnp.float32) ** 2, -1))
    got = ops.score_matmul_cosine(q, d, dn)
    qn = jnp.sqrt(jnp.sum(q.astype(jnp.float32) ** 2, -1, keepdims=True))
    want = ref.score_matmul_cosine(q, d, qn, dn[None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("b,n,k", [(1, 512, 1), (3, 1200, 7), (2, 2048, 64)])
def test_topk_kernel_sweep(rng, b, n, k):
    s = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32))
    fv, fi = ops.local_topk_blocks(s, k=k)
    rv, ri = jax.lax.top_k(s, k)
    assert (fi == ri).all()
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv))


def test_topk_kernel_ties():
    s = jnp.zeros((2, 1024))
    fv, fi = ops.local_topk_blocks(s, k=4)
    assert (np.asarray(fi) == np.arange(4)).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8]))
def test_property_kernel_exactness(seed, bits):
    rng = np.random.default_rng(seed)
    lo, hi = (-8, 8) if bits == 4 else (-128, 128)
    q = jnp.asarray(rng.integers(lo, hi, size=(2, 128)), jnp.int8)
    d = jnp.asarray(rng.integers(lo, hi, size=(96, 128)), jnp.int8)
    planes = B.to_bitplanes(d, bits=bits)
    got = np.asarray(ops.dirc_mac(q, B.pack_words(planes), bits=bits))
    want = np.asarray(q, np.int64) @ np.asarray(d, np.int64).T
    assert (got == want).all()


# ------------------------------------------ interpret mode from the platform
def _platform_is(monkeypatch, name, calls=None):
    """Make the kernels see `name` as the default backend (counting
    queries in `calls`)."""
    from repro.kernels import _env

    def fake():
        if calls is not None:
            calls.append(name)
        return name

    monkeypatch.setattr(_env.jax, "default_backend", fake)


def _case_cpu_interprets(monkeypatch):
    from repro.kernels._env import resolve_interpret

    _platform_is(monkeypatch, "cpu")
    assert resolve_interpret(None) is True


def _case_tpu_compiles(monkeypatch):
    from repro.kernels._env import resolve_interpret

    _platform_is(monkeypatch, "tpu")
    assert resolve_interpret(None) is False


def _case_explicit_false_honoured(monkeypatch):
    from repro.kernels._env import resolve_interpret

    _platform_is(monkeypatch, "cpu")
    assert resolve_interpret(False) is False


def _case_explicit_true_honoured(monkeypatch):
    from repro.kernels._env import resolve_interpret

    _platform_is(monkeypatch, "tpu")
    assert resolve_interpret(True) is True


def _case_public_kernels_default_to_none(monkeypatch):
    """Public kernel entry points must leave `interpret` unset so the
    platform decides; hard-coding it would pin a chip run to the
    interpreter (or a CPU run to Mosaic)."""
    import inspect

    from repro.kernels import dirc_mac, paged_attend, score_matmul, topk_select

    fns = [score_matmul.score_matmul_int, score_matmul.score_matmul_cosine,
           dirc_mac.dirc_mac_packed, topk_select.blockwise_topk,
           paged_attend.paged_attend_fused]
    for fn in fns:
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, f"{fn.__name__} hard-codes interpret"


def _case_ops_resolve_at_call_time(monkeypatch):
    """The ops wrappers ask for the platform while they are traced, not
    when the module is imported: a fresh shape consults it again."""
    calls = []
    _platform_is(monkeypatch, "cpu", calls)
    assert not hasattr(ops, "INTERPRET")
    q = jnp.ones((1, 160), jnp.int8)
    d = jnp.ones((131, 160), jnp.int8)
    assert np.asarray(ops.score_matmul(q, d)).shape == (1, 131)
    assert calls, "score_matmul traced without consulting the platform"


INTERPRET_CASES = {
    "cpu_interprets": _case_cpu_interprets,
    "tpu_compiles": _case_tpu_compiles,
    "explicit_false_honoured": _case_explicit_false_honoured,
    "explicit_true_honoured": _case_explicit_true_honoured,
    "public_kernels_default_to_none": _case_public_kernels_default_to_none,
    "ops_resolve_at_call_time": _case_ops_resolve_at_call_time,
}


@pytest.mark.parametrize("case", sorted(INTERPRET_CASES))
def test_interpret_resolves_from_platform(monkeypatch, case):
    INTERPRET_CASES[case](monkeypatch)
