"""Plain reference of the decoder LMs in chip_bench/configs: float32.

The published block of Phi-3 and Phi-4-mini: RMSNorm before attention
and before the MLP, grouped-query attention (query head i reads KV head
i // group) with rotary position embeddings on the split halves of each
head (theta from the configuration, every dimension rotated), causal
softmax attention scaled by head_dim ** -0.5, a SwiGLU MLP
(silu(x Wg) * (x Wu)) Wd, residual adds, a final RMSNorm and the
output head (the embedding table when tied). It takes a configuration
as run: where the program departs from the source (a configuration's
`departures`), the reference follows the program, and refuses a
configuration that asks for partial or scaled rotary embeddings.

It runs one layer at a time under jit, with every matmul at HIGHEST
precision, from weights it makes itself with `chip_bench.weights.make`
(the same seeded call the benchmark hands the program's copy from). It
imports nothing of the program.

`precision="fp8"` is the control: every matmul operand is scaled per
tensor into float8_e4m3fn and back before the product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 256  # sequences are padded to a multiple of this (fewer compiles)


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, precision: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (s, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _layer(x, w, n_real, *, dims, precision):
    h, kh, hd, eps, theta = dims
    s = x.shape[0]
    pos = jnp.arange(s)
    y = _rms(x, w["attn_norm"], eps)
    q = _mm(y, w["wq"], precision).reshape(s, h, hd)
    k = _mm(y, w["wk"], precision).reshape(s, kh, hd)
    v = _mm(y, w["wv"], precision).reshape(s, kh, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = h // kh
    qg = q.reshape(s, kh, g, hd).transpose(1, 2, 0, 3)   # (kh, g, s, hd)
    kt = k.transpose(1, 2, 0)                              # (kh, hd, s)
    scores = _mm(qg, kt[:, None], precision) * hd ** -0.5  # (kh, g, s, s)
    visible = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
    scores = jnp.where(visible, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    vt = v.transpose(1, 0, 2)[:, None]                     # (kh, 1, s, hd)
    o = _mm(p, vt, precision)                              # (kh, g, s, hd)
    o = o.transpose(2, 0, 1, 3).reshape(s, h * hd)
    x = x + _mm(o, w["wo"], precision)
    y = _rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_mm(y, w["w_gate"], precision)) * _mm(y, w["w_up"],
                                                           precision)
    return x + _mm(a, w["w_down"], precision)


HEAD_ROWS = 128  # output-head rows per launch


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "precision"))
def _head(x, r0, norm, table, *, vocab, eps, precision):
    rows = jax.lax.dynamic_slice_in_dim(x, r0, HEAD_ROWS)
    y = _rms(rows, norm, eps)
    return _mm(y, table, precision)[:, :vocab]


def _layer_weights(params: dict, i: int) -> dict:
    b = params["blocks"]
    return {"attn_norm": b["attn_norm"]["scale"][i],
            "mlp_norm": b["mlp_norm"]["scale"][i],
            **{n: b["attn"][n][i] for n in ("wq", "wk", "wv", "wo")},
            **{n: b["mlp"][n][i] for n in ("w_gate", "w_up", "w_down")}}


def logits(config: dict, params: dict, tokens, first: int,
           precision: str = "f32") -> np.ndarray:
    """Logits (len(tokens) - first, vocab) at positions first.. of one
    sequence, float32 on the host."""
    if config.get("partial_rotary_factor", 1.0) != 1.0 or config.get(
            "rope_scaling"):
        raise NotImplementedError("the reference rotates every head "
                                  "dimension with plain RoPE")
    tokens = np.asarray(tokens, np.int32)
    n = tokens.size
    s = -(-n // PAD) * PAD
    padded = np.zeros((s,), np.int32)
    padded[:n] = tokens
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]))
    x = params["embedding"]["embed"][jnp.asarray(padded)].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, _layer_weights(params, i), n, dims=dims,
                   precision=precision)
    emb = params["embedding"]
    table = emb["embed"].T if config["tie_word_embeddings"] \
        else emb["lm_head"]
    x = jnp.pad(x, ((0, HEAD_ROWS), (0, 0)))
    out = [np.asarray(_head(x, r0, params["final_norm"]["scale"], table,
                            vocab=config["vocab_size"], eps=dims[3],
                            precision=precision), np.float32)
           for r0 in range(first, n, HEAD_ROWS)]
    return np.concatenate(out)[: n - first]


def served_gaps(ref: np.ndarray, served) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the best)."""
    served = np.asarray(served, np.int64)
    rows = np.arange(served.size)
    return ref.max(axis=-1) - ref[rows, served]
