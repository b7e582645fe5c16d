"""Seeded weights of a decoder LM, made by the benchmark on the device.

One jitted call makes every leaf from the seed, in the dtype the
configuration serves (bf16), in the layout of the program's parameter
tree: embedding table (rows padded to a multiple of 256, as the program
pads its vocabulary), per-layer q/k/v/o and SwiGLU matrices stacked on a
leading layer axis, RMSNorm scales of one. The program is handed these
weights; the reference makes the same ones again with the same call
(`make`), so it takes nothing the program has made.

`make` checks the tree against the program's own `model.init` shapes
(`jax.eval_shape`), so a program whose parameter layout moved fails
here, loudly, rather than being handed weights it reads differently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .traffic import jax_seed


def padded_vocab(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


def shapes(c: dict) -> dict:
    """Leaf shapes of the tree, from the configuration file's sizes."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    hd, L = c["head_dim"], c["num_hidden_layers"]
    vp = padded_vocab(c["vocab_size"])
    emb = {"embed": (vp, d)}
    if not c["tie_word_embeddings"]:
        emb["lm_head"] = (d, vp)
    return {
        "embedding": emb,
        "blocks": {
            "attn_norm": {"scale": (L, d)},
            "attn": {"wq": (L, d, h * hd), "wk": (L, d, kh * hd),
                     "wv": (L, d, kh * hd), "wo": (L, h * hd, d)},
            "mlp_norm": {"scale": (L, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
        "final_norm": {"scale": (d,)},
    }


def _std(path: str, c: dict) -> float:
    d, f = c["hidden_size"], c["intermediate_size"]
    L = c["num_hidden_layers"]
    hq = c["num_attention_heads"] * c["head_dim"]
    return {
        "embed": 0.02, "lm_head": d ** -0.5, "wq": d ** -0.5,
        "wk": d ** -0.5, "wv": d ** -0.5,
        "wo": hq ** -0.5 / (2 * L) ** 0.5, "w_gate": d ** -0.5,
        "w_up": d ** -0.5, "w_down": f ** -0.5 / (2 * L) ** 0.5,
    }[path]


@functools.partial(jax.jit, static_argnames=("frozen",))
def _make(key, frozen: tuple):
    c = dict(frozen)
    out: dict = {}
    leaves = jax.tree_util.tree_leaves_with_path(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name == "scale":
            leaf = jnp.ones(shape, jnp.bfloat16)
        else:
            k = jax.random.fold_in(key, i)
            leaf = (_std(name, c) * jax.random.normal(k, shape, jnp.float32)
                    ).astype(jnp.bfloat16)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p.key, {})
        node[name] = leaf
    return out


SIZE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "tie_word_embeddings")


def make(config: dict, seed: int) -> dict:
    """The weights of `config` for `seed`, bf16, on the default device."""
    frozen = tuple(sorted((k, config[k]) for k in SIZE_KEYS))
    return _make(jax.random.key(jax_seed(seed)), frozen)


def check_layout(params: dict, model) -> None:
    """Raise unless `params` matches the program's `model.init` tree."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got):
        raise ValueError("the program's parameter tree differs from the "
                         f"benchmark's: {jax.tree_util.tree_structure(want)}")
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"leaf {g.shape} {g.dtype}: the program "
                             f"expects {w.shape} {w.dtype}")
