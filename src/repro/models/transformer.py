"""Decoder-only transformer LM (dense / MoE / VLM families).

One scan-compiled stack: per-layer params are stacked on a leading L axis
and the block body is traced ONCE (`jax.lax.scan`), keeping HLO size —
and therefore 512-device SPMD compile time — independent of depth.
Optional remat ("full" | "dots") wraps the block body.

Supports:
  * pre-norm blocks (llama/phi) and parallel blocks (command-r: one shared
    input norm, attn and MLP in parallel);
  * GQA attention with RoPE / M-RoPE / no positions;
  * MoE FFN (Arctic dense-residual included) with aux-loss accumulation;
  * train forward, prefill (returns stacked KV caches), single-token decode.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention, layers, moe as moe_mod, rope, sharding


class DecodeCaches(NamedTuple):
    k: jax.Array        # (L, b, S, kh, hd)
    v: jax.Array        # (L, b, S, kh, hd)
    length: jax.Array   # (b,) shared across layers


class PagedDecodeCaches(NamedTuple):
    """Block-pooled decode caches: the pools are shared by every sequence
    and carry NO per-sequence state — block tables and lengths are pure
    inputs to `paged_step`, owned by the host-side
    `serving.paged_cache.PagedCacheManager`."""

    k_pool: jax.Array   # (L, n_blocks, block_size, kh, hd)
    v_pool: jax.Array   # (L, n_blocks, block_size, kh, hd)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return fn


class DecoderLM:
    """Functional decoder-only LM; all methods are pure."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- params
    def _init_block(self, key) -> dict:
        cfg = self.cfg
        ka, km, kn1, kn2 = jax.random.split(key, 4)
        p = {
            "attn_norm": layers.init_norm(cfg),
            "attn": attention.init_attention(cfg, ka),
        }
        if not cfg.parallel_block:
            p["mlp_norm"] = layers.init_norm(cfg)
        if cfg.moe is not None:
            p["moe"] = moe_mod.init_moe(cfg, km)
        else:
            p["mlp"] = layers.init_mlp(cfg, km)
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        ke, kb, kf = jax.random.split(key, 3)
        block_keys = jax.random.split(kb, cfg.n_layers)
        blocks = jax.vmap(self._init_block)(block_keys)
        return {
            "embedding": layers.init_embedding(cfg, ke),
            "blocks": blocks,
            "final_norm": layers.init_norm(cfg),
        }

    # ------------------------------------------------------------- angles
    def _angles(self, positions: Optional[jax.Array], b: int, s: int):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        if cfg.rope_style == "none":
            return None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            if cfg.rope_style == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, b, s))
        if cfg.rope_style == "mrope":
            return rope.mrope_angles(positions, hd, cfg.rope_theta,
                                     cfg.mrope_sections)
        return rope.rope_angles(positions, hd, cfg.rope_theta)

    # ------------------------------------------------------------ forward
    def _block_fwd(self, p, x, angles):
        cfg = self.cfg
        x = sharding.constrain(x, ("batch", "seq", None))
        if cfg.parallel_block:
            h = layers.apply_norm(cfg, p["attn_norm"], x)
            a = attention.attend_train(cfg, p["attn"], h, angles)
            if cfg.moe is not None:
                m, aux = moe_mod.apply_moe(cfg, p["moe"], h)
            else:
                m, aux = layers.apply_mlp(cfg, p["mlp"], h), None
            # add the two PARTIAL outputs first, then one shared
            # reduce(-scatter) onto the seq-sharded residual: halves the
            # parallel-block's output collectives.
            y = x + sharding.constrain(a + m, ("batch", "seq", None))
        else:
            h = layers.apply_norm(cfg, p["attn_norm"], x)
            x = x + attention.attend_train(cfg, p["attn"], h, angles)
            h2 = layers.apply_norm(cfg, p["mlp_norm"], x)
            if cfg.moe is not None:
                m, aux = moe_mod.apply_moe(cfg, p["moe"], h2)
            else:
                m, aux = layers.apply_mlp(cfg, p["mlp"], h2), None
            y = x + m
        y = sharding.constrain(y, ("batch", "seq", None))
        aux_vec = (
            jnp.zeros((3,), jnp.float32)
            if aux is None
            else jnp.stack([aux.load_balance_loss, aux.router_z_loss,
                            aux.dropped_fraction])
        )
        return y, aux_vec

    def hidden_states(self, params, tokens=None, embeds=None,
                      positions=None) -> tuple[jax.Array, jax.Array]:
        """Run the stack; returns (hidden (b, s, d), aux (3,))."""
        cfg = self.cfg
        if embeds is None:
            embeds = layers.embed_tokens(cfg, params["embedding"], tokens)
        b, s, _ = embeds.shape
        angles = self._angles(positions, b, s)
        body = _remat(cfg, self._block_fwd)

        def scan_fn(x, p):
            y, aux = body(p, x, angles)
            return y, aux

        x, auxes = jax.lax.scan(scan_fn, embeds, params["blocks"],
                                unroll=cfg.scan_unroll)
        x = layers.apply_norm(cfg, params["final_norm"], x)
        return x, jnp.mean(auxes, axis=0)

    def forward(self, params, tokens=None, embeds=None, positions=None):
        x, aux = self.hidden_states(params, tokens, embeds, positions)
        logits = layers.logits_from_hidden(self.cfg, params["embedding"], x)
        return logits, aux

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, aux = self.hidden_states(
            params,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            positions=batch.get("positions"),
        )
        ce = layers.lm_head_loss(cfg, params["embedding"], x, batch["labels"])
        total = ce
        if cfg.moe is not None:
            total = total + cfg.moe.aux_loss_weight * aux[0] + 1e-4 * aux[1]
        metrics = {"ce": ce, "load_balance": aux[0], "router_z": aux[1],
                   "dropped": aux[2]}
        return total, metrics

    # ------------------------------------------------------------ serving
    def _block_join(self, p, x, h, y):
        """Residual join after attention: x is the block input, h its
        normed copy, y the attention output. Applies the MLP/MoE branch
        in parallel-block or sequential form (shared by the prefill,
        decode, and paged-decode block bodies; auxes are dropped —
        serving never trains)."""
        cfg = self.cfg
        if cfg.parallel_block:
            if cfg.moe is not None:
                m, _ = moe_mod.apply_moe(cfg, p["moe"], h)
            else:
                m = layers.apply_mlp(cfg, p["mlp"], h)
            return x + y + m
        x = x + y
        h2 = layers.apply_norm(cfg, p["mlp_norm"], x)
        if cfg.moe is not None:
            m, _ = moe_mod.apply_moe(cfg, p["moe"], h2)
        else:
            m = layers.apply_mlp(cfg, p["mlp"], h2)
        return x + m

    def _block_prefill(self, p, x, angles, cache_len):
        cfg = self.cfg
        h = layers.apply_norm(cfg, p["attn_norm"], x)
        y, cache = attention.prefill(cfg, p["attn"], h, angles, cache_len)
        return self._block_join(p, x, h, y), cache

    def prefill(self, params, tokens=None, embeds=None, positions=None,
                cache_len: Optional[int] = None):
        """Returns (logits of last position (b, V), DecodeCaches)."""
        cfg = self.cfg
        if embeds is None:
            embeds = layers.embed_tokens(cfg, params["embedding"], tokens)
        b, s, _ = embeds.shape
        cache_len = cache_len or s
        angles = self._angles(positions, b, s)

        def scan_fn(x, p):
            y, cache = self._block_prefill(p, x, angles, cache_len)
            return y, cache

        x, caches = jax.lax.scan(scan_fn, embeds, params["blocks"],
                                unroll=cfg.scan_unroll)
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.logits_from_hidden(cfg, params["embedding"], x[:, -1])
        return logits, DecodeCaches(k=caches.k, v=caches.v,
                                    length=caches.length[0])

    def init_caches(self, batch: int, cache_len: int,
                    prefix_len) -> DecodeCaches:
        """Empty caches of logical length `prefix_len` (decode dry-run)."""
        cfg = self.cfg
        kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cdt = layers.dt(cfg.compute_dtype)
        shape = (cfg.n_layers, batch, cache_len, kh, hd)
        length = jnp.broadcast_to(jnp.asarray(prefix_len, jnp.int32), (batch,))
        return DecodeCaches(k=jnp.zeros(shape, cdt), v=jnp.zeros(shape, cdt),
                            length=length)

    def _block_decode(self, carry, p_and_cache):
        x, angles = carry
        p, (k, v, length) = p_and_cache
        cfg = self.cfg
        cache = attention.KVCache(k=k, v=v, length=length)
        h = layers.apply_norm(cfg, p["attn_norm"], x)
        y, new_cache = attention.decode_step(cfg, p["attn"], h, cache, angles)
        return (self._block_join(p, x, h, y), angles), \
            (new_cache.k, new_cache.v)

    def decode_step(self, params, caches: DecodeCaches, token: jax.Array,
                    positions: Optional[jax.Array] = None):
        """token (b, 1) -> (logits (b, V), new caches). One new token
        against per-layer KV caches (scan over layers)."""
        cfg = self.cfg
        x = layers.embed_tokens(cfg, params["embedding"], token)
        b = x.shape[0]
        if positions is None:
            positions = caches.length[:, None]  # (b, 1)
            if cfg.rope_style == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, b, 1))
        angles = self._angles(positions, b, 1)
        length_b = jnp.broadcast_to(caches.length, (b,)) \
            if caches.length.ndim else jnp.full((b,), caches.length)

        def scan_fn(carry, inp):
            return self._block_decode(carry, inp)

        (x, _), (k_new, v_new) = jax.lax.scan(
            scan_fn, (x, angles),
            (params["blocks"], (caches.k, caches.v,
                                jnp.broadcast_to(length_b, (cfg.n_layers, b)))),
            unroll=cfg.scan_unroll,
        )
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.logits_from_hidden(cfg, params["embedding"], x[:, -1])
        new = DecodeCaches(k=k_new, v=v_new, length=caches.length + 1)
        return logits, new

    # ----------------------------------------------------- paged serving
    def init_paged_caches(self, n_blocks: int,
                          block_size: int) -> PagedDecodeCaches:
        """Shared K/V block pools (no per-sequence state; see
        `serving.paged_cache` for the allocator that owns block tables)."""
        cfg = self.cfg
        kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cdt = layers.dt(cfg.compute_dtype)
        shape = (cfg.n_layers, n_blocks, block_size, kh, hd)
        return PagedDecodeCaches(k_pool=jnp.zeros(shape, cdt),
                                 v_pool=jnp.zeros(shape, cdt))

    def paged_step(self, params, pools: PagedDecodeCaches,
                   block_tables: jax.Array, lengths: jax.Array,
                   tokens: jax.Array, n_valid: jax.Array,
                   positions: Optional[jax.Array] = None,
                   paged_kernel: Optional[bool] = None):
        """Advance each row by its next `n_valid[b] <= t` tokens.

        tokens (b, t) holds row b's tokens for logical positions
        `lengths[b] .. lengths[b] + n_valid[b] - 1` (entries past n_valid
        are padding). t == 1 with n_valid == 1 is the batched decode
        step; t == prefill_chunk at b == 1 is one chunked-prefill piece —
        one trace, two compiled shapes. Returns (logits (b, V) at each
        row's LAST VALID position, new pools). Inactive rows (all-null
        block table, length 0) write only the scratch block and their
        logits are garbage the caller ignores. `paged_kernel` selects the
        fused Pallas path in `attention.paged_attend` (None defers to
        `cfg.paged_kernel`).

        The layer loop carries the whole pools and hands each layer its
        index; both paths write only that layer's new positions, so
        under a jit that donates `pools` no program copies a pool.
        """
        cfg = self.cfg
        x = layers.embed_tokens(cfg, params["embedding"], tokens)
        b, t, _ = x.shape
        if positions is None:
            positions = lengths[:, None] + jnp.arange(t)[None, :]
            if cfg.rope_style == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, b, t))
        angles = self._angles(positions, b, t)

        def scan_fn(carry, inp):
            x, kp, vp = carry
            p, layer = inp
            cache = attention.PagedKVCache(
                k_pool=kp, v_pool=vp, block_table=block_tables,
                length=lengths)
            h = layers.apply_norm(cfg, p["attn_norm"], x)
            y, kp, vp = attention.paged_attend(
                cfg, p["attn"], h, cache, angles, n_valid,
                paged_kernel=paged_kernel, layer=layer)
            return (self._block_join(p, x, h, y), kp, vp), None

        (x, k_new, v_new), _ = jax.lax.scan(
            scan_fn, (x, pools.k_pool, pools.v_pool),
            (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
            unroll=cfg.scan_unroll,
        )
        x = layers.apply_norm(cfg, params["final_norm"], x)
        idx = jnp.clip(n_valid - 1, 0, t - 1)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        logits = layers.logits_from_hidden(cfg, params["embedding"], last)
        return logits, PagedDecodeCaches(k_pool=k_new, v_pool=v_new)
