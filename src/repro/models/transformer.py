"""Decoder/encoder transformer with scan-over-layers (dense, MoE, VLM,
encoder families).

Layer parameters are stacked on a leading [L] axis and consumed with
``jax.lax.scan`` so an 80-layer model traces exactly one block — mandatory
for compiling the big dry-run cells and standard practice at scale.  A
model with leading dense layers (``first_k_dense``) keeps two stacks,
``dense_blocks`` then ``blocks``, each scanned, and its cache is a tuple
of one :class:`KVCache` per stack.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, constrain_layer_params
from repro.models import moe as moe_mod
from repro.models.attention import (
    KVCache,
    attention,
    init_attn_params,
    init_kv_cache,
    init_mla_params,
    mla_attention,
)
from repro.models.common import (
    best_grouping,
    dense,
    dense_init,
    dtype_of,
    embed_init,
    maybe_remat,
    rms_norm,
    swiglu,
)


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #

def _init_block(cfg, key, d_ff=None):
    """One layer: an expert layer for the MoE family, else (or with
    ``d_ff``, a leading dense layer's width) a SwiGLU MLP."""
    dtype = dtype_of(cfg)
    k_attn, k_mlp = jax.random.split(key)
    init_attn = init_mla_params if cfg.kv_lora_rank else init_attn_params
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attn(k_attn, cfg, dtype),
    }
    if cfg.family == "moe" and d_ff is None:
        p["moe"] = moe_mod.init_moe_params(k_mlp, cfg, dtype)
    else:
        d_ff = d_ff or cfg.d_ff
        km = jax.random.split(k_mlp, 3)
        p["mlp"] = {
            "w1": dense_init(km[0], cfg.d_model, d_ff, dtype),
            "w2": dense_init(km[1], d_ff, cfg.d_model, dtype),
            "w3": dense_init(km[2], cfg.d_model, d_ff, dtype),
        }
    return p


def init_params(cfg, key) -> Dict:
    dtype = dtype_of(cfg)
    k_embed, k_layers, k_head, k_front = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.layers)
    lead = cfg.first_k_dense
    params = {
        "embed": {"tokens": embed_init(k_embed, cfg.vocab, cfg.d_model,
                                       dtype)},
        "blocks": jax.vmap(lambda k: _init_block(cfg, k))(layer_keys[lead:]),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if lead:
        params["dense_blocks"] = jax.vmap(
            lambda k: _init_block(cfg, k, cfg.dense_d_ff))(layer_keys[:lead])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab, dtype)
    if cfg.frontend == "audio_frames":
        # stub for the conv feature extractor: frames arrive pre-extracted
        params["frontend"] = {
            "proj": dense_init(k_front, cfg.d_model, cfg.d_model, dtype)
        }
    return params


def _stacks(params) -> list:
    """The stacks of like layers, in order."""
    if "dense_blocks" in params:
        return [params["dense_blocks"], params["blocks"]]
    return [params["blocks"]]


# --------------------------------------------------------------------------- #
# Blocks
# --------------------------------------------------------------------------- #

def _block(cfg, p, x, positions, cache=None, cache_pos=None, layer=None):
    """One layer: (x, new_cache, routed) — ``routed`` [B, S, held] marks
    the held experts each token chose (None without held experts).
    ``layer`` indexes a stacked cache (``attention.write_rows``)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    attn = mla_attention if cfg.kv_lora_rank else attention
    attn_out, new_cache = attn(
        p["attn"], cfg, h, positions=positions, cache=cache,
        cache_pos=cache_pos, layer=layer,
    )
    x = x + attn_out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    routed = None
    if "moe" in p:
        y, routed = moe_mod.moe_ffn(p["moe"], cfg, h)
        x = x + y
    else:
        x = x + swiglu(h, p["mlp"]["w1"], p["mlp"]["w2"], p["mlp"]["w3"],
                       quantize=cfg.quantization == "bitnet")
    x = constrain(x, "batch", "seq", "embed")
    return x, new_cache, routed


def _scan_blocks(cfg, blocks, x, positions, caches=None, cache_pos=None):
    """lax.scan over stacked layer params (and a stacked KV cache, written
    at ``cache_pos``, or from 0 without one).
    Returns (x, new caches, routed [L, B, S, held] or None)."""
    layers = jax.tree.leaves(blocks)[0].shape[0]

    if caches is None:
        def body(carry, layer_p):
            layer_p = constrain_layer_params(layer_p, cfg)
            y, _, _ = _block(cfg, layer_p, carry, positions)
            return y, None

        groups = best_grouping(layers) if cfg.remat != "none" else 1
        if groups > 1:
            # sqrt-remat: outer scan over G checkpointed groups, plain inner
            # scan over layers-per-group — G + L/G saved carries, not L
            grouped = jax.tree.map(
                lambda a: a.reshape(groups, layers // groups,
                                    *a.shape[1:]), blocks,
            )

            inner = maybe_remat(body, cfg)   # per-layer remat inside too

            def group_body(carry, group_params):
                y, _ = jax.lax.scan(inner, carry, group_params)
                return y, None

            x, _ = jax.lax.scan(maybe_remat(group_body, cfg), x, grouped)
        else:
            x, _ = jax.lax.scan(maybe_remat(body, cfg), x, blocks)
        return x, None, None

    # the stacked cache rides in the carry: each layer writes its new rows
    # into it in place (a prefill from 0, a decode or chunk at cache_pos)
    # and reads its own lanes from it, so no new cache is stacked up
    def body(carry, xs):
        y, cache = carry
        layer_p, layer = xs
        y, cache, routed = _block(cfg, layer_p, y, positions, cache=cache,
                                  cache_pos=cache_pos, layer=layer)
        return (y, cache), routed

    (x, caches), routed = jax.lax.scan(body, (x, caches),
                                       (blocks, jnp.arange(layers)))
    return x, caches, routed


def _scan_stacks(cfg, params, x, positions, cache, cache_pos=None):
    """Every stack in order over its part of the cache: (x, new cache,
    routed of the last stack that routes, or None)."""
    stacks = _stacks(params)
    if len(stacks) == 1:
        return _scan_blocks(cfg, stacks[0], x, positions, cache, cache_pos)
    new, routed = [], None
    for blocks, c in zip(stacks, cache):
        x, c, r = _scan_blocks(cfg, blocks, x, positions, c, cache_pos)
        new.append(c)
        routed = r if r is not None else routed
    return x, tuple(new), routed


def _logits(cfg, params, x):
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tokens"].T
    else:
        logits = dense(x, params["lm_head"])
    # seq deliberately unsharded here: vocab takes the model axis
    return constrain(logits, "batch", None, "vocab")


def _embed_inputs(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x [B, S, d], positions [B, S])."""
    if cfg.frontend == "audio_frames":
        # stub frontend: precomputed frame embeddings [B, S, d]
        x = dense(batch["frames"], params["frontend"]["proj"])
        b, s = x.shape[:2]
        return x, jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    tokens = batch["tokens"]
    x = params["embed"]["tokens"][tokens]
    if cfg.frontend == "vision_patches":
        # stub ViT: precomputed patch embeddings prepended to the text
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x],
                            axis=1)
    b, s = x.shape[:2]
    return x, jnp.broadcast_to(jnp.arange(s)[None], (b, s))


# --------------------------------------------------------------------------- #
# Public forward functions
# --------------------------------------------------------------------------- #

def forward_train(cfg, params, batch) -> jnp.ndarray:
    """Returns logits [B, S_total, V]."""
    x, positions = _embed_inputs(cfg, params, batch)
    x = constrain(x, "batch", "seq", "embed")
    for blocks in _stacks(params):
        x, _, _ = _scan_blocks(cfg, blocks, x, positions)
    return _logits(cfg, params, x)


def init_cache(cfg, batch: int, max_seq: int):
    """A stacked :class:`KVCache` over the layers; with leading dense
    layers, a tuple of one per stack."""
    dtype = dtype_of(cfg)
    single = init_kv_cache(cfg, batch, max_seq, dtype)

    def stack(n):
        return KVCache(*(jnp.broadcast_to(a[None], (n,) + a.shape)
                         for a in single))

    if cfg.first_k_dense:
        return (stack(cfg.first_k_dense),
                stack(cfg.layers - cfg.first_k_dense))
    return stack(cfg.layers)


def forward_prefill(cfg, params, batch, cache: KVCache):
    """Prompt pass: fills cache[:, :, :, :S), returns (last_logits, cache)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, new_cache, _ = _scan_stacks(cfg, params, x, positions, cache)
    logits = _logits(cfg, params, x[:, -1:, :])
    return logits, new_cache


def forward_prefill_chunk(cfg, params, tokens, cache: KVCache, pos0):
    """One fixed-budget slice of an in-flight prefill.

    tokens [B, C] int32, ``pos0`` scalar: writes cache[:, :, :, pos0:pos0+C)
    and attends causally over everything at or below each chunk position —
    earlier chunks already live in the cache below ``pos0``.  Returns
    (last_logits [B, 1, V], new_cache); the logits are only meaningful on a
    prompt's final chunk.  Chaining chunks is bit-exact with
    ``forward_prefill`` over the whole prompt.
    """
    x = params["embed"]["tokens"][tokens]
    b, c = tokens.shape
    positions = jnp.broadcast_to(
        (pos0 + jnp.arange(c, dtype=jnp.int32))[None], (b, c)
    )
    x, new_cache, _ = _scan_stacks(cfg, params, x, positions, cache, pos0)
    logits = _logits(cfg, params, x[:, -1:, :])
    return logits, new_cache


def forward_decode(cfg, params, token, cache: KVCache, pos):
    """One decode step. token [B] int32, pos scalar or per-slot [B] int32.

    Returns (logits [B, 1, V], new_cache); a model with held experts adds
    ``routed`` [B, expert layers, held] bool, the held experts each slot's
    token chose in each expert layer.
    """
    x = params["embed"]["tokens"][token][:, None, :]     # [B, 1, d]
    if jnp.ndim(pos) == 1:
        positions = pos[:, None].astype(jnp.int32)
    else:
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    x, new_cache, routed = _scan_stacks(cfg, params, x, positions, cache,
                                        pos)
    if routed is None:
        return _logits(cfg, params, x), new_cache
    return _logits(cfg, params, x), new_cache, routed[:, :, 0].swapaxes(0, 1)
