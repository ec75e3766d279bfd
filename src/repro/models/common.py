"""Shared model building blocks (pure JAX, no framework dependencies)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.quant.bitnet import (
    TernaryWeight,
    fake_quant_act,
    fake_quant_weight,
    quantize_act_int8,
    ternary_dot,
)


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def dense(
    x: jnp.ndarray, w, *, quantize: bool = False,
) -> jnp.ndarray:
    """Linear layer.  A :class:`TernaryWeight` (served weights, quantized
    once by ``serve.engine.prepare_params``) runs the integer BitLinear;
    on a plain array ``quantize`` applies BitNet QAT fake-quant (STE).

    BitLinear = absmax-int8 activations x absmean-ternary weights.  The
    caller normalizes ``x`` first (BitNet wraps RMSNorm around quant).
    """
    if isinstance(w, TernaryWeight):
        return ternary_dot(x, w)
    if quantize:
        x = fake_quant_act(x)
        w = fake_quant_weight(w)
    return x @ w


def bitlinear_weight(w, quantize: bool) -> jnp.ndarray:
    """The values :func:`dense` multiplies by, in float32: a served
    weight's codes times gamma, the fake-quantized latent weight, or the
    plain weight."""
    if isinstance(w, TernaryWeight):
        return w.q.astype(jnp.float32) * w.gamma
    if quantize:
        w = fake_quant_weight(w)
    return w.astype(jnp.float32)


def bitlinear_codes(x, w, quantize: bool):
    """The activations :func:`dense` multiplies ``w`` by, as float32 codes
    and a per-token scale [..., 1] (values = codes * scale): a served
    weight's int8 codes and absmax scale, else the fake-quantized or plain
    values with scale 1."""
    if isinstance(w, TernaryWeight):
        xq, xs = quantize_act_int8(x)
        return xq.astype(jnp.float32), xs.astype(jnp.float32)
    if quantize:
        x = fake_quant_act(x)
    return x.astype(jnp.float32), jnp.ones(x.shape[:-1] + (1,), jnp.float32)


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float):
    """cos/sin tables for RoPE. positions [...], returns [..., head_dim/2]."""
    half = head_dim // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x [..., S, H, D]; cos/sin [..., S, D/2] broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w1, w2, w3, *, quantize: bool):
    """SwiGLU MLP: (silu(x@w1) * (x@w3)) @ w2."""
    h = jax.nn.silu(dense(x, w1, quantize=quantize)) * dense(
        x, w3, quantize=quantize
    )
    h = constrain(h, "batch", "seq", "ff")
    return dense(h, w2, quantize=quantize)


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean token cross-entropy; logits [B, S, V] f32-cast, targets [B, S]."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0]
    return jnp.mean(logz - gold)


def embed_init(key, vocab: int, d_model: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


def best_grouping(layers: int) -> int:
    """Divisor G of ``layers`` minimizing G + layers/G (sqrt-remat): a
    two-level scan saves G outer carries + one group's inner carries
    instead of all L — same 2x-forward compute as flat per-layer remat."""
    best = 1
    for g in range(1, layers + 1):
        if layers % g == 0 and (g + layers // g) < (best + layers // best):
            best = g
    return best


def maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn

    def wrapped(carry, xs):
        # barrier: keeps the saved scan carry in its storage dtype — without
        # it XLA's convert-hoisting can materialize the whole [L, b, s, d]
        # residual stack in f32 (2x HBM)
        carry = jax.lax.optimization_barrier(carry)
        return fn(carry, xs)

    if cfg.remat == "dots":
        return jax.checkpoint(
            wrapped,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    return jax.checkpoint(wrapped, policy=None)
