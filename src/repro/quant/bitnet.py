"""BitNet b1.58 quantization (QAT) — the model side of the paper's workloads.

Training uses fake-quant with straight-through estimators (QAT, the BitNet
recipe [5]): ternary absmean weights + per-token absmax int8 activations.
Serving materializes the real packed-ternary weights consumed by the
``kernels/bitlinear`` Pallas kernel.

All functions are pure and differentiable where it matters.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.quant import packing

EPS = 1e-5


class QuantizedTensor(NamedTuple):
    """Real (serving-side) quantized weight: packed payload + scale."""

    packed: jnp.ndarray   # uint8 [..., K/ (8/bits)]
    scale: jnp.ndarray    # f32 scalar (absmean) — dequant = unpack * scale
    bits: int
    shape: tuple          # original unpacked shape

    def dequantize(self) -> jnp.ndarray:
        vals = packing.unpack(self.packed, self.bits).astype(jnp.float32)
        return (vals * self.scale).reshape(self.shape)


# --------------------------------------------------------------------------- #
# Weight quantization: absmean ternary (BitNet b1.58)
# --------------------------------------------------------------------------- #

def weight_scale(w: jnp.ndarray) -> jnp.ndarray:
    """gamma = mean(|W|) (per tensor)."""
    return jnp.mean(jnp.abs(w)) + EPS


def quantize_weight_ternary(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """W -> (Q in {-1,0,1} int8, gamma). dequant = Q * gamma."""
    gamma = weight_scale(w)
    q = jnp.clip(jnp.round(w / gamma), -1, 1).astype(jnp.int8)
    return q, gamma


def pack_weight_ternary(w: jnp.ndarray) -> QuantizedTensor:
    q, gamma = quantize_weight_ternary(w)
    return QuantizedTensor(
        packed=packing.pack_2bit(q.reshape(-1, q.shape[-1])),
        scale=gamma, bits=2, shape=w.shape,
    )


def fake_quant_weight(w: jnp.ndarray) -> jnp.ndarray:
    """Ternary fake-quant with STE: forward = dequant(quant(w)), grad = 1."""
    q, gamma = quantize_weight_ternary(w)
    wq = q.astype(w.dtype) * gamma.astype(w.dtype)
    return w + jax.lax.stop_gradient(wq - w)


# --------------------------------------------------------------------------- #
# Activation quantization: per-token absmax int8 (BitNet)
# --------------------------------------------------------------------------- #

def act_scale(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + EPS


def quantize_act_int8(
    x: jnp.ndarray, axis: int = -1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    s = act_scale(x, axis)
    q = jnp.clip(jnp.round(x / s), -128, 127).astype(jnp.int8)
    return q, s


def fake_quant_act(x: jnp.ndarray) -> jnp.ndarray:
    """Per-token int8 fake-quant with STE."""
    s = act_scale(x)
    xq = jnp.clip(jnp.round(x / s), -128, 127) * s
    return x + jax.lax.stop_gradient(xq.astype(x.dtype) - x)


# --------------------------------------------------------------------------- #
# BitLinear: y = act_fq(x) @ weight_fq(W)    (QAT path)
#            y = int8(x) @ unpack(W_packed) * scales (serving path)
# --------------------------------------------------------------------------- #

def bit_linear_train(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """QAT forward: fake-quantized activations x fake-quantized weights.

    ``x`` is assumed pre-normalized (BitLinear wraps RMSNorm before quant —
    done by the caller in ``models.layers``).
    """
    return fake_quant_act(x) @ fake_quant_weight(w)


def bit_linear_serve(
    x: jnp.ndarray, qw: QuantizedTensor, *, backend: str = "reference",
) -> jnp.ndarray:
    """Serving forward with real ternary weights.

    backend="reference": pure-jnp (dry-run / XLA path).
    backend="pallas":    kernels.bitlinear fused unpack+matmul (TPU path).
    """
    if backend == "pallas":
        from repro.kernels.bitlinear import ops as bl_ops
        # qw packs along N (what dequantize reads); the kernel streams
        # K-major packing, so the same values are repacked for it
        w = packing.unpack(qw.packed, qw.bits).reshape(qw.shape)
        pack_k = (packing.pack_2bit_kmajor if qw.bits == 2
                  else packing.pack_4bit_kmajor)
        xq, xs = quantize_act_int8(x)
        out = bl_ops.tile_gemm(xq, pack_k(w), bits=qw.bits, backend="pallas")
        return out.astype(x.dtype) * (xs * qw.scale).astype(x.dtype)
    w = qw.dequantize().astype(x.dtype)
    xq, xs = quantize_act_int8(x)
    return (xq.astype(x.dtype) * xs.astype(x.dtype)) @ w
